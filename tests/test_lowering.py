"""The seam behind which a Mosaic call site's lowering is decided,
remembered and reported (ops/pallas/lowering.py): the gate, the form, the
record and the question."""
import logging
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.config import flagship_model_config
from dalle_tpu.models import attention
from dalle_tpu.models.dalle import DALLE, init_params

REPO = pathlib.Path(__file__).resolve().parent.parent

SMALL = dict(depth=9, head_dim=32, text_seq_len=16, image_grid=4,
             vocab_text=64, vocab_image=32, head_chunk=0)


def _cfg(heads):
    return flagship_model_config(dim=heads * 32, heads=heads, **SMALL)


def _step(cfg):
    """The model's jitted loss and its parameters' shapes (initialising
    them traces the model once)."""
    model = DALLE(cfg)
    params = jax.eval_shape(lambda: init_params(model, jax.random.PRNGKey(0)))
    tokens = (jnp.zeros((1, cfg.text_seq_len), jnp.int32),
              jnp.zeros((1, cfg.image_seq_len), jnp.int32))
    return jax.jit(lambda p: model.apply(p, *tokens)[0]), params


def _zoo_key(cfg):
    return (cfg.head_dim, cfg.heads * cfg.head_dim, cfg.total_seq_len,
            cfg.text_seq_len)


def test_no_model_or_kernel_module_but_the_seam_reads_the_gate():
    """A plain source scan, in the manner of
    ``test_every_config_field_is_read_by_the_program``: whether there is a
    Mosaic backend, and the interpret flag, are read in
    ``ops/pallas/lowering.py`` and nowhere else under ``models/`` or
    ``ops/pallas/``. The flag's storage stays the one attribute of
    ``models/attention.py`` that the benchmark's harness, ``chip_smoke.py``
    and the tests write (ROADMAP Design 11b)."""
    gate = re.compile(r"_pallas_by_default|_PALLAS_INTERPRET|default_backend")
    seam = REPO / "dalle_tpu" / "ops" / "pallas" / "lowering.py"
    named = [
        f"{path.relative_to(REPO)}: {line.strip()}"
        for folder in ("models", "ops/pallas")
        for path in sorted((REPO / "dalle_tpu" / folder).glob("*.py"))
        if path != seam
        for line in path.read_text().splitlines() if gate.search(line)]
    assert named == ["dalle_tpu/models/attention.py: "
                     "_PALLAS_INTERPRET = False"], named
    assert len(gate.findall(seam.read_text())) >= 2


def test_the_record_keeps_two_models_apart_and_says_what_it_has_not_seen(
        monkeypatch, lowering_record):
    """Keyed by everything a choice is made from: a model whose heads fill
    the lane tiles and one whose heads do not, traced in one process,
    neither vouch for nor taint each other; a model never traced reads
    "none traced", and with the gate shut every question reads "no Mosaic
    backend" whatever the record holds."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    fills, odd = _cfg(4), _cfg(3)
    site = "axial_row attention"
    assert lowering_record.why_not(site, _zoo_key(fills)) == "none traced"
    assert lowering_record.recorded(site, _zoo_key(fills)) is None
    jax.eval_shape(*_step(odd))
    assert lowering_record.why_not(site, _zoo_key(odd)) == (
        "3 heads of 32 do not fill 128-lane tiles")
    assert lowering_record.why_not(site, _zoo_key(fills)) == "none traced"
    jax.eval_shape(*_step(fills))
    assert lowering_record.why_not(site, _zoo_key(fills)) is None
    assert lowering_record.recorded(site, _zoo_key(fills)) == {"why_not": None}
    assert lowering_record.why_not(site, _zoo_key(odd)) is not None
    # all of these took it, or the first refusal says why none did
    types = ("axial_row", "axial_col", "conv_like")
    assert lowering_record.first_refusal(
        (f"{t} attention", _zoo_key(fills)) for t in types) is None
    assert lowering_record.first_refusal(
        (f"{t} attention", _zoo_key(cfg)) for t in types
        for cfg in (fills, odd)) == "3 heads of 32 do not fill 128-lane tiles"
    assert attention.attn_layout_record(fills) == \
        "lane-dense 128: 9 of 9 layers"
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", False)
    assert lowering_record.why_not(site, _zoo_key(fills)) == \
        "no Mosaic backend"
    assert lowering_record.recorded(site, _zoo_key(fills)) == {"why_not": None}
    assert attention.attn_layout_record(fills) == \
        "lane-dense 128: 0 of 9 layers"


def test_a_trace_served_from_the_jit_cache_leaves_the_record_as_it_was(
        monkeypatch, lowering_record):
    """The record is the process's and not a window around one trace: the
    benchmark traces ``grad_step`` in its reference check, ``warmup`` then
    traces nothing, and the ``setup/warmup`` row still says what ran."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = _cfg(4)
    step, params = _step(cfg)
    lowered = step.lower(params)
    before = dict(lowering_record._RECORD)
    assert before and all(site.endswith("attention") or site in (
        "LayerNorm", "GEGLU feed-forward") for site, _ in before)
    # a second lowering finds the traced program in the cache: no site runs
    writes = []
    monkeypatch.setattr(lowering_record, "record",
                        lambda *a, **kw: writes.append(a))
    assert step.lower(params).as_text() == lowered.as_text()
    assert not writes and lowering_record._RECORD == before
    assert attention.attn_layout_record(cfg) == \
        "lane-dense 128: 9 of 9 layers"


@pytest.mark.parametrize("interpret", [False, True])
def test_the_form_of_a_site(interpret, monkeypatch, caplog, lowering_record):
    """Gate shut: the XLA lowering on the whole arrays, said once, nothing
    remembered. Gate open: per shard, the kernel where the site's predicate
    takes the local shapes and the XLA lowering where it refuses, each
    remembered under the local shapes and said once."""
    from dalle_tpu.parallel.mesh import LANES_SPEC, make_mesh

    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", interpret)
    assert lowering_record.mosaic() is interpret
    assert lowering_record.interpret() is interpret

    def choose(x):
        why_not = None if x.shape[2] % 2 == 0 else "odd lanes"
        return lowering_record.chose("halving", x.shape[1:], why_not,
                                     why_not or f"local {x.shape}", tile=8)

    def run(lanes):
        x = jnp.ones((8, 4, lanes))
        return lowering_record.site(
            "halving", choose, lambda x: x * 0.5, lambda x: x / 2.0,
            make_mesh(dp=4, tp=2), (LANES_SPEC,), LANES_SPEC)(x)

    with caplog.at_level(logging.INFO, logger=lowering_record.logger.name):
        for lanes in (8, 8, 6):
            np.testing.assert_array_equal(run(lanes), 0.5)
    # the seam's own words: a trainer run earlier in the process leaves
    # the compile counter saying what compiles after its last step
    said = [r.getMessage() for r in caplog.records
            if r.name == lowering_record.logger.name]
    if not interpret:
        assert said == ["halving: XLA lowering (no Mosaic backend)"]
        assert not lowering_record._RECORD
        assert lowering_record.why_not("halving", (4, 4)) == \
            "no Mosaic backend"
        return
    # tp 2: a shard's half of the lanes
    assert said == ["halving: Pallas kernel (local (2, 4, 4))",
                    "halving: XLA lowering (odd lanes)"]
    assert lowering_record.recorded("halving", (4, 4)) == {"why_not": None, "tile": 8}
    assert lowering_record.why_not("halving", (4, 3)) == "odd lanes"
    assert lowering_record.why_not("halving", (4, 8)) == "none traced"


# -- what a site's tracing costs (PR 54) -------------------------------------

@pytest.fixture
def counter():
    """The process's compile counter recording into a tracer of the test's:
    the sites' brackets time themselves where there is one."""
    from dalle_tpu.obs import compiles
    from dalle_tpu.obs.trace import Tracer
    tracer = Tracer(peer="sites")
    yield compiles.install(tracer)
    compiles.install(None)


def _halving(record, kernel=lambda x: x * 0.5):
    def choose(x):
        return record.chose("halving", x.shape[1:], None, f"local {x.shape}")
    return record.site("halving", choose, kernel, lambda x: x / 2.0)


def test_a_traced_call_times_itself_in_the_record_and_the_ring(
        monkeypatch, lowering_record, counter):
    """Every traced call of a site is one bracket: ``traced_n``, ``trace_s``
    and ``again_s`` in the record's row, and one span of plane ``train``,
    ``trace/site`` the first time a ``(site, key)`` is traced and
    ``trace/site_again`` after that, the child of the span that was open.
    A jitted caller's second call is served from the cache: no bracket."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    tracer = counter.tracer
    wide, narrow = jnp.ones((2, 4, 8)), jnp.ones((2, 4, 6))
    with tracer.span("train", "setup/warmup", "setup"):
        for x in (wide, wide, narrow):
            _halving(lowering_record)(x)
    step = jax.jit(_halving(lowering_record))
    step(wide), step(wide)                   # one trace, outside any span
    rows = [r for r in tracer.dump() if r["phase"].startswith("trace/")]
    assert [(r["phase"], r["a"]["nth"], r.get("parent")) for r in rows] == [
        ("trace/site", 1, "setup/warmup"),
        ("trace/site_again", 2, "setup/warmup"),
        ("trace/site", 1, "setup/warmup"),
        ("trace/site_again", 3, None)]
    assert {r["a"]["site"] for r in rows} == {"halving"}
    digest = lowering_record.key_digest
    assert [r["a"]["key"] for r in rows] == [
        digest((4, 8)), digest((4, 8)), digest((4, 6)), digest((4, 8))]
    assert len(digest((4, 8))) == 8 and digest((4, 8)) != digest((4, 6))
    assert all(r["plane"] == "train" and r["dur_s"] > 0 for r in rows)
    timed = lowering_record.timing("halving", (4, 8))
    assert timed["traced_n"] == 3
    assert timed["trace_s"] == pytest.approx(
        sum(r["dur_s"] for r in rows if r["a"]["key"] == digest((4, 8))),
        rel=0.2)
    assert 0 < timed["again_s"] < timed["trace_s"]
    assert lowering_record.timing("halving", (4, 6)) == {
        "traced_n": 1, "trace_s": pytest.approx(rows[2]["dur_s"], rel=0.2),
        "again_s": 0.0}
    # the facts are the site's own, as before: a choice said again keeps
    # what the row's tracing cost
    assert lowering_record.recorded("halving", (4, 8)) == {"why_not": None}
    by_site = counter.snapshot()["by_site"]
    assert by_site == lowering_record.by_site()
    assert by_site["halving"] == {
        "calls": 4, "keys": 2, "again_n": 2,
        "trace_s": pytest.approx(sum(r["dur_s"] for r in rows), rel=0.2),
        "again_s": pytest.approx(timed["again_s"])}


def test_sites_nest_and_the_inner_rows_parent_says_so(
        monkeypatch, lowering_record, counter):
    """A site traced inside another site's call is a row of its own, whose
    ``parent`` is the outer's phase: a reader takes the union."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)

    def outer_kernel(x):
        with lowering_record.traced("inner", x.shape):
            return x * 0.5
    _halving(lowering_record, outer_kernel)(jnp.ones((2, 4, 8)))
    inner, outer = [r for r in counter.tracer.dump()
                    if r["phase"].startswith("trace/")]
    assert (inner["a"]["site"], inner["parent"]) == ("inner", "trace/site")
    assert outer["a"]["site"] == "halving" and "parent" not in outer
    assert outer["t0"] <= inner["t0"]
    assert inner["t0"] + inner["dur_s"] <= outer["t0"] + outer["dur_s"] + 2e-6
    # a site with no choice of its own gets a row that took its kernel
    assert lowering_record.recorded("inner", (2, 4, 8)) == {"why_not": None}


def test_with_no_counter_a_site_runs_as_it_did(monkeypatch, lowering_record):
    """The tools that lower for a described chip from the sandbox have no
    task, no tracer and no counter: no clock is read, nothing is kept."""
    from dalle_tpu.obs import compiles
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    monkeypatch.setattr(compiles, "_installed", None)
    np.testing.assert_array_equal(
        _halving(lowering_record)(jnp.ones((2, 4, 8))), 0.5)
    assert lowering_record._RECORD == {("halving", (4, 8)): {"why_not": None}}
    assert lowering_record.timing("halving", (4, 8)) == {
        "traced_n": 0, "trace_s": 0, "again_s": 0}
    assert lowering_record.by_site() == {}
    # and a counter with no tracer (scripts/lowered_step.py) keeps the
    # record's half alone
    counter = compiles.CompileCounter(None)
    monkeypatch.setattr(compiles, "_installed", counter)
    _halving(lowering_record)(jnp.ones((2, 4, 8)))
    assert lowering_record.timing("halving", (4, 8))["traced_n"] == 1
    assert counter.snapshot()["by_site"]["halving"]["calls"] == 1


def _lowered_step(preset, lowering_record, monkeypatch, **overrides):
    """A preset's grad step lowered for a TPU without one, as
    ``tests/benchmark_tests/test_benchmark_census.py`` lowers the cells':
    (kernel names -> count, the sites' account of that one trace)."""
    from benchmark.harness import kernel_census
    from dalle_tpu.cli.run_trainer import MODEL_PRESETS
    from dalle_tpu.models import family
    from dalle_tpu.parallel.mesh import make_mesh
    from dalle_tpu.training.steps import make_grad_step
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = MODEL_PRESETS[preset](**overrides)
    fam = family(cfg)
    model = fam.build(cfg, make_mesh(devices=jax.devices()[:1]))
    params = jax.eval_shape(
        lambda: fam.init_params(model, jax.random.PRNGKey(0)))
    lowering_record._RECORD.clear()      # the step's calls alone
    batch = {"text": jax.ShapeDtypeStruct((2, cfg.text_seq_len), jnp.int32),
             "image": jax.ShapeDtypeStruct((2, cfg.image_seq_len),
                                           jnp.int32)}
    text = jax.jit(make_grad_step(model, accum_steps=1)).trace(
        params, batch).lower(lowering_platforms=("tpu",)).as_text()
    return kernel_census(text), lowering_record.by_site()


def _every_row_was_timed(record, but=()):
    for (site, key), row in record._RECORD.items():
        if (site, key) not in but:
            assert record.timing(site, key)["traced_n"] >= 1, (site, key)
            assert row["trace_s"] >= row["again_s"] >= 0


def test_every_site_of_a_tiny_sparse_step_counts_its_traced_calls(
        monkeypatch, lowering_record, counter):
    """After one traced step every ``(site, key)`` the record knows was
    timed. An attention site's calls are its forward kernels in the
    lowered step, one a layer (its output is saved: no replay). Elsewhere
    the two counts differ, and say how: a rematerialised layer's replay
    lowers a site's forward kernel again without calling the site's
    Python, and a jitted entry (the token-major sum, the rows) is called
    at every site and lowered once a shape."""
    from dalle_tpu.models import sparse_lm
    census, sites = _lowered_step(
        "smallthinker21b", lowering_record, monkeypatch, hidden_size=256,
        num_hidden_layers=2, layer_kinds=("full_nope", "window_rope"),
        num_heads=2, num_kv_heads=1, head_dim=128, expert_width=128,
        num_experts=8, experts_per_token=2, experts_held=4, expert_offset=2,
        vocab_size=512, window=512, text_seq_len=256, image_grid=16,
        vocab_text=256, vocab_image=256)
    # the expert block's form is a fact of the products' call, written
    # under a key of its own with no call of its own; the streamed head's
    # row is a fact its derivative rule writes (no kernel, no bracket)
    head = sparse_lm.HEAD_SITE, sparse_lm._head_key(256, 512, 2, False,
                                                    "bfloat16")
    _every_row_was_timed(lowering_record, but={
        (sparse_lm.PRODUCTS_SITE, sparse_lm._block_key(256, 128,
                                                       "bfloat16")), head})
    assert lowering_record.recorded(*head)["carried"] == "bfloat16"
    attention_calls = sum(at["calls"] for site, at in sites.items()
                          if site.endswith(" attention"))
    assert attention_calls == census["_causal_fwd_kernel"] == 2
    assert sites["rotary"]["calls"] == census["_head_norm_bwd_kernel"] == 2
    assert census["_head_norm_fwd_kernel"] == 4        # each replayed
    assert sites[sparse_lm.PRODUCTS_SITE]["calls"] == 2
    assert census["_gated_hidden_kernel"] == 4         # each replayed
    for site, kernel in ((sparse_lm.SUM_SITE, "_token_sum_kernel"),
                         (sparse_lm.ROWS_SITE, "_rows_of_kernel")):
        assert sites[site]["calls"] > census[kernel] >= 1
        assert sites[site]["again_n"] == sites[site]["calls"] \
            - sites[site]["keys"]
    assert all(at["trace_s"] >= at["again_s"] for at in sites.values())


def test_every_site_of_a_small_dalle_step_counts_its_traced_calls(
        monkeypatch, lowering_record, counter):
    """The zoo's sites at the flagship's widths, six layers: every row was
    timed, and each site's calls are its forward kernels in the lowered
    step (attention's output and the feed-forward's are saved; LayerNorm
    is replayed in the rematerialised blocks, where it is lowered again
    without a call)."""
    census, sites = _lowered_step("flagship", lowering_record, monkeypatch,
                                  depth=6)
    _every_row_was_timed(lowering_record)
    attention_calls = sum(at["calls"] for site, at in sites.items()
                          if site.endswith(" attention"))
    assert attention_calls == census["_fwd_kernel"] \
        + census["_win_fwd_kernel"]
    assert sites["GEGLU feed-forward"]["calls"] == census["_ff_fwd_kernel"]
    assert 1 <= sites["LayerNorm"]["calls"] <= census["_ln_fwd_kernel"]
    assert sites["LayerNorm"]["keys"] == 1
    assert sites["LayerNorm"]["again_n"] == sites["LayerNorm"]["calls"] - 1
