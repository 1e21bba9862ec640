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
