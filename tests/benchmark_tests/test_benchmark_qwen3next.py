"""The ``qwen3next80b`` configuration, its cell, its yardstick and its metric
files: they pass every check the suite applies to a manifest; the file
holds the source's config under the source's keys (the catalog row's
numbers) with the cut values for ``reduced``; the counts of the yardstick
against hand arithmetic; both new metrics read a number from the scopes
the program writes; a tiny rehearsal of the preset runs through
``harness.run_cell``; and (marked ``slow`` from the start: the compile reads
80 s in the sandbox) the real widths compile for a described v5e, fit, fill
every role of the census and hold no T x T array and no state a token (ONE
lowering and compile, shared by the tests that read it). A sentence the
program says is held by the clauses a test is about (``startswith``, ``in``),
never whole: a later PR may lengthen it."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import benchmark_checks as checks
import pytest

from benchmark import manifest as M
from benchmark import trace as T
from benchmark.harness import RunContext, mosaic_census, peaks_for
from dalle_tpu.cli.run_trainer import MODEL_PRESETS

ROOT = M.ROOT
MAN = M.Manifest()
CONFIG, CELL = "qwen3next80b", "qwen3next80b-train-solo"
OTHER = "trinitymini-train-solo"
OWN_METRICS = ("gdn_share_pct", "gdn_rule_roofline")
# the metrics the cell reads through the entries the other sparse cells
# read: its name appended to their ``workloads``, no copy
SHARED = ("attn_roofline", "moe_experts_roofline", "moe_router_share_pct",
          "moe_dispatch_share_pct", "moe_experts_share_pct",
          "moe_load_max_over_mean", "moe_assignments_here_pct",
          "moe_dense_calls", "moe_dropped", "moe_sum_spills",
          "moe_shared_share_pct", "attn_gate_share_pct")
PARAMETERS = 424_340_544


def _catalog_row():
    """The catalog row's ``config``, where the guide is installed."""
    path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not path.is_file():
        return None
    rows = [json.loads(line) for line in path.read_text().splitlines()
            if line.strip()]
    return next(r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct")


def test_everything_the_pr_adds_passes_every_check():
    checks.every_check(MAN, MODEL_PRESETS)
    cell = MAN.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "solo-8x8"
    assert cell.traffic == MAN.cell(OTHER).traffic      # the file, unedited
    read = {m["name"] for m in cell.per_layer}
    assert read >= set(OWN_METRICS) | set(SHARED)
    # it has no dense block, no convolution operator, no state-space mixer
    assert not read & {"ff_dense_share_pct", "conv_share_pct",
                       "conv_mix_roofline", "ssm_share_pct",
                       "ssm_scan_roofline"}
    assert not [name for name in read if CELL in name]      # no copy
    for name in MAN.cells:
        if name != CELL:
            assert not {m["name"] for m in MAN.cell(name).per_layer} \
                & set(OWN_METRICS)
    files = {m["name"]: m for m in cell.per_layer}
    theirs = {m["name"]: m for m in MAN.cell(OTHER).per_layer}
    for name in SHARED:
        assert files[name] == theirs[name], name
    for name in ("attn_roofline", "moe_experts_roofline"):
        least = files[name]["params"]["least"]
        assert getattr(cell.yardstick, least).__module__ \
            != getattr(MAN.cell(OTHER).yardstick, least).__module__
    # the two new entries stand side by side after every entry the
    # benchmark had (a later PR's come after them: no pin on the tail)
    names = [m["name"] for m in MAN.data["per_layer"]]
    at = names.index(OWN_METRICS[0])
    assert names[at:at + 2] == list(OWN_METRICS)
    assert at > names.index("ssm_scan_roofline")
    assert "unscoped_share_pct counts these operations too" in \
        files["gdn_share_pct"]["note"]
    assert files["gdn_rule_roofline"]["params"] == {
        "pattern": ".", "scope": "gdn/(.*/)?(rule|conv)",
        "least": "gdn_rule_min_seconds_per_sample"}
    assert files["gdn_share_pct"]["params"]["scope"] == "(^|/)gdn(/|:|$)"
    # no pin on the count: a later PR's cell is one more (the pin of PR 57's
    # file, == 9, is red since this PR and a ``benchmark`` PR's to turn)
    assert len(MAN.cells) >= 10
    assert sum(MAN.cell(name).chips == 4 for name in MAN.cells) >= 1


def test_the_file_holds_the_sources_config_under_the_sources_keys():
    """Every key of the catalog row's config is a top-level key of the
    file with the published value, but the ones ``reduced`` names, which
    hold the value as run; every width agrees with ``model``."""
    on_file = json.loads((ROOT / MAN.configs[CONFIG]["file"]).read_text())
    model, reduced = on_file["model"], on_file["reduced"]
    assert reduced == ["num_hidden_layers", "experts_held", "vocab_size"]
    assert on_file["published"] == {
        "num_hidden_layers": 48, "experts_held": 512, "vocab_size": 151936}
    row = _catalog_row()
    if row is not None:
        assert on_file["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in reduced:
                assert on_file[key] == model[key] != value
                assert on_file["published"][key] == value
            else:
                assert on_file[key] == value, key
    # the layers run are one whole period of the interval
    interval = on_file["full_attention_interval"]
    assert interval == 4 and model["num_hidden_layers"] == interval
    assert model["layer_kinds"] == [
        "full_rope" if (i + 1) % interval == 0 else "gated_delta"
        for i in range(interval)]
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("expert_width", "moe_intermediate_size"),
                         ("num_experts", "num_experts"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("linear_num_key_heads", "linear_num_key_heads"),
                         ("linear_num_value_heads", "linear_num_value_heads"),
                         ("linear_key_head_dim", "linear_key_head_dim"),
                         ("linear_value_head_dim", "linear_value_head_dim"),
                         ("linear_conv_kernel_dim", "linear_conv_kernel_dim"),
                         ("partial_rotary_factor", "partial_rotary_factor"),
                         ("rope_theta", "rope_theta"),
                         ("rms_eps", "rms_norm_eps"),
                         ("router_softmax_over_chosen", "norm_topk_prob"),
                         ("hidden_act", "hidden_act"),
                         ("tied_embeddings", "tie_word_embeddings")):
        assert model[ours] == on_file[theirs], ours
    assert model["num_shared_experts"] * model["expert_width"] == \
        on_file["shared_expert_intermediate_size"] == 512
    assert (model["hidden_size"], model["expert_width"], model["head_dim"]) \
        == (2048, 512, 256)
    assert on_file["decoder_sparse_step"] == 1
    assert on_file["mlp_only_layers"] == [] and not model["num_dense_layers"]
    assert model["shared_expert_gate"] and model["attention_gate"] \
        and model["qk_norm"] and model["delta_chunk"] == 64
    # the floors of a cut: a whole period, 8 experts and more, an eighth of
    # the rows; no width touched
    assert model["experts_held"] == 16 >= 8
    assert model["vocab_size"] * 8 >= on_file["published"]["vocab_size"]
    assert model["vocab_text"] + model["vocab_image"] == model["vocab_size"]
    assert on_file["layer_shared_by"] == 32
    assert on_file["layer_shared_by"] * model["experts_held"] == \
        model["num_experts"]
    assert on_file["yardstick"] == "qwen3next"
    assert "MTP 1" in on_file["source_note"] \
        and "no part of this configuration" in on_file["source_note"]
    for name in on_file["assumed"]:
        assert len(on_file["assumed_because"][name]) > 20, name
    assert {"layer_kinds", "delta_chunk", "router_input", "attention_gate",
            "qk_norm", "shared_expert_gate", "embed_init_std"} <= set(
                on_file["assumed"])
    unkeyed = [k for k in on_file["assumed_because"] if "no key of model" in k]
    for start in ("norm_scale", "in_proj_layout", "gated_norm_order", "init",
                  "partial_rotary"):
        assert any(k.startswith(start) for k in unkeyed), start
    roles = on_file["mosaic_kernels"]
    assert {"_causal_fwd_kernel", "_causal_(?!fwd_)\\w+", "_gmm_kernel",
            "_tgmm_kernel", "_token_sum_kernel", "_head_norm_fwd_kernel",
            "_ssm_taps_fwd_kernel"} <= set(roles)
    tol = on_file["tolerance"]
    assert 0 < tol["loss_rel"] < 1e-3 and 0 < tol["grad_rel_l2"] < 1.0
    assert 0 < tol["grad_rel_l2_median"] < tol["grad_rel_l2"]
    assert len(tol["reason"]) > 200 and "float8" in tol["reason"]


def test_the_counts_of_the_yardstick_against_hand_arithmetic():
    """424.3 M parameters; forward a token a gated-delta mixer 67.4 MFLOP of
    projections + the rule's own 3.2 (and the taps), the attention layer
    54.5 + 67.1, an expert block 10.4, the head 77.8: 453 in all, the three
    mixers 47% of it; the least seconds of the rule from its bytes."""
    cell = MAN.cell(CELL)
    y, model = cell.yardstick, cell.config["model"]
    t = y.tokens_per_sample(model)
    assert t == 8192
    assert y.gdn_layers(model) == 3 and y.expert_layers(model) == 4
    assert y.held_assignments_per_token(model) == 0.3125
    gdn = 2048 * (8192 + 4096 + 64) + 4096 * 2048
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert y.gdn_conv_lanes(model) == 8192 and y.gdn_value_lanes(model) == 4096
    assert y.gdn_matmul_params(model) == gdn == 33_685_504
    assert y.attention_matmul_params(model) == attn == 27_262_976
    assert round(2 * gdn / 1e6, 1) == 67.4
    assert round(2 * attn / 1e6, 1) == 54.5
    rule = 6 * 32 * 128 * 128 + 2 * 4 * 8192
    assert y.gdn_rule_flops_forward(model) == rule == 3_211_264
    pairs = t * (t + 1) // 2
    assert y.attention_pairs(model, "full_rope") == pairs
    assert y.attention_pairs(model, "gated_delta") == 0
    scores = 4 * pairs * 256 * 16
    assert y.attention_flops_forward(model, "full_rope") == scores
    assert round(scores / t / 1e6, 1) == 67.1
    router, shared, expert = 2048 * 512, 3 * 2048 * 512, 3 * 2048 * 512
    block = router + shared + 2048 + 0.3125 * expert
    assert y.shared_width(model) == 512
    assert y.expert_layer_matmul_params(model) == block == 5_179_392
    assert round(2 * block / 1e6, 1) == 10.4
    head = 2 * 2048 * 18992
    assert round(head / 1e6, 1) == 77.8
    fwd = (3 * t * (2 * gdn + rule) + 2 * t * attn + scores
           + 4 * 2 * t * block + head * (t - 1))
    assert y.train_flops_per_sample(model) == pytest.approx(3 * fwd)
    assert round(fwd / t / 1e6) == 453                  # MFLOP a token
    assert 0.46 < 3 * (2 * gdn + rule) * t / fwd < 0.47     # the mixers
    assert 0.17 < head * (t - 1) / fwd < 0.18               # the head
    assert round(3 * fwd * 8 / 1e12) == 89              # TFLOP a step
    # the parameters the program initialises
    g_layer = gdn + 4 * 8192 + 32 + 32 + 128 + 2048
    a_layer = attn + 2 * 256 + 2048
    e_block = router + shared + 2048 + 16 * expert + 2048
    assert (g_layer, a_layer, e_block) == (33_720_512, 27_265_536, 54_530_048)
    whole = 3 * g_layer + a_layer + 4 * e_block + 2 * 18992 * 2048 + 2048
    assert whole == PARAMETERS and round(whole / 1e6, 1) == 424.3
    peaks = peaks_for("TPU v5 lite")
    attn_least = y.attention_min_seconds_per_sample(model, peaks)
    experts = y.experts_min_seconds_per_sample(model, peaks)
    rule_least = y.gdn_rule_min_seconds_per_sample(model, peaks)
    assert attn_least["bandwidth_bound_share"] == 0.0
    assert attn_least["seconds"] == pytest.approx(
        3 * scores / peaks["bf16_flops_per_s"])
    assert experts["seconds"] == pytest.approx(
        4 * 3 * 2 * 0.3125 * expert * t / peaks["bf16_flops_per_s"])
    # the rule is its bytes: q, k, v, b and a read, o written, 24.7 KB a
    # token forward; those and o's cotangent read, their cotangents written
    assert rule_least["bandwidth_bound_share"] == 1.0
    forward = (8192 + 64 + 4096) * 2
    backward = (2 * (8192 + 64) + 4096) * 2
    assert forward == 24_704
    assert rule_least["seconds"] == pytest.approx(
        3 * t * (forward + backward) / peaks["hbm_bytes_per_s"])
    slow = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1e30}
    assert y.gdn_rule_min_seconds_per_sample(model, slow)["seconds"] == \
        pytest.approx(3 * 3 * t * rule)


def _path(rest, layer="layer_0", backward=False):
    root = "jit(grad_step)/while/body/closed_call/"
    if backward:
        root += "transpose(jvp(SparseLM))/jvp(SparseLM)/checkpoint/"
    else:
        root += "jvp(SparseLM)/"
    return root + f"{layer}/" + rest


def _scoped_trace():
    """Device operations under the scope paths the program's grad step
    writes (module names and ``jax.named_scope``): 100 ns each, back to
    back."""
    ops = [
        ("convolution_bitcast_fusion", _path("gdn/in_proj/qkv/dot_general:")),
        ("taps[mosaic]", _path("gdn/conv/taps/pallas_call:")),
        ("fusion", _path("gdn/rule/exp:")),
        ("fusion", _path("gdn/rule/while/body/checkpoint/while/body/"
                         "checkpoint/bgrid,bgrdp->bgrip/dot_general:")),
        ("fusion", _path("rematted_computation/layer_0/gdn/rule/"
                         "bngrij,bnjgrp->nbgrip/dot_general:", layer="x",
                         backward=True)),
        ("taps[mosaic]", _path("gdn/conv/taps/pallas_call:", backward=True)),
        ("qk_norm[mosaic]", _path("gdn/gate_norm/qk_norm/pallas_call:")),
        ("fusion", _path("gdn/gate_norm/mul:")),
        ("fusion", _path("gdn/out_proj/dot_general:")),
        ("attn[mosaic]", _path("attn/pallas_call:", layer="layer_3")),
        ("fusion", _path("attn/q/dot_general:", layer="layer_3")),
        ("fusion", _path("attn/gate/logistic:", layer="layer_3")),
        ("fusion", _path("ff.route/ff/router/btd,de->bte/dot_general:",
                         layer="layer_1")),
        ("experts[mosaic]", _path(
            "ff/cond/branch_1_fun/experts/pallas_call:", layer="layer_1")),
        ("fusion", _path("ff/shared/up/dot_general:", layer="layer_1")),
        ("fusion", _path("ff/shared/logistic:", layer="layer_1")),
        ("fusion", _path("rms_norm/mul:")),
        ("fusion", "jit(grad_step)/while/body/closed_call/jvp(SparseLM)/"
                   "while/body/closed_call/head/dot_general:"),
    ]
    events = [[name, 100 * i, 100, scope]
              for i, (name, scope) in enumerate(ops)]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": events}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench/traced_window", 0, 100 * len(ops)]]}]}]}, len(ops)


def test_both_new_metrics_read_the_programs_scopes():
    cell = MAN.cell(CELL)
    files = {m["name"]: m for m in cell.per_layer}
    raw, n_ops = _scoped_trace()
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}
    ctx = RunContext(model=cell.config["model"], yardstick=cell.yardstick,
                     chips=1, peaks=peaks, trace=T.Reduced(raw),
                     traced_steps=3, samples_per_step=8, values={})

    def read(name):
        m = files[name]
        return M.reducer(m["reducer"])(ctx, **m.get("params", {}))

    share = 100.0 / n_ops
    # the mixer whole: projections, taps, rule, the heads' norm, the gate;
    # forward, replay and backward
    assert read("gdn_share_pct") == pytest.approx(9 * share)
    y, model = cell.yardstick, cell.config["model"]
    least = y.gdn_rule_min_seconds_per_sample(model, peaks)["seconds"]
    # ... and the taps and the rule alone: five operations, whatever their
    # names (a Mosaic pass among them)
    assert read("gdn_rule_roofline") == pytest.approx(
        100 * least * 24 / 500e-9)
    attn = y.attention_min_seconds_per_sample(model, peaks)["seconds"]
    assert read("attn_roofline") == pytest.approx(100 * attn * 24 / 100e-9)
    assert read("moe_router_share_pct") == pytest.approx(share)
    assert read("moe_experts_share_pct") == pytest.approx(share)
    # the shared expert and its gate
    assert read("moe_shared_share_pct") == pytest.approx(2 * share)
    # the attention's gate; the mixer's norm is under gdn, not attn
    assert read("attn_gate_share_pct") == pytest.approx(share)
    # the accepted attention share keeps meaning attention; the accepted
    # unscoped share counts the mixer's operations too, as the new file's
    # note says (those that are XLA code: the projections and the rule's),
    # all but ``gdn/gate_norm`` (its expression knows a scope that ends in
    # ``norm``)
    assert read("attn_xla_share_pct") == pytest.approx(2 * share)
    assert read("unscoped_share_pct") == pytest.approx(5 * share)
    # a program with none of these scopes (the parent): a share of nothing
    # reads 0, a roofline with nothing to read is left out
    bare = dict(raw, planes=[dict(raw["planes"][0], lines=[{
        "name": "XLA Ops", "events": [["fusion", 0, 100, ""]]}]),
        raw["planes"][1]])
    ctx.trace = T.Reduced(bare)
    assert read("gdn_share_pct") == 0.0
    assert read("gdn_rule_roofline") is None


def test_a_traced_rehearsal_of_the_preset_runs_through_the_harness(
        tmp_path):
    """The tiny preset, its yardstick and the new metric files through
    ``harness.run_cell`` on the CPU, the kernels interpreted: the reference
    check passes, the program-fed metrics are read under the names the
    sparse cells share, the trace-fed ones are left out (no device plane
    here), ``engagement`` prints the new attribute."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", SECS="5",
               PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable,
         str(Path(__file__).parent / "qwen3next_rehearse.py"), "1",
         str(tmp_path)], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL")
    result = json.loads(last.split(":", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    own = lambda name: got[name]["value"]
    assert 0 < own("moe_assignments_here_pct") < 100
    assert own("moe_load_max_over_mean") >= 1.0
    assert own("moe_dense_calls") == 0.0 and own("moe_dropped") == 0.0
    for name in ("grad_step_s", "loop_grad_step_s", "warmup_s",
                 "compiles_after_first_step", "state_bytes_per_param"):
        assert name in got, name
    assert got["compiles_after_first_step"]["value"] == 0
    for name in (*OWN_METRICS, "attn_roofline", "moe_router_share_pct"):
        assert name not in got
    line = [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith('{"reference_check"')][0]
    check = line["reference_check"]
    assert check["loss_rel_err"] < 1e-5 and check["grad_rel_l2_max"] < 1e-4
    said = line["engagement"]
    assert set(said) >= {"attn_layout", "gdn_layout", "moe_layout",
                         "memory_layout", "layer_loop"}
    assert said["gdn_layout"].startswith(
        "gated-delta-rule mixer: 1 of 2 layers, 1 query/key heads x 128 "
        "serving 2 value heads x 128, 4 taps with no bias over 512 lanes; "
        "the rule in chunks of 16, 4 a sequence of 64")
    assert "gdn/rule: XLA chunks (no Mosaic kernel is written" \
        in said["gdn_layout"]
    assert "taps and SiLU: the Mamba-2 mixer's pass" in said["gdn_layout"]
    assert "one head of 256 over 2 lane tiles" in said["attn_layout"]
    assert "rotary of a head's first 64 lanes (in the head pass" \
        in said["attn_layout"]
    assert "backward: one kernel a tile" in said["attn_layout"]
    assert "under a sigmoid gate a token" in said["moe_layout"]
    assert list(result)[-1] == "compared"


# -- the real widths, lowered and compiled once for a described v5e ----------

@pytest.fixture(scope="module")
def for_a_v5e():
    """The cell's grad step (micro 1 x accum 8 of 8 192 tokens) lowered
    and compiled in the sandbox for one v5e chip, once for the tests below
    (80 s in the sandbox: the tests that read it are marked
    ``slow``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    from dalle_tpu.models import sparse_lm
    from dalle_tpu.parallel.mesh import batch_sharding, make_mesh
    from dalle_tpu.training.steps import make_grad_step

    cell = MAN.cell(CELL)
    cfg = MODEL_PRESETS[CONFIG]()
    mesh = make_mesh(devices=topo.devices[:1])
    everywhere = NamedSharding(mesh, P())
    model = sparse_lm.build(cfg, mesh)
    shapes = jax.eval_shape(lambda: sparse_lm.init_params(
        model, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=everywhere), shapes)
    accum = cell.traffic["grad_accum_steps"]
    n = cell.traffic["per_device_batch"] * accum
    tokens = lambda rows, length: jax.ShapeDtypeStruct(
        (rows, length), jnp.int32, sharding=batch_sharding(mesh))
    batch = {"text": tokens(n, cfg.text_seq_len),
             "image": tokens(n, cfg.image_seq_len)}
    step = jax.jit(make_grad_step(model, accum_steps=accum))
    default_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache clean
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:       # the dispatchers pick the Mosaic kernels for a TPU
        lowered = step.lower(params, batch)
        compiled = lowered.compile()
        said = sparse_lm.engagement_records(cfg, mesh)
    finally:
        jax.default_backend = default_backend
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    shapes_of = {jax.tree_util.keystr(path): a.shape for path, a in
                 jax.tree_util.tree_flatten_with_path(shapes)[0]}
    return cell, shapes_of, lowered.as_text(), compiled, said


@pytest.mark.slow
def test_the_real_widths_compile_for_a_described_v5e_and_fit(for_a_v5e):
    """The blockwise kernels take 8 query heads of 256 lanes a key-value
    head, the taps' pass the mixer's 8 192 lanes; nothing of size T x T and
    no state a token is in the step's plan; and the parameters' state (18
    bytes a parameter in buffers at the loop's peak, PERF.md section 4) +
    plan + code stays under the chip's 15.75 GiB."""
    cell, shapes, lowered, compiled, said = for_a_v5e
    count = sum(int(__import__("math").prod(s)) for s in shapes.values())
    assert count == PARAMETERS
    experts = "['params']['layer_1']['ff']['experts']"
    assert shapes[experts + "['up']"] == (16, 2048, 512)
    assert shapes[experts + "['down']"] == (16, 512, 2048)
    way_in = "['params']['layer_0']['gdn']['in_proj']"
    assert shapes[way_in + "['qkv']['kernel']"] == (2048, 8192)
    assert shapes[way_in + "['z']['kernel']"] == (2048, 4096)
    assert shapes[way_in + "['ba']['kernel']"] == (2048, 64)
    assert shapes["['params']['layer_0']['gdn']['taps']"] == (4, 8192)
    assert shapes["['params']['layer_3']['attn']['k']['kernel']"] == (
        2048, 512)
    assert shapes["['params']['layer_3']['ff']['shared_gate']"] == (2048,)
    text = compiled.as_text()
    # no (T, T) scores: nothing f32 of 8192 x 8192 (the mixer's [q ; k ; v]
    # is 8 192 lanes of bfloat16, a coincidence of the widths), nothing with
    # a head's 16 or 8 beside it; no state a token: nothing with 8192 tokens
    # beside a head's (128, 128) state
    assert not re.findall(r"f32\[(?:[0-9]+,)*8192,8192\]", text)
    assert not re.findall(r"\[(?:[0-9]+,)*(?:8|16),8192,8192\]", text)
    assert not re.findall(r"\[(?:[0-9]+,)*8192,(?:[0-9]+,)*128,128\]", text)
    # the chunks' states are a block's, not the sequence's: 16 chunks of 32
    # heads' (128 x 128), never 128 chunks
    assert not re.findall(r"f32\[128,(?:[0-9]+,)*128,128\]", text)
    assert "tensor<1x8192x4096xbf16>" in lowered    # queries, 16 x 256
    assert "tensor<1x8192x512xbf16>" in lowered     # keys, 2 x 256
    assert "tensor<16x2048x512xbf16>" in lowered
    plan = compiled.memory_analysis().temp_size_in_bytes
    gib = 2 ** 30
    assert 4.0 * gib < plan < 6.5 * gib, plan / gib
    assert 18 * count + plan + 0.4 * gib < 15.75 * gib
    assert said["attn_layout"].startswith(
        "blockwise 512: 1 of 1 attention layers, 1 full rope, one head of "
        "256 over 2 lane tiles, 8 query heads a key-value head, backward: "
        "one kernel a tile (1 of 1 layers), normed queries and keys (one "
        "pass on the lanes: 1 of 1 layers), rotary of a head's first 64 "
        "lanes (in the head pass: 1 of 1 rope layers), gated output")
    assert said["gdn_layout"].startswith(
        "gated-delta-rule mixer: 3 of 4 layers, 16 query/key heads x 128 "
        "serving 32 value heads x 128, 4 taps with no bias over 8192 lanes; "
        "the rule in chunks of 64, 128 a sequence of 8192")
    assert "gdn/rule: XLA chunks (no Mosaic kernel is written" \
        in said["gdn_layout"]
    assert "taps and SiLU: the Mamba-2 mixer's pass" in said["gdn_layout"]
    assert "the heads' norm before the gate: one pass on the lanes" \
        in said["gdn_layout"]
    assert said["moe_layout"].startswith(
        "16 of 512 experts held (0-15), top 10 of 512, softmax over the "
        "chosen, a shared expert of 512 under a sigmoid gate a token, no "
        "exchange: one device; token-major sums: runs of rows")
    assert "expert block: gate, up and activation one kernel" \
        in said["moe_layout"]
    assert "ssm_layout" not in said and "conv_layout" not in said


@pytest.mark.slow
def test_the_census_of_the_real_step_fills_every_role(for_a_v5e):
    """Every role is filled; the full layer's attention runs on the
    blockwise kernels (no dense attention in the step), the mixer's taps on
    the Mamba-2 pass, the experts on the grouped kernels; a role whose
    kernels are gone is ``missing``."""
    cell, _, lowered, _, _ = for_a_v5e
    roles = cell.config["mosaic_kernels"]
    census = mosaic_census(lowered, roles)
    assert census["missing"] == [], census
    found = census["found"]
    # one attention layer: its forward once, its backward once
    assert found["_causal_fwd_kernel"] == 1
    assert found["_causal_bwd_kernel"] == 1
    # the head pass: queries' and keys' norm + rotary, the mixer's norm
    assert found["_head_norm_fwd_kernel"] and found["_head_norm_bwd_kernel"]
    assert found["_ssm_taps_fwd_kernel"] and found["_ssm_taps_bwd_kernel"]
    assert found["_gated_hidden_kernel"] and found["_gated_hidden_grads_kernel"]
    assert found["_gmm_kernel"] and found["_tgmm_kernel"]
    assert found["_token_sum_kernel"]
    for name, role in (("_causal_fwd_kernel", "_causal_fwd_kernel"),
                       ("_causal_bwd_kernel", "_causal_(?!fwd_)\\w+"),
                       ("_ssm_taps_fwd_kernel", "_ssm_taps_fwd_kernel"),
                       ("_token_sum_kernel", "_token_sum_kernel")):
        assert mosaic_census(lowered.replace(f'"{name}"', '"_other"'),
                             roles)["missing"] == [role]
