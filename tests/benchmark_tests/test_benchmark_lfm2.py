"""The ``lfm2moe`` configuration, its cell, its yardstick and its metric
files: they pass every check the suite applies to a manifest; the file
holds the source's config under the source's keys (the catalog row's
numbers) with the cut values for ``reduced``; the counts of the yardstick
against hand arithmetic; both new metrics read a number from the scopes
the program writes; a tiny rehearsal of the preset runs through
``harness.run_cell``; the census of its real grad step (attention on the
XLA lowering is ``missing``); and the real widths compile for a described
v5e, fit, and hold no T x T array (one compile, shared by the tests that
read it)."""
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
CONFIG, CELL = "lfm2moe", "lfm2moe-train-solo"
OTHER = "trinitymini-train-solo"
OWN_METRICS = ("conv_share_pct", "conv_mix_roofline")
# the metrics the cell reads through the entries the other sparse cells
# read: its name appended to their ``workloads``, no copy
SHARED = ("attn_roofline", "moe_experts_roofline", "moe_router_share_pct",
          "moe_dispatch_share_pct", "moe_experts_share_pct",
          "moe_load_max_over_mean", "moe_assignments_here_pct",
          "moe_dense_calls", "moe_dropped", "moe_sum_spills",
          "ff_dense_share_pct", "attn_gate_share_pct")

# the catalog row's ``config`` (config.json of LiquidAI/LFM2-8B-A1B)
PERIOD = ["full_attention", "conv", "conv", "conv"]
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv"] + PERIOD * 4
    + ["full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}


def test_everything_the_pr_adds_passes_every_check():
    checks.every_check(MAN, MODEL_PRESETS)
    cell = MAN.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "solo-8x8"
    assert cell.traffic == MAN.cell(OTHER).traffic      # the file, unedited
    read = {m["name"] for m in cell.per_layer}
    assert read >= set(OWN_METRICS) | set(SHARED)
    assert "moe_shared_share_pct" not in read           # no shared expert
    assert not [name for name in read if CELL in name]      # no copy
    for name in MAN.cells:
        if name != CELL:
            assert not {m["name"] for m in MAN.cell(name).per_layer} \
                & set(OWN_METRICS)
    # one entry and one file for the cells that share a metric, each
    # through the functions of its own yardstick
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
    assert at > names.index("mtp_share_pct")
    assert "unscoped_share_pct counts these operations too" in \
        files["conv_share_pct"]["note"]
    assert files["conv_mix_roofline"]["params"]["least"] == \
        "short_conv_min_seconds_per_sample"


def test_the_file_holds_the_sources_config_under_the_sources_keys():
    """Every key of the catalog row's config is a top-level key of the
    file with the published value, but the ones ``reduced`` names, which
    hold the value as run; every width agrees with ``model``."""
    on_file = json.loads((ROOT / MAN.configs[CONFIG]["file"]).read_text())
    model, reduced = on_file["model"], on_file["reduced"]
    assert reduced == ["num_hidden_layers", "num_dense_layers",
                       "experts_held", "vocab_size"]
    assert on_file["published"] == {
        "num_hidden_layers": 24, "num_dense_layers": 2, "experts_held": 32,
        "vocab_size": 65536}
    assert len(PUBLISHED["layer_types"]) == 24 and \
        PUBLISHED["layer_types"].count("conv") == 18
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert on_file[key] == model[key] != value
            assert on_file["published"][key] == value
        else:
            assert on_file[key] == value, key
    assert on_file["published"]["experts_held"] == \
        PUBLISHED["num_experts"] == model["num_experts"]
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("dense_width", "intermediate_size"),
                         ("expert_width", "moe_intermediate_size"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("conv_kernel", "conv_L_cache"),
                         ("conv_bias", "conv_bias"),
                         ("rope_theta", "rope_theta"),
                         ("rms_eps", "norm_eps"),
                         ("route_norm", "norm_topk_prob"),
                         ("route_scale", "routed_scaling_factor"),
                         ("selection_bias", "use_expert_bias")):
        assert model[ours] == PUBLISHED[theirs], ours
    assert model["head_dim"] * PUBLISHED["num_attention_heads"] == \
        PUBLISHED["hidden_size"]
    # the layers run: published layer 0 and the period 2-5, kind by kind
    kind = {"conv": "short_conv", "full_attention": "full_rope"}
    ran = [0, 2, 3, 4, 5]
    assert PUBLISHED["layer_types"][2:6] == PERIOD
    assert model["layer_kinds"] == [kind[PUBLISHED["layer_types"][i]]
                                    for i in ran]
    assert not model["num_shared_experts"] and model["tied_embeddings"]
    # the floors of a cut: the leading dense layer, a whole period of four
    # after it, 8 experts, an eighth of the rows
    assert model["num_hidden_layers"] - model["num_dense_layers"] >= 4
    assert model["experts_held"] >= 8
    assert model["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert model["vocab_text"] + model["vocab_image"] == model["vocab_size"]
    assert on_file["layer_shared_by"] == 4
    assert on_file["layer_shared_by"] * model["experts_held"] == \
        model["num_experts"]
    assert on_file["yardstick"] == "lfm2"
    for name in on_file["assumed"]:
        assert len(on_file["assumed_because"][name]) > 20, name
    assert {"layer_kinds", "head_dim", "tied_embeddings", "router_input",
            "attention_bias", "qk_norm", "selection_bias",
            "embed_init_std"} <= set(on_file["assumed"])
    # the two choices no key of model holds
    assert len([k for k in on_file["assumed_because"]
                if "no key of model" in k]) == 2
    roles = on_file["mosaic_kernels"]
    assert roles == ["_halves_fwd_kernel", "_halves_(?!fwd_)\\w+",
                     "_gmm_kernel", "_tgmm_kernel", "_token_sum_kernel"]
    tol = on_file["tolerance"]
    assert 0 < tol["loss_rel"] < 1e-3 and 0 < tol["grad_rel_l2"] < 0.5
    assert len(tol["reason"]) > 200


def test_the_counts_of_the_yardstick_against_hand_arithmetic():
    """ISSUE 48's numbers: 507.8 M parameters, 433 MFLOP a token forward;
    required work only (the causal pairs of the one attention layer on
    64 lanes; the held experts' assignments in expectation; the tied head
    once) and the least seconds of ``conv/mix`` from its bytes."""
    cell = MAN.cell(CELL)
    y, model = cell.yardstick, cell.config["model"]
    t = y.tokens_per_sample(model)
    assert t == 8192
    assert y.conv_layers(model) == 4 and y.expert_layers(model) == 4
    assert y.held_assignments_per_token(model) == 1.0
    conv = 2048 * 3 * 2048 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert y.conv_matmul_params(model) == conv == 16_777_216
    assert y.attention_matmul_params(model) == attn == 10_485_760
    assert round(2 * conv / 1e6, 1) == 33.6 and round(2 * attn / 1e6, 1) \
        == 21.0
    pairs = t * (t + 1) // 2
    assert y.attention_pairs(model, "full_rope") == pairs
    assert y.attention_pairs(model, "short_conv") == 0
    scores = 4 * pairs * 64 * 32
    assert y.attention_flops_forward(model, "full_rope") == scores
    assert round(scores / t / 1e6, 1) == 33.6
    dense, expert = 3 * 2048 * 7168, 3 * 2048 * 1792
    assert round(2 * dense / 1e6, 1) == 88.1
    assert round(2 * expert / 1e6, 1) == 22.0
    assert y.feed_forward_matmul_params(model, 0) == dense
    assert y.feed_forward_matmul_params(model, 1) == 2048 * 32 + expert
    mix = (2 * 3 + 2) * 2048
    assert y.conv_mix_flops_forward(model) == mix
    fwd = (2 * t * (4 * conv + attn + dense + 4 * (2048 * 32 + expert))
           + scores + 4 * t * mix + 2 * 2048 * 16384 * (t - 1))
    assert y.train_flops_per_sample(model) == pytest.approx(3 * fwd)
    assert round(fwd / t / 1e6) == 433                  # MFLOP a token
    assert round(3 * fwd / t / 1e9, 2) == 1.30
    assert 0.30 < 4 * 2 * conv * t / fwd < 0.32         # "31%"
    assert 0.43 < (4 * 2 * conv * t + 2 * attn * t + scores) / fwd < 0.45
    assert 0.15 < 2 * 2048 * 16384 * (t - 1) / fwd < 0.16   # the head
    # the parameters the program initialises
    conv_op = conv + 3 * 2048
    attn_op = attn + 2 * 64
    held = 8 * expert + 2048 * 32 + 32
    layers = {"layer 0": conv_op + dense + 2 * 2048,
              "attention, experts": attn_op + held + 2 * 2048,
              "convolution, experts": conv_op + held + 2 * 2048}
    assert round(layers["layer 0"] / 1e6, 2) == 60.83
    assert round(layers["attention, experts"] / 1e6, 2) == 98.64
    assert round(3 * layers["convolution, experts"] / 1e6, 2) == 314.80
    whole = (layers["layer 0"] + layers["attention, experts"]
             + 3 * layers["convolution, experts"] + 16384 * 2048 + 2048)
    assert whole == 507_820_288 and round(whole / 1e6, 1) == 507.8
    peaks = peaks_for("TPU v5 lite")
    attn_least = y.attention_min_seconds_per_sample(model, peaks)
    experts = y.experts_min_seconds_per_sample(model, peaks)
    conv_least = y.short_conv_min_seconds_per_sample(model, peaks)
    assert attn_least["bandwidth_bound_share"] == 0.0
    assert attn_least["seconds"] == pytest.approx(
        3 * scores / peaks["bf16_flops_per_s"])
    assert experts["seconds"] == pytest.approx(
        3 * 4 * 2 * expert * t / peaks["bf16_flops_per_s"])
    # conv/mix is its bytes: 4 arrays of (T, 2048) bf16 forward (16 KiB a
    # token), 7 backward, a convolution layer; 0.16 ms a layer forward
    assert conv_least["bandwidth_bound_share"] == 1.0
    array = t * 2048 * 2
    assert 4 * array / t == 16384
    assert conv_least["seconds"] == pytest.approx(
        4 * 11 * array / peaks["hbm_bytes_per_s"])
    assert round(4 * array / peaks["hbm_bytes_per_s"] * 1e3, 2) == 0.16
    slow = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1e30}
    assert y.short_conv_min_seconds_per_sample(model, slow)["seconds"] == \
        pytest.approx(4 * 3 * t * mix)


def _path(rest, layer="layer_2", backward=False):
    root = "jit(grad_step)/while/body/closed_call/"
    if backward:
        root += "transpose(jvp(SparseLM))/jvp(SparseLM)/checkpoint/"
    else:
        root += "jvp(SparseLM)/"
    return root + f"{layer}/" + rest


def _scoped_trace():
    """Device operations under the scope paths the program's grad step
    writes on the chip (``chiprun_out``'s ``device_scopes.json`` of the
    cell's first traced run, PR 48): 100 ns each, back to back."""
    ops = [
        ("convolution_bitcast_fusion", _path("conv/in_proj/dot_general:")),
        ("slice_multiply_fusion", _path("conv/mix/mul:")),
        ("fusion", _path("conv/mix/convert_element_type:", backward=True)),
        ("bitcast_multiply_fusion", _path("conv/mix/mul:", backward=True)),
        ("fusion", _path("rematted_computation/layer_2/conv/mix/"
                         "convert_element_type:", layer="x", backward=True)),
        ("fusion", _path("conv/out_proj/dot_general:")),
        ("select_add_fusion", _path("conv/in_proj/dot_general:",
                                    backward=True)),
        ("attn[mosaic]", _path("attn/pallas_call:", layer="layer_1")),
        ("fusion", _path("attn/q/dot_general:", layer="layer_1")),
        ("fusion", _path("attn/qk_norm/rms_norm/convert_element_type:",
                         layer="layer_1")),
        ("fusion", _path("attn/qk_norm/convert_element_type:",
                         layer="layer_1")),
        ("fusion", _path("ff.route/ff/router/btd,de->bte/dot_general:")),
        ("experts[mosaic]", _path(
            "ff/cond/branch_1_fun/experts/pallas_call:")),
        ("fusion", _path("ff/dense/up/dot_general:", layer="layer_0")),
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
    # the operator whole: its projections and its mix, forward, replay and
    # backward
    assert read("conv_share_pct") == pytest.approx(7 * share)
    y, model = cell.yardstick, cell.config["model"]
    least = y.short_conv_min_seconds_per_sample(model, peaks)["seconds"]
    # ... and the mix alone: four operations, whatever their names
    assert read("conv_mix_roofline") == pytest.approx(
        100 * least * 24 / 400e-9)
    attn = y.attention_min_seconds_per_sample(model, peaks)["seconds"]
    assert read("attn_roofline") == pytest.approx(100 * attn * 24 / 100e-9)
    # the head norm and rotary as XLA code, under attn/qk_norm
    assert read("attn_gate_share_pct") == pytest.approx(2 * share)
    assert read("ff_dense_share_pct") == pytest.approx(share)
    assert read("moe_router_share_pct") == pytest.approx(share)
    assert read("moe_experts_share_pct") == pytest.approx(share)
    # the accepted attention share keeps meaning attention; the accepted
    # unscoped share counts the convolution's XLA operations, as the new
    # file's note says
    assert read("attn_xla_share_pct") == pytest.approx(3 * share)
    assert read("unscoped_share_pct") == pytest.approx(7 * share)
    # a program with none of these scopes (the parent): a share of nothing
    # reads 0, a roofline with nothing to read is left out
    bare = dict(raw, planes=[dict(raw["planes"][0], lines=[{
        "name": "XLA Ops", "events": [["fusion", 0, 100, ""]]}]),
        raw["planes"][1]])
    ctx.trace = T.Reduced(bare)
    assert read("conv_share_pct") == 0.0
    assert read("conv_mix_roofline") is None
    assert read("attn_roofline") is None


def test_a_traced_rehearsal_of_the_preset_runs_through_the_harness(
        tmp_path):
    """The tiny preset, its yardstick and the new metric files through
    ``harness.run_cell`` on the CPU, the kernels for two heads a lane tile
    interpreted: the reference check passes, the program-fed metrics are
    read under the names the sparse cells share, the trace-fed ones are
    left out (no device plane here), ``engagement`` prints the new
    attributes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", SECS="6",
               PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable,
         str(Path(__file__).parent / "lfm2_rehearse.py"), "1",
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
    assert set(said) >= {"attn_layout", "conv_layout", "head_layout",
                         "moe_layout", "memory_layout", "layer_loop"}
    assert said["attn_layout"].startswith(
        "blockwise 512: 1 of 1 attention layers, 1 full rope, 2 heads of 64 "
        "a lane tile, 2 query heads a key-value head, backward: one kernel a "
        "tile (1 of 1 layers), normed queries and keys (XLA: head_dim 64 is "
        "not whole 128-lane tiles)")
    assert said["conv_layout"].startswith(
        "gated short convolution: 2 of 3 layers, 3 taps")
    assert said["head_layout"].startswith("tied: the head is the embedding")
    assert list(result)[-1] == "compared"


# -- the real widths, compiled once for a described v5e ----------------------

@pytest.fixture(scope="module")
def for_a_v5e():
    """The cell's grad step (micro 1 x accum 8 of 8 192 tokens) lowered
    and compiled in the sandbox for one v5e chip, once for the tests below
    (about a minute)."""
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
    try:       # the dispatchers pick the Mosaic kernels for a TPU
        lowered = step.lower(params, batch)
        compiled = lowered.compile()
        said = sparse_lm.engagement_records(cfg, mesh)
        # ... and a second lowering whose attention gives way to the dense
        # XLA code, as a width the kernels refuse would
        refused, sparse_lm.kernels.blockwise_fits = \
            sparse_lm.kernels.blockwise_fits, lambda *a: "the test says so"
        try:
            on_xla = jax.jit(make_grad_step(model, accum_steps=accum)) \
                .lower(params, batch).as_text()
        finally:
            sparse_lm.kernels.blockwise_fits = refused
    finally:
        jax.default_backend = default_backend
    count = sum(a.size for a in jax.tree.leaves(shapes))
    return cell, count, lowered.as_text(), compiled, said, on_xla


def test_the_real_widths_compile_for_a_described_v5e_and_fit(for_a_v5e):
    """The Mosaic kernels for two 64-wide heads a lane tile lower, nothing
    of size T x T is in the step's plan, no (.., 3, 2048) array of the
    taps' shifts, one table and no copy of its transpose, and the
    parameters' state (18 bytes a parameter in buffers at the loop's peak,
    PERF.md section 4) + plan + code stays under the chip's 15.75 GiB."""
    cell, count, lowered, compiled, said, _ = for_a_v5e
    assert count == 507_820_288
    text = compiled.as_text()
    assert not re.findall(r"\[(?:[0-9]+,)*8192,8192\]", text)
    assert not re.findall(r"\[(?:[0-9]+,)*8192,3,2048\]", text)
    # the head reads the table where it lies, rows major: every (2048,
    # 16384) array is a chunk of the logits in f32 (or its mask), none a
    # bf16 copy of the table's transpose, and no table is laid out columns
    # major
    assert "bf16[16384,2048]{1,0" in text
    assert not re.findall(r"bf16\[2048,16384\]", text)
    assert not re.findall(r"\[16384,2048\]\{0,1", text)
    # queries and keys reach the kernels 2048 and 512 lanes wide
    assert "tensor<1x8192x2048xbf16>" in lowered
    assert "tensor<1x8192x512xbf16>" in lowered
    plan = compiled.memory_analysis().temp_size_in_bytes
    gib = 2 ** 30
    assert 4.0 * gib < plan < 6.5 * gib, plan / gib
    assert 18 * count + plan + 0.3 * gib < 15.75 * gib
    assert said["attn_layout"] == (
        "blockwise 512: 1 of 1 attention layers, 1 full rope, 2 heads of 64 "
        "a lane tile, 4 query heads a key-value head, backward: one kernel a "
        "tile (1 of 1 layers), normed queries and keys (XLA: head_dim 64 is "
        "not whole 128-lane tiles), rotary (XLA: head_dim 64 is not whole "
        "128-lane tiles)")
    assert said["conv_layout"].startswith(
        "gated short convolution: 4 of 5 layers, 3 taps, causal, depthwise "
        "over 2048 lanes; conv/mix is XLA code")
    assert said["moe_layout"] == (
        "8 of 32 experts held (0-7), top 4 of 32, sigmoid, bias, norm, x1, "
        "layers 0-0 dense 7168, no exchange: one device; token-major sums: "
        "runs of rows, 256 tokens a tile, windows of 64 rows")
    assert said["head_layout"].startswith(
        "tied: the head is the embedding's table (16384 x 2048)")


def test_the_census_of_the_real_step_fills_every_role(for_a_v5e):
    """Every role is filled; the backward is one role however many kernels
    it is; attention on the XLA lowering is ``missing``."""
    cell, _, lowered, _, _, on_xla = for_a_v5e
    roles = cell.config["mosaic_kernels"]
    census = mosaic_census(lowered, roles)
    assert census["missing"] == [] and census["unlisted"] == {}, census
    found = census["found"]
    # one attention layer: its forward once (the backward replays the
    # projections, not the kernel), its backward once
    assert found["_halves_fwd_kernel"] == 1
    assert found["_halves_bwd_kernel"] == 1
    assert found["_gmm_kernel"] and found["_tgmm_kernel"] \
        and found["_token_sum_kernel"]
    # a backward split in two fills the role
    split = lowered.replace('"_halves_bwd_kernel"', '"_halves_dq_kernel"')
    assert mosaic_census(split, roles)["missing"] == []
    only_fwd = lowered.replace('"_halves_bwd_kernel"', '"_other"')
    assert mosaic_census(only_fwd, roles)["missing"] == [
        "_halves_(?!fwd_)\\w+"]
    # the 128-wide kernels' names fill neither role
    theirs = lowered.replace('"_halves_', '"_causal_')
    assert mosaic_census(theirs, roles)["missing"] == [
        "_halves_fwd_kernel", "_halves_(?!fwd_)\\w+"]
    # attention on the XLA lowering: both roles are missing, the rest stay
    census = mosaic_census(on_xla, roles)
    assert census["missing"] == ["_halves_fwd_kernel",
                                 "_halves_(?!fwd_)\\w+"], census
    assert "_gmm_kernel" in census["found"]
