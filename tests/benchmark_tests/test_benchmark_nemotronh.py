"""The ``twotower30b`` configuration, its cell, its yardstick and its metric
files: they pass every check the suite applies to a manifest; the file
holds the source's config under the source's keys (the catalog row's
numbers) with the cut values for ``reduced``; the counts of the yardstick
against hand arithmetic; both new metrics read a number from the scopes
the program writes; a tiny rehearsal of the preset runs through
``harness.run_cell``; and the real widths compile for a described v5e, fit,
fill every role of the census and hold no T x T array and no state a token
(ONE lowering and compile, shared by the tests that read it). A sentence
the program says is held by the clauses a test is about (``startswith``,
``in``), never whole: a later PR may lengthen it."""
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
CONFIG, CELL = "twotower30b", "twotower30b-train-solo"
OTHER = "joyaiflash-train-solo"
OWN_METRICS = ("ssm_share_pct", "ssm_scan_roofline")
# the metrics the cell reads through the entries the other sparse cells
# read: its name appended to their ``workloads``, no copy
SHARED = ("attn_roofline", "moe_experts_roofline", "moe_router_share_pct",
          "moe_dispatch_share_pct", "moe_experts_share_pct",
          "moe_load_max_over_mean", "moe_assignments_here_pct",
          "moe_dense_calls", "moe_dropped", "moe_sum_spills",
          "moe_shared_share_pct")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
PARAMETERS = 528_092_736 + 3 * 128      # + the three routers' bias buffers


def _catalog_row():
    """The catalog row's ``config``, where the guide is installed."""
    path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not path.is_file():
        return None
    rows = [json.loads(line) for line in path.read_text().splitlines()
            if line.strip()]
    return next(r for r in rows if r["name"]
                == "Nemotron-Labs-TwoTower-30B-A3B-Base-BF16")


def test_everything_the_pr_adds_passes_every_check():
    checks.every_check(MAN, MODEL_PRESETS)
    cell = MAN.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "solo-8x8"
    assert cell.traffic == MAN.cell(OTHER).traffic      # the file, unedited
    read = {m["name"] for m in cell.per_layer}
    assert read >= set(OWN_METRICS) | set(SHARED)
    # it has no dense block, no gate, no convolution operator
    assert not read & {"ff_dense_share_pct", "attn_gate_share_pct",
                       "conv_share_pct", "conv_mix_roofline"}
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
    assert at > names.index("kernel_site_again_calls")
    assert "unscoped_share_pct counts these operations too" in \
        files["ssm_share_pct"]["note"]
    assert files["ssm_scan_roofline"]["params"]["least"] == \
        "ssm_scan_min_seconds_per_sample"
    assert len(MAN.cells) == 9
    assert sum(MAN.cell(name).chips == 4 for name in MAN.cells) == 1


def test_the_file_holds_the_sources_config_under_the_sources_keys():
    """Every key of the catalog row's config is a top-level key of the
    file with the published value, but the ones ``reduced`` names, which
    hold the value as run; every width agrees with ``model``."""
    on_file = json.loads((ROOT / MAN.configs[CONFIG]["file"]).read_text())
    model, reduced = on_file["model"], on_file["reduced"]
    assert reduced == ["num_hidden_layers", "experts_held", "vocab_size"]
    assert on_file["published"] == {
        "num_hidden_layers": 52, "experts_held": 128, "vocab_size": 131072}
    row = _catalog_row()
    if row is not None:
        assert on_file["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in reduced:
                assert on_file[key] == model[key] != value
                assert on_file["published"][key] == value
            else:
                assert on_file[key] == value, key
    assert on_file["hybrid_override_pattern"] == PATTERN
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) \
        == (23, 23, 6) and len(PATTERN) == 52
    # the layers run are the pattern's first seven, letter by letter
    kind = {"M": "mamba2", "E": "experts", "*": "full_nope"}
    assert model["layer_kinds"] == [kind[c] for c in PATTERN[:7]]
    assert model["num_hidden_layers"] == 7 and model["one_part_layers"]
    assert [i for i, c in enumerate(PATTERN) if c == "*"] == [
        5, 12, 19, 26, 33, 42]
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("expert_width", "moe_intermediate_size"),
                         ("shared_expert_width",
                          "moe_shared_expert_intermediate_size"),
                         ("num_shared_experts", "n_shared_experts"),
                         ("num_experts", "n_routed_experts"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("conv_kernel", "conv_kernel"),
                         ("conv_bias", "use_conv_bias"),
                         ("mamba_num_heads", "mamba_num_heads"),
                         ("mamba_head_dim", "mamba_head_dim"),
                         ("ssm_groups", "n_groups"),
                         ("ssm_state_size", "ssm_state_size"),
                         ("ssm_chunk", "chunk_size"),
                         ("rms_eps", "norm_eps"),
                         ("rms_eps", "layer_norm_epsilon"),
                         ("route_norm", "norm_topk_prob"),
                         ("route_scale", "routed_scaling_factor"),
                         ("tied_embeddings", "tie_word_embeddings"),
                         ("attention_bias", "attention_bias")):
        assert model[ours] == on_file[theirs], ours
    assert (model["hidden_size"], model["expert_width"],
            model["shared_expert_width"]) == (2688, 1856, 3712)
    assert model["hidden_act"] == on_file["mlp_hidden_act"] == "relu2"
    assert not model["expert_gated"]
    # the floors of a cut: a whole turn of the pattern's cycle, 8 experts,
    # an eighth of the rows; no width touched
    assert model["experts_held"] >= 8
    assert model["vocab_size"] * 8 >= on_file["published"]["vocab_size"]
    assert model["vocab_text"] + model["vocab_image"] == model["vocab_size"]
    assert on_file["layer_shared_by"] == 16
    assert on_file["layer_shared_by"] * model["experts_held"] == \
        model["num_experts"]
    assert on_file["yardstick"] == "nemotronh"
    assert "denoiser tower" in on_file["source_note"] \
        and "no part of this configuration" in on_file["source_note"]
    for name in on_file["assumed"]:
        assert len(on_file["assumed_because"][name]) > 20, name
    assert {"layer_kinds", "one_part_layers", "mamba_num_heads",
            "rope_theta", "router_input", "selection_bias",
            "residual_rescale_layers", "embed_init_std"} <= set(
                on_file["assumed"])
    assert on_file["rescale_prenorm_residual"] is True
    assert model["residual_rescale_layers"] == \
        on_file["published"]["num_hidden_layers"]
    unkeyed = [k for k in on_file["assumed_because"] if "no key of model" in k]
    assert any(k.startswith("gated_norm") for k in unkeyed)
    assert any(k.startswith("init") for k in unkeyed)
    roles = on_file["mosaic_kernels"]
    assert {"_causal_fwd_kernel", "_gmm_kernel", "_tgmm_kernel",
            "_token_sum_kernel"} <= set(roles)
    tol = on_file["tolerance"]
    assert 0 < tol["loss_rel"] < 1e-3 and 0 < tol["grad_rel_l2"] < 0.5
    assert len(tol["reason"]) > 200 and "float8" in tol["reason"]


def test_the_counts_of_the_yardstick_against_hand_arithmetic():
    """ISSUE 57's numbers: 528.1 M parameters; forward a token an M layer
    77.4 MFLOP of projections + the recurrence's own 2.1 (and the taps), an
    E layer 39.9 shared + 0.7 router + 7.5 routed, the attention layer 46.8
    + 67.1, the head 88.1; the least seconds of the scan from its bytes."""
    cell = MAN.cell(CELL)
    y, model = cell.yardstick, cell.config["model"]
    t = y.tokens_per_sample(model)
    assert t == 8192
    assert y.mamba_layers(model) == 3 and y.expert_layers(model) == 3
    assert y.held_assignments_per_token(model) == 0.375
    mamba = 2688 * 10304 + 4096 * 2688
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256
    assert y.mamba_inner(model) == 4096 and y.mamba_conv_lanes(model) == 6144
    assert y.mamba_matmul_params(model) == mamba == 38_707_200
    assert y.attention_matmul_params(model) == attn == 23_396_352
    assert round(2 * mamba / 1e6, 1) == 77.4
    assert round(2 * attn / 1e6, 1) == 46.8
    scan = 4 * 4096 * 128 + 2 * 4 * 6144
    assert y.ssm_scan_flops_forward(model) == scan == 2_146_304
    pairs = t * (t + 1) // 2
    assert y.attention_pairs(model, "full_nope") == pairs
    assert y.attention_pairs(model, "mamba2") == 0
    scores = 4 * pairs * 128 * 32
    assert y.attention_flops_forward(model, "full_nope") == scores
    assert round(scores / t / 1e6, 1) == 67.1
    shared, expert, router = 2 * 2688 * 3712, 2 * 2688 * 1856, 2688 * 128
    assert round(2 * shared / 1e6, 1) == 39.9
    assert round(2 * router / 1e6, 1) == 0.7
    assert round(2 * 0.375 * expert / 1e6, 1) == 7.5
    assert y.expert_layer_matmul_params(model) == \
        router + shared + 0.375 * expert
    head = 2 * 2688 * 16384
    assert round(head / 1e6, 1) == 88.1
    fwd = (3 * t * (2 * mamba + scan)
           + 3 * 2 * t * (router + shared + 0.375 * expert)
           + 2 * t * attn + scores + head * (t - 1))
    assert y.train_flops_per_sample(model) == pytest.approx(3 * fwd)
    assert round(fwd / t / 1e6) == 585                  # MFLOP a token
    assert 0.40 < 3 * (2 * mamba + scan) * t / fwd < 0.42   # the mixers
    assert 0.15 < head * (t - 1) / fwd < 0.16               # the head
    # the parameters the program initialises
    m_layer = mamba + 4 * 6144 + 6144 + 3 * 64 + 4096 + 2688
    a_layer = attn + 2688
    e_layer = router + shared + 8 * expert + 2688
    assert (m_layer, a_layer, e_layer) == (38_744_896, 23_399_040,
                                           100_125_312)
    whole = 3 * m_layer + 3 * e_layer + a_layer + 2 * 16384 * 2688 + 2688
    assert whole == 528_092_736 and round(whole / 1e6, 1) == 528.1
    peaks = peaks_for("TPU v5 lite")
    attn_least = y.attention_min_seconds_per_sample(model, peaks)
    experts = y.experts_min_seconds_per_sample(model, peaks)
    scan_least = y.ssm_scan_min_seconds_per_sample(model, peaks)
    assert attn_least["bandwidth_bound_share"] == 0.0
    assert attn_least["seconds"] == pytest.approx(
        3 * scores / peaks["bf16_flops_per_s"])
    # two products a direction, not three
    assert experts["seconds"] == pytest.approx(
        3 * 3 * 2 * 0.375 * expert * t / peaks["bf16_flops_per_s"])
    # the scan is its bytes: xBC and dt read, y written, 20.6 KB a token
    # forward; xBC, dt and y's cotangent read, two cotangents written back
    assert scan_least["bandwidth_bound_share"] == 1.0
    forward = (6144 + 64 + 4096) * 2
    backward = (2 * (6144 + 64) + 4096) * 2
    assert forward == 20_608
    assert scan_least["seconds"] == pytest.approx(
        3 * t * (forward + backward) / peaks["hbm_bytes_per_s"])
    assert round(t * forward / peaks["hbm_bytes_per_s"] * 1e3, 2) == 0.21
    slow = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1e30}
    assert y.ssm_scan_min_seconds_per_sample(model, slow)["seconds"] == \
        pytest.approx(3 * 3 * t * scan)


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
        ("convolution_bitcast_fusion", _path("ssm/in_proj/dot_general:")),
        ("fusion", _path("ssm/conv/mul:")),
        ("fusion", _path("ssm/scan/exp:")),
        ("fusion", _path("ssm/scan/while/body/mul:", backward=True)),
        ("fusion", _path("rematted_computation/layer_0/ssm/scan/"
                         "bcgrij,bcjgrp->bcigrp/dot_general:", layer="x",
                         backward=True)),
        ("fusion", _path("ssm/conv/logistic:", backward=True)),
        ("fusion", _path("ssm/gate_norm/mul:")),
        ("fusion", _path("ssm/out_proj/dot_general:")),
        ("attn[mosaic]", _path("attn/pallas_call:", layer="layer_5")),
        ("fusion", _path("attn/q/dot_general:", layer="layer_5")),
        ("fusion", _path("ff.route/ff/router/btd,de->bte/dot_general:",
                         layer="layer_1")),
        ("experts[mosaic]", _path(
            "ff/cond/branch_1_fun/experts/pallas_call:", layer="layer_1")),
        ("fusion", _path("ff/shared/up/dot_general:", layer="layer_1")),
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
    # the mixer whole: projections, taps, scan, gated norm; forward,
    # replay and backward
    assert read("ssm_share_pct") == pytest.approx(8 * share)
    y, model = cell.yardstick, cell.config["model"]
    least = y.ssm_scan_min_seconds_per_sample(model, peaks)["seconds"]
    # ... and the taps and the scan alone: five operations, whatever
    # their names
    assert read("ssm_scan_roofline") == pytest.approx(
        100 * least * 24 / 500e-9)
    attn = y.attention_min_seconds_per_sample(model, peaks)["seconds"]
    assert read("attn_roofline") == pytest.approx(100 * attn * 24 / 100e-9)
    assert read("moe_router_share_pct") == pytest.approx(share)
    assert read("moe_experts_share_pct") == pytest.approx(share)
    assert read("moe_shared_share_pct") == pytest.approx(share)
    # the accepted attention share keeps meaning attention; the accepted
    # unscoped share counts the mixer's operations too, as the new file's
    # note says, all but ``ssm/gate_norm`` (its expression knows a scope
    # that ends in ``norm``)
    assert read("attn_xla_share_pct") == pytest.approx(share)
    assert read("unscoped_share_pct") == pytest.approx(7 * share)
    # a program with none of these scopes (the parent): a share of nothing
    # reads 0, a roofline with nothing to read is left out
    bare = dict(raw, planes=[dict(raw["planes"][0], lines=[{
        "name": "XLA Ops", "events": [["fusion", 0, 100, ""]]}]),
        raw["planes"][1]])
    ctx.trace = T.Reduced(bare)
    assert read("ssm_share_pct") == 0.0
    assert read("ssm_scan_roofline") is None


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
         str(Path(__file__).parent / "nemotronh_rehearse.py"), "1",
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
    assert set(said) >= {"attn_layout", "ssm_layout", "moe_layout",
                         "memory_layout", "layer_loop"}
    assert said["ssm_layout"].startswith(
        "Mamba-2 mixer: 1 of 3 layers, 4 heads x 8, 2 groups of B and C, "
        "state 16, 4 taps")
    assert "chunks of 8, 4 a sequence of 32" in said["ssm_layout"]
    assert "one part a layer behind one norm: mamba2 experts full_nope" \
        in said["layer_loop"]
    assert "two products an expert, not gated" in said["moe_layout"]
    assert "backward: one kernel a tile" in said["attn_layout"]
    assert list(result)[-1] == "compared"


# -- the real widths, lowered and compiled once for a described v5e ----------

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


def test_the_real_widths_compile_for_a_described_v5e_and_fit(for_a_v5e):
    """The grouped kernels take the width 1 856 with the leaves unpadded,
    the blockwise kernels 16 query heads a key-value tile; nothing of size
    T x T and no state a token is in the step's plan; and the parameters'
    state (18 bytes a parameter in buffers at the loop's peak, PERF.md
    section 4) + plan + code stays under the chip's 15.75 GiB."""
    cell, shapes, lowered, compiled, said = for_a_v5e
    count = sum(int(__import__("math").prod(s)) for s in shapes.values())
    assert count == PARAMETERS
    experts = "['params']['layer_1']['ff']['experts']"
    assert shapes[experts + "['up']"] == (8, 2688, 1856)
    assert shapes[experts + "['down']"] == (8, 1856, 2688)
    assert shapes["['params']['layer_0']['ssm']['in_proj']['kernel']"] == (
        2688, 10304)
    assert shapes["['params']['layer_5']['attn']['k']['kernel']"] == (
        2688, 256)
    text = compiled.as_text()
    # no (T, T) array; no state a token: nothing with 8192 tokens beside a
    # head's (64, 128) state, in either layout of the heads
    assert not re.findall(r"\[(?:[0-9]+,)*8192,8192\]", text)
    assert not re.findall(r"\[(?:[0-9]+,)*8192,(?:[0-9]+,)*64,128\]", text)
    assert not re.findall(r"\[(?:[0-9]+,)*8192,(?:[0-9]+,)*128,64\]", text)
    # what the chunked form does hold: the chunks' states, and the masked
    # (128 x 128) form a head and chunk
    assert re.findall(r"f32\[64,1,8,8,64,128\]", text)
    assert re.findall(r"f32\[(?:1,)?64,8,8,128,128\]", text)
    # the unpadded leaves reach the grouped kernels as they are, and
    # queries and keys the blockwise kernels 4096 and 256 lanes wide
    assert "tensor<8x2688x1856xbf16>" in lowered
    assert "tensor<8x1856x2688xbf16>" in lowered
    assert not re.findall(r"x1920[x>]", lowered)    # nothing padded to 15
    assert "tensor<1x8192x4096xbf16>" in lowered
    assert "tensor<1x8192x256xbf16>" in lowered
    plan = compiled.memory_analysis().temp_size_in_bytes
    gib = 2 ** 30
    assert 4.0 * gib < plan < 6.0 * gib, plan / gib
    assert 18 * count + plan + 0.3 * gib < 15.75 * gib
    assert said["attn_layout"].startswith(
        "blockwise 512: 1 of 1 attention layers, 1 full no-rope + 0 window "
        "0 rope, 16 query heads a key-value head, backward: one kernel a "
        "tile (1 of 1 layers)")
    assert said["ssm_layout"].startswith(
        "Mamba-2 mixer: 3 of 7 layers, 64 heads x 64, 8 groups of B and C, "
        "state 128, 4 taps with a bias over 6144 lanes; chunked scan: "
        "chunks of 128, 64 a sequence of 8192")
    assert "XLA code" in said["ssm_layout"]
    assert said["moe_layout"].startswith(
        "8 of 128 experts held (0-7), top 6 of 128, sigmoid, bias, norm, "
        "x2.5, a shared expert of 3712, no exchange: one device")
    assert "expert block: two products an expert, not gated: up and the " \
        "activation one kernel" in said["moe_layout"]
    assert "one part a layer behind one norm: mamba2 experts mamba2 " \
        "experts mamba2 full_nope experts" in said["layer_loop"]
    assert "conv_layout" not in said


def test_the_census_of_the_real_step_fills_every_role(for_a_v5e):
    """Every role is filled and no kernel is unlisted; the experts run on
    the grouped kernels (the ungated block's two among them); a role whose
    kernels are gone is ``missing``."""
    cell, _, lowered, _, _ = for_a_v5e
    roles = cell.config["mosaic_kernels"]
    census = mosaic_census(lowered, roles)
    assert census["missing"] == [] and census["unlisted"] == {}, census
    found = census["found"]
    # one attention layer: its forward once, its backward once
    assert found["_causal_fwd_kernel"] == 1
    assert found["_causal_bwd_kernel"] == 1
    # three expert layers: the block's forward in the forward pass and in
    # the replay, its backward once; the down product, the rows' gradient
    # and two weight gradients a layer
    assert found["_hidden_kernel"] == 6 and found["_hidden_grads_kernel"] == 3
    assert found["_gmm_kernel"] == 9 and found["_tgmm_kernel"] == 6
    assert found["_token_sum_kernel"]
    assert not [name for name in found if "gated" in name]
    only_xla = lowered.replace('"_hidden_kernel"', '"_other"').replace(
        '"_hidden_grads_kernel"', '"_other"')
    assert mosaic_census(only_xla, roles)["missing"] == ["_hidden\\w*"]
    dense = re.sub(r'"_(gmm|tgmm|hidden|hidden_grads)_kernel"', '"_other"',
                   lowered)
    assert set(mosaic_census(dense, roles)["missing"]) == {
        "_gmm_kernel", "_tgmm_kernel", "_hidden\\w*"}
