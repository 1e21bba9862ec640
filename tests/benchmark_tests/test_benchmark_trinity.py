"""The ``trinitymini`` configuration, its cell, its yardstick and its metric
files: they pass every check the suite applies to a manifest; the file
holds the source's config under the source's keys; the counts of the
yardstick; every new per-layer metric reads a number from what the program
writes; a tiny rehearsal of the preset runs through ``harness.run_cell``;
and (slow) the real widths compile for a described v5e and fit."""
import json
import os
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
CONFIG, CELL = "trinitymini", "trinitymini-train-solo"
SPARSE_CELL = "smallthinker21b-train-solo"
OWN_METRICS = ("moe_shared_share_pct", "ff_dense_share_pct",
               "attn_gate_share_pct")
# the metrics the cell reads through the entry the sparse cell reads (its
# own copies under ``<metric>.<cell>`` until PR 43)
SHARED = ("attn_roofline", "moe_experts_roofline", "moe_router_share_pct",
          "moe_dispatch_share_pct", "moe_experts_share_pct",
          "moe_load_max_over_mean", "moe_assignments_here_pct",
          "moe_dense_calls", "moe_dropped", "moe_sum_spills")

# config.json of arcee-ai/Trinity-Mini: its numbers
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": PERIOD * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}


def test_everything_the_pr_adds_passes_every_check():
    checks.every_check(MAN, MODEL_PRESETS)
    cell = MAN.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "solo-8x8"
    # the same 8 sequences a step as the sparse cell's solo-8x4
    other = MAN.cell(SPARSE_CELL).traffic
    assert (cell.traffic["per_device_batch"],
            cell.traffic["grad_accum_steps"]) == (1, 8)
    assert other["per_device_batch"] * other["grad_accum_steps"] == 8
    for key in set(other) - {"why", "per_device_batch", "grad_accum_steps"}:
        assert cell.traffic[key] == other[key], key
    read = {m["name"] for m in cell.per_layer}
    assert read >= set(OWN_METRICS) | set(SHARED)
    assert not [name for name in read if CELL in name]      # no copy
    for name in ("flagship-train-solo", "xl-train-solo",
                 "flagship-train-dp4", SPARSE_CELL):
        assert not {m["name"] for m in MAN.cell(name).per_layer} \
            & set(OWN_METRICS)
    # one entry and one file for both sparse cells, each through the
    # functions of its own yardstick
    files = {m["name"]: m for m in cell.per_layer}
    theirs = {m["name"]: m for m in MAN.cell(SPARSE_CELL).per_layer}
    for name in SHARED:
        assert files[name] == theirs[name], name
    least = files["moe_experts_roofline"]["params"]["least"]
    assert getattr(cell.yardstick, least).__module__ \
        != getattr(MAN.cell(SPARSE_CELL).yardstick, least).__module__


def test_the_file_holds_the_sources_config_under_the_sources_keys():
    """Every key of the source's config.json is a top-level key of the
    file with the published value, but the ones ``reduced`` names, which
    hold the value as run; every width agrees with ``model``."""
    on_file = json.loads((ROOT / MAN.configs[CONFIG]["file"]).read_text())
    model, reduced = on_file["model"], on_file["reduced"]
    assert reduced == ["num_hidden_layers", "num_dense_layers",
                       "experts_held", "vocab_size"]
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
                         ("head_dim", "head_dim"),
                         ("dense_width", "intermediate_size"),
                         ("expert_width", "moe_intermediate_size"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("num_shared_experts", "num_shared_experts"),
                         ("window", "sliding_window"),
                         ("rope_theta", "rope_theta"),
                         ("rms_eps", "rms_norm_eps"),
                         ("hidden_act", "hidden_act"),
                         ("score_func", "score_func"),
                         ("route_norm", "route_norm"),
                         ("route_scale", "route_scale"),
                         ("mup_enabled", "mup_enabled")):
        assert model[ours] == PUBLISHED[theirs], ours
    # the layers run: published layer 0 (dense) and layers 4-7 (a period)
    kind = {"sliding_attention": "window_rope", "full_attention": "full_nope"}
    ran = [PUBLISHED["layer_types"][i] for i in (0, 4, 5, 6, 7)]
    assert model["layer_kinds"] == [kind[k] for k in ran]
    # the floors of a cut: leading dense layers once, a whole period and
    # four layers after them, 8 experts, an eighth of the rows
    assert model["num_dense_layers"] >= 1
    assert model["num_hidden_layers"] - model["num_dense_layers"] >= 4
    assert model["experts_held"] >= 8
    assert model["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert model["vocab_text"] + model["vocab_image"] == model["vocab_size"]
    assert on_file["layer_shared_by"] == 16
    assert on_file["yardstick"] == "trinity"
    for name in on_file["assumed"]:
        assert len(on_file["assumed_because"][name]) > 20, name
    assert {"attention_gate", "qk_norm", "sandwich_norms", "layer_kinds",
            "embed_init_std"} <= set(on_file["assumed"])
    assert "_token_sum_kernel" in on_file["mosaic_kernels"]
    tol = on_file["tolerance"]
    # between the system's largest reading on the chip and the float8
    # control's, with room on both sides (PERF.md section 6, PR 33)
    assert 4.7e-6 < tol["loss_rel"] < 1e-3
    assert 1.3 * 0.147 < tol["grad_rel_l2"] < 0.320 / 1.3
    assert len(tol["reason"]) > 200


def test_the_counts_of_the_yardstick():
    """Required work only: the five projections, the dense block, the
    shared expert, the held experts' assignments in expectation, pairs
    inside the window, the sliced head."""
    cell = MAN.cell(CELL)
    y, model = cell.yardstick, cell.config["model"]
    t = y.tokens_per_sample(model)
    assert t == 8192
    full = y.attention_pairs(model, "full_nope")
    window = y.attention_pairs(model, "window_rope")
    assert full == t * (t + 1) // 2
    assert window == 2048 * 2049 // 2 + (t - 2048) * 2048
    assert 0.43 < window / full < 0.44
    assert y.held_assignments_per_token(model) == 0.5
    attn = 2048 * 128 * (3 * 32 + 2 * 4)            # q, out, gate; k, v
    assert y.attention_matmul_params(model) == attn
    # the issue's counts a token and layer, forward: 54.5 / 75 / 12.6 MFLOP
    assert round(2 * attn / 1e6, 1) == 54.5
    assert round(2 * 3 * 2048 * 6144 / 1e6, 1) == 75.5
    assert round(2 * 3 * 2048 * 1024 / 1e6, 1) == 12.6
    expert = 3 * 2048 * 1024
    fwd = (2 * t * ((attn + 3 * 2048 * 6144)
                    + 4 * (attn + 2048 * 128 + (1 + 0.5) * expert))
           + 4 * 128 * 32 * (4 * window + full)
           + 2 * 2048 * 25024 * (t - 1))
    assert y.train_flops_per_sample(model) == pytest.approx(3 * fwd)
    assert 2.0e9 < 3 * fwd / t < 2.4e9             # "2.2 GFLOP a token"
    peaks = peaks_for("TPU v5 lite")
    least = y.attention_min_seconds_per_sample(model, peaks)
    experts = y.experts_min_seconds_per_sample(model, peaks)
    assert least["bandwidth_bound_share"] == 0.0
    assert experts["bandwidth_bound_share"] == 0.0
    assert least["seconds"] == pytest.approx(
        3 * 4 * 128 * 32 * (4 * window + full) / peaks["bf16_flops_per_s"])
    assert experts["seconds"] == pytest.approx(
        3 * 4 * 2 * expert * 0.5 * t / peaks["bf16_flops_per_s"])


def _path(rest, layer=1):
    return (f"jit(grad_step)/while/body/closed_call/jvp(SparseLM)/"
            f"layer_{layer}/" + rest)


def _scoped_trace():
    """Device operations under the scope paths the program's grad step
    writes on the chip (read from a traced run's device_scopes.json at
    PR 33): 100 ns each, back to back."""
    ops = [
        ("attn[mosaic]", _path("attn/pallas_call:")),
        ("fusion", _path("attn/q/dot_general:")),
        ("fusion", _path("attn/gate/dot_general:")),
        ("fusion", _path("attn/gate/mul:")),
        ("fusion", _path("attn/qk_norm/rms_norm/mul:")),
        ("fusion", _path("ff.route/ff/router/btd,de->bte/dot_general:")),
        ("sort", _path("ff/cond/branch_1_fun/dispatch/sort:")),
        ("experts[mosaic]", _path(
            "ff/cond/branch_1_fun/experts/pallas_call:")),
        ("token_major_sum[mosaic]", _path(
            "ff/cond/branch_1_fun/combine/jit(token_major_sum)/"
            "pallas_call:")),
        ("fusion", _path("ff/shared/gate/dot_general:")),
        ("fusion", _path("ff/shared/mul:")),
        ("fusion", _path("ff/dense/up/dot_general:", layer=0)),
        ("fusion", _path("ff/dense/down/dot_general:", layer=0)),
        ("fusion", _path("ff/dense/mul:", layer=0)),
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


def test_every_trace_fed_metric_of_the_pr_reads_the_programs_scopes():
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
    assert read("moe_shared_share_pct") == pytest.approx(2 * share)
    assert read("ff_dense_share_pct") == pytest.approx(3 * share)
    assert read("attn_gate_share_pct") == pytest.approx(3 * share)
    assert read("moe_router_share_pct") == pytest.approx(share)
    assert read("moe_dispatch_share_pct") == pytest.approx(2 * share)
    assert read("moe_experts_share_pct") == pytest.approx(share)
    y, model = cell.yardstick, cell.config["model"]
    attn = y.attention_min_seconds_per_sample(model, peaks)["seconds"]
    assert read("attn_roofline") == pytest.approx(100 * attn * 24 / 100e-9)
    experts = y.experts_min_seconds_per_sample(model, peaks)["seconds"]
    assert read("moe_experts_roofline") == pytest.approx(
        100 * experts * 24 / 100e-9)
    # the accepted shares keep their meaning: the new scopes lie under
    # their roots, a shared expert's gate is none of attention's
    assert read("ff_xla_share_pct") == pytest.approx(7 * share)
    assert read("attn_xla_share_pct") == pytest.approx(4 * share)
    assert read("head_ce_share_pct") == pytest.approx(share)
    assert read("mosaic_share_pct") == pytest.approx(3 * share)
    assert read("unscoped_share_pct") == 0.0
    # a program with none of these scopes (the parent): shares of nothing
    # read 0, a roofline with nothing to read is left out
    bare = dict(raw, planes=[dict(raw["planes"][0], lines=[{
        "name": "XLA Ops", "events": [["fusion", 0, 100, ""]]}]),
        raw["planes"][1]])
    ctx.trace = T.Reduced(bare)
    for name in OWN_METRICS:
        assert read(name) == 0.0
    assert read("moe_experts_roofline") is None
    assert read("attn_roofline") is None


def test_a_traced_rehearsal_of_the_preset_runs_through_the_harness(
        tmp_path):
    """The tiny preset, its yardstick and the new metric files through
    ``harness.run_cell`` on the CPU: the reference check passes, the
    program-fed metrics of the cell are read under the names both sparse
    cells share, the trace-fed ones are left out (no device plane here)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", SECS="6",
               PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable,
         str(Path(__file__).parent / "trinity_rehearse.py"), "1",
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
    # interpreted kernels: the sorted lowering takes every call of the two
    # expert layers, nothing is dropped
    assert own("moe_dense_calls") == 0.0 and own("moe_dropped") == 0.0
    assert own("moe_sum_spills") >= 0.0
    for name in ("grad_step_s", "loop_grad_step_s", "warmup_s",
                 "compiles_after_first_step", "grad_step_plan_gib"):
        assert name in got, name
    assert got["compiles_after_first_step"]["value"] == 0
    for name in (*OWN_METRICS, "attn_roofline", "moe_router_share_pct"):
        assert name not in got
    line = [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith('{"reference_check"')][0]
    check = line["reference_check"]
    assert check["loss_rel_err"] < 1e-5 and check["grad_rel_l2_max"] < 1e-4
    # the same line carries the census and the mechanisms' own word on how
    # they engaged (the ``setup/warmup`` row's attributes)
    assert line["census"]["missing"] == [] == list(
        line["census"]["unlisted"])         # interpreted: no Mosaic call
    said = line["engagement"]
    assert set(said) >= {"attn_layout", "moe_layout", "layer_loop",
                         "grad_reduction"}
    assert said["attn_layout"].startswith("blockwise ")
    assert "experts held" in said["moe_layout"]
    assert list(result)[-1] == "compared"
    assert result["compared"]["grad_rel_l2"][0] == check["grad_rel_l2_max"]


@pytest.mark.slow
def test_the_real_widths_compile_for_a_described_v5e_and_fit():
    """The grad step of the cell (micro 1 x accum 8 of 8192 tokens) and
    the reference check's program, compiled in the sandbox for one v5e
    chip: the Mosaic kernels lower, no T x T array is in the step's plan,
    and the parameters' state (14 bytes a parameter resident) + plan
    stays under 14 GB; at micro 2 it does not."""
    import re

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
    count = sum(a.size for a in jax.tree.leaves(shapes))
    assert round(count / 1e6, 1) == 504.1
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=everywhere), shapes)
    accum = cell.traffic["grad_accum_steps"]
    n = cell.traffic["per_device_batch"] * accum
    tokens = lambda rows, length: jax.ShapeDtypeStruct(
        (rows, length), jnp.int32, sharding=batch_sharding(mesh))
    default_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:       # the dispatchers pick the Mosaic kernels for a TPU
        lowered = jax.jit(make_grad_step(model, accum_steps=accum)).lower(
            params, {"text": tokens(n, cfg.text_seq_len),
                     "image": tokens(n, cfg.image_seq_len)})
        compiled = lowered.compile()
    finally:
        jax.default_backend = default_backend
    census = mosaic_census(lowered.as_text(), cell.config["mosaic_kernels"])
    # ``unlisted`` is the program's to grow: the run's record prints it
    assert census["missing"] == [], census
    text = compiled.as_text()
    assert not re.findall(r"(?:f32|bf16)\[[0-9,]*8192,8192[0-9,]*\]", text)
    plan = compiled.memory_analysis().temp_size_in_bytes
    assert 14 * count + plan < 14e9, (count, plan)
