"""The ``smallthinker21b`` configuration, its cell, its yardstick and its
metric files: they pass every check the suite applies to a manifest; the
file holds the source's config under the source's keys; a tiny rehearsal of
the preset runs through ``harness.run_cell``; every new per-layer metric
reads a number from what the program writes; and (slow) the real widths
compile for a described v5e and fit."""
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
CONFIG, CELL = "smallthinker21b", "smallthinker21b-train-solo"
NEW_METRICS = ("attn_roofline", "moe_experts_roofline",
               "moe_router_share_pct", "moe_dispatch_share_pct",
               "moe_experts_share_pct", "moe_load_max_over_mean",
               "moe_assignments_here_pct", "moe_dense_calls", "moe_dropped")

# config.json of PowerInfer/SmallThinker-21BA3B-Instruct: its numbers
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
    "moe_num_primary_experts": 64, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "num_attention_heads": 28,
    "num_hidden_layers": 52, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


def test_everything_the_pr_adds_passes_every_check():
    checks.every_check(MAN, MODEL_PRESETS)
    cell = MAN.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "solo-8x4"
    assert (cell.traffic["per_device_batch"],
            cell.traffic["grad_accum_steps"]) == (2, 4)
    read = {m["name"] for m in cell.per_layer}
    assert read >= set(NEW_METRICS)
    # attn_roofline is one entry for every cell since PR 43 (the cell's
    # own copy until then); the moe_* ones are the sparse cells' alone
    for other in ("flagship-train-solo", "xl-train-solo",
                  "flagship-train-dp4"):
        assert {m["name"] for m in MAN.cell(other).per_layer} \
            & set(NEW_METRICS) == {"attn_roofline"}


def test_the_file_holds_the_sources_config_under_the_sources_keys():
    """Every key of the source's config.json is a top-level key of the
    file with the published value, but the ones ``reduced`` names, which
    hold the value as run; the widths agree with ``model``."""
    on_file = json.loads((ROOT / MAN.configs[CONFIG]["file"]).read_text())
    model, reduced = on_file["model"], on_file["reduced"]
    assert reduced == ["num_hidden_layers", "experts_held", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert on_file[key] == model[key] != value
            assert on_file["published"][key] == value
        else:
            assert on_file[key] == value, key
    assert on_file["published"]["experts_held"] == \
        PUBLISHED["moe_num_primary_experts"] == model["num_experts"]
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("expert_width", "moe_ffn_hidden_size"),
                         ("experts_per_token",
                          "moe_num_active_primary_experts"),
                         ("window", "sliding_window_size"),
                         ("rope_theta", "rope_theta"),
                         ("rms_eps", "rms_norm_eps")):
        assert model[ours] == PUBLISHED[theirs], ours
    period = ["full_nope" if not flag else "window_rope"
              for flag in PUBLISHED["sliding_window_layout"][:4]]
    assert model["layer_kinds"] == period
    # the floors of a cut: a whole period, 8 experts, an eighth of the rows
    assert model["num_hidden_layers"] >= 4 and model["experts_held"] >= 8
    assert model["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert on_file["layer_shared_by"] == 8
    tol = on_file["tolerance"]
    # between the system's largest reading on the chip and the float8
    # control's, with room on both sides (PERF.md section 6, PR 31)
    assert 2.0e-5 < tol["loss_rel"] < 1e-3
    assert 1.3 * 0.096 < tol["grad_rel_l2"] < 0.226 / 1.3
    assert len(tol["reason"]) > 200


def test_the_counts_of_the_yardstick():
    """Required work only: pairs inside the band, the held experts'
    assignments in expectation, the sliced head."""
    cell = MAN.cell(CELL)
    y, model = cell.yardstick, cell.config["model"]
    t = y.tokens_per_sample(model)
    assert t == 8192
    full = y.attention_pairs(model, "full_nope")
    window = y.attention_pairs(model, "window_rope")
    assert full == t * (t + 1) // 2
    assert window == 4096 * 4097 // 2 + (t - 4096) * 4096
    assert 0.74 < window / full < 0.76
    assert y.held_assignments_per_token(model) == 0.75
    per_layer = 2 * 2560 * 128 * 32 + 2560 * 64 + 0.75 * 3 * 2560 * 768
    fwd = (2 * 4 * per_layer * t + 4 * 128 * 28 * (full + 3 * window)
           + 2 * 2560 * 18992 * (t - 1))
    assert y.train_flops_per_sample(model) == pytest.approx(3 * fwd)
    head_share = 3 * 2 * 2560 * 18992 * (t - 1) \
        / y.train_flops_per_sample(model)
    assert 0.15 < head_share < 0.25       # "about a fifth" (PERF.md)
    peaks = peaks_for("TPU v5 lite")
    attn = y.attention_min_seconds_per_sample(model, peaks)
    experts = y.experts_min_seconds_per_sample(model, peaks)
    # both compute-bound at these shapes
    assert attn["bandwidth_bound_share"] == 0.0
    assert experts["bandwidth_bound_share"] == 0.0
    assert attn["seconds"] == pytest.approx(
        3 * 4 * 128 * 28 * (full + 3 * window) / peaks["bf16_flops_per_s"])
    assert experts["seconds"] == pytest.approx(
        3 * 4 * 2 * 3 * 2560 * 768 * 0.75 * t / peaks["bf16_flops_per_s"])


def _path(rest):
    return ("jit(grad_step)/while/body/closed_call/jvp(SparseLM)/layer_1/"
            + rest)


def _scoped_trace():
    """Device operations under the scope paths the program's grad step
    writes on the chip (read from a traced run's device_scopes.json at
    PR 31): 100 ns each, back to back."""
    ops = [
        ("attn[mosaic]", _path("attn/pallas_call:")),
        ("attn[mosaic]", _path("attn/pallas_call:")),
        ("fusion", _path("attn/q/dot_general:")),
        ("fusion", _path("ff.route/ff/router/btd,de->bte/dot_general:")),
        ("sort", _path("ff/cond/branch_1_fun/dispatch/sort:")),
        ("fusion", _path("ff/cond/branch_1_fun/combine/gather:")),
        ("experts[mosaic]", _path(
            "ff/cond/branch_1_fun/experts/pallas_call:")),
        ("fusion", _path("ff/cond/branch_1_fun/experts/mul:")),
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
    assert read("moe_router_share_pct") == pytest.approx(share)
    assert read("moe_dispatch_share_pct") == pytest.approx(2 * share)
    assert read("moe_experts_share_pct") == pytest.approx(2 * share)
    y, model = cell.yardstick, cell.config["model"]
    attn = y.attention_min_seconds_per_sample(model, peaks)["seconds"]
    assert read("attn_roofline") == pytest.approx(
        100 * attn * 24 / 200e-9)
    experts = y.experts_min_seconds_per_sample(model, peaks)["seconds"]
    assert read("moe_experts_roofline") == pytest.approx(
        100 * experts * 24 / 200e-9)
    # and the accepted shares keep their meaning in the new cell
    assert read("ff_xla_share_pct") == pytest.approx(4 * share)
    assert read("attn_xla_share_pct") == pytest.approx(share)
    assert read("head_ce_share_pct") == pytest.approx(share)
    assert read("mosaic_share_pct") == pytest.approx(3 * share)
    assert read("unscoped_share_pct") == 0.0
    assert read("layer_scan_share_pct") == 0.0
    # a program with none of these scopes (the parent): shares of nothing
    # read 0, a roofline with nothing to read is left out
    bare = dict(raw, planes=[dict(raw["planes"][0], lines=[{
        "name": "XLA Ops", "events": [["fusion", 0, 100, ""]]}]),
        raw["planes"][1]])
    ctx.trace = T.Reduced(bare)
    assert read("moe_experts_roofline") is None
    assert read("attn_roofline") is None


def test_the_program_attribute_reducer(monkeypatch):
    """``program_attr``: the median (or sum, or maximum) over the
    window's steps of an attribute of the program's ``loop/step`` rows;
    nothing where the rows carry none (another model, the parent). The
    two counters of events are read as their files say: a slow call in
    one step of four shows, where its median would read 0."""
    from benchmark.reducers import program_attr, program_span
    rows = [{"plane": "train", "phase": "loop/step", "trace": f"step:{n}",
             "t0": float(n), "dur_s": 1.0,
             "a": {"moe_load_max_over_mean": float(n),
                   "moe_dense_calls": 0.25 * (n == 6),
                   "moe_dropped": 3.0 * (n == 5)}}
            for n in range(1, 11)]
    monkeypatch.setattr(program_span, "ring_rows", lambda: rows)
    ctx = RunContext(values={"n_intervals": 4}, traced_steps=3)
    # steps 4..7: the 4 before the 3 traced ones
    assert program_attr.read(ctx, "loop/step",
                             "moe_load_max_over_mean") == 5.5
    files = {m["name"]: m for m in MAN.cell(CELL).per_layer}
    read = lambda name: M.reducer(files[name]["reducer"])(
        ctx, **files[name]["params"])
    assert read("moe_load_max_over_mean") == 5.5
    assert read("moe_dense_calls") == 0.25 and read("moe_dropped") == 3.0
    assert program_attr.read(ctx, "loop/step", "moe_dense_calls") == 0.0
    assert program_attr.read(ctx, "loop/step", "no_such") is None
    monkeypatch.setattr(program_span, "ring_rows", lambda: None)
    assert program_attr.read(ctx, "loop/step",
                             "moe_load_max_over_mean") is None


def test_a_traced_rehearsal_of_the_preset_runs_through_the_harness(
        tmp_path):
    """The tiny preset, its yardstick and the new metric files through
    ``harness.run_cell`` on the CPU: the reference check passes, the
    program-fed metrics of the PR are read, the trace-fed ones are left
    out (no device plane here)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", SECS="6",
               PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable,
         str(Path(__file__).parent / "smallthinker_rehearse.py"), "1",
         str(tmp_path)], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL")
    result = json.loads(last.split(":", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    assert 0 < got["moe_assignments_here_pct"]["value"] < 100
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    # the rehearsal's expert sizes are no lane tiles' but its kernels are
    # interpreted: the sorted lowering takes every call, nothing is dropped
    assert got["moe_dense_calls"]["value"] == 0.0
    assert got["moe_dropped"]["value"] == 0.0
    for name in ("grad_step_s", "loop_grad_step_s", "warmup_s",
                 "compiles_after_first_step", "grad_step_plan_gib"):
        assert name in got, name
    assert got["compiles_after_first_step"]["value"] == 0
    for name in NEW_METRICS[:5]:
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
    """The grad step of the cell (micro 2 x accum 4 of 8192 tokens) and
    the reference check's program, compiled in the sandbox for one v5e
    chip: the Mosaic kernels lower, no T x T array is in the step's plan,
    and parameters' state (22 bytes a parameter: f32 parameters, 8-bit
    moments, the swarm's f32 accumulator, the step's f32 output and the
    two gradient trees inside it) + plan stays under 14 GB."""
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
    assert round(count / 1e6, 1) == 370.5
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

    reference = jax.jit(lambda p, t, i: cell.yardstick.loss_fn(
        p, t, i, cell.config["model"], True)[0])
    one = NamedSharding(mesh, P())
    two = lambda length: jax.ShapeDtypeStruct((2, length), jnp.int32,
                                              sharding=one)
    with jax.default_matmul_precision("highest"):
        check = jax.jit(jax.grad(reference)).lower(
            params, two(cfg.text_seq_len), two(cfg.image_seq_len)).compile()
    mem = check.memory_analysis()
    # beside it live the train state (14 bytes a parameter less the
    # gradient it shares) and the system's gradients
    assert (mem.temp_size_in_bytes + mem.output_size_in_bytes
            + 14 * count) < 15.5e9
