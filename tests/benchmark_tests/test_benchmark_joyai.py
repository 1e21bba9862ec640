"""The ``joyaiflash`` configuration, its cell, its yardstick and its metric
files: they pass every check the suite applies to a manifest; the file
holds the source's config under the source's keys; the counts of the
yardstick against hand arithmetic; every trace-fed metric of the PR reads
a number from what the program writes; the census of its real grad step
(a backward split any way fills the role, attention on the XLA lowering is
``missing``); a tiny rehearsal of the preset runs through
``harness.run_cell``; and the real widths compile for a described v5e and
fit (one compile, shared by the tests that read it)."""
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
CONFIG, CELL = "joyaiflash", "joyaiflash-train-solo"
OTHER = "trinitymini-train-solo"
OWN_METRICS = ("attn_latent_share_pct", "mtp_share_pct")
# the metrics the cell reads through the entries the other sparse cells
# read: its name appended to their ``workloads``, no copy
SHARED = ("attn_roofline", "moe_experts_roofline", "moe_router_share_pct",
          "moe_dispatch_share_pct", "moe_experts_share_pct",
          "moe_load_max_over_mean", "moe_assignments_here_pct",
          "moe_dense_calls", "moe_dropped", "moe_sum_spills",
          "moe_shared_share_pct", "ff_dense_share_pct")

# config.json of jdopensource/JoyAI-LLM-Flash: its numbers
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}


def test_everything_the_pr_adds_passes_every_check():
    checks.every_check(MAN, MODEL_PRESETS)
    cell = MAN.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "solo-8x8"
    assert cell.traffic == MAN.cell(OTHER).traffic      # the file, unedited
    read = {m["name"] for m in cell.per_layer}
    assert read >= set(OWN_METRICS) | set(SHARED)
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
    # the two new entries stand at the end of the list
    assert [m["name"] for m in MAN.data["per_layer"][-2:]] == list(
        OWN_METRICS)
    assert "OVERLAPS" in files["mtp_share_pct"]["note"]


def test_the_file_holds_the_sources_config_under_the_sources_keys():
    """Every key of the source's config.json is a top-level key of the
    file with the published value, but the ones ``reduced`` names, which
    hold the value as run; every width agrees with ``model``."""
    on_file = json.loads((ROOT / MAN.configs[CONFIG]["file"]).read_text())
    model, reduced = on_file["model"], on_file["reduced"]
    assert reduced == ["num_hidden_layers", "experts_held", "vocab_size"]
    assert on_file["published"] == {"num_hidden_layers": 40,
                                    "experts_held": 256,
                                    "vocab_size": 129280}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert on_file[key] == model[key] != value
            assert on_file["published"][key] == value
        else:
            assert on_file[key] == value, key
    assert on_file["published"]["experts_held"] == \
        PUBLISHED["n_routed_experts"] == model["num_experts"]
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("q_lora_rank", "q_lora_rank"),
                         ("kv_lora_rank", "kv_lora_rank"),
                         ("qk_nope_head_dim", "qk_nope_head_dim"),
                         ("qk_rope_head_dim", "qk_rope_head_dim"),
                         ("v_head_dim", "v_head_dim"),
                         ("rope_interleave", "rope_interleave"),
                         ("dense_width", "intermediate_size"),
                         ("expert_width", "moe_intermediate_size"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("num_shared_experts", "n_shared_experts"),
                         ("num_dense_layers", "first_k_dense_replace"),
                         ("num_nextn_predict_layers",
                          "num_nextn_predict_layers"),
                         ("rope_theta", "rope_theta"),
                         ("rms_eps", "rms_norm_eps"),
                         ("hidden_act", "hidden_act"),
                         ("score_func", "scoring_func"),
                         ("route_norm", "norm_topk_prob"),
                         ("route_scale", "routed_scaling_factor"),
                         ("attention_bias", "attention_bias"),
                         ("tied_embeddings", "tie_word_embeddings")):
        assert model[ours] == PUBLISHED[theirs], ours
    assert model["qk_nope_head_dim"] + model["qk_rope_head_dim"] == \
        PUBLISHED["qk_head_dim"]
    assert model["layer_kinds"] == ["full_rope"]
    # the floors of a cut: the leading dense layer, four expert layers
    # after it, 8 experts, an eighth of the rows
    assert model["num_hidden_layers"] - model["num_dense_layers"] >= 4
    assert model["experts_held"] >= 8
    assert model["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert model["vocab_text"] + model["vocab_image"] == model["vocab_size"]
    assert on_file["layer_shared_by"] == 32
    assert on_file["layer_shared_by"] * model["experts_held"] == \
        model["num_experts"]
    assert on_file["yardstick"] == "joyai"
    for name in on_file["assumed"]:
        assert len(on_file["assumed_because"][name]) > 20, name
    assert {"layer_kinds", "router_input", "selection_bias",
            "embed_init_std", "mtp_loss_weight"} <= set(on_file["assumed"])
    # the two layout choices no key of model holds
    assert len([k for k in on_file["assumed_because"]
                if k.startswith("mtp_") and "no key of model" in k]) == 2
    roles = on_file["mosaic_kernels"]
    assert "_token_sum_kernel" in roles and "_latent_fwd_kernel" in roles
    tol = on_file["tolerance"]
    # between the system's largest reading on the chip over 19 seeds and
    # the float8 control's, with room on both sides (PERF.md section 6,
    # PR 44); the loss's is the accepted sparse cells'
    assert 3.04e-5 < tol["loss_rel"] <= 5e-5
    assert 1.3 * 0.2352 < tol["grad_rel_l2"] < 0.584 / 1.3
    assert len(tol["reason"]) > 200


def test_the_counts_of_the_yardstick_against_hand_arithmetic():
    """ISSUE 44's table: 491.7 M parameters, 1.12 GFLOP a token forward;
    required work only (the causal pairs, 192 and 128 wide; the held
    experts' assignments in expectation; both passes of the head)."""
    cell = MAN.cell(CELL)
    y, model = cell.yardstick, cell.config["model"]
    t = y.tokens_per_sample(model)
    assert t == 8192
    assert y.blocks(model) == 6 and y.expert_layers(model) == 5
    assert y.attention_pairs(model) == t * (t + 1) // 2
    assert y.held_assignments_per_token(model) == 0.25
    attn = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576
            + 512 * 32 * 256 + 32 * 128 * 2048)
    assert y.attention_matmul_params(model) == attn == 26_345_472
    assert round(2 * attn / 1e6, 1) == 52.7             # five projections
    pairs = t * (t + 1) // 2
    scores = 2 * pairs * (192 + 128) * 32
    assert y.attention_flops_forward(model) == scores
    assert round(scores / t / 1e6, 1) == 83.9
    expert = 3 * 2048 * 768
    ff = 2048 * 256 + (1 + 0.25) * expert
    assert y.expert_layer_ff_params(model) == ff
    assert round(2 * ff / 1e6, 1) == 12.8               # the issue's 12.9
    fwd = (2 * t * (6 * attn + 3 * 2048 * 7168 + 5 * ff + 2 * 2048 * 2048)
           + 6 * scores
           + 2 * 2048 * 16160 * ((t - 1) + (t - 2)))
    assert y.train_flops_per_sample(model) == pytest.approx(3 * fwd)
    assert round(fwd / t / 1e9, 2) == 1.12              # GFLOP a token
    assert round(3 * fwd / t / 1e9, 2) == 3.36
    assert 0.72 < 6 * (2 * t * attn + scores) / fwd < 0.74   # "73%"
    # the parameters the program initialises: the issue's table
    parts = {"attention": attn + 1536 + 512,
             "dense layer": attn + 1536 + 512 + 3 * 2048 * 7168 + 2 * 2048,
             "expert layer": attn + 1536 + 512 + 2048 * 256 + 256
             + 9 * expert + 2 * 2048}
    assert round(parts["attention"] / 1e6, 2) == 26.35
    assert round(parts["dense layer"] / 1e6, 2) == 70.39
    assert round(parts["expert layer"] / 1e6, 2) == 69.34
    module = parts["expert layer"] + 4096 * 2048 + 3 * 2048
    assert round(module / 1e6, 2) == 77.74              # the issue's 77.73
    whole = (parts["dense layer"] + 4 * parts["expert layer"] + module
             + 2 * 16160 * 2048 + 2048)
    assert whole == 491_697_408 and round(whole / 1e6, 1) == 491.7
    peaks = peaks_for("TPU v5 lite")
    least = y.attention_min_seconds_per_sample(model, peaks)
    experts = y.experts_min_seconds_per_sample(model, peaks)
    assert least["bandwidth_bound_share"] == 0.0
    assert experts["bandwidth_bound_share"] == 0.0
    assert least["seconds"] == pytest.approx(
        3 * 6 * scores / peaks["bf16_flops_per_s"])
    assert experts["seconds"] == pytest.approx(
        3 * 5 * 2 * expert * 0.25 * t / peaks["bf16_flops_per_s"])
    # the one rotary key is read once, not once a head: 64 lanes of the
    # forward's 32 x (2 x 128 + 64 + 2 x 128) + 64
    slow = {"bf16_flops_per_s": 1e30, "hbm_bytes_per_s": 1.0}
    by_bytes = y.attention_min_seconds_per_sample(model, slow)["seconds"]
    assert by_bytes == 6 * t * 2 * (32 * (4 * 128 + 64) + 64
                                    + 32 * (8 * 128 + 2 * 64) + 2 * 64)


def _path(rest, layer="layer_1"):
    return (f"jit(grad_step)/while/body/closed_call/jvp(SparseLM)/"
            f"{layer}/" + rest)


def _scoped_trace():
    """Device operations under the scope paths the program's grad step
    writes (the module names and named scopes of ``models/sparse_lm.py``):
    100 ns each, back to back."""
    mtp = lambda rest: _path(rest, layer="mtp")
    ops = [
        ("attn[mosaic]", _path("attn/pallas_call:")),
        ("fusion", _path("attn/q_a/dot_general:")),
        ("fusion", _path("attn/q_b/dot_general:")),
        ("fusion", _path("attn/kv_a/dot_general:")),
        ("fusion", _path("attn/kv_b/dot_general:")),
        ("fusion", _path("attn/latent_norm/rms_norm/mul:")),
        ("fusion", _path("attn/rotary/mul:")),
        ("fusion", _path("attn/out/dot_general:")),
        ("fusion", _path("attn/concatenate:")),
        ("fusion", _path("ff.route/ff/router/btd,de->bte/dot_general:")),
        ("experts[mosaic]", _path(
            "ff/cond/branch_1_fun/experts/pallas_call:")),
        ("fusion", _path("ff/shared/gate/dot_general:")),
        ("fusion", _path("ff/dense/up/dot_general:", layer="layer_0")),
        ("fusion", _path("rms_norm/mul:")),
        ("fusion", "jit(grad_step)/while/body/closed_call/jvp(SparseLM)/"
                   "while/body/closed_call/head/dot_general:"),
        # the prediction module: its own, its block's, its head pass
        ("fusion", "jit(grad_step)/while/body/closed_call/jvp(SparseLM)/"
                   "mtp/embed/gather:"),
        ("fusion", mtp("norms/rms_norm/mul:")),
        ("fusion", mtp("proj/dot_general:")),
        ("attn[mosaic]", mtp("block/attn/pallas_call:")),
        ("fusion", mtp("block/attn/kv_b/dot_general:")),
        ("fusion", mtp("block/ff/shared/up/dot_general:")),
        ("fusion", "jit(grad_step)/while/body/closed_call/jvp(SparseLM)/"
                   "mtp/while/body/closed_call/head/dot_general:"),
        ("fusion", "jit(grad_step)/while/body/closed_call/jvp(SparseLM)/"
                   "mtp/while/body/closed_call/ce/reduce:"),
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
    # the four projections, the latent norms, the rotary; the module's
    # block's kv_b with them
    assert read("attn_latent_share_pct") == pytest.approx(7 * share)
    # everything under the root ``mtp``, its kernel among it
    assert read("mtp_share_pct") == pytest.approx(8 * share)
    assert read("moe_shared_share_pct") == pytest.approx(2 * share)
    assert read("ff_dense_share_pct") == pytest.approx(share)
    assert read("moe_router_share_pct") == pytest.approx(share)
    assert read("moe_experts_share_pct") == pytest.approx(share)
    y, model = cell.yardstick, cell.config["model"]
    attn = y.attention_min_seconds_per_sample(model, peaks)["seconds"]
    assert read("attn_roofline") == pytest.approx(100 * attn * 24 / 200e-9)
    experts = y.experts_min_seconds_per_sample(model, peaks)["seconds"]
    assert read("moe_experts_roofline") == pytest.approx(
        100 * experts * 24 / 100e-9)
    # the accepted shares count the module's operations with the modules
    # they name: what is left dark is its embedding and W_eh
    assert read("attn_xla_share_pct") == pytest.approx(9 * share)
    assert read("ff_xla_share_pct") == pytest.approx(4 * share)
    assert read("head_ce_share_pct") == pytest.approx(3 * share)
    assert read("embed_share_pct") == pytest.approx(share)
    assert read("mosaic_share_pct") == pytest.approx(3 * share)
    assert read("unscoped_share_pct") == pytest.approx(share)   # mtp/proj
    # a program with none of these scopes (the parent): shares of nothing
    # read 0, a roofline with nothing to read is left out
    bare = dict(raw, planes=[dict(raw["planes"][0], lines=[{
        "name": "XLA Ops", "events": [["fusion", 0, 100, ""]]}]),
        raw["planes"][1]])
    ctx.trace = T.Reduced(bare)
    for name in OWN_METRICS:
        assert read(name) == 0.0
    assert read("attn_roofline") is None


def test_a_traced_rehearsal_of_the_preset_runs_through_the_harness(
        tmp_path):
    """The tiny preset, its yardstick and the new metric files through
    ``harness.run_cell`` on the CPU, the latent kernels interpreted: the
    reference check passes on the sum of the two losses, the program-fed
    metrics are read under the names the sparse cells share, the
    trace-fed ones are left out (no device plane here)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", SECS="6",
               PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable,
         str(Path(__file__).parent / "joyai_rehearse.py"), "1",
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
                 "compiles_after_first_step", "grad_step_plan_gib",
                 "state_bytes_per_param"):
        assert name in got, name
    assert got["compiles_after_first_step"]["value"] == 0
    for name in (*OWN_METRICS, "attn_roofline", "moe_router_share_pct"):
        assert name not in got
    line = [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith('{"reference_check"')][0]
    check = line["reference_check"]
    assert check["loss_rel_err"] < 1e-5 and check["grad_rel_l2_max"] < 1e-4
    said = line["engagement"]
    assert set(said) >= {"attn_layout", "mtp_layout", "moe_layout",
                         "layer_loop", "grad_reduction"}
    assert said["attn_layout"].startswith(
        "latent 48 / 32 + one rotary key of 64, heads 2 x (128 + 64 | 128), "
        "blockwise 512: 3 of 3 layers")
    assert "shares the embedding and the head" in said["mtp_layout"]
    assert list(result)[-1] == "compared"


# -- faults planted under the cell's own comparison --------------------------

@pytest.fixture(scope="module")
def near_ties(tmp_path_factory):
    """A small ``joyaiflash`` in bfloat16 with enough tokens (768 a
    sequence) and experts (64, 8 held) that top-8 sets flip as they do at
    the cell's size, **held to the cell's own limits**: the task, its
    cell, and ``harness.reference_check`` with the float32 reference
    computed once."""
    from benchmark_rehearse import tiny_root

    from benchmark import harness
    from dalle_tpu.cli import run_trainer
    from dalle_tpu.task import TrainingTask
    over = dict(
        hidden_size=128, num_hidden_layers=4, num_heads=4, num_kv_heads=4,
        expert_width=64, num_experts=64, experts_per_token=8,
        experts_held=8, expert_offset=0, vocab_size=512, text_seq_len=512,
        image_grid=16, vocab_text=256, vocab_image=256, dtype="bfloat16",
        head_chunk=256, dense_width=256, q_lora_rank=48, kv_lora_rank=32)
    cell = tiny_root(
        tmp_path_factory.mktemp("near_ties"), preset=CONFIG, overrides=over,
        trainer_args=[x for key, value in over.items()
                      for x in ("--" + key.replace("_", "-"), value)],
        yardstick="joyai")
    cell.config["tolerance"] = MAN.cell(CELL).config["tolerance"]
    cell.traffic.update(per_device_batch=1, grad_accum_steps=1)
    seed = 2**31 + 4444
    task = TrainingTask(*run_trainer.configs_from_args(
        run_trainer.build_parser().parse_args(
            harness.trainer_argv(cell, seed))))
    once = {}
    plain = cell.yardstick.loss_and_grads
    cell.yardstick.loss_and_grads = lambda *a, **kw: (
        once.get("it") or once.setdefault("it", plain(*a, **kw)))
    yield task, cell, lambda stand_in: harness.reference_check(
        stand_in, cell, seed)
    cell.yardstick.loss_and_grads = plain


def _float8_operands(task, cell):
    from benchmark.probes.joyai_precision import float8_control
    return float8_control(cell.yardstick, cell.config["model"])


def _a_leaf_left_unmoved(task, cell):
    import jax
    leaf = "['mtp']['block']['attn']['kv_a_norm']"

    def step(params, batch):
        grads, metrics = task.grad_step(params, batch)
        return jax.tree_util.tree_map_with_path(
            lambda path, g: 0 * g if jax.tree_util.keystr(path).endswith(leaf)
            else g, grads), metrics
    return step


def _the_modules_loss_dropped(task, cell):
    import dataclasses

    import jax

    from dalle_tpu.models import sparse_lm
    from dalle_tpu.training.steps import make_grad_step
    return jax.jit(make_grad_step(sparse_lm.build(dataclasses.replace(
        task.model_cfg, mtp_loss_weight=0.0), task.mesh)))


@pytest.mark.parametrize("fault", [
    None, _float8_operands, _a_leaf_left_unmoved, _the_modules_loss_dropped],
    ids=lambda f: f.__name__.strip("_") if f else "as_shipped")
def test_a_fault_under_the_cells_limits_is_not_correct(near_ties, fault):
    """``harness.reference_check``, the comparison that decides ``correct``,
    with the cell's ``loss_rel`` and ``grad_rel_l2``: the program as it
    ships is inside both; the reference's equations with float8 operands
    (the control of ``probes/joyai_precision.py``, which makes this same
    call at the cell's size on the chip), a gradient leaf left at nought
    and the prediction module's loss left out of the program each stand
    where the task stands and come out not correct."""
    from benchmark.probes.joyai_precision import _InItsPlace
    task, cell, check = near_ties
    if fault is None:
        verdict = check(task)
        assert verdict["ok"], verdict
        assert 0.05 < verdict["grad_rel_l2_max"] < 0.37   # sets that flip
        return
    verdict = check(_InItsPlace(task, fault(task, cell)))
    assert verdict["ok"] is False
    tol = cell.config["tolerance"]
    assert (verdict["grad_rel_l2_max"] > tol["grad_rel_l2"]
            or verdict["loss_rel_err"] > tol["loss_rel"])


# -- the real widths, compiled once for a described v5e ----------------------

@pytest.fixture(scope="module")
def for_a_v5e():
    """The cell's grad step (micro 1 x accum 8 of 8 192 tokens) lowered
    and compiled in the sandbox for one v5e chip, once for the tests below
    (about 80 s)."""
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
        # ... and a second lowering whose latent attention gives way to
        # the dense XLA code, as a width the kernels refuse would
        refused, sparse_lm.kernels.latent_fits = \
            sparse_lm.kernels.latent_fits, lambda *a: "the test says so"
        try:
            on_xla = jax.jit(make_grad_step(model, accum_steps=accum)) \
                .lower(params, batch).as_text()
        finally:
            sparse_lm.kernels.latent_fits = refused
    finally:
        jax.default_backend = default_backend
    count = sum(a.size for a in jax.tree.leaves(shapes))
    return cell, count, lowered.as_text(), compiled, said, on_xla


def test_the_real_widths_compile_for_a_described_v5e_and_fit(for_a_v5e):
    """The Mosaic kernels lower, nothing of size T x T is in the step's
    plan, no copy of the rotary key a head, and the parameters' state
    (18 bytes a parameter in buffers at the loop's peak, PERF.md section
    4) + plan + code stays under the chip's 15.75 GiB with 1 GiB of room
    for the runtime's reservation over the plan."""
    cell, count, lowered, compiled, said, _ = for_a_v5e
    assert round(count / 1e6, 1) == 491.7
    text = compiled.as_text()
    # a layer's scores would be [.., 32, 8192, 8192]; [1,8192,8192] is
    # kv_b's output and its cotangent (8 192 tokens x 32 heads x (128 + 128)
    # lanes), not a score
    assert not re.findall(r"\[(?:[0-9]+,)*32,8192,8192\]", text)
    assert not re.findall(r"\[(?:[0-9]+,)*8192,8192,32\]", text)
    # the one rotary key reaches the kernels as its (B, T, 256) placement,
    # and no array holds it once a head ((.., 32, 64) or (.., 32, 192))
    assert "tensor<1x8192x256xbf16>" in lowered
    assert not re.findall(r"x32x(?:64|192)x(?:bf16|f32)", lowered)
    plan = compiled.memory_analysis().temp_size_in_bytes
    gib = 2 ** 30
    assert 4.0 * gib < plan < 6.0 * gib, plan / gib
    assert 18 * count + plan + 0.3 * gib < (15.75 - 1.0) * gib
    assert said["attn_layout"] == (
        "latent 1536 / 512 + one rotary key of 64, heads 32 x (128 + 64 | "
        "128), blockwise 512: 6 of 6 layers, 2 heads a step, backward: one "
        "kernel a tile, rotary (XLA: interleaved pairs on the 64-wide "
        "parts)")
    assert said["moe_layout"].startswith(
        "8 of 256 experts held (0-7), top 8 of 256, sigmoid, bias, norm, "
        "x2.5, a shared expert of 768, layers 0-0 dense 7168")


def test_the_census_of_the_real_step_fills_every_role(for_a_v5e):
    """``joyaiflash``'s cases of what the census tests hold the other
    sparse configurations to: every role is filled; the backward is one
    role however many kernels it is; attention on the XLA lowering is
    ``missing``."""
    cell, _, lowered, _, _, on_xla = for_a_v5e
    roles = cell.config["mosaic_kernels"]
    census = mosaic_census(lowered, roles)
    assert census["missing"] == [], census
    found = census["found"]
    # six blocks: the forward kernel once a block (the backward replays
    # the projections, not the kernel), the backward once
    assert found["_latent_fwd_kernel"] == 6
    assert found["_latent_bwd_kernel"] == 6
    assert found["_gmm_kernel"] and found["_tgmm_kernel"] \
        and found["_token_sum_kernel"]
    # a backward split any way fills the role
    split = lowered.replace('"_latent_bwd_kernel"', '"_latent_dq_kernel"', 3)
    census = mosaic_census(split, roles)
    assert census["missing"] == [] and census["found"][
        "_latent_dq_kernel"] == 3
    # the forward's name does not fill the backward's role, nor the other
    # way round
    only_fwd = lowered.replace('"_latent_bwd_kernel"', '"_other"')
    assert mosaic_census(only_fwd, roles)["missing"] == [
        "_latent_(?!fwd_)\\w+"]
    # attention on the XLA lowering: both roles are missing, the rest stay
    census = mosaic_census(on_xla, roles)
    assert census["missing"] == ["_latent_fwd_kernel",
                                 "_latent_(?!fwd_)\\w+"], census
    assert "_gmm_kernel" in census["found"]
