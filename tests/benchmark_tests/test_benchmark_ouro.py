"""What PR 67 adds to the benchmark: the configuration ``ouro2b6``, its
cell ``ouro2b6-train-solo``, the traffic mix ``solo-2x2``, the yardstick
``ouro`` and three per-layer metrics, held to the checks every entry is
held to (``benchmark_checks``), to the source's config, to hand arithmetic
and to one traced rehearsal at a tiny size."""
import json
import os
import subprocess
import sys
from pathlib import Path

import benchmark_checks as checks
import pytest

from benchmark import manifest as M
from benchmark import trace as T
from benchmark.harness import RunContext, peaks_for
from dalle_tpu.cli.run_trainer import MODEL_PRESETS

ROOT = M.ROOT
MAN = M.Manifest()
CONFIG, CELL = "ouro2b6", "ouro2b6-train-solo"
OTHER = "trinitymini-train-solo"
OWN_METRICS = ("exit_gate_share_pct", "pass_loop_share_pct",
               "exit_expected_pass")
SHARED = ("attn_roofline", "ff_dense_share_pct")
PARAMETERS = 408_997_889


def _catalog_row():
    """The catalog row's ``config``, where the guide is installed."""
    path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not path.is_file():
        return None
    rows = [json.loads(line) for line in path.read_text().splitlines()
            if line.strip()]
    return next(r for r in rows if r["name"] == "Ouro-2.6B")


def test_everything_the_pr_adds_passes_every_check():
    checks.every_check(MAN, MODEL_PRESETS)
    cell = MAN.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "solo-2x2"
    eight = MAN.cell(OTHER).traffic
    assert {k: v for k, v in cell.traffic.items() if k != "why"} == dict(
        {k: v for k, v in eight.items() if k != "why"}, grad_accum_steps=2)
    read = {m["name"] for m in cell.per_layer}
    assert read >= set(OWN_METRICS) | set(SHARED)
    # a dense stack: no expert layer's metric, no other operator's
    assert not [name for name in read if name.startswith("moe_")]
    assert not read & {"conv_share_pct", "ssm_share_pct", "gdn_share_pct",
                       "mtp_share_pct", "attn_gate_share_pct"}
    for name in MAN.cells:
        if name != CELL:
            assert not {m["name"] for m in MAN.cell(name).per_layer} \
                & set(OWN_METRICS)
    files = {m["name"]: m for m in cell.per_layer}
    theirs = {m["name"]: m for m in MAN.cell(OTHER).per_layer}
    for name in SHARED:
        assert files[name] == theirs[name], name
    # the three new entries stand side by side after every entry the
    # benchmark had (a later PR's come after them: no pin on the tail)
    names = [m["name"] for m in MAN.data["per_layer"]]
    at = names.index(OWN_METRICS[0])
    assert names[at:at + 3] == list(OWN_METRICS)
    assert at > names.index("gdn_rule_roofline")
    assert files["exit_gate_share_pct"]["params"]["scope"] == \
        "(^|/)exit_gate(/|:|$)"
    assert files["exit_expected_pass"]["params"] == {
        "phase": "loop/step", "attr": "exit_expected_pass"}
    assert len(MAN.cells) >= 11
    assert sum(MAN.cell(name).chips == 4 for name in MAN.cells) >= 1


def test_the_file_holds_the_sources_config_under_the_sources_keys():
    """Every key of the catalog row's config is a top-level key of the
    file with the published value, but the ones ``reduced`` names, which
    hold the value as run; every width agrees with ``model``."""
    on_file = json.loads((ROOT / MAN.configs[CONFIG]["file"]).read_text())
    model, reduced = on_file["model"], on_file["reduced"]
    assert reduced == ["num_hidden_layers", "vocab_size"]
    assert on_file["published"] == {"num_hidden_layers": 48,
                                    "vocab_size": 49152}
    row = _catalog_row()
    if row is not None:
        assert on_file["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in reduced:
                assert on_file[key] == model[key] != value
                assert on_file["published"][key] == value
            else:
                assert on_file[key] == value, key
    assert set(on_file["layer_types"]) == {"full_attention"}
    assert model["layer_kinds"] == ["full_rope"]
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("dense_width", "intermediate_size"),
                         ("total_ut_steps", "total_ut_steps"),
                         ("rope_theta", "rope_theta"),
                         ("rms_eps", "rms_norm_eps"),
                         ("hidden_act", "hidden_act"),
                         ("tied_embeddings", "tie_word_embeddings"),
                         ("num_hidden_layers", "num_hidden_layers"),
                         ("vocab_size", "vocab_size")):
        assert model[ours] == on_file[theirs], ours
    # no width is cut, nor the passes: depth and vocabulary alone, inside
    # the floors (four layers of a period of one, an eighth of the rows)
    assert (model["hidden_size"], model["num_heads"], model["num_kv_heads"],
            model["head_dim"], model["dense_width"],
            model["total_ut_steps"]) == (2048, 16, 16, 128, 5632, 4)
    assert model["num_hidden_layers"] == model["num_dense_layers"] == 6 >= 4
    assert model["vocab_size"] * 8 >= on_file["published"]["vocab_size"]
    assert model["vocab_text"] + model["vocab_image"] == model["vocab_size"]
    # a dense stack is a stated case
    assert (model["num_experts"], model["experts_held"],
            model["experts_per_token"], model["expert_width"],
            model["num_shared_experts"]) == (0, 0, 0, 0, 0)
    assert on_file["layer_shared_by"] == 2
    assert on_file["yardstick"] == "ouro"
    assert "early_exit_threshold 1 is read at inference" in \
        on_file["source_note"]
    for name in on_file["assumed"]:
        assert len(on_file["assumed_because"][name]) > 20, name
    assert {"pass_input", "exit_gate_input", "exit_gate_bias", "exit_loss",
            "exit_entropy_weight", "exit_gate_init_std", "embed_init_std",
            "attention_bias", "sandwich_norms"} <= set(on_file["assumed"])
    assert "THE TRAINING OBJECTIVE ITSELF" in \
        on_file["assumed_because"]["exit_loss"]
    assert on_file["mosaic_kernels"] == [
        "_causal_fwd_kernel", "_causal_(?!fwd_)\\w+",
        "_head_norm_fwd_kernel", "_head_norm_(?!fwd_)\\w+"]
    tol = on_file["tolerance"]
    # no router, no near-tie sets: the limits stand near bfloat16's own
    # distance, not at the sparse cells' sixth and more
    assert 0 < tol["loss_rel"] < 1e-3 and 0 < tol["grad_rel_l2"] < 0.1
    assert 0 < tol["grad_rel_l2_median"] < tol["grad_rel_l2"]
    assert len(tol["reason"]) > 200 and "float8" in tol["reason"]


def test_the_counts_of_the_yardstick_against_hand_arithmetic():
    cell = MAN.cell(CELL)
    y, model = cell.yardstick, cell.config["model"]
    t, d, f, v = 8192, 2048, 5632, 24576
    assert y.tokens_per_sample(model) == t
    assert y.layer_applications(model) == 6 * 4
    layer = 4 * d * d + 3 * d * f                     # q, k, v, out; the block
    assert y.layer_matmul_params(model) == layer == 51_380_224
    pairs = t * (t + 1) // 2
    assert y.attention_pairs(model) == pairs
    attention = 4 * pairs * 128 * 16
    assert y.attention_flops_forward(model) == attention
    forward = 24 * (2 * t * layer + attention) \
        + 4 * 2 * d * (t + v * (t - 1))
    assert y.train_flops_per_sample(model) == pytest.approx(3.0 * forward)
    # four passes: a stack run once would count a quarter of the layers'
    # work and one head
    once = dict(model, total_ut_steps=1)
    assert y.train_flops_per_sample(model) == pytest.approx(
        4 * y.train_flops_per_sample(once))
    # ~11 GFLOP a token, the heads 11% of it
    assert 3.0 * forward / t == pytest.approx(11.0e9, rel=0.02)
    assert 4 * 2 * d * v * (t - 1) / forward == pytest.approx(0.11, abs=0.01)
    peaks = peaks_for("TPU v5 lite")
    least = y.attention_min_seconds_per_sample(model, peaks)
    wide = t * 16 * 128 * 2
    calls = 24 * (max(attention / peaks["bf16_flops_per_s"],
                      4 * wide / peaks["hbm_bytes_per_s"])
                  + max(2 * attention / peaks["bf16_flops_per_s"],
                        8 * wide / peaks["hbm_bytes_per_s"]))
    assert least["seconds"] == pytest.approx(calls)
    assert least["bandwidth_bound_share"] == 0.0
    assert y.attention_min_seconds_per_sample(once, peaks)["seconds"] \
        == pytest.approx(calls / 4)
    # the leaves the preset draws are the count the cut was sized by
    import jax
    from dalle_tpu.models import sparse_lm
    cfg = MODEL_PRESETS["ouro2b6"]()
    shapes = jax.eval_shape(lambda: sparse_lm.init_params(
        sparse_lm.build(cfg), jax.random.PRNGKey(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == PARAMETERS
    assert 6 * (layer + 4 * d) + d + 2 * v * d + d + 1 == PARAMETERS


def _path(rest, backward=False):
    root = "jit(grad_step)/while/body/closed_call/"
    root += "transpose(jvp(SparseLM))/" if backward else "jvp(SparseLM)/"
    return root + rest


def _scoped_trace():
    """Device operations under the scope paths the program's grad step
    writes (as a compile for a described v5e names them): 100 ns each,
    back to back."""
    layer = "passes/while/body/closed_call/layer_3/CheckpointLayer_3/"
    replay = ("passes/while/body/closed_call/layer_3/layer_3/checkpoint/"
              "rematted_computation/CheckpointLayer_3/")
    ops = [
        # the loop's own: 6
        ("fusion", _path("passes/while/body/dynamic_update_slice:")),
        ("fusion", _path("passes/while/body/squeeze:", backward=True)),
        ("fusion", _path("passes/while/body/dynamic_slice:", backward=True)),
        ("fusion", _path("passes/while/body/closed_call/add_any:",
                         backward=True)),
        ("fusion", _path("passes/convert_element_type:")),
        ("fusion", _path("passes/convert_element_type:", backward=True)),
        # a layer's, a norm's: none of the loop's
        ("attn[mosaic]", _path(layer + "attn/pallas_call:")),
        ("rotary[mosaic]", _path(layer + "attn/rotary/pallas_call:")),
        ("fusion", _path(layer + "attn/q/dot_general:")),
        ("fusion", _path(layer + "ff/dense/up/dot_general:")),
        ("fusion", _path(replay + "ff/dense/gate/dot_general:",
                         backward=True)),
        ("fusion", _path(layer + "rms_norm/mul:")),
        ("fusion", _path(layer + "add:")),
        ("fusion", _path("passes/while/body/closed_call/rms_norm/mul:")),
        ("fusion", _path("passes/while/body/closed_call/checkpoint/"
                         "rms_norm/reduce_sum:", backward=True)),
        # the gate: 3
        ("fusion", _path("exit_gate/reduce_sum:")),
        ("fusion", _path("exit_gate/jit(log_sigmoid)/jit(softplus)/exp:")),
        ("fusion", _path("exit_gate/mul:", backward=True)),
        ("fusion", _path("while/body/closed_call/head/dot_general:")),
        ("fusion", _path("embed/jit(_take)/gather:")),
    ]
    events = [[name, 100 * i, 100, scope]
              for i, (name, scope) in enumerate(ops)]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": events}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench/traced_window", 0, 100 * len(ops)]]}]}]}, len(ops)


def test_the_new_metrics_read_the_programs_scopes():
    cell = MAN.cell(CELL)
    files = {m["name"]: m for m in cell.per_layer}
    raw, n_ops = _scoped_trace()
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}
    ctx = RunContext(model=cell.config["model"], yardstick=cell.yardstick,
                     chips=1, peaks=peaks, trace=T.Reduced(raw),
                     traced_steps=3, samples_per_step=2, values={})

    def read(name):
        m = files[name]
        return M.reducer(m["reducer"])(ctx, **m.get("params", {}))

    share = 100.0 / n_ops
    assert read("exit_gate_share_pct") == pytest.approx(3 * share)
    assert read("pass_loop_share_pct") == pytest.approx(6 * share)
    assert read("ff_dense_share_pct") == pytest.approx(2 * share)
    y, model = cell.yardstick, cell.config["model"]
    attn = y.attention_min_seconds_per_sample(model, peaks)["seconds"]
    assert read("attn_roofline") == pytest.approx(100 * attn * 6 / 100e-9)
    # the accepted shares keep their meaning under the loop's scope
    assert read("attn_xla_share_pct") == pytest.approx(share)
    assert read("head_ce_share_pct") == pytest.approx(share)
    assert read("embed_share_pct") == pytest.approx(share)
    # a program with none of these scopes (the parent): a share of nothing
    # reads 0
    bare = dict(raw, planes=[dict(raw["planes"][0], lines=[{
        "name": "XLA Ops", "events": [["fusion", 0, 100, ""]]}]),
        raw["planes"][1]])
    ctx.trace = T.Reduced(bare)
    assert read("exit_gate_share_pct") == 0.0
    assert read("pass_loop_share_pct") == 0.0


def test_a_traced_rehearsal_of_the_preset_runs_through_the_harness(
        tmp_path):
    """The tiny preset, its yardstick and the new metric files through
    ``harness.run_cell`` on the CPU, the kernels interpreted: the reference
    check passes, the program-fed metric is read, the trace-fed ones are
    left out (no device plane here), ``engagement`` prints the loop's
    record and no expert layer's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", SECS="4",
               PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, str(Path(__file__).parent / "ouro_rehearse.py"),
         "1", str(tmp_path)], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL")
    result = json.loads(last.split(":", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    # an untrained gate of two passes: between the first and the second
    assert 1.0 < got["exit_expected_pass"]["value"] < 2.0
    for name in ("grad_step_s", "loop_grad_step_s", "warmup_s",
                 "compiles_after_first_step", "state_bytes_per_param",
                 "grad_step_trace_lower_s"):
        assert name in got, name
    assert got["compiles_after_first_step"]["value"] == 0
    for name in ("exit_gate_share_pct", "pass_loop_share_pct",
                 "attn_roofline", "ff_dense_share_pct"):
        assert name not in got
    assert not [name for name in got if name.startswith("moe_")]
    line = [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith('{"reference_check"')][0]
    check = line["reference_check"]
    assert check["loss_rel_err"] < 1e-5 and check["grad_rel_l2_max"] < 1e-4
    assert line["census"]["missing"] == []
    said = line["engagement"]
    assert set(said) >= {"attn_layout", "loop_layout", "head_layout",
                         "memory_layout", "layer_loop"}
    assert "moe_layout" not in said
    assert said["loop_layout"] == (
        "1 layers x 2 passes: 2 applications of 1 parameter sets, one "
        "traced pass")
    assert "the 2 exits' rows in one call" in said["head_layout"]
    assert "backward: one kernel a tile" in said["attn_layout"]
    assert "rotary (one pass on the lanes: 1 of 1 rope layers)" \
        in said["attn_layout"]
    assert list(result)[-1] == "compared"


@pytest.mark.slow
def test_the_real_widths_compile_for_a_described_v5e_and_fit(monkeypatch):
    """The cell's grad step at the published widths and the timed sizes,
    lowered and compiled for a described v5e (no chip): ONE traced pass
    (six layers' kernels, not twenty-four), every role of the
    configuration's ``mosaic_kernels`` filled, and a plan that leaves the
    state its room."""
    import jax
    from jax.experimental import topologies

    from benchmark.harness import mosaic_census
    from dalle_tpu.models import family
    from dalle_tpu.parallel.mesh import batch_sharding, make_mesh
    from dalle_tpu.training.steps import make_grad_step
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax.numpy as jnp

    cell = MAN.cell(CELL)
    cfg = MODEL_PRESETS["ouro2b6"]()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = make_mesh(devices=topo.devices[:1])
    module = family(cfg)
    model = module.build(cfg, mesh)
    shapes = jax.eval_shape(
        lambda: module.init_params(model, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, P())), shapes)
    rows = cell.traffic["per_device_batch"] * cell.traffic["grad_accum_steps"]
    tokens = lambda length: jax.ShapeDtypeStruct(
        (rows, length), jnp.int32, sharding=batch_sharding(mesh))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered = jax.jit(make_grad_step(
        model, accum_steps=cell.traffic["grad_accum_steps"])).lower(
        params, {"text": tokens(cfg.text_seq_len),
                 "image": tokens(cfg.image_seq_len)})
    census = mosaic_census(lowered.as_text(), cell.config["mosaic_kernels"])
    assert census["missing"] == [] and census["unlisted"] == {}
    assert census["found"]["_causal_fwd_kernel"] == 6
    assert census["found"]["_causal_bwd_kernel"] == 6
    plan = lowered.compile().memory_analysis()
    state = 18 * PARAMETERS             # the loop's peak a parameter
    assert plan.temp_size_in_bytes + state < 14.5 * 2 ** 30
