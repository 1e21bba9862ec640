"""The ``keyevl2`` configuration, its cell, its yardstick and its metric
files: they pass every check the suite applies to a manifest; the file
holds the source's config under the source's keys (the catalog row's
numbers) with the cut values for ``reduced``; the counts of the yardstick
against hand arithmetic; the new metrics read a number from the scopes the
program writes; a tiny rehearsal of the preset runs through
``harness.run_cell``; two faults planted under ``harness.reference_check``
come out not correct; and the real widths lower for a described v5e with a
Mosaic kernel in every role and no (T, T) array a head, two layers of them
compiled for the plan's size. It pins neither ``per_layer``'s tail nor the
census's ``unlisted`` nor a layout sentence whole: a later PR's entries,
kernels and words come after."""
import dataclasses
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
from benchmark.harness import RunContext, mosaic_census
from dalle_tpu.cli.run_trainer import MODEL_PRESETS

ROOT = M.ROOT
MAN = M.Manifest()
CONFIG, CELL = "keyevl2", "keyevl2-train-solo"
OTHER = "trinitymini-train-solo"
OWN_METRICS = ("attn_indexer_share_pct", "indexer_select_roofline",
               "indexer_align_share_pct", "sparse_selected_pct")
# the metrics the cell reads through the entries the other sparse cells
# read: its name appended to their ``workloads``, no copy
SHARED = ("attn_roofline", "moe_experts_roofline", "moe_router_share_pct",
          "moe_dispatch_share_pct", "moe_experts_share_pct",
          "moe_load_max_over_mean", "moe_assignments_here_pct",
          "moe_dense_calls", "moe_dropped", "moe_sum_spills",
          "attn_gate_share_pct")

# the catalog row's ``config`` (config.json of Kwai-Keye/Keye-VL-2.0-30B-A3B)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_everything_the_pr_adds_passes_every_check():
    checks.every_check(MAN, MODEL_PRESETS)
    cell = MAN.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "solo-8x8"
    assert cell.traffic == MAN.cell(OTHER).traffic      # the file, unedited
    read = {m["name"] for m in cell.per_layer}
    assert read >= set(OWN_METRICS) | set(SHARED)
    for absent in ("moe_shared_share_pct", "ff_dense_share_pct",
                   "mtp_share_pct", "conv_share_pct"):
        assert absent not in read
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
    # the new entries come after every entry the benchmark had, in one run
    names = [m["name"] for m in MAN.data["per_layer"]]
    at = names.index(OWN_METRICS[0])
    assert names[at:at + 4] == list(OWN_METRICS)
    assert at > names.index("conv_mix_roofline")
    for name in OWN_METRICS:
        assert files[name]["moves"] == "train_tokens_per_s"
        assert len(files[name]["note"]) > 80
    assert "attn_xla_share_pct" in files["attn_indexer_share_pct"]["note"]
    assert files["indexer_select_roofline"]["params"]["least"] == \
        "indexer_min_seconds_per_sample"
    entry = MAN.configs[CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "experts_held",
                                "vocab_size"]
    assert [w["name"] for w in MAN.data["workloads"]
            if w["config"] == CONFIG] == [CELL]


def test_the_file_holds_the_sources_config_under_the_sources_keys():
    """Every key of the catalog row's config is a top-level key of the
    file with the published value, but the ones ``reduced`` names, which
    hold the value as run; every width agrees with ``model``."""
    on_file = json.loads((ROOT / MAN.configs[CONFIG]["file"]).read_text())
    model, reduced = on_file["model"], on_file["reduced"]
    assert on_file["published"] == {
        "num_hidden_layers": 48, "experts_held": 128, "vocab_size": 151936}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert on_file[key] == model[key] != value
            assert on_file["published"][key] == value
        else:
            assert on_file[key] == value, key
    sa, rope = PUBLISHED["sa_config"], PUBLISHED["rope_scaling"]
    for ours, theirs in (
            ("hidden_size", PUBLISHED["hidden_size"]),
            ("num_heads", PUBLISHED["num_attention_heads"]),
            ("num_kv_heads", PUBLISHED["num_key_value_heads"]),
            ("head_dim", PUBLISHED["head_dim"]),
            ("expert_width", PUBLISHED["moe_intermediate_size"]),
            ("num_experts", PUBLISHED["num_experts"]),
            ("experts_per_token", PUBLISHED["num_experts_per_tok"]),
            ("rope_theta", PUBLISHED["rope_theta"]),
            ("rms_eps", PUBLISHED["rms_norm_eps"]),
            ("hidden_act", PUBLISHED["hidden_act"]),
            ("router_softmax_over_chosen", PUBLISHED["norm_topk_prob"]),
            ("tied_embeddings", PUBLISHED["tie_word_embeddings"]),
            ("attention_bias", PUBLISHED["attention_bias"]),
            ("index_topk", sa["topk"]),
            ("index_heads", sa["indexer_num_heads"]),
            ("index_head_dim", sa["indexer_head_dim"]),
            ("index_chunk", sa["q_chunk_size"]),
            ("mrope_section", rope["mrope_section"])):
        assert model[ours] == theirs, ours
    assert model["layer_kinds"] == ["selected_rope"]
    assert not model["num_dense_layers"] and not model["num_shared_experts"]
    # the floors of a cut: four layers, 8 experts, an eighth of the rows
    assert 4 <= model["num_hidden_layers"] <= 7
    assert model["experts_held"] == 8
    assert model["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert model["vocab_text"] + model["vocab_image"] == model["vocab_size"]
    assert on_file["layer_shared_by"] == 16
    assert on_file["layer_shared_by"] * model["experts_held"] == \
        model["num_experts"]
    assert "16 v5e chips" in on_file["deployment"]
    assert on_file["yardstick"] == "keye"
    because = on_file["assumed_because"]
    for name in on_file["assumed"]:
        assert len(because[name]) > 20, name
    assert {"qk_norm", "indexer_rotary", "indexer_loss_weight",
            "index_chunk", "router_input", "attention_bias",
            "embed_init_std", "text_seq_len", "image_grid"} \
        <= set(on_file["assumed"])
    # the choices no key of model holds
    unkeyed = {k.split(" ")[0] for k in because if "no key of model" in k}
    assert {"indexer_loss", "position_rule"} <= unkeyed
    roles = on_file["mosaic_kernels"]
    assert "_selected_fwd_kernel" in roles
    assert any(re.fullmatch(r, "_selected_bwd_kernel")
               and not re.fullmatch(r, "_selected_fwd_kernel")
               and not re.fullmatch(r, "_selected_mean_kernel")
               for r in roles)
    tol = on_file["tolerance"]
    assert 0 < tol["loss_rel"] < 1e-3 and 0 < tol["grad_rel_l2"] < 0.5
    assert len(tol["reason"]) > 200
    assert "float8" in tol["reason"] and "sets" in tol["reason"]


def test_the_counts_of_the_yardstick_against_hand_arithmetic():
    cell = MAN.cell(CELL)
    y, model = cell.yardstick, cell.config["model"]
    t = 8192
    assert y.tokens_per_sample(model) == t
    assert y.causal_pairs(model) == 33_558_528
    # 2 048 rows that choose every key before them, 6 144 that choose 2 048
    assert y.selected_pairs(model) == 2048 * 2049 // 2 + 6144 * 2048 \
        == 14_681_088
    assert y.attention_flops_forward(model) == 4 * 14_681_088 * 128 * 32
    assert y.indexer_flops_forward(model) == 2 * 33_558_528 * 16 * 64
    attention = 2048 * 128 * 2 * (32 + 4)
    indexer = 2048 * (16 * 64 + 64 + 16)
    layer = attention + indexer + 2048 * 128 + 0.5 * 3 * 2048 * 768
    assert y.layer_matmul_params(model) == pytest.approx(layer)
    fwd = 2.0 * t * 7 * layer + 7 * (
        y.attention_flops_forward(model) + y.indexer_flops_forward(model)) \
        + 2.0 * 2048 * 18992 * (t - 1)
    assert y.train_flops_per_sample(model) == pytest.approx(3 * fwd)
    # a token's forward flops a layer, as the issue counts them (MFLOP)
    per_token = lambda flops: flops / t / 1e6
    assert per_token(y.attention_flops_forward(model)) == pytest.approx(
        29.4, abs=0.1)
    assert per_token(y.indexer_flops_forward(model)) == pytest.approx(
        8.4, abs=0.1)
    slow = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1e30}
    assert y.attention_min_seconds_per_sample(model, slow)["seconds"] == \
        pytest.approx(7 * 3 * y.attention_flops_forward(model))
    assert y.indexer_min_seconds_per_sample(model, slow)["seconds"] == \
        pytest.approx(7 * 3 * y.indexer_flops_forward(model))
    narrow = {"bf16_flops_per_s": 1e30, "hbm_bytes_per_s": 1.0}
    operands = t * (17 * 64 * 2 + 16 * 4)
    assert y.indexer_min_seconds_per_sample(model, narrow)["seconds"] == \
        pytest.approx(7 * (3 * operands + 2 * 33_558_528 / 8))
    # the position rows of the two fields
    rows = y.position_rows(model)
    assert rows.shape == (3, t) and (rows[:, :4096] == range(4096)).all()
    assert rows[:, 4096].tolist() == [4096, 4096, 4096]
    assert rows[:, 4096 + 65].tolist() == [4096, 4097, 4097]
    assert rows[:, -1].tolist() == [4096, 4096 + 63, 4096 + 63]


def _path(rest, layer="layer_2", backward=False):
    root = "jit(grad_step)/while/body/closed_call/"
    if backward:
        root += "transpose(jvp(SparseLM))/jvp(SparseLM)/checkpoint/"
    else:
        root += "jvp(SparseLM)/"
    return root + f"{layer}/" + rest


def _scoped_trace():
    """Device operations under the scope paths the program's grad step
    writes on the chip (``chiprun_out/pr52_first_traced``'s
    ``device_scopes.json``, PR 52): 100 ns each, back to back."""
    ops = [
        ("attn[mosaic]", _path("attn/pallas_call:")),
        ("attn[mosaic]", _path("attn/pallas_call:", backward=True)),
        ("scores[mosaic]", _path("attn/indexer/scores/pallas_call:")),
        ("scores[mosaic]", _path("attn/indexer/scores/pallas_call:",
                                 backward=True)),
        ("convert_reduce_fusion", _path(
            "attn/indexer/select/closed_call/while/body/closed_call/"
            "reduce_sum:")),
        ("convert_reduce_fusion", _path(
            "attn/indexer/select/while/body/closed_call/reduce_sum:",
            backward=True)),
        ("align[mosaic]", _path("attn/indexer/align/pallas_call:")),
        ("fusion", _path("attn/indexer/align/closed_call/reduce_sum:",
                         backward=True)),
        ("fusion", _path("attn/indexer/proj/q/dot_general:")),
        ("fusion", _path("attn/q/dot_general:")),
        ("qk_norm[mosaic]", _path("attn/qk_norm/pallas_call:")),
        ("fusion", _path("ff.route/ff/router/btd,de->bte/dot_general:")),
        ("experts[mosaic]", _path(
            "ff/cond/branch_1_fun/experts/pallas_call:")),
        ("fusion", _path("rms_norm/mul:")),
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
                     traced_steps=3, samples_per_step=8, values={})

    def read(name):
        m = files[name]
        return M.reducer(m["reducer"])(ctx, **m.get("params", {}))

    share = 100.0 / n_ops
    # the indexer whole, kernels included: scores, select, align, proj
    assert read("attn_indexer_share_pct") == pytest.approx(7 * share)
    assert read("indexer_align_share_pct") == pytest.approx(2 * share)
    y, model = cell.yardstick, cell.config["model"]
    least = y.indexer_min_seconds_per_sample(model, peaks)["seconds"]
    # scoring and selecting: four operations, whatever implements them
    assert read("indexer_select_roofline") == pytest.approx(
        100 * least * 24 / 400e-9)
    attn = y.attention_min_seconds_per_sample(model, peaks)["seconds"]
    assert read("attn_roofline") == pytest.approx(100 * attn * 24 / 200e-9)
    # the accepted shares keep their meaning: the head pass under
    # attn/qk_norm; the indexer's XLA operations among attention's
    assert read("attn_gate_share_pct") == pytest.approx(share)
    assert read("attn_xla_share_pct") == pytest.approx(5 * share)
    assert read("moe_router_share_pct") == pytest.approx(share)
    assert read("moe_experts_share_pct") == pytest.approx(share)
    # a program with none of these scopes (the parent): a share of nothing
    # reads 0, a roofline with nothing to read is left out
    bare = dict(raw, planes=[dict(raw["planes"][0], lines=[{
        "name": "XLA Ops", "events": [["fusion", 0, 100, ""]]}]),
        raw["planes"][1]])
    ctx.trace = T.Reduced(bare)
    assert read("attn_indexer_share_pct") == 0.0
    assert read("indexer_select_roofline") is None


def test_a_traced_rehearsal_of_the_preset_runs_through_the_harness(
        tmp_path):
    """The tiny preset, its yardstick and the new metric files through
    ``harness.run_cell`` on the CPU, the kernels interpreted: the
    reference check passes, the program-fed metrics are read (the new
    ``sparse_selected_pct`` among them), the trace-fed ones are left out
    (no device plane here), ``engagement`` prints the new attributes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", SECS="10",
               PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable,
         str(Path(__file__).parent / "keye_rehearse.py"), "1",
         str(tmp_path)], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL")
    result = json.loads(last.split(":", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    own = lambda name: got[name]["value"]
    t, k = 80, 24
    assert own("sparse_selected_pct") == pytest.approx(
        100.0 * sum(min(i + 1, k) for i in range(t)) / (t * (t + 1) / 2),
        rel=1e-5)
    assert 0 < own("moe_assignments_here_pct") < 100
    assert own("moe_dense_calls") == 0.0 and own("moe_dropped") == 0.0
    assert got["compiles_after_first_step"]["value"] == 0
    for name in ("attn_indexer_share_pct", "indexer_select_roofline",
                 "indexer_align_share_pct", "attn_roofline"):
        assert name not in got
    line = [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith('{"reference_check"')][0]
    check = line["reference_check"]
    assert check["loss_rel_err"] < 1e-5 and check["grad_rel_l2_max"] < 1e-4
    said = line["engagement"]
    assert set(said) >= {"attn_layout", "sparse_layout", "moe_layout",
                         "memory_layout", "layer_loop"}
    assert said["attn_layout"].startswith(
        "over 24 keys a query, chosen by an indexer of 2 heads of 64 over "
        "one key head, blockwise 512: 2 of 2 layers")
    assert "three rows by sections" in said["attn_layout"]
    assert "no sort" in said["sparse_layout"]
    assert "none is skipped" in said["sparse_layout"]
    assert list(result)[-1] == "compared"


# -- faults planted under the cell's own comparison --------------------------

@pytest.fixture(scope="module")
def in_the_harness(tmp_path_factory):
    """A small ``keyevl2`` in float32 (72 tokens a sequence, 20 keys a
    query) **held to the cell's own limits**: the task, its cell, and
    ``harness.reference_check`` with the reference computed once."""
    from benchmark_rehearse import tiny_root
    from keye_rehearse import OVERRIDES, trainer_args

    from benchmark import harness
    from dalle_tpu.cli import run_trainer
    from dalle_tpu.task import TrainingTask
    cell = tiny_root(tmp_path_factory.mktemp("keye_faults"), preset=CONFIG,
                     overrides=OVERRIDES, trainer_args=trainer_args(),
                     yardstick="keye")
    cell.config["tolerance"] = MAN.cell(CELL).config["tolerance"]
    cell.traffic.update(per_device_batch=1, grad_accum_steps=1)
    seed = 2**31 + 5252
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


def _step_of(task, cfg):
    import jax

    from dalle_tpu.models import sparse_lm
    from dalle_tpu.training.steps import make_grad_step
    return jax.jit(make_grad_step(sparse_lm.build(cfg, task.mesh)))


def _half_the_keys(task, monkeypatch):
    return _step_of(task, dataclasses.replace(
        task.model_cfg, index_topk=task.model_cfg.index_topk // 2))


def _the_key_un_normed(task, monkeypatch):
    from dalle_tpu.models import sparse_lm
    monkeypatch.setattr(sparse_lm.nn, "LayerNorm",
                        lambda **kw: (lambda x: x))
    return _step_of(task, task.model_cfg)


@pytest.mark.parametrize("fault", [None, _half_the_keys, _the_key_un_normed],
                         ids=lambda f: f.__name__.strip("_") if f
                         else "as_shipped")
def test_a_fault_under_the_cells_limits_is_not_correct(in_the_harness, fault,
                                                       monkeypatch):
    """``harness.reference_check``, the comparison that decides ``correct``,
    with the cell's ``loss_rel`` and ``grad_rel_l2``: the program as it
    ships is inside both; a selection of half the keys and the indexer's
    key left un-normed each stand where the task stands and come out not
    correct."""
    from benchmark.probes.joyai_precision import _InItsPlace
    task, cell, check = in_the_harness
    if fault is None:
        verdict = check(task)
        assert verdict["ok"], verdict
        return
    verdict = check(_InItsPlace(task, fault(task, monkeypatch)))
    assert verdict["ok"] is False
    tol = cell.config["tolerance"]
    assert (verdict["grad_rel_l2_max"] > tol["grad_rel_l2"]
            or verdict["loss_rel_err"] > tol["loss_rel"])


# -- the real widths, lowered once for a described v5e -----------------------

@pytest.fixture(scope="module")
def for_a_v5e():
    """The cell's grad step (micro 1 x accum 8 of 8 192 tokens) lowered in
    the sandbox for one v5e chip, and the same with two layers compiled
    (the whole seven take two and a half minutes: PERF.md section 4 has
    their plan from the chip)."""
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
    mesh = make_mesh(devices=topo.devices[:1])
    accum = cell.traffic["grad_accum_steps"]
    n = cell.traffic["per_device_batch"] * accum

    def lowered(cfg):
        model = sparse_lm.build(cfg, mesh)
        shapes = jax.eval_shape(lambda: sparse_lm.init_params(
            model, jax.random.PRNGKey(0)))
        params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, P())), shapes)
        tokens = lambda length: jax.ShapeDtypeStruct(
            (n, length), jnp.int32, sharding=batch_sharding(mesh))
        return jax.jit(make_grad_step(model, accum_steps=accum)).lower(
            params, {"text": tokens(cfg.text_seq_len),
                     "image": tokens(cfg.image_seq_len)})

    cfg = MODEL_PRESETS[CONFIG]()
    default_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:       # the dispatchers pick the Mosaic kernels for a TPU
        whole = lowered(cfg).as_text()
        said = sparse_lm.engagement_records(cfg, mesh)
        two = lowered(dataclasses.replace(cfg, num_hidden_layers=2)) \
            .compile()
        # ... and a lowering whose selected attention gives way to the
        # dense XLA code, as a width the kernels refuse would
        refused, sparse_lm.kernels.selected_fits = \
            sparse_lm.kernels.selected_fits, lambda *a: "the test says so"
        try:
            on_xla = lowered(dataclasses.replace(
                cfg, num_hidden_layers=1, text_seq_len=512, image_grid=16,
                index_topk=128)).as_text()
        finally:
            sparse_lm.kernels.selected_fits = refused
    finally:
        jax.default_backend = default_backend
    return cell, whole, said, two, on_xla


def test_the_real_widths_lower_for_a_described_v5e_and_two_layers_fit(
        for_a_v5e):
    """Nothing of size T x T a head is in the step (three (T, T) f32
    arrays a layer and direction are: the scores, the selection, the
    heads' mean), queries and keys reach the kernels 4 096 and 512 lanes
    wide, the indexer's 1 024 and 256 (the one key placed), and the plan
    of two layers holds one layer's (T, T) arrays at a time."""
    cell, whole, said, two, _ = for_a_v5e
    assert not re.findall(r"tensor<(?:\d+x){2,}8192x8192x", whole)
    assert "tensor<1x8192x8192xf32>" in whole
    assert "tensor<1x8192x4096xbf16>" in whole
    assert "tensor<1x8192x512xbf16>" in whole
    assert "tensor<1x8192x1024xbf16>" in whole
    assert "tensor<1x8192x256xbf16>" in whole
    text = two.as_text()
    assert not re.findall(r"\[(?:\d+,)+8192,8192\]", text.replace(
        "[1,8192,8192]", ""))
    plan = two.memory_analysis().temp_size_in_bytes
    gib = 2 ** 30
    # 3.26 GiB as this PR leaves it; 3.90 with every layer's arrays held to
    # the end of the backward pass (PERF.md section 6, PR 52)
    assert 2.5 * gib < plan < 3.6 * gib, plan / gib
    assert said["attn_layout"].startswith(
        "over 2048 keys a query, chosen by an indexer of 16 heads of 64 "
        "over one key head, blockwise 512: 7 of 7 layers, 8 query heads a "
        "key-value head, backward: one kernel a tile")
    assert "one pass on the lanes: 7 of 7 layers" in said["attn_layout"]
    assert "sections [16, 24, 24]" in said["attn_layout"]
    assert said["sparse_layout"].startswith("scores (T, T) f32 a sequence")
    assert "8 of 128 experts held (0-7), top 8 of 128, softmax over the " \
        "chosen" in said["moe_layout"]


def test_the_census_of_the_real_step_fills_every_role(for_a_v5e):
    """Every role is filled, the selected attention's forward and backward
    among them; the backward is one role however many kernels it is;
    attention on the XLA lowering is ``missing``."""
    cell, whole, _, _, on_xla = for_a_v5e
    roles = cell.config["mosaic_kernels"]
    census = mosaic_census(whole, roles)
    assert census["missing"] == [], census
    found = census["found"]
    # a layer's forward once (the backward pass keeps the context and the
    # statistics), its backward once; the scores and the heads' mean forward
    # and in the backward rule (and in the layer's replay, which the
    # compiler drops: nothing reads its (T, T) arrays)
    assert found["_selected_fwd_kernel"] == 7
    assert found["_selected_bwd_kernel"] == 7
    assert found["_selected_mean_kernel"] >= 14
    assert found["_index_scores_kernel"] >= 14
    assert found["_index_grads_kernel"] == 7
    assert found["_gmm_kernel"] and found["_tgmm_kernel"] \
        and found["_token_sum_kernel"] and found["_head_norm_fwd_kernel"]
    # a backward split in two fills the role
    split = whole.replace('"_selected_bwd_kernel"', '"_selected_dq_kernel"')
    assert mosaic_census(split, roles)["missing"] == []
    only_fwd = whole.replace('"_selected_bwd_kernel"', '"_other"')
    assert mosaic_census(only_fwd, roles)["missing"] == [
        "_selected_(?!fwd_|mean_)\\w+"]
    # the band's kernels' names fill none of the roles
    theirs = whole.replace('"_selected_', '"_causal_')
    assert set(mosaic_census(theirs, roles)["missing"]) == {
        "_selected_fwd_kernel", "_selected_(?!fwd_|mean_)\\w+",
        "_selected_mean_kernel"}
    # on the XLA lowering the five roles of the site are missing
    missing = mosaic_census(on_xla, roles)["missing"]
    assert set(missing) >= {
        "_selected_fwd_kernel", "_selected_(?!fwd_|mean_)\\w+",
        "_selected_mean_kernel", "_index_scores_kernel",
        "_index_(?!scores_)\\w+"}
