"""The gradient's sum over the mesh's ``dp`` axis runs once a step, after
the accumulation scan (training/steps._accumulate_grads): where the
compiled step's collectives sit, parity with the one-device step, and what
``per_shard`` binds when it is called inside that ``shard_map``. A file of
its own so that a test worker can take it beside tests/test_train.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_init import init_params
from dalle_tpu.config import OptimizerConfig, tiny_model_config
from dalle_tpu.data.synthetic import SyntheticCodes
from dalle_tpu.models.dalle import DALLE
from dalle_tpu.optim import make_optimizer
from dalle_tpu.parallel.mesh import batch_sharding, make_mesh
from dalle_tpu.training.steps import (
    TrainState,
    make_grad_step,
    make_train_step,
)


def _flagship_shaped(depth=9):
    """The flagship's structure at toy widths: four weight-shared blocks
    cycled two passes at a time, a conv block after them, rematerialised,
    parameters cast once at the top of the loss. Depth 9 is one pass of
    the eight slots (no scan is built); depth 16 is the flagship's case,
    15 layers in 2 x 8 slots, the last of them conditional."""
    return tiny_model_config(
        dim=64, heads=4, head_dim=16, depth=depth, shared_block_cycle=4,
        scan_unroll=2, attn_types=("axial_row", "axial_col", "axial_row",
                                   "axial_row"),
        final_conv_block=True, conv_kernel=3, remat=True, ln_fusion=True,
        param_cast_hoist=True)


def _batch16(cfg, masked):
    """16 samples; ``masked``: a ragged caption mask (data/dataset.py's:
    ones on image positions, a different padding length per sample)."""
    batch = next(SyntheticCodes(cfg, num_samples=16, seed=1)
                 .batches(16, seed=0))
    if masked:
        lengths = 1 + (np.arange(16) * 7) % cfg.text_seq_len
        text_mask = np.arange(cfg.text_seq_len)[None] < lengths[:, None]
        batch["mask"] = np.concatenate(
            [text_mask, np.ones((16, cfg.image_seq_len), bool)],
            axis=1).astype(np.float32)
    return batch


class TestDpReduction:
    @pytest.mark.parametrize("accum,masked", [(2, False), (2, True),
                                              (1, False)])
    def test_no_dp_collective_inside_a_loop(self, accum, masked,
                                            monkeypatch):
        from dalle_tpu.models import attention
        from scripts.collectives import collectives, spans_axis
        monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
        cfg = _flagship_shaped()
        mesh = make_mesh(dp=4, devices=jax.devices()[:4])
        model = DALLE(cfg, mesh=mesh)
        params = init_params(DALLE(cfg), jax.random.PRNGKey(0))
        rep = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
        compiled = jax.jit(make_grad_step(model, accum)).lower(
            jax.device_put(params, rep),
            jax.device_put(_batch16(cfg, masked),
                           batch_sharding(mesh))).compile()
        over_dp = [c for c in collectives(compiled.as_text())
                   if spans_axis(c, (4, 1, 1, 1), 0)]
        # in a loop: nothing but the masked loss's two denominators
        in_loop = [c for c in over_dp if c.in_loop]
        assert all(c.kind == "all-reduce" and c.elements <= 2
                   for c in in_loop), in_loop
        assert bool(in_loop) == (masked and accum > 1)
        # after it: every gradient element and the three aux scalars (the
        # loss is one of them) cross the chips exactly once, in f32
        n_params = sum(p.size for p in jax.tree.leaves(params))
        once = [c for c in over_dp
                if not c.in_loop and c.kind == "all-reduce"]
        assert sum(c.elements for c in once if c.elements > 2) \
            == n_params + 3, once
        assert all(c.nbytes == 4 * c.elements for c in once)
        # the microbatches are cut from each shard's own samples; only a
        # masked batch is dealt across the shards first, outside the scan
        moved = [c for c in over_dp if c.kind != "all-reduce"]
        assert not any(c.in_loop for c in moved)
        assert bool(moved) == (masked and accum > 1), moved

    @pytest.mark.parametrize("mesh_kind", ["no_mesh", "one_device_mesh"])
    def test_one_device_step_holds_no_shard_map(self, mesh_kind):
        cfg = _flagship_shaped()
        mesh = None if mesh_kind == "no_mesh" else make_mesh(
            dp=1, devices=jax.devices()[:1])
        params = init_params(DALLE(cfg), jax.random.PRNGKey(0))
        jaxpr = jax.make_jaxpr(make_grad_step(DALLE(cfg, mesh=mesh), 2))(
            params, _batch16(cfg, True))
        assert "shard_map" not in str(jaxpr)

    @pytest.mark.parametrize("step_kind", ["grad_step", "train_step"])
    @pytest.mark.parametrize("masked", [False, True],
                             ids=["no_mask", "ragged_mask"])
    def test_dp4_step_equals_the_one_device_step(self, masked, step_kind):
        """Same 16 samples, accum 2: the dp-4 step's loss, aux and
        gradients (after LAMB: parameters) are the one-device step's. A
        masked loss is normalised per microbatch, so this also holds the
        microbatches' make-up and the cross-shard denominators. (XLA
        lowerings: the kernels' own nesting parity is in their files.)"""
        self._dp4_equals_one_device(_flagship_shaped(), masked, step_kind)

    def test_dp4_step_equals_the_one_device_step_with_a_conditional_slot(
            self):
        """The layer scan's conditional slot under the ``shard_map`` over
        ``dp``: its predicate is the scan index, the same on every
        shard."""
        from dalle_tpu.models.transformer import layer_loop_record
        cfg = _flagship_shaped(depth=16)
        assert layer_loop_record(cfg) == (
            "15 layers in 2 x 8 slots: 7 always run, 1 conditional "
            "(runs 1 of 2)")
        self._dp4_equals_one_device(cfg, False, "grad_step")

    @staticmethod
    def _dp4_equals_one_device(cfg, masked, step_kind):
        mesh = make_mesh(dp=4, devices=jax.devices()[:4])
        params = init_params(DALLE(cfg), jax.random.PRNGKey(0))
        batch = _batch16(cfg, masked)
        rep = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
        tx = make_optimizer(OptimizerConfig(
            learning_rate=3e-3, warmup_steps=2, total_steps=100))

        def run(mesh_):
            model = DALLE(cfg, mesh=mesh_)
            put = (lambda x, s: x) if mesh_ is None else jax.device_put
            b = put(batch, mesh_ and batch_sharding(mesh_))
            if step_kind == "grad_step":
                return jax.jit(make_grad_step(model, 2))(
                    put(params, rep), b)
            state, metrics = jax.jit(make_train_step(model, tx, 2))(
                put(TrainState.create(params, tx), rep), b)
            return state.params, metrics

        tree_1, aux_1 = run(None)
        tree_4, aux_4 = run(mesh)
        assert set(aux_4) == set(aux_1)
        for k in aux_1:
            assert float(aux_4[k]) == pytest.approx(float(aux_1[k]),
                                                    rel=1e-5), k
        for a, b in zip(jax.tree.leaves(tree_4), jax.tree.leaves(tree_1)):
            assert a.dtype == jnp.float32
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=2e-6)

    def test_nested_per_shard_binds_only_the_axes_left(self):
        """Inside a ``shard_map`` manual over ``dp``, ``per_shard`` names
        the context's mesh, makes fsdp/tp/sp manual and splits its
        operands over those alone; outside, over the whole mesh."""
        from jax.sharding import PartitionSpec as P

        from dalle_tpu.parallel.mesh import LANES_SPEC, per_shard
        mesh = make_mesh(dp=2, fsdp=2, tp=2)

        def call(x):
            return per_shard(lambda x: x * 2, mesh, (LANES_SPEC,),
                             LANES_SPEC)(x)
        x = jnp.ones((8, 4, 8))

        def inner_eqn(jaxpr):
            eqns = [e for e in jaxpr.eqns if e.primitive.name == "shard_map"]
            assert len(eqns) == 1
            return eqns[0]

        flat = inner_eqn(jax.make_jaxpr(call)(x).jaxpr)
        assert flat.params["manual_axes"] == frozenset(mesh.axis_names)
        assert flat.params["in_specs"] == (LANES_SPEC,)

        nested = jax.shard_map(call, mesh=mesh, in_specs=P("dp"),
                               out_specs=P("dp"), axis_names={"dp"},
                               check_vma=False)
        outer = inner_eqn(jax.make_jaxpr(nested)(x).jaxpr)
        inner = inner_eqn(outer.params["jaxpr"])
        assert inner.params["manual_axes"] == frozenset({"fsdp", "tp", "sp"})
        assert inner.params["in_specs"] == (P("fsdp", None, "tp"),)
        assert inner.params["out_specs"] == (P("fsdp", None, "tp"),)
        assert inner.params["mesh"].manual_axes == ("dp",)
        np.testing.assert_array_equal(np.asarray(jax.jit(nested)(x)),
                                      2 * np.ones(x.shape))

    @pytest.mark.parametrize("shape,plan", [
        (None, "single device: none"),
        ((1, 1, 1, 1), "single device: none"),
        ((1, 2, 2, 1), "dp=1: none"),
        ((4, 1, 1, 1), "over dp=4: once per step"),
        ((2, 2, 1, 1), "over dp=2: once per step"),
        ((2, 1, 1, 2), "over dp=2: once per step"),
        # dp manual beside two live automatic axes aborts XLA's
        # partitioner on some programs (steps._reduces_once)
        ((2, 2, 2, 1),
         "over dp=2: inside the scans, where the partitioner puts it"),
    ])
    def test_plan_follows_the_mesh(self, shape, plan):
        from dalle_tpu.training.steps import grad_reduction_plan
        mesh = None if shape is None else make_mesh(
            *shape, devices=jax.devices()[:int(np.prod(shape))])
        assert grad_reduction_plan(mesh) == plan
