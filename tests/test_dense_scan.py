"""dense_scan: the cycle=0 (no weight sharing) stack as an nn.scan with
STACKED per-iteration params (transformer.py). The unrolled dense tree and
the scanned dense tree must express the SAME model: slicing each scan
repetition out of the stacked leaves reproduces the unrolled layers
(which is also how models/decode.py::layer_params reads the scanned tree).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from dalle_init import init_params
from dalle_tpu.config import flagship_model_config
from dalle_tpu.models.dalle import DALLE


def _cfg(dense_scan, depth=9):
    return flagship_model_config(
        depth=depth, dim=64, heads=2, head_dim=32, text_seq_len=8,
        image_grid=4, vocab_text=32, vocab_image=32, head_chunk=0,
        shared_block_cycle=0, remat_skip_blocks=0, scan_unroll=2,
        # f32 so scanned-vs-unrolled parity is EXACT (measured 0.0 diff);
        # under bf16 the two reduction orders drift like any reordering
        dense_scan=dense_scan, dtype="float32")


def _init(cfg):
    return init_params(DALLE(cfg), jax.random.PRNGKey(0))


@functools.cache
def _loss_and_grads(cfg, batch):
    """One jitted program a configuration and batch, shared by the cases
    that ask for the same model's loss and gradients."""
    model = DALLE(cfg)
    text = jnp.zeros((batch, cfg.text_seq_len), jnp.int32)
    image = jnp.ones((batch, cfg.image_seq_len), jnp.int32)
    return jax.jit(jax.value_and_grad(
        lambda p: model.apply(p, text, image)[0]))


def _unrolled_from_scanned(params, cfg):
    """Slice the stacked cycle/block_{sub} leaves into block_{uid} entries
    of the unrolled tree (same mapping as decode.layer_params)."""
    import copy
    group = len(cfg.attn_types)
    tr = params["params"]["transformer"]
    out_tr = {k: v for k, v in tr.items() if k != "cycle"}
    body = cfg.depth - (1 if cfg.final_conv_block else 0)
    for uid in range(body):
        rep, sub = divmod(uid, group)
        out_tr[f"block_{uid}"] = jax.tree.map(
            lambda a: np.asarray(a)[rep], tr["cycle"][f"block_{sub}"])
    out = copy.copy(params)
    out["params"] = dict(params["params"], transformer=out_tr)
    return out


class TestDenseScan:
    def test_scanned_tree_shape(self):
        cfg = _cfg(True)
        params = _init(cfg)
        tr = params["params"]["transformer"]
        assert "cycle" in tr and "block_wconv" in tr
        # 8 body layers / group 4 = 2 reps, stacked leading axis
        k = tr["cycle"]["block_0"]["attn"]["q"]["kernel"]
        assert k.shape == (2, cfg.dim, cfg.dim)
        # no unrolled body blocks alongside the scan
        assert not any(k.startswith("block_") and k != "block_wconv"
                       for k in tr)

    def test_scanned_matches_unrolled_forward_and_grads(self):
        cfg_s, cfg_u = _cfg(True), _cfg(False)
        params_s = _init(cfg_s)
        params_u = _unrolled_from_scanned(params_s, cfg_s)

        l_s, g_s = _loss_and_grads(cfg_s, 2)(params_s)
        l_u, g_u = _loss_and_grads(cfg_u, 2)(params_u)
        l_s, l_u = float(l_s), float(l_u)
        assert abs(l_s - l_u) / abs(l_u) < 1e-6, (l_s, l_u)

        # compare per-layer: slice the scanned grads like the params
        g_su = _unrolled_from_scanned(g_s, cfg_s)
        flat_u, _ = jax.tree_util.tree_flatten_with_path(g_u["params"])
        flat_s = dict(jax.tree_util.tree_flatten_with_path(
            g_su["params"])[0])
        for path, a in flat_u:
            b = flat_s[path]
            # atol 5e-6, not 1e-6: the scanned model's backward pass
            # accumulates the embedding-grad carry in scan order while
            # the unrolled model sums per-layer contributions — two f32
            # reduction orders. Seed repro (this box, jax 0.4.37 CPU):
            # 1/8192 token_emb elements off by 1.07e-6 absolute
            # (3e-4 relative on a ~3.5e-3 element) — reassociation
            # noise, orders of magnitude below any real wiring bug,
            # which this test catches at O(1e-1).
            np.testing.assert_allclose(
                np.asarray(b, np.float32), np.asarray(a, np.float32),
                rtol=1e-5, atol=5e-6,
                err_msg=jax.tree_util.keystr(path))

    def test_overhang_discarded(self):
        # depth 10 -> body 9 = 2 reps x 4 + 1: the 3 overhanging block
        # applications of rep 2 must not change the loss, and their param
        # slices must get ZERO grads
        cfg = _cfg(True, depth=10)
        _, g = _loss_and_grads(cfg, 1)(_init(cfg))
        tr = g["params"]["transformer"]["cycle"]
        # rep 2 exists for block_1..block_3 only as overhang
        for sub in (1, 2, 3):
            leaf = tr[f"block_{sub}"]["attn"]["q"]["kernel"]
            assert leaf.shape[0] == 3
            assert float(jnp.abs(leaf[2]).max()) == 0.0, sub
        # the real slot of rep 2 (block_0 -> layer 8) has signal
        assert float(jnp.abs(tr["block_0"]["attn"]["q"]["kernel"][2]).max()) > 0

    def test_shallow_dense_scan_unrolls_and_decodes(self):
        # body depth <= group: no scan happens (reps 1), the tree stores
        # plain block_{uid} params, and layer_params must NOT try to
        # slice a stacked axis (dense_scan_reps() is the shared guard)
        from dalle_tpu.models.decode import layer_params
        cfg = _cfg(True, depth=4)
        assert cfg.dense_scan_reps() == 0
        params = _init(cfg)
        tr = params["params"]["transformer"]
        assert "cycle" not in tr and "block_0" in tr
        layers = layer_params(params, cfg)
        assert len(layers) == cfg.depth
        assert layers[0]["attn"]["q"]["kernel"].ndim == 2

    def test_stacked_kernels_shard_like_unrolled(self):
        # the sharding rules were written for rank-2 kernels; the stacked
        # rank-3 leaves must shift fsdp/tp onto the SAME matmul dims
        # (reps unsharded), not onto (reps, contraction)
        from jax.sharding import PartitionSpec as P

        from dalle_tpu.parallel.sharding import param_specs
        cfg = _cfg(True)
        params = _init(cfg)
        specs = param_specs(params)
        tr = specs["params"]["transformer"]
        assert tr["cycle"]["block_0"]["attn"]["q"]["kernel"] == P(
            None, "fsdp", "tp")
        assert tr["cycle"]["block_0"]["ff"]["wo"]["kernel"] == P(
            None, "tp", "fsdp")
        # unstacked w_conv keeps the plain rank-2 layout
        assert tr["block_wconv"]["attn"]["q"]["kernel"] == P("fsdp", "tp")

    def test_lamb_trust_ratio_matches_unrolled(self):
        # LAMB computes trust ratios per tensor; for stacked leaves that
        # must mean PER SLICE, or the stacked model would optimize
        # differently from the unrolled model it re-stages
        from dalle_tpu.config import OptimizerConfig
        from dalle_tpu.optim import make_optimizer

        cfg_s, cfg_u = _cfg(True), _cfg(False)
        params_s = _init(cfg_s)
        params_u = _unrolled_from_scanned(params_s, cfg_s)
        _, g_s = _loss_and_grads(cfg_s, 2)(params_s)
        _, g_u = _loss_and_grads(cfg_u, 2)(params_u)

        tx = make_optimizer(OptimizerConfig(state_bits=32, warmup_steps=2,
                                            total_steps=100))
        first_update = jax.jit(lambda g, p: tx.update(g, tx.init(p), p)[0])
        upd_s = first_update(g_s, params_s)
        upd_u = first_update(g_u, params_u)
        upd_su = _unrolled_from_scanned(upd_s, cfg_s)
        flat_u = jax.tree_util.tree_flatten_with_path(upd_u["params"])[0]
        flat_s = dict(jax.tree_util.tree_flatten_with_path(
            upd_su["params"])[0])
        for path, a in flat_u:
            np.testing.assert_allclose(
                np.asarray(flat_s[path], np.float32),
                np.asarray(a, np.float32), rtol=1e-5, atol=1e-7,
                err_msg=jax.tree_util.keystr(path))

    def test_decode_layer_params_slices_scanned_tree(self):
        from dalle_tpu.models.decode import layer_params
        cfg = _cfg(True)
        params = _init(cfg)
        layers = layer_params(params, cfg)
        assert len(layers) == cfg.depth
        group = len(cfg.attn_types)
        tr = params["params"]["transformer"]
        for uid in (0, 5, 7):
            rep, sub = divmod(uid, group)
            want = tr["cycle"][f"block_{sub}"]["attn"]["q"]["kernel"][rep]
            got = layers[uid]["attn"]["q"]["kernel"]
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
        assert layers[-1]["attn_type"] == "conv_like"
