"""Transformer stack with the reference's weight-sharing scheme.

The reference flagship (``task.py:62-83`` of learning-at-home/dalle) is depth
64 but only ~5 unique blocks: ``shared_attn_ids``/``shared_ff_ids`` cycle
``(0, 1, 2, 3)`` over the first 63 layers and the final layer is a distinct
``'w_conv'`` conv-like block. Weight sharing is expressed here by calling the
same Flax submodule instance at every layer that shares its id — Flax reuses
the parameters, XLA sees 64 layer applications reading 5 parameter sets.

Memory: the reference uses reversible residual layers (``reversible=True``,
``task.py:81``) to get O(1) activation memory; the XLA-idiomatic equivalent is
rematerialisation — each block is wrapped in ``jax.checkpoint`` via
``nn.remat`` so backward recomputes activations block by block.

The layer scan: the body's layers run as one ``nn.scan`` over
:class:`BlockCycle`, whose body holds ``shared_block_cycle x scan_unroll``
block slots. Where the body is not a whole number of iterations (the
flagship's 63 layers in 8 x 8 slots) the last iteration has no layer for
its final slots: such a slot runs under a conditional on the scan index
(:func:`_run_if`), the slots that are never empty call their block
outright, and the chip computes the 63 layers the model has.
:func:`layer_loop_record` says which case a configuration is.
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.extend import core as jex_core
from jax.sharding import PartitionSpec as P

from dalle_tpu.config import ModelConfig
from dalle_tpu.models.attention import (
    apply_rotary_lanes,
    rotary_cos_sin,
    zoo_attention_lanes,
)
from dalle_tpu.ops.pallas import lowering
from dalle_tpu.parallel.mesh import TOKENS_SPEC, per_shard

logger = logging.getLogger(__name__)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _param_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


class ZooAttention(nn.Module):
    """Multi-head attention with a static zoo type (full/axial/conv_like).

    When ``cfg.sequence_parallel != "none"`` and a mesh with ``sp > 1`` is
    attached, the attention op is an explicit ``shard_map`` program over the
    sequence axis (ring or Ulysses all-to-all; parallel/sequence.py).
    """

    cfg: ModelConfig
    attn_type: str
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, rot=None) -> jax.Array:
        cfg = self.cfg
        b, t, _ = x.shape
        # Separate q/k/v projections: a fused qkv matmul needs three strided
        # slices of its output, which XLA materializes as HBM copies per
        # layer; three matmuls of the same total FLOPs fuse cleanly instead.
        # q/k/v stay the (B, T, H*d) arrays the projections emit, through
        # rotary and the attention kernels to the out projection: an array
        # with a minor dimension of head_dim (64) is tiled half empty.
        proj = dict(use_bias=False, dtype=_dtype(cfg),
                    param_dtype=_param_dtype(cfg))
        q = nn.Dense(cfg.dim, **proj, name="q")(x)
        k = nn.Dense(cfg.dim, **proj, name="k")(x)
        v = nn.Dense(cfg.dim, **proj, name="v")(x)
        if rot is not None:
            cos, sin = rot
            q = apply_rotary_lanes(q, cos, sin, cfg.head_dim)
            k = apply_rotary_lanes(k, cos, sin, cfg.head_dim)
        # names for the optional remat save-policy (config.remat_policy):
        # saving rotated q/k/v lets the backward pass skip recomputing the
        # projections. They go on the (B, T, H*d) arrays, so what
        # save_attn keeps is unpadded; the attention kernel's own outputs
        # are named "attn_out"/"attn_stats" inside its custom_vjp fwd rule
        # (ops/pallas/attention_kernels.py) so policies can prune the
        # kernel replay too
        q = checkpoint_name(q, "attn_q")
        k = checkpoint_name(k, "attn_k")
        v = checkpoint_name(v, "attn_v")
        zoo = dict(attn_type=self.attn_type, text_len=cfg.text_seq_len,
                   grid=cfg.image_grid, conv_kernel=cfg.conv_kernel,
                   mesh=self.mesh)
        if (cfg.sequence_parallel != "none" and self.mesh is not None
                and self.mesh.shape.get("sp", 1) > 1):
            from dalle_tpu.parallel.sequence import sp_zoo_attention
            out = sp_zoo_attention(
                *(a.reshape(b, t, cfg.heads, cfg.head_dim)
                  for a in (q, k, v)),
                mode=cfg.sequence_parallel, **zoo)
            # names emitted inside the shard_map body don't surface to
            # the outer remat policy: name the sp output here so
            # save_ctx/save_attn at least save the attention RESULT
            # (pruning the output recompute; shard_map internals still
            # replay for their own residuals)
            out = checkpoint_name(out, "attn_ctx")
        else:
            out = zoo_attention_lanes(q, k, v, head_dim=cfg.head_dim,
                                      scope=self.name, **zoo)
        # (the attention output is named for the remat save-policies at
        # its source: "attn_out"/"attn_stats" inside the Pallas kernels'
        # custom_vjp fwd rules, "attn_ctx" on the dense/axial XLA paths —
        # exactly one set per layer. Ring-SP layers are unnamed: their
        # shard_map internals are not policy-saveable.)
        out = out.reshape(b, t, cfg.dim)
        return nn.Dense(cfg.dim, dtype=_dtype(cfg),
                        param_dtype=_param_dtype(cfg), name="out")(out)


class FusedLayerNorm(nn.Module):
    """Parameter-compatible stand-in for ``nn.LayerNorm``: owns the same
    ``{scale, bias}`` (d,) params in param dtype, routed through the
    single-pass Pallas kernel (ops/pallas/ln_kernels.py) when the shape
    supports it. The fallback is the flax lowering written out inline
    (f32 stats, fast variance, f32 affine) so both paths share one
    parameter tree and one numerical contract. With a ``mesh`` of more
    than one device the kernel runs per shard of the token rows."""

    cfg: ModelConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones_init(), (d,),
                           _param_dtype(cfg))
        bias = self.param("bias", nn.initializers.zeros_init(), (d,),
                          _param_dtype(cfg))
        # one numerical contract (flax's): statistics are formed in f32
        # from the ORIGINAL input. The kernel reads activation-dtype
        # tiles, so it is used only when the input is ALREADY in
        # activation dtype (the model's steady state — the cast below is
        # then a no-op); a wider input (f32 into a bf16 model) takes the
        # inline fallback, whose f32 stats match nn.LayerNorm exactly
        # (ADVICE r4: the two paths previously diverged on such inputs)
        xla = functools.partial(_layer_norm_xla, out_dtype=_dtype(cfg))
        if x.ndim == 3 and x.dtype == jnp.dtype(_dtype(cfg)):
            return lowering.site(
                "LayerNorm", _layer_norm_tiles, _layer_norm_kernel, xla,
                self.mesh, (TOKENS_SPEC, P(), P()), TOKENS_SPEC,
                self.name)(x, scale, bias)
        return xla(x, scale, bias)


def _layer_norm_xla(x, scale, bias, out_dtype):
    from dalle_tpu.ops.pallas.ln_kernels import _stats
    xf = x.astype(jnp.float32)
    mean, rstd = _stats(xf, 1e-6)
    y = ((xf - mean) * rstd
         * scale.astype(jnp.float32) + bias.astype(jnp.float32))
    return y.astype(out_dtype)


def _layer_norm_tiles(x, scale, bias) -> bool:
    """One shard's LayerNorm: the kernel where its LOCAL rows tile."""
    from dalle_tpu.ops.pallas.ln_kernels import ln_supported
    m, d = x.shape[0] * x.shape[1], x.shape[-1]
    ok = ln_supported(m, d)
    words = f"ln_supported({m} local rows, {d}) is {ok}"
    return lowering.chose("LayerNorm", (m, d), None if ok else words, words)


def _layer_norm_kernel(x, scale, bias):
    from dalle_tpu.ops.pallas.ln_kernels import layer_norm
    y = layer_norm(x.reshape(-1, x.shape[-1]), scale, bias, 1e-6, 256,
                   lowering.interpret())
    return y.reshape(x.shape)


def _norm(cfg: ModelConfig, name: str, mesh=None):
    """The block norm: fused Pallas LN when ``cfg.ln_fusion``, else flax's
    ``nn.LayerNorm`` — identical {scale, bias} param tree either way."""
    if cfg.ln_fusion:
        return FusedLayerNorm(cfg, mesh=mesh, name=name)
    return nn.LayerNorm(dtype=_dtype(cfg), param_dtype=_param_dtype(cfg),
                        name=name)


class DenseKernel(nn.Module):
    """Parameter-compatible stand-in for ``nn.Dense``: owns the identical
    ``{name: {'kernel': (in, out), 'bias': (out,)}}`` param tree (same
    init, same dtype) but returns the parameter VALUES so the caller can
    feed them to a fused kernel — checkpoints trained either way
    interchange. The FF keeps nn.Dense's default biases (dalle-pytorch's
    FeedForward uses biased nn.Linear); attention stays bias-free."""

    features: int
    param_dtype: Any

    @nn.compact
    def __call__(self, in_features: int):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (in_features, self.features), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,), self.param_dtype)
        return kernel, bias


class GEGLUFeedForward(nn.Module):
    """GEGLU MLP (dalle-pytorch's FeedForward uses a GEGLU gate).

    ``fuse`` routes through the Pallas fused kernel
    (ops/pallas/geglu_kernels.py): the (B*T, inner) intermediates stay in
    VMEM tiles and backward saves only ``x`` — on a NON-rematted block
    that removes the dominant autodiff residual (PERF.md r3 headroom #1).
    Shapes the kernel cannot tile fall back to the unfused path. With a
    ``mesh`` of more than one device the kernel runs per shard: token
    rows over dp/fsdp/sp, the inner dimension over tp with one psum of
    the partial products.
    """

    cfg: ModelConfig
    fuse: bool = False
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        inner = cfg.ff_mult * cfg.dim
        d = x.shape[-1]
        cd = _dtype(cfg)
        # Separate value/gate matmuls: one fused projection + split costs
        # two big HBM slice copies per layer (see ZooAttention).
        wi, bi = DenseKernel(inner, _param_dtype(cfg), name="wi")(d)
        wg, bg = DenseKernel(inner, _param_dtype(cfg), name="gate")(d)
        wo, bo = DenseKernel(cfg.dim, _param_dtype(cfg), name="wo")(inner)
        wi, wg, wo = wi.astype(cd), wg.astype(cd), wo.astype(cd)
        bi, bg, bo = bi.astype(cd), bg.astype(cd), bo.astype(cd)
        x = x.astype(cd)
        if self.fuse and lowering.mosaic():
            tp = self.mesh.shape["tp"] if self.mesh is not None else 1
            return per_shard(
                functools.partial(_geglu_shard, tp=tp), self.mesh,
                (TOKENS_SPEC, P(None, "tp"), P(None, "tp"), P("tp", None),
                 P("tp"), P("tp"), P(), P("tp")), TOKENS_SPEC,
                scope=self.name)(
                    x, wi, wg, wo, bi, bg, bo, jnp.arange(tp))
        return _geglu_xla(x, wi, wg, wo, bi, bg, bo)


def _geglu_xla(x, wi, wg, wo, bi, bg, bo):
    h = jnp.dot(x, wi) + bi
    gate = jnp.dot(x, wg) + bg
    return jnp.dot(h * nn.gelu(gate), wo) + bo


def _geglu_shard(x, wi, wg, wo, bi, bg, bo, tp_index, *, tp: int):
    """One shard's GEGLU FF: the fused kernel where the LOCAL shapes tile.
    Under tp each shard holds a slice of the inner dimension, so its
    output is a partial product: the output bias joins on one shard only
    (``tp_index``: (1,), the shard's slice of ``arange(tp)``; see
    parallel/mesh.shard_map_unbound) and the partials are summed over
    ``tp``."""
    from dalle_tpu.ops.pallas.geglu_kernels import geglu_ff, geglu_supported
    b, t, d = x.shape
    inner = wi.shape[1]
    if tp > 1:
        bo = jnp.where(tp_index[0] == 0, bo, jnp.zeros_like(bo))
    ok = geglu_supported(b * t, d, inner, x.dtype)
    words = (f"geglu_supported({b * t} local rows, {d}, {inner}, {x.dtype}) "
             f"is {ok}")
    site, key = "GEGLU feed-forward", (b * t, d, inner, x.dtype.name)
    took = lowering.chose(site, key, None if ok else words, words)
    with lowering.traced(site, key):
        if took:
            out = geglu_ff(x.reshape(b * t, d), wi, wg, wo, bi, bg, bo,
                           256, 512, lowering.interpret()).reshape(b, t, d)
        else:
            out = _geglu_xla(x, wi, wg, wo, bi, bg, bo)
    return jax.lax.psum(out, "tp") if tp > 1 else out


class TransformerBlock(nn.Module):
    """Pre-norm attention + GEGLU FF with residuals.

    ``fuse_ff`` routes the FF through the fused Pallas GEGLU kernel —
    set on NON-rematted blocks (cfg.ff_fusion), where the fused
    custom_vjp shrinks the block's saved residuals to the kernel inputs.
    """

    cfg: ModelConfig
    attn_type: str
    mesh: Any = None
    fuse_ff: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, rot=None) -> jax.Array:
        cfg = self.cfg
        h = _norm(cfg, "attn_norm", self.mesh)(x)
        x = x + ZooAttention(cfg, self.attn_type, mesh=self.mesh,
                             name="attn")(h, rot)
        h = _norm(cfg, "ff_norm", self.mesh)(x)
        x = x + GEGLUFeedForward(cfg, fuse=self.fuse_ff, mesh=self.mesh,
                                 name="ff")(h)
        return x


def _scan_plan(cfg: ModelConfig):
    """How the body of ``cfg``'s schedule is scanned: ``(n_body, cycle,
    per_iter, reps)``. The scan body holds ``per_iter`` block slots, which
    cycle ``cycle`` unique blocks, and runs ``reps`` times; ``reps`` is 0
    where the stack unrolls instead (no cycle, or a body no longer than
    one iteration)."""
    n_body = len(cfg.layer_schedule()) - (1 if cfg.final_conv_block else 0)
    # dense (cycle=0) with dense_scan: scan one attn-type group with
    # STACKED per-iteration params — the compiled body stays one group
    # while every iteration reads its own weights (a 64-block dense
    # flagship otherwise unrolls to an XLA program ~16x the shared
    # model's, past the compile service's budget). Each iteration's param
    # slice is one group of layers, so in-iteration unrolling would REUSE
    # that slice — and the unroll lever only exists to amortize the
    # shared-weight grad accumulation dense models don't have: unroll 1.
    dense_scan = cfg.dense_scan_reps() > 0
    cycle = len(cfg.attn_types) if dense_scan else cfg.shared_block_cycle
    per_iter = cycle * (1 if dense_scan else max(1, cfg.scan_unroll))
    reps = -(-n_body // per_iter) if cycle else 0
    return n_body, cycle, per_iter, reps if reps > 1 else 0


def layer_loop_record(cfg: ModelConfig) -> str:
    """How the layer scan runs ``cfg``'s body, in words — the
    ``layer_loop`` attribute of the ``train`` plane's ``setup/warmup``
    row: how many slots :class:`BlockCycle` always runs and how many sit
    under a conditional because the last iteration has no layer for
    them."""
    n_body, _, per_iter, reps = _scan_plan(cfg)
    if not reps:
        return "unrolled"
    always = n_body - (reps - 1) * per_iter
    head = f"{n_body} layers in {reps} x {per_iter} slots: "
    if always == per_iter:
        return head + "all always run"
    return head + (f"{always} always run, {per_iter - always} conditional "
                   f"(runs {reps - 1} of {reps})")


@functools.lru_cache(maxsize=None)
def _log_layer_loop(record: str) -> None:
    logger.info("layer loop: %s", record)


def _run_if(active, fn, consts, x):
    """``fn(consts, x)`` where ``active`` (a traced scalar), else ``x``: a
    conditional that costs the empty turn nothing, forward or backward.

    Differentiated by its own rule, because ``lax.cond``'s returns every
    value its backward reads as an output of the forward conditional, the
    branch's own inputs among them: inside the layer scan each iteration
    would copy the block's weights and rotary tables out of the
    conditional and stack them with the residuals (the XL step planned
    2.9 GiB more that way). Here the forward conditional returns the
    result and those residuals only that the live branch computes: one
    that IS an input of the branch (a weight, the block's input) or
    another output (the result itself) is read where it already lives. A
    rematted block, whose residuals are its inputs, so crosses the
    conditional with nothing but its result; a plain one with what it
    saves. The backward conditional runs the branch's pullback or passes
    the cotangent through beside zero weight gradients.
    """
    zeros = functools.partial(jax.tree.map,
                              lambda a: jnp.zeros(a.shape, a.dtype))
    args, in_tree = jax.tree.flatten((consts, x))
    # ``fn`` is traced here, once, as the always-live slots' blocks are;
    # both directions below work on its jaxpr
    traced = jax.make_jaxpr(
        lambda *args: fn(*jax.tree.unflatten(in_tree, args)))(*args)

    def live(*args):
        return jex_core.jaxpr_as_fun(traced)(*args)[0]

    @jax.custom_vjp
    def run(active, *args):
        return jax.lax.cond(active, live, lambda *args: args[-1], *args)

    def fwd(active, *args):
        closed, out_shape = jax.make_jaxpr(
            lambda *args: jax.vjp(live, *args), return_shape=True)(*args)
        # where each output of the live branch comes from: an input or a
        # constant of the branch, or the first output that holds the
        # same value
        source = {id(v): ("in", i) for i, v in enumerate(closed.jaxpr.invars)}
        source.update({id(v): ("const", i)
                       for i, v in enumerate(closed.jaxpr.constvars)})
        kept = []
        for i, v in enumerate(closed.jaxpr.outvars):
            if id(v) not in source:
                source[id(v)] = ("kept", len(kept))
                kept.append(i)
        assert kept[0] == 0, "the branch's result is its own output"

        def branch(*args):
            outs = jex_core.jaxpr_as_fun(closed)(*args)
            return [outs[i] for i in kept]

        def empty(*args):
            return [args[-1]] + zeros([closed.out_avals[i] for i in kept[1:]])

        held = {"in": args, "const": closed.consts,
                "kept": jax.lax.cond(active, branch, empty, *args)}
        y, pullback = jax.tree.unflatten(
            jax.tree.structure(out_shape),
            [held[kind][i] for kind, i in
             (source[id(v)] for v in closed.jaxpr.outvars)])
        return y, (active, pullback)

    def bwd(residuals, ct):
        active, pullback = residuals
        return (None,) + tuple(jax.lax.cond(
            active, lambda pullback, ct: pullback(ct),
            lambda pullback, ct: (*zeros(traced.in_avals[:-1]), ct),
            pullback, ct))

    run.defvjp(fwd, bwd)
    return run(active, *args)


def _as_function(block: nn.Module):
    """A bound block as a function of ``((variables, rot), x)``: what
    :func:`_run_if` takes (its conditional is jax's, not a lifted one)."""
    call = nn.apply(lambda blk, x, rot: blk(x, rot), block)
    return lambda consts, x: call(consts[0], x, consts[1])


class BlockCycle(nn.Module):
    """One pass over the unique weight-shared blocks (the scan body).

    The body's depth bounds the global layer index: when it is not a
    clean multiple of the slots an iteration holds (the flagship's 63 in
    8 x 8), the last of the ``reps`` iterations has no layer for its final
    slots. A slot the last iteration still fills is always live: it calls
    its block and takes the result. A slot that can be empty runs under a
    conditional on the scan index — the block in one branch, the identity
    in the other — so the empty turn costs no forward, no replay and no
    backward, and the cycle is still compiled once.
    """

    cfg: ModelConfig
    block_cls: Any
    mesh: Any = None
    # blocks with uid >= cycle - remat_skip_blocks use this class instead
    # (plain, no remat) — partial remat, cfg.remat_skip_blocks
    plain_cls: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, it: jax.Array) -> jax.Array:
        cfg = self.cfg
        rot = _make_rot(cfg)
        # the weight-shared path cycles cfg.shared_block_cycle unique
        # blocks; the dense_scan path (stacked per-iteration params) one
        # attn-type group
        n_body, cycle, per_iter, reps = _scan_plan(cfg)
        # the slots the last iteration still fills: those never empty
        filled = n_body - (reps - 1) * per_iter
        first_plain = cycle - cfg.remat_skip_blocks
        blocks = {}
        for uid in range(cycle):
            attn_type = cfg.attn_types[uid % len(cfg.attn_types)]
            is_plain = self.plain_cls is not None and uid >= first_plain
            cls = self.plain_cls if is_plain else self.block_cls
            blocks[uid] = cls(cfg, attn_type, mesh=self.mesh,
                              fuse_ff=cfg.fuse_ff(is_plain),
                              name=f"block_{uid}")
        for slot in range(per_iter):
            # one module instance per uid, called ``per_iter / cycle`` times:
            # Flax shares the parameters across the calls
            block = blocks[slot % cycle]
            # (while initializing, the plain call: both branches of a
            # conditional must make the same variables, and with unroll 1
            # the conditional slot is its block's first call)
            if slot < filled or self.is_initializing():
                x = block(x, rot)
            else:
                x = _run_if(it * per_iter + slot < n_body,
                            _as_function(block), (block.variables, rot), x)
        return x, None


def _make_rot(cfg: ModelConfig):
    if not cfg.rotary:
        return None
    positions = jnp.arange(cfg.total_seq_len)
    return rotary_cos_sin(positions, cfg.head_dim, heads=cfg.heads)


class Transformer(nn.Module):
    """The depth-``cfg.depth`` stack following ``cfg.layer_schedule()``.

    Blocks with the same unique id are the same module instance, so their
    parameters are shared (reference weight sharing, ``task.py:65,78-79``).
    When the schedule is a clean repetition of the unique cycle, the
    repetitions run as one ``nn.scan`` with broadcast parameters — XLA
    compiles the cycle once instead of unrolling 64 layers (SURVEY.md §2:
    "lax.scan over a stack of 4 unique blocks repeated 16x"), and the
    shared weights' gradients accumulate through the scan. A body that is
    not a whole number of iterations is scanned all the same: the slots
    its last iteration leaves empty are conditional (:class:`BlockCycle`).
    """

    cfg: ModelConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        sched = cfg.layer_schedule()

        block_cls = TransformerBlock
        if cfg.remat:
            # Every attention lowering names its output exactly once at
            # the source — "attn_out"+"attn_stats" inside the Pallas
            # kernels' custom_vjp fwd rules (so backward never re-runs
            # the forward kernel), "attn_ctx" on the dense/axial XLA
            # paths — so saving all three names never double-stores a
            # layer, and a model that mixes lowerings (e.g. a conv layer
            # past the window kernel's VMEM budget falling back to dense)
            # still saves every layer's context.
            ctx_names = ("attn_out", "attn_stats", "attn_ctx")
            if cfg.remat_policy == "save_attn":
                policy = jax.checkpoint_policies.save_only_these_names(
                    "attn_q", "attn_k", "attn_v", *ctx_names)
            elif cfg.remat_policy == "save_ctx":
                # Saves only the attention outputs: backward replays the
                # cheap projections/rotary but never the attention itself.
                # ~10 MB/layer at flagship micro 4 vs ~42 MB/layer for
                # full save_attn.
                policy = jax.checkpoint_policies.save_only_these_names(
                    *ctx_names)
            else:
                policy = None  # blanket remat: save only block boundaries
            block_cls = nn.remat(TransformerBlock, policy=policy)

        body, _, _, reps = _scan_plan(cfg)
        _log_layer_loop(layer_loop_record(cfg))
        if reps:
            dense_scan = cfg.dense_scan_reps() > 0
            scan = nn.scan(
                BlockCycle,
                variable_broadcast=() if dense_scan else "params",
                variable_axes={"params": 0} if dense_scan else {},
                split_rngs={"params": dense_scan})
            x, _ = scan(cfg, block_cls, mesh=self.mesh,
                        plain_cls=(TransformerBlock if cfg.remat
                                   and cfg.remat_skip_blocks
                                   and not dense_scan else None),
                        name="cycle")(x, jnp.arange(reps))
            rest = sched[body:]
        else:
            rest = sched

        rot = _make_rot(cfg)
        # partial remat must also apply on the unrolled path (cycle == 0 or
        # a single repetition): the highest `remat_skip_blocks` unique body
        # uids keep their activations (w_conv stays rematted)
        body_uids = sorted({u for u, _ in rest if u != -1})
        plain_uids = set(body_uids[len(body_uids) - cfg.remat_skip_blocks:]
                         if cfg.remat and cfg.remat_skip_blocks else [])
        blocks = {}
        for uid, attn_type in rest:
            if uid not in blocks:
                name = "block_wconv" if uid == -1 else f"block_{uid}"
                is_plain = uid in plain_uids
                cls = TransformerBlock if is_plain else block_cls
                blocks[uid] = cls(cfg, attn_type, mesh=self.mesh,
                                  fuse_ff=cfg.fuse_ff(is_plain),
                                  name=name)
            x = blocks[uid](x, rot)

        return _norm(cfg, "final_norm", self.mesh)(x)
