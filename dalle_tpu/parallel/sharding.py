"""Parameter partitioning rules (GSPMD via path-pattern -> PartitionSpec).

Megatron-style tensor parallelism for the block matmuls, FSDP sharding of the
remaining large tensors, replication for small ones. Rules are matched on the
flattened parameter path, most-specific first; the first rule whose pattern is
a substring of the path wins. This replaces the reference's single-axis
torch_xla data parallelism (``lib/training/tpu.py``) with a full 4-axis
layout while remaining a no-op on a 1-device mesh.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (path-substring, PartitionSpec); first match wins. Kernel layouts:
#   q/k/v: (dim, dim)         -> columns (heads) split over tp, rows fsdp
#   out:  (dim, dim)          -> rows (heads) split over tp, cols fsdp
#   wi/gate: (dim, inner)     -> columns over tp
#   wo:   (inner, dim)        -> rows over tp
#   token_emb: (vocab, dim)   -> vocab over tp (tied head contracts over dim)
PARAM_RULES: Tuple[Tuple[str, P], ...] = (
    ("attn/q/kernel", P("fsdp", "tp")),
    ("attn/k/kernel", P("fsdp", "tp")),
    ("attn/v/kernel", P("fsdp", "tp")),
    ("attn/out/kernel", P("tp", "fsdp")),
    ("ff/wi/kernel", P("fsdp", "tp")),
    ("ff/gate/kernel", P("fsdp", "tp")),
    ("ff/wo/kernel", P("tp", "fsdp")),
    ("token_emb", P("tp", None)),
    ("text_pos_emb", P(None, None)),
    ("img_row_emb", P(None, None)),
    ("img_col_emb", P(None, None)),
    ("lm_head/kernel", P("fsdp", "tp")),
)


def spec_for_path(path: str) -> P:
    for pattern, spec in PARAM_RULES:
        if pattern in path:
            return spec
    return P()  # norms, biases, scalars: replicated


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_specs(params) -> Any:
    """PartitionSpec pytree matching the parameter pytree."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    specs = []
    for path, leaf in flat:
        spec = spec_for_path(_path_str(path))
        # dense_scan stacks per-iteration params: the leaf carries ONE
        # extra leading scan-reps axis over the rank its rule was written
        # for — shift the spec right so fsdp/tp land on the same matmul
        # dims as the unrolled layout (reps stay unsharded).
        if spec and leaf.ndim == len(spec) + 1:
            spec = P(None, *spec)
        # Trim the spec to the leaf's rank; divisibility against a concrete
        # mesh is handled in param_shardings.
        kept = [ax if i < leaf.ndim else None
                for i, ax in enumerate(spec)]
        specs.append(P(*kept) if kept else P())
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(mesh: Mesh, params) -> Any:
    specs = param_specs(params)

    def _fix(leaf, spec):
        # Drop shardings whose mesh axis doesn't divide the dimension (XLA
        # requires even sharding); the remaining axes stay sharded.
        axes = []
        for i, ax in enumerate(spec):
            if ax is None:
                axes.append(None)
                continue
            size = mesh.shape[ax] if isinstance(ax, str) else 1
            if i < leaf.ndim and leaf.shape[i] % size == 0:
                axes.append(ax)
            else:
                axes.append(None)
        return NamedSharding(mesh, P(*axes))

    return jax.tree.map(_fix, params, specs)


def opt_state_shardings(mesh: Mesh, opt_state, params) -> Any:
    """Shardings for optimizer state: moment trees (same treedef as the
    params) inherit the param shardings; block-quantized moments shard
    their (n_blocks, ...) codes/absmax over the fsdp axis; everything else
    (step counts, scalars) replicates.

    Replicating fp32 moments — the largest tensors in training — on every
    chip would defeat FSDP and negate the memory point of 8-bit state.
    """
    from dalle_tpu.ops.quant import Quantized, blocks_spec

    rep = NamedSharding(mesh, P())
    pshards = param_shardings(mesh, params)
    ptreedef = jax.tree.structure(params)

    def _is_q(x) -> bool:
        return isinstance(x, Quantized)

    def _quantized_shardings(q: Quantized) -> Quantized:
        blocks = NamedSharding(mesh, blocks_spec(mesh, q.codes.shape[0]))
        return Quantized(codes=blocks, absmax=blocks,
                         shape=q.shape, signed=q.signed)

    def _moment_tree(tree):
        # dense moment leaves share their param's shape, so its sharding
        # applies directly
        def f(m, s):
            return _quantized_shardings(m) if _is_q(m) else s
        return jax.tree.map(f, tree, pshards, is_leaf=_is_q)

    def place(node):
        try:
            if jax.tree.structure(node, is_leaf=_is_q) == ptreedef:
                return _moment_tree(node)
        except (TypeError, ValueError):
            pass
        if isinstance(node, tuple):
            rebuilt = [place(child) for child in node]
            return (type(node)(*rebuilt) if hasattr(node, "_fields")
                    else tuple(rebuilt))
        return jax.tree.map(lambda _: rep, node)

    return place(opt_state)


def shard_train_state(mesh: Mesh, state):
    """Place a TrainState on the mesh: params per PARAM_RULES, optimizer
    moments inheriting the param shardings (Quantized codes/absmax sharded
    over fsdp), step counters replicated. The single canonical placement
    used by the driver dry-run, the benchmark, and the trainer CLI."""
    rep = NamedSharding(mesh, P())
    opt_sh = opt_state_shardings(mesh, state.opt_state, state.params)
    return type(state)(
        step=jax.device_put(state.step, rep),
        params=jax.device_put(state.params, param_shardings(mesh,
                                                            state.params)),
        opt_state=jax.tree.map(jax.device_put, state.opt_state, opt_sh))
