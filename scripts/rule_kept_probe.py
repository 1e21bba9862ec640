#!/usr/bin/env python
"""One gated-delta mixer of ``qwen3next80b`` alone at the cell's local shape
(1 x 8 192 tokens), on the chip: a loss of the layer under ``nn.remat`` and
its gradients by what the policy keeps of the rule
(``delta_rule_kernels.KEPT``):

- ``nothing``: the names taken out of ``sparse_lm.KEPT_OF_A_LAYER`` (the
  replay runs the forward kernel again: the tree before PR 66);
- ``made``: what the kernel made (``o``, the state a grid step and the
  inverses), not what it read;
- ``as_shipped``: ``KEPT_OF_A_LAYER`` as it stands;
- ``everything``: no replay at all (``everything_saveable``): the gradient
  of the forward pass as it ran.

Prints each one's device time a call of the loss and its gradients (a
profile of five calls, by operation) and how far its numbers lie from
``everything``'s and from ``nothing``'s (the worst leaf's relative L2
distance, and whether every leaf is equal bit for bit): what a replay that
XLA fuses otherwise than the forward pass changes in the numbers. Fails
without a TPU::

    python3 scripts/rule_kept_probe.py [--seed N] [--out chiprun_out/<dir>]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CALLS = 5


def device_seconds(trace_dir: Path) -> dict:
    """The device's self seconds in the newest profile there, by operation
    (scripts/align_probe.py)."""
    from benchmark import trace
    reduced = trace.Reduced(trace.load_xplane(trace.find_xplane(trace_dir)))
    return {trace.op_key(name): seconds
            for name, seconds in reduced.seconds_by_name().items() if seconds}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_tpu.config import qwen3next80b_model_config
    from dalle_tpu.models import sparse_lm
    from dalle_tpu.ops.pallas import delta_rule_kernels as K

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")
    cfg = qwen3next80b_model_config()
    others = tuple(n for n in sparse_lm.KEPT_OF_A_LAYER if n not in K.KEPT)
    named = jax.checkpoint_policies.save_only_these_names
    policies = {
        "nothing": named(*others),
        "made": named(*others, K.KEPT_MADE),
        "as_shipped": named(*sparse_lm.KEPT_OF_A_LAYER),
        "everything": jax.checkpoint_policies.everything_saveable,
    }
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    a = jax.random.normal(keys[0], (1, cfg.total_seq_len, cfg.hidden_size),
                          jnp.dtype(cfg.dtype))
    weigh = jax.random.normal(keys[1], a.shape, jnp.float32)
    mixer = lambda policy: nn.remat(sparse_lm.GatedDeltaMixer,
                                    policy=policy)(cfg, None)
    weights = jax.jit(mixer(None).init)(keys[2], a)
    # ``A_log`` as the tests draw it: a state kept some fifteen tokens
    weights = {"params": {**weights["params"], "A_log": jnp.full_like(
        weights["params"]["A_log"], np.log(0.05))}}

    def grads(policy):
        # a loss whose cotangent reads the output: the first forward runs
        loss = lambda w, a: 0.5 * jnp.sum(jnp.square(
            mixer(policy).apply(w, a).astype(jnp.float32) * weigh))
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

    out = {"device": device.device_kind, "seed": args.seed,
           "shape": list(a.shape), "policies": {}}
    leaves = {}
    for name, policy in policies.items():
        fn = grads(policy)
        got = jax.block_until_ready(fn(weights, a))
        leaves[name] = {jax.tree_util.keystr(k): np.asarray(v, np.float32)
                        for k, v in
                        jax.tree_util.tree_flatten_with_path(got)[0]}
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(CALLS):
                    jax.block_until_ready(fn(weights, a))
            ops = device_seconds(Path(tmp))
        ms = {op: round(s / CALLS * 1e3, 4) for op, s in sorted(
            ops.items(), key=lambda kv: -kv[1])[:8]}
        out["policies"][name] = {
            "ms_a_call": round(sum(ops.values()) / CALLS * 1e3, 4),
            "by_operation": ms}
    for name, got in leaves.items():
        for other in ("everything", "nothing"):
            want = leaves[other]
            apart = {leaf: float(np.linalg.norm(got[leaf] - want[leaf])
                                 / max(np.linalg.norm(want[leaf]), 1e-30))
                     for leaf in want}
            worst = max(apart, key=apart.get)
            out["policies"][name]["from_" + other] = {
                "bit_equal": all(np.array_equal(got[leaf], want[leaf])
                                 for leaf in want),
                "worst_leaf": worst, "worst_rel_l2": apart[worst]}
        print(name, json.dumps(out["policies"][name]), flush=True)
    if args.out:
        Path(args.out).mkdir(parents=True, exist_ok=True)
        (Path(args.out) / "kept.json").write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
