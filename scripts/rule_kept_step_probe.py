#!/usr/bin/env python
"""``qwen3next80b``'s whole grad step (micro 1 x accum 8, the cell's) on the
chip, traced twice in one process on the same seeded weights and batch: with
``sparse_lm.KEPT_OF_A_LAYER`` as shipped, and without the rule's names
(``delta_rule_kernels.KEPT``: a layer's replay runs the forward kernel and
the XLA code of ``gdn/rule`` again, the program before PR 66). Prints the
two losses and, layer by layer, how far the two programs' gradient leaves lie
apart (relative L2: how many are bit-equal, the median, the worst), then the
twelve leaves furthest apart: what ``correct``'s comparison with the float32
reference cannot tell of two programs that read alike there (PERF.md section
7, PR 66). About 4 min; fails without a TPU::

    python3 scripts/rule_kept_step_probe.py [--seed N] [--out <dir>]
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ACCUM = 8


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=66)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_tpu.cli.run_trainer import MODEL_PRESETS
    from dalle_tpu.models import family, sparse_lm
    from dalle_tpu.ops.pallas import delta_rule_kernels as K
    from dalle_tpu.parallel.mesh import make_mesh
    from dalle_tpu.training.steps import make_grad_step

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")
    cfg = MODEL_PRESETS["qwen3next80b"]()
    module = family(cfg)
    model = module.build(cfg, make_mesh(devices=[device]))
    params = jax.jit(lambda key: module.init_params(model, key))(
        jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    batch = {"text": jnp.asarray(rng.integers(
                 2, cfg.vocab_text, (ACCUM, cfg.text_seq_len)), jnp.int32),
             "image": jnp.asarray(rng.integers(
                 0, cfg.vocab_image, (ACCUM, cfg.image_seq_len)), jnp.int32)}
    shipped = sparse_lm.KEPT_OF_A_LAYER
    policies = {"as_shipped": shipped,
                "without_the_rules_names": tuple(
                    name for name in shipped if name not in K.KEPT)}
    leaves, out = {}, {"device": device.device_kind, "seed": args.seed}
    for name, kept in policies.items():
        sparse_lm.KEPT_OF_A_LAYER = kept        # read when the step is traced
        grads, aux = jax.jit(make_grad_step(model, accum_steps=ACCUM))(
            params, batch)
        leaves[name] = {jax.tree_util.keystr(path): np.asarray(leaf)
                        for path, leaf in
                        jax.tree_util.tree_flatten_with_path(grads)[0]}
        out[name] = {"loss": float(aux["loss"])}
        print(name, out[name], flush=True)
        del grads
    sparse_lm.KEPT_OF_A_LAYER = shipped
    ours, theirs = leaves.values()
    apart = {leaf: float(np.linalg.norm(ours[leaf] - theirs[leaf])
                         / max(np.linalg.norm(theirs[leaf]), 1e-30))
             for leaf in ours}
    by_layer = collections.defaultdict(list)
    for leaf, far in apart.items():
        layer = leaf.split("']['")[1] if "['layer_" in leaf else "no layer"
        by_layer[layer].append(far)
    out["by_layer"] = {
        layer: {"leaves": len(far), "bit_equal": sum(x == 0 for x in far),
                "median": float(np.median(far)), "worst": max(far)}
        for layer, far in sorted(by_layer.items())}
    out["furthest"] = dict(sorted(apart.items(), key=lambda kv: -kv[1])[:12])
    for key in ("by_layer", "furthest"):
        for name, value in out[key].items():
            print(name, json.dumps(value), flush=True)
    if args.out:
        Path(args.out).mkdir(parents=True, exist_ok=True)
        (Path(args.out) / "step_leaves.json").write_text(
            json.dumps(dict(out, apart=apart), indent=1))


if __name__ == "__main__":
    main()
