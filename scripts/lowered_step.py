#!/usr/bin/env python
"""A preset's lowered grad step as text two trees can be compared by.

Lowers ``grad_step`` of a sparse-model preset at a cell's batch for a
DESCRIBED ``v5e:2x2`` chip (from the sandbox, no chip; the dispatchers are
told the backend is a TPU, so the Mosaic kernels are in) and writes its
StableHLO with every Mosaic kernel's serialized body, which carries source
lines, replaced by the body as MLIR without locations. Two trees whose
outputs are byte-equal run the same program::

    JAX_PLATFORMS=cpu PYTHONPATH=<tree> python scripts/lowered_step.py \\
        --preset smallthinker21b --micro 2 --accum 4 --out <file>

What a PR that edits ``models/sparse_lm.py`` shows for the presets it
must not move (PERF.md section 6, PR 33). After the byte count it prints
what the trace cost by Mosaic call site (calls, keys, seconds: the compile
counter's ``by_site``), so a PR that adds sites sees what they cost tracing
before it spends a chip-minute. It holds libtpu's lock while it runs: one
at a time.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import os
import re

BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def kernel_text(body_b64: str) -> str:
    """A serialized Mosaic kernel as MLIR text without locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True      # the versioned dialect
    with ctx:
        module = ir.Module.parse(base64.b64decode(body_b64))
        return module.operation.get_asm(enable_debug_info=False)


def lowered_text(preset: str, micro: int, accum: int,
                 devices: int = 1) -> str:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dalle_tpu.cli.run_trainer import MODEL_PRESETS
    from dalle_tpu.models import family
    from dalle_tpu.parallel.mesh import batch_sharding, make_mesh
    from dalle_tpu.training.steps import make_grad_step

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cfg = MODEL_PRESETS[preset]()
    mesh = make_mesh(devices=topo.devices[:devices])
    module = family(cfg)
    model = module.build(cfg, mesh)
    shapes = jax.eval_shape(
        lambda: module.init_params(model, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, P())), shapes)
    tokens = lambda length: jax.ShapeDtypeStruct(
        (micro * accum, length), jnp.int32, sharding=batch_sharding(mesh))
    default_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        text = jax.jit(make_grad_step(model, accum_steps=accum)).lower(
            params, {"text": tokens(cfg.text_seq_len),
                     "image": tokens(cfg.image_seq_len)}).as_text()
    finally:
        jax.default_backend = default_backend
    seen = {}

    def without_locations(match):
        body = match.group(1)
        if body not in seen:
            seen[body] = kernel_text(body)
        return "\\22body\\22: <<" + seen[body] + ">>"

    return BODY.sub(without_locations, text)


def site_account(snap) -> str:
    """What this one trace cost, on this box's CPU (proportions and
    counts, not the chip host's speeds): the step's tracing and lowering,
    and every Mosaic call site's calls, keys and seconds."""
    step = snap["by_program"].get("grad_step", {})
    lines = [f"grad_step traced in {step.get('trace_s', 0.0):.1f} s, "
             f"lowered in {step.get('lower_s', 0.0):.1f} s; by call site:",
             f"  {'site':28s} calls keys  trace_s again_s"]
    for site, at in sorted(snap["by_site"].items(),
                           key=lambda kv: -kv[1]["trace_s"]):
        lines.append(f"  {site:28s} {at['calls']:5d} {at['keys']:4d} "
                     f"{at['trace_s']:8.2f} {at['again_s']:7.2f}")
    return "\n".join(lines)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--preset", default="smallthinker21b")
    parser.add_argument("--micro", type=int, default=2)
    parser.add_argument("--accum", type=int, default=4)
    parser.add_argument("--devices", type=int, default=1,
                        help="of the described host's four, all on dp; "
                        "--micro is then the devices' micro-batches together")
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)
    from dalle_tpu.obs import compiles
    counter = compiles.install(None)     # no task: the counter alone
    text = lowered_text(args.preset, args.micro, args.accum, args.devices)
    with open(args.out, "w") as f:
        f.write(text)
    print(len(text), "bytes, sha256",
          hashlib.sha256(text.encode()).hexdigest())
    print(site_account(counter.snapshot()))


if __name__ == "__main__":
    main()
