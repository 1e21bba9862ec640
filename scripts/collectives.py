#!/usr/bin/env python
"""Which collectives a compiled program holds, where, and how often.

Reads ``compiled.as_text()`` (optimised HLO): every all-reduce,
all-gather, reduce-scatter, all-to-all and collective-permute with its
payload, its replica groups, the computation it sits in, and how many
times a step runs it (the product of the known trip counts of the
``while`` loops around it). ``tests/test_train.py`` holds the gradient
step to "no reduction over ``dp`` inside a loop" with it on four virtual
CPU devices; run as a script it compiles the flagship's ``grad_step`` at
the ``flagship-train-dp4`` cell's shapes for a DESCRIBED ``v5e:2x2`` (a
compile from the sandbox, no chip: the verify skill's recipe) and prints
the table PERF.md quotes::

    JAX_PLATFORMS=cpu python scripts/collectives.py [--accum 8] [--micro 4]
"""

from __future__ import annotations

import math
import os
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OP = re.compile(r"=\s*(\(.*?\)|\S+)\s+(" + "|".join(KINDS)
                 + r")(-start)?\(")
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")
_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|"
    r"false_computation)=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
_TRIPS = re.compile(r'known_trip_count[^0-9]*(\d+)')
_OP_NAME = re.compile(r'op_name="([^"]*)"')


class Collective(NamedTuple):
    kind: str
    nbytes: int                    # payload of one run, on one device
    elements: int
    groups: List[List[int]]        # device positions, as the mesh orders them
    computation: str
    in_loop: bool
    runs: Optional[int]            # a step; None = a trip count is unknown
    op_name: str


def _groups(line: str) -> List[List[int]]:
    m = re.search(r"replica_groups=\{(\{[\d,{} ]*\})\}", line)
    if m:
        return [[int(i) for i in g.split(",") if i.strip()]
                for g in re.findall(r"\{([\d, ]*)\}", m.group(1))]
    # iota form: [groups,size]<=[dims] with an optional transpose T(perm)
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]"
                  r"(?:T\(([\d,]+)\))?", line)
    if m:
        import numpy as np
        n_groups, size = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        ids = np.arange(math.prod(dims)).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(p) for p in m.group(4).split(",")])
        return ids.reshape(n_groups, size).tolist()
    m = re.search(r"source_target_pairs=\{([\d,{} ]*)\}", line)
    if m:
        return [[int(i) for i in g.split(",")]
                for g in re.findall(r"\{(\d+,\d+)\}", m.group(1))]
    return []


def collectives(hlo_text: str) -> List[Collective]:
    """Every collective of an optimised HLO module (``-done`` halves of
    the asynchronous pairs are not counted twice)."""
    found = []                     # (computation, line)
    calls: Dict[str, list] = {}    # computation -> (callee, trips, a loop's)
    entry = current = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = head.group(1)
            calls[current] = []
            if line.startswith("ENTRY"):
                entry = current
            continue
        if current is None or "=" not in line:
            continue
        loop = re.search(r"\swhile\(", line) is not None
        trips = _TRIPS.search(line) if loop else None
        factor = (int(trips.group(1)) if trips else None) if loop else 1
        for name, branches in _CALLED.findall(line):
            for callee in ([name] if name else
                           [b.strip().lstrip("%")
                            for b in branches.split(",")]):
                calls[current].append((callee, factor, loop))
        if _OP.search(line):
            found.append((current, line))

    # runs a step: a loop's body runs (its trips) x (its holder's runs)
    runs: Dict[str, Optional[int]] = {}
    in_loop: set = set()
    todo = [(entry, 1, False)]
    while todo:
        comp, n, looped = todo.pop()
        if comp in runs and not (looped and comp not in in_loop):
            continue
        runs[comp] = n
        if looped:
            in_loop.add(comp)
        for callee, factor, loop in calls.get(comp, ()):
            todo.append((callee, None if n is None or factor is None
                         else n * factor, looped or loop))
    out = []
    for comp, line in found:
        shape, kind, _ = _OP.search(line).groups()
        sizes = [(math.prod(int(d) for d in dims.split(",") if d),
                  _DTYPE_BYTES[dt]) for dt, dims in _SHAPE.findall(shape)]
        name = _OP_NAME.search(line)
        out.append(Collective(
            kind=kind, nbytes=sum(n * b for n, b in sizes),
            elements=sum(n for n, _ in sizes), groups=_groups(line),
            computation=comp, in_loop=comp in in_loop,
            runs=runs.get(comp, 1),
            op_name=name.group(1) if name else ""))
    return out


def spans_axis(c: Collective, mesh_shape: Sequence[int], axis: int) -> bool:
    """Whether some group of ``c`` holds devices at different positions
    along mesh axis ``axis`` (device ids are positions in the mesh's
    flattened device array, as ``use_global_device_ids`` numbers them)."""
    stride = math.prod(mesh_shape[axis + 1:])
    return any(len({i // stride % mesh_shape[axis] for i in g}) > 1
               for g in c.groups)


def flagship_dp4_grad_step(micro: int = 4, accum: int = 8):
    """The flagship ``grad_step`` at the dp4 cell's shapes, compiled for a
    described ``v5e:2x2`` (no chip; nothing runs)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dalle_tpu.config import flagship_model_config
    from dalle_tpu.models.dalle import DALLE
    from dalle_tpu.parallel.mesh import BATCH_SPEC, make_mesh
    from dalle_tpu.training.steps import make_grad_step

    devs = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    # scratch only: the dispatchers ask the backend whether Mosaic is there
    jax.default_backend = lambda: "tpu"
    mesh = make_mesh(dp=4, devices=devs)
    cfg = flagship_model_config()
    model = DALLE(cfg, mesh=mesh)
    n = micro * accum * 4
    text = jax.ShapeDtypeStruct((n, cfg.text_seq_len), jnp.int32,
                                sharding=NamedSharding(mesh, BATCH_SPEC))
    image = jax.ShapeDtypeStruct((n, cfg.image_seq_len), jnp.int32,
                                 sharding=NamedSharding(mesh, BATCH_SPEC))
    params = jax.eval_shape(
        lambda: DALLE(cfg).init(jax.random.PRNGKey(0),
                                jnp.zeros((2, cfg.text_seq_len), jnp.int32),
                                jnp.zeros((2, cfg.image_seq_len), jnp.int32)))
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype,
                                       sharding=NamedSharding(mesh, P())),
        params)
    step = jax.jit(make_grad_step(model, accum_steps=accum))
    return step.lower(params, {"text": text, "image": image}).compile()


def main(argv=None) -> int:
    import argparse
    sys.path.insert(0, _REPO)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--micro", type=int, default=4)
    ap.add_argument("--accum", type=int, default=8)
    args = ap.parse_args(argv)
    compiled = flagship_dp4_grad_step(args.micro, args.accum)
    text = compiled.as_text()
    once = looped = 0
    print(f"{'kind':<20}{'MB':>10}{'runs':>6}  in loop  op_name")
    for c in collectives(text):
        if not spans_axis(c, (4, 1, 1, 1), 0):
            continue
        if c.in_loop:
            looped += c.nbytes * (c.runs or 1)
        else:
            once += c.nbytes
        print(f"{c.kind:<20}{c.nbytes / 1e6:>10.3f}{str(c.runs):>6}  "
              f"{str(c.in_loop):<7}  {c.op_name[-90:]}")
    mem = compiled.memory_analysis()
    kernels = text.count('custom_call_target="tpu_custom_call"')
    print(f"collectives over dp, a chip a step: {once / 1e9:.3f} GB outside "
          f"any loop; {looped / 1e9:.3f} GB inside loops (runs None: the "
          f"trip count is not in the text, one run counted); "
          f"tpu_custom_call {kernels}; temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.3f} GiB")
    return 0


if __name__ == "__main__":
    sys.exit(main())
