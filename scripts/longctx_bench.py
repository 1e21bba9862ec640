"""Long-context sequence-parallel scaling artifact (VERDICT r2 next #9).

Compiles the zigzag-ring attention shard_map program for a 64x64-grid
long-context workload (4096 image tokens, full-causal) on meshes of
exactly sp=1/2/4 virtual CPU devices (one subprocess per sp so the mesh
is pure sequence parallelism) and reports XLA's per-device FLOP and
bytes-moved estimates — hardware-independent evidence of the sp scaling
(wall-clock needs real multi-chip ICI).

    python scripts/longctx_bench.py            # table over sp=1,2,4
    python scripts/longctx_bench.py --one 2    # internal: one sp value
"""

import json
import os
import subprocess
import sys

GRID, H, D, B = 64, 16, 64, 2
T_IMG = GRID * GRID  # 4096 tokens


def run_one(sp: int):
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from dalle_tpu.parallel.mesh import make_mesh
    from dalle_tpu.parallel.sequence import sp_zoo_attention

    mesh = make_mesh(dp=1, fsdp=1, tp=1, sp=sp)
    q = jnp.zeros((B, T_IMG, H, D), jnp.bfloat16)

    def attn(q, k, v):
        return sp_zoo_attention(q, k, v, mesh=mesh, mode="ring",
                                attn_type="full", text_len=0, grid=GRID)

    compiled = jax.jit(attn).lower(q, q, q).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    print(json.dumps({"sp": sp, "flops": cost.get("flops", -1.0),
                      "bytes": cost.get("bytes accessed", -1.0)}))


def main():
    if len(sys.argv) > 2 and sys.argv[1] == "--one":
        return run_one(int(sys.argv[2]))

    print(f"long-context zigzag ring attention: {T_IMG} image tokens "
          f"({GRID}x{GRID} grid), B={B}, H={H}, d={D}; mesh = sp only")
    print(f"{'sp':>3} {'per-device GFLOP':>17} {'per-device GB moved':>20}")
    base = None
    for sp in (1, 2, 4):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={sp}")
        res = subprocess.run([sys.executable, __file__, "--one", str(sp)],
                             env=env, capture_output=True, text=True,
                             timeout=600)
        lines = [ln for ln in res.stdout.splitlines()
                 if ln.startswith("{")]
        if res.returncode != 0 or not lines:
            print(res.stdout[-2000:], file=sys.stderr)
            print(res.stderr[-2000:], file=sys.stderr)
            raise RuntimeError(f"sp={sp} child failed "
                               f"(rc={res.returncode})")
        r = json.loads(lines[-1])
        # cost_analysis reports the per-device SPMD program
        flops, bytes_ = r["flops"], r["bytes"]
        if base is None:
            base = flops
        print(f"{sp:>3} {flops/1e9:>17.2f} {bytes_/1e9:>20.2f}"
              f"   ({base/flops:.2f}x less compute per device)")


if __name__ == "__main__":
    sys.exit(main())
