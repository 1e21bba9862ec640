"""DALL-E-XL (~3B, BASELINE.json config 5) executed-step evidence.

VERDICT r3 weak #3: the XL preset was shape-deep (an eval_shape census).
This script EXECUTES real train steps at the XL shape and writes a
driver-readable artifact (XL_STEP.json):

- backend == tpu  -> the FULL xl config (dim 1792, depth 64, seq 1280)
  on the real chip: params+8bit state+grads allocated, N timed
  accumulate+update steps, loss finite, throughput recorded. One v5e
  *can* hold the XL state (f32 params 1.38 GB + f32 grads + 8-bit
  moments) with blanket remat + streamed head — the "one chip cannot
  hold its state" sizing note in config.py referred to practical
  training with headroom; this proves the memory plan's arithmetic.
- backend == cpu  -> the SHARDED path at the true XL width (dim 1792,
  28 heads — the axes fsdp/tp actually split), with depth/sequence
  reduced (and recorded in the artifact): depth 5 keeps the full
  unique-parameter set (4 shared blocks + w_conv). Three runs: one
  2-virtual-device run per axis (fsdp=2, then tp=2; seq 32 keeps
  text+image segments) and — r5 — the COMBINED fsdp=2 x tp=2 mesh on 4
  virtual devices. The combined mesh's crossed subgroup collectives
  must clear XLA:CPU's spinning collective rendezvous between OS
  preemptions on the one-core host: at seq 32 they die inside it, at
  seq 12 (text 8, image grid 2) they pass with near-stall warnings
  that all resolve. Shard shapes scale linearly in depth/seq, so the
  per-device memory plan extrapolates directly.

Run:  python scripts/xl_step.py            (TPU)
      JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python scripts/xl_step.py            (CPU mesh)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def run(out_path="XL_STEP.json", cpu_axis="fsdp"):
    import jax
    import numpy as np

    from dalle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from dalle_tpu.config import OptimizerConfig, xl_model_config
    from dalle_tpu.data.synthetic import SyntheticCodes
    from dalle_tpu.models.dalle import DALLE, init_params
    from dalle_tpu.optim import make_optimizer
    from dalle_tpu.parallel.mesh import batch_sharding, make_mesh
    from dalle_tpu.parallel.sharding import shard_train_state
    from dalle_tpu.training.steps import TrainState, make_train_step

    backend = jax.default_backend()
    if backend == "tpu":
        cfg = xl_model_config()          # the REAL thing
        mesh = make_mesh(dp=-1)
        micro = int(sys.argv[1]) if len(sys.argv) > 1 else 1
        accum = int(sys.argv[2]) if len(sys.argv) > 2 else 8
        iters = 2
        mesh_desc = f"dp={jax.local_device_count()} (single chip)"
    else:
        # f32 activations: CPU bf16 is emulated (~10x slower). Sharded
        # execution on the 1-core host must respect XLA:CPU's spinning
        # collective rendezvous: per-axis proofs run 2 devices each at
        # seq 32; the combined fsdp x tp mesh (4 devices, crossed
        # subgroup collectives) needs seq 12 to clear the rendezvous
        # between OS preemptions (see the shape override below). depth 5
        # = the 4 shared blocks + w_conv (the full unique-parameter set
        # at full dim 1792 / 28 heads).
        # combined-mesh shape: text 8 + image 2x2 = seq 12 (vs the
        # per-axis runs' text 16 + 4x4 = seq 32, which the crossed
        # collectives cannot survive — see the docstring)
        seq_kw = (dict(text_seq_len=8, image_grid=2)
                  if cpu_axis == "fsdp_tp"
                  else dict(text_seq_len=16, image_grid=4))
        cfg = xl_model_config(depth=5, conv_kernel=3, head_chunk=1024,
                              dtype="float32", **seq_kw)
        if cpu_axis == "fsdp_tp":
            # the COMBINED mesh (VERDICT r4 next #7): both sharded axes
            # at once at the true width — 4 virtual devices on the 1-core
            # host, so the crossed subgroup collectives must fit inside
            # XLA:CPU's 40 s spinning rendezvous between OS preemptions
            mesh = make_mesh(dp=1, fsdp=2, tp=2)
        else:
            mesh = (make_mesh(dp=1, fsdp=2, tp=1) if cpu_axis == "fsdp"
                    else make_mesh(dp=1, fsdp=1, tp=2))
        micro, accum, iters = 2, 1, 2
        mesh_desc = ("fsdp=2 x tp=2 (4 virtual CPU devices)"
                     if cpu_axis == "fsdp_tp"
                     else f"{cpu_axis}=2 (2 virtual CPU devices)")
    cfg.validate()

    model = DALLE(cfg, mesh=mesh)
    t0 = time.time()
    params = init_params(model, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    tx = make_optimizer(OptimizerConfig(warmup_steps=2, total_steps=100),
                        mesh=mesh)
    state = shard_train_state(mesh, TrainState.create(params, tx))
    del params
    t_init = time.time() - t0

    batch_size = micro * accum
    data = SyntheticCodes(cfg, num_samples=batch_size, seed=0)
    batch = jax.device_put(next(data.batches(batch_size, seed=0)),
                           batch_sharding(mesh))
    t0 = time.time()
    # plain jit dispatch for stepping: a .lower().compile() executable is
    # STRICT about input shardings, and the compiler replicates small
    # (dim,) leaves on sharded meshes, so step 2's inputs would mismatch
    step = jax.jit(make_train_step(model, tx, accum_steps=accum),
                   donate_argnums=0)
    # exact compiled HBM budget (for the PERF.md memory plan table); the
    # persistent compile cache makes this lowering ~free
    mem = {}
    try:
        ma = step.lower(state, batch).compile().memory_analysis()
        if ma is not None:
            mem = {
                "argument_gb": round(ma.argument_size_in_bytes / 2**30, 2),
                "output_gb": round(ma.output_size_in_bytes / 2**30, 2),
                "temp_gb": round(ma.temp_size_in_bytes / 2**30, 2),
            }
    except Exception as e:  # noqa: BLE001 - analysis is best-effort
        mem = {"error": str(e)[:120]}

    state, metrics = step(state, batch)
    first_loss = float(jax.device_get(metrics["loss"]))
    t_compile_and_first = time.time() - t0

    t0 = time.time()
    loss = None
    for _ in range(iters):
        state, metrics = step(state, batch)
        loss = float(jax.device_get(metrics["loss"]))
    dt = (time.time() - t0) / iters

    assert loss == loss, "NaN loss in XL step"
    result = {
        "metric": f"dalle-xl executed train step ({backend})",
        "mesh": mesh_desc,
        "config": {"dim": cfg.dim, "depth": cfg.depth, "heads": cfg.heads,
                   "seq": cfg.total_seq_len, "vocab_image": cfg.vocab_image,
                   "micro": micro, "accum": accum,
                   "ln_fusion": cfg.ln_fusion},
        "unique_params_m": round(n_params / 1e6, 1),
        "init_s": round(t_init, 1),
        "compile_plus_first_step_s": round(t_compile_and_first, 1),
        "step_s": round(dt, 2),
        "images_per_sec": round(batch_size / dt, 3),
        "first_loss": round(first_loss, 4),
        "loss_after": round(loss, 4),
        "compiled_memory": mem,
    }
    line = json.dumps(result)
    print(line, flush=True)
    # anchor the artifact to the repo root regardless of CWD (like the
    # sibling bench scripts)
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", out_path)
    with open(out_path, "a") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    import jax as _jax

    if _jax.default_backend() == "tpu":
        run()
    elif sys.argv[1:] and sys.argv[1] in ("fsdp", "tp", "fsdp_tp"):
        run(cpu_axis=sys.argv[1])
    else:
        run(cpu_axis="fsdp")
        run(cpu_axis="tp")
        run(cpu_axis="fsdp_tp")
