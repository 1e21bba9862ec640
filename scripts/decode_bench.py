"""Flagship decode bench (VERDICT r2 next #5): compile time + images/min
for the reference's generation workload (inference/run_inference.py:
87-90,132 generates 16 images x 8 iterations per query).

Run on the TPU host:  python scripts/decode_bench.py [batch] [iters] [buckets]

``buckets`` defaults to the SHIPPED adaptive choice (generate_images
buckets=None) so the trend file tracks production; pass an explicit
count to sweep alternatives (the r4 bucket table in PERF.md).

Appends one driver-readable JSON line per run to DECODE_BENCH.json at
the repo root (VERDICT r3 weak #6: the decode trend must be as
auditable as the train number).

Measured r3 (one v5e, pre-round stack), decode restructured as a lax.scan over
the 4 weight-shared blocks with the KV cache as an in-place carry in a
128-clean (B, T, H*d) layout, ROW-granular writes and per-block reads
(an earlier version rewrote a whole rep slice per position — ~4x the
necessary cache traffic — and at B>=8 its slice storms faulted the
TPU worker):

  - compile+first query: ~42-81 s (the r2 Python-unrolled depth-64 body
    was never compilable at flagship scale; the unmerged cache layout
    alone needed 31 GB HBM)
  - steady state with prefix bucketing (generate_images buckets=4):
    B=8 -> 12.2 s/query (39.4 img/min, the throughput sweet spot);
    B=16 -> 29.8 s/query (32.2 img/min)
  - the reference's 16x8=128-image query set: ~3.3 min at B=8.

Decode is KV-cache-bandwidth-bound: the r3 levers (row-granular carry
updates; per-bucket statically-truncated cache reads) removed the
avoidable traffic; what remains is the genuine prefix read.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from dalle_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

from dalle_tpu.config import flagship_model_config  # noqa: E402
from dalle_tpu.models.dalle import DALLE, init_params  # noqa: E402
from dalle_tpu.models.decode import (SamplingConfig,  # noqa: E402
                                     generate_images)


def main():
    # "xl" as the first arg benches the ~3B preset (BASELINE config 5);
    # "e2e" extends each query to the reference's FULL per-query pipeline
    # (codes -> VQGAN f8 pixel decode -> CLIP ViT-B/32 rerank,
    # inference/run_inference.py:131-142) so the headline img/min covers
    # the whole workload, not just transformer code generation
    # (VERDICT r4 weak #6)
    args = [a for a in sys.argv[1:] if a not in ("xl", "e2e")]
    xl = "xl" in sys.argv[1:]
    e2e = "e2e" in sys.argv[1:]
    b = int(args[0]) if len(args) > 0 else 4
    iters = int(args[1]) if len(args) > 1 else 4
    buckets = int(args[2]) if len(args) > 2 else None
    if xl:
        from dalle_tpu.config import xl_model_config
        cfg = xl_model_config(param_dtype="bfloat16")
    else:
        cfg = flagship_model_config(param_dtype="bfloat16")
    model = DALLE(cfg)
    params = init_params(model, jax.random.PRNGKey(0))
    text = jnp.ones((b, cfg.text_seq_len), jnp.int32)
    gen = jax.jit(lambda p, t, r: generate_images(
        p, cfg, t, r, SamplingConfig(temperature=1.0, top_k=64),
        buckets=buckets))

    pixel_fn = None
    pixels_valid = clip_scored = None
    if e2e:
        # Full-shape VQGAN f8 decoder (8192-codebook Gumbel, 256px out;
        # XL: 16384/f16) + CLIP ViT-B/32, randomly initialized — the
        # FLOPs/bandwidth of the real per-query pipeline without shipping
        # checkpoints into the bench box. Weight values do not change the
        # cost of a conv stack or a ViT forward.
        from dalle_tpu.models.clip import (CLIPConfig, CLIPModel,
                                           clip_scores, resize_for_clip)
        from dalle_tpu.models.vqgan import (VQGANConfig, VQGANDecoder,
                                            decode_codes)
        # flagship: f8 VQGAN (32x32 codes -> 256px). XL: a VQGAN-f16
        # pipeline (config.py xl_model_config: 16384 codes, 512px from
        # 32x32) — one more upsampling stage, else the e2e row would
        # decode 4x fewer pixels than the real XL per-query cost
        if xl:
            vq_cfg = VQGANConfig(n_embed=cfg.vocab_image,
                                 ch_mult=(1, 1, 2, 2, 4),
                                 resolution=cfg.image_grid * 16)
        else:
            vq_cfg = VQGANConfig(n_embed=cfg.vocab_image,
                                 resolution=cfg.image_grid * 8)
        clip_cfg = CLIPConfig()
        code_tpl = jnp.zeros((b, cfg.image_grid, cfg.image_grid),
                             jnp.int32)
        vq_params = jax.eval_shape(
            lambda k: VQGANDecoder(vq_cfg).init(k, code_tpl),
            jax.random.PRNGKey(0))
        vq_params = jax.tree.map(
            lambda s: jax.random.normal(jax.random.PRNGKey(3), s.shape,
                                        s.dtype) * 0.02, vq_params)
        img_tpl = jnp.zeros((b, clip_cfg.image_size, clip_cfg.image_size,
                             3), jnp.float32)
        tok_tpl = jnp.ones((1, clip_cfg.context_length), jnp.int32)
        clip_params = jax.eval_shape(
            lambda k: CLIPModel(clip_cfg).init(k, img_tpl, tok_tpl),
            jax.random.PRNGKey(1))
        clip_params = jax.tree.map(
            lambda s: jax.random.normal(jax.random.PRNGKey(4), s.shape,
                                        s.dtype) * 0.02, clip_params)

        def _pixels_and_scores(codes, toks):
            grid = codes.reshape(b, cfg.image_grid, cfg.image_grid)
            imgs = decode_codes(vq_params, vq_cfg, grid)
            scores = clip_scores(clip_params, clip_cfg,
                                 resize_for_clip(imgs, clip_cfg), toks)
            return imgs, scores

        pixel_fn = jax.jit(_pixels_and_scores)

    t0 = time.time()
    codes = gen(params, text, jax.random.PRNGKey(1))
    if pixel_fn is not None:
        jax.device_get(pixel_fn(codes, jnp.ones(
            (1, 77), jnp.int32)))
    jax.device_get(codes)
    print(f"compile+first: {time.time() - t0:.1f}s", flush=True)

    t_compile = time.time() - t0

    t0 = time.time()
    for i in range(iters):
        # serialize queries: device_get per call (async-queuing several
        # multi-GB cache allocations destabilized the TPU worker)
        codes = gen(params, text, jax.random.PRNGKey(2 + i))
        if pixel_fn is not None:
            imgs, scores = jax.device_get(pixel_fn(
                codes, jnp.ones((1, 77), jnp.int32)))
        codes = jax.device_get(codes)
    dt = time.time() - t0
    ok = bool((codes >= 0).all() and (codes < cfg.vocab_image).all())
    if pixel_fn is not None:
        import numpy as np
        res = cfg.image_grid * (16 if xl else 8)
        pixels_valid = bool(imgs.shape == (b, res, res, 3)
                            and imgs.dtype == np.uint8)
        clip_scored = bool(np.isfinite(scores).all()
                           and scores.shape == (b, 1))
    img_per_min = b * iters / dt * 60
    print(f"B={b}: {dt / iters:.1f}s/query -> {img_per_min:.1f} "
          f"img/min (codes valid: {ok}, e2e: {e2e})")

    out_path = os.path.join(os.path.dirname(__file__), "..",
                            "DECODE_BENCH.json")
    # record the RESOLVED bucket count for adaptive (None) runs so every
    # row stays joinable to the bucket-sweep table even if the adaptive
    # thresholds in generate_images change later
    from dalle_tpu.models.decode import resolve_buckets
    with open(out_path, "a") as f:
        f.write(json.dumps({
            "metric": ("dalle-xl decode images/min" if xl
                       else "dalle-1.3b decode images/min"),
            "batch": b,
            "iters": iters,
            "buckets": resolve_buckets(buckets, b),
            "compile_plus_first_s": round(t_compile, 1),
            "sec_per_query": round(dt / iters, 2),
            "value": round(img_per_min, 1),
            "unit": "images/min",
            "codes_valid": ok,
            # e2e rows: the query included VQGAN pixel decode + CLIP
            # rerank (reference inference/run_inference.py:131-142)
            "e2e": e2e,
            "pixels_valid": pixels_valid,
            "clip_scored": clip_scored,
        }) + "\n")


if __name__ == "__main__":
    main()
