"""Inference CLI: generate VQGAN code grids for text queries.

Capability parity with the reference's offline generation tool
(``inference/run_inference.py:46-146`` of learning-at-home/dalle): load the
trained checkpoint, tokenize each query, sample ``--images-per-query``
image-code sequences with temperature/top-k/top-p (``:96-105``), and save
the results. The reference then VQGAN-decodes to pixels and reranks with
CLIP ViT-B/32; here the primary artifact is the (B, 32, 32) code grids as
``.npz`` (the training data itself ships as codes, ``data.py:29-30``) —
pixel decoding plugs in behind ``--vqgan-checkpoint`` when a decoder
checkpoint is available.

Usage::

    python -m dalle_tpu.cli.run_inference \
        --checkpoint-dir ck/ --tokenizer-path tok/tokenizer.json \
        --preset tiny --query "a red cat" --out out.npz
"""

from __future__ import annotations

import argparse
import logging
import sys
from typing import Optional, Sequence

from dalle_tpu.cli._args import add_dataclass_args, dataclass_from_args
from dalle_tpu.cli.run_trainer import (MODEL_PRESETS,
                                       decodable_model_from_args)
from dalle_tpu.config import ModelConfig

logger = logging.getLogger("dalle_tpu.inference")


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="dalle-tpu-inference", description=__doc__.splitlines()[0])
    parser.add_argument("--preset", choices=sorted(MODEL_PRESETS),
                        default="flagship")
    parser.add_argument("--checkpoint-dir", type=str, required=True)
    parser.add_argument("--tokenizer-path", type=str, required=True)
    parser.add_argument("--query", action="append", required=True,
                        help="caption to generate for (repeatable)")
    parser.add_argument("--images-per-query", type=int, default=16,
                        help="reference generates 16 per query (:132)")
    parser.add_argument("--temperature", type=float, default=1.0)
    parser.add_argument("--top-k", type=int, default=0)
    parser.add_argument("--top-p", type=float, default=1.0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", type=str, default="generated.npz")
    parser.add_argument("--platform", type=str, default=None)
    parser.add_argument("--log-level", type=str, default="INFO")
    parser.add_argument(
        "--vqgan-checkpoint", type=str, default=None,
        help="taming-transformers f8 VQGAN .ckpt; decodes code grids to "
             "RGB pixels (reference inference/run_inference.py:122-124)")
    parser.add_argument(
        "--clip-checkpoint", type=str, default=None,
        help="openai CLIP ViT-B/32 .pt; reranks decoded images against the "
             "query (reference :126,135-138; requires --vqgan-checkpoint "
             "and --clip-bpe)")
    parser.add_argument(
        "--clip-bpe", type=str, default=None,
        help="path to bpe_simple_vocab_16e6.txt.gz for CLIP tokenization")
    parser.add_argument(
        "--allow-unsafe-pickle", action="store_true",
        help="permit torch's permissive pickle loader for VQGAN/CLIP "
             "checkpoints the safe weights-only loader rejects; this "
             "EXECUTES code from the file — only for checkpoints whose "
             "origin you trust (utils/torch_io.py)")
    add_dataclass_args(parser, ModelConfig)
    return parser


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    # refused here, before anything is built, if nothing decodes the preset
    cfg = decodable_model_from_args(args, "dalle-tpu-inference")
    logging.basicConfig(level=args.log_level)
    from dalle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    import jax
    import numpy as np

    from dalle_tpu.data.tokenizer import CaptionTokenizer
    from dalle_tpu.models.dalle import DALLE, init_params
    from dalle_tpu.models.decode import SamplingConfig, generate_images
    from dalle_tpu.training.checkpoint import CheckpointManager

    tokenizer = CaptionTokenizer.load(args.tokenizer_path)

    # params-only restore: inference needs no optimizer state, and this
    # stays loadable regardless of which optimizer flags trained the
    # checkpoint
    model = DALLE(cfg)
    template = init_params(model, jax.random.PRNGKey(0))
    restored = CheckpointManager(
        args.checkpoint_dir,
        async_writes=False).restore_params_latest(template)
    if restored is None:
        logger.error("no loadable checkpoint under %s", args.checkpoint_dir)
        return 1
    params, epoch = restored
    logger.info("loaded checkpoint at epoch %d", epoch)

    sampling = SamplingConfig(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p)
    gen = jax.jit(lambda t, r: generate_images(
        params, cfg, t, r, sampling))

    # Optional pixel decoding + CLIP reranking (the reference's full
    # pipeline: generate -> VQGAN decode -> CLIP score, run_inference.py
    # :87-138). Both stages are plain JAX models fed by torch-deserialized
    # public checkpoints (models/vqgan.py, models/clip.py).
    vqgan = clip_bundle = None
    if args.vqgan_checkpoint:
        from dalle_tpu.models.vqgan import (VQGANConfig, decode_codes,
                                            load_taming_checkpoint)
        # f8 decoder: 8px per code in both axes, so the output resolution
        # follows the model's code grid (32 -> 256px, 64 -> 512px)
        vq_cfg = VQGANConfig(n_embed=cfg.vocab_image,
                             resolution=cfg.image_grid * 8)
        vqgan = (jax.jit(lambda p, c: decode_codes(p, vq_cfg, c)),
                 load_taming_checkpoint(args.vqgan_checkpoint, vq_cfg,
                                        allow_unsafe=args.allow_unsafe_pickle))
    if args.clip_checkpoint:
        if not (vqgan and args.clip_bpe):
            logger.error("--clip-checkpoint requires --vqgan-checkpoint "
                         "and --clip-bpe")
            return 1
        from dalle_tpu.models.clip import (CLIPConfig, CLIPTokenizer,
                                           clip_scores,
                                           load_openai_checkpoint,
                                           resize_for_clip)
        cl_cfg = CLIPConfig()
        clip_bundle = (
            jax.jit(lambda p, im, tok: clip_scores(
                p, cl_cfg, resize_for_clip(im, cl_cfg), tok)),
            load_openai_checkpoint(args.clip_checkpoint, cl_cfg,
                                   allow_unsafe=args.allow_unsafe_pickle),
            CLIPTokenizer(args.clip_bpe, cl_cfg.context_length))

    rng = jax.random.PRNGKey(args.seed)
    results = {}
    for qi, query in enumerate(args.query):
        ids, _ = tokenizer.encode(query, cfg.text_seq_len)
        text = np.tile(ids[None], (args.images_per_query, 1))
        rng, sub = jax.random.split(rng)
        codes = np.asarray(gen(jax.numpy.asarray(text), sub))
        grids = codes.reshape(-1, cfg.image_grid, cfg.image_grid)
        results[f"query_{qi}_codes"] = grids
        results[f"query_{qi}_text"] = np.asarray(query)
        logger.info("query %r -> %d code grids (%dx%d, vocab %d)",
                    query, grids.shape[0], cfg.image_grid, cfg.image_grid,
                    cfg.vocab_image)
        if vqgan is not None:
            decode, vq_params = vqgan
            images = np.asarray(decode(vq_params, jax.numpy.asarray(
                grids.reshape(grids.shape[0], -1))))
            if clip_bundle is not None:
                score_fn, cl_params, cl_tok = clip_bundle
                tok = cl_tok.encode(query)[None]
                scores = np.asarray(score_fn(
                    cl_params, jax.numpy.asarray(images),
                    jax.numpy.asarray(tok)))[:, 0]
                order = np.argsort(-scores)
                images, grids = images[order], grids[order]
                results[f"query_{qi}_codes"] = grids
                results[f"query_{qi}_clip_scores"] = scores[order]
                logger.info("query %r best CLIP score %.4f",
                            query, float(scores[order][0]))
            results[f"query_{qi}_images"] = images
    np.savez(args.out, **results)
    logger.info("saved %s", args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
