"""Serving CLI: the continuous-batching HTTP front-end.

Where ``run_inference`` is the reference's one-shot offline tool, this
serves online traffic: a slot-recycled KV-cache engine
(``dalle_tpu/serving/``) admits requests mid-flight instead of waiting
for batch formation, and VQGAN pixel decode + CLIP rerank of finished
requests overlap ongoing token generation on a worker thread.

Usage::

    python -m dalle_tpu.cli.run_server \
        --checkpoint-dir ck/ --tokenizer-path tok/tokenizer.json \
        --preset tiny --http-port 8080

    curl -s localhost:8080/generate -d '{"text": "a red cat", \
        "n_images": 4, "seed": 7, "temperature": 0.8, "top_k": 64}'
    curl -s localhost:8080/stats

``--temperature``/``--top-k``/``--top-p`` set the engine-wide default;
a request body may override any of them per request — sampling knobs
are traced runtime operands of the chunk program, so serving a novel
temperature never recompiles anything.

``--random-init`` serves freshly initialized weights (smoke tests and
benches — the serving path's cost does not depend on weight values).
Ctrl-C and SIGTERM (k8s/systemd stop) both drain: queued and in-flight
requests finish (bounded by ``--drain-timeout-s``), the engine and
pixel worker are reaped, then the process exits.
"""

from __future__ import annotations

import argparse
import logging
import sys
from typing import Optional, Sequence

from dalle_tpu.cli._args import (add_dataclass_args, check_no_collisions,
                                 dataclass_from_args)
from dalle_tpu.cli.run_trainer import (MODEL_PRESETS,
                                       decodable_model_from_args)
from dalle_tpu.config import ModelConfig, PeerConfig, ServingConfig

logger = logging.getLogger("dalle_tpu.server")

CONFIG_CLASSES = (ModelConfig, ServingConfig, PeerConfig)


def build_parser() -> argparse.ArgumentParser:
    check_no_collisions(*CONFIG_CLASSES)
    parser = argparse.ArgumentParser(
        prog="dalle-tpu-server", description=__doc__.splitlines()[0])
    parser.add_argument("--preset", choices=sorted(MODEL_PRESETS),
                        default="flagship")
    parser.add_argument("--checkpoint-dir", type=str, default=None)
    parser.add_argument("--random-init", action="store_true",
                        help="serve freshly initialized weights (smoke "
                             "tests / benches) instead of a checkpoint")
    parser.add_argument("--tokenizer-path", type=str, default=None,
                        help="tokenizer.json; without it only "
                             "pre-tokenized 'tokens' requests are served")
    parser.add_argument("--temperature", type=float, default=1.0)
    parser.add_argument("--top-k", type=int, default=0)
    parser.add_argument("--top-p", type=float, default=1.0)
    parser.add_argument("--metrics-file", type=str, default=None,
                        help="append one serving-metrics JSON line per "
                             "--metrics-interval-s")
    parser.add_argument(
        "--vqgan-checkpoint", type=str, default=None,
        help="taming-transformers VQGAN .ckpt: decode finished requests "
             "to pixels on the overlap worker")
    parser.add_argument(
        "--clip-checkpoint", type=str, default=None,
        help="openai CLIP .pt: score decoded images against the query "
             "(requires --vqgan-checkpoint and --clip-bpe)")
    parser.add_argument("--clip-bpe", type=str, default=None)
    parser.add_argument(
        "--allow-unsafe-pickle", action="store_true",
        help="permit torch's permissive pickle loader for VQGAN/CLIP "
             "checkpoints (EXECUTES code from the file — trusted "
             "origins only; utils/torch_io.py)")
    parser.add_argument(
        "--advertise", action="store_true",
        help="join the swarm DHT (PeerConfig flags: --port, "
             "--initial-peers, --identity-path, --experiment-prefix) "
             "and advertise this engine's /readyz slice under "
             "{prefix}_serving so a run_router front-end places to it")
    parser.add_argument(
        "--advertise-url", type=str, default=None,
        help="the URL OTHER hosts reach this engine at (default "
             "http://<http-host>:<http-port> — override when bound to "
             "0.0.0.0 or behind a port map)")
    parser.add_argument("--advert-ttl", type=float, default=None,
                        help="serving-record TTL seconds (default "
                             "router.DEFAULT_SERVING_TTL)")
    parser.add_argument(
        "--prime-service-s", type=float, default=None,
        help="seed the decode service EMA with this calibrated "
             "per-request cadence (seconds): the deadline shedder is "
             "live from request one, and a fleet router is not fed "
             "the compile-inflated samples a cold engine's first wave "
             "otherwise bakes into its advertised cadence")
    parser.add_argument("--platform", type=str, default=None)
    parser.add_argument("--log-level", type=str, default="INFO")
    for cls in CONFIG_CLASSES:
        add_dataclass_args(parser, cls)
    return parser


def _load_params(args, cfg):
    import jax

    from dalle_tpu.models.dalle import DALLE, init_params

    template = init_params(DALLE(cfg), jax.random.PRNGKey(0))
    if args.random_init:
        return template
    if not args.checkpoint_dir:
        return None
    from dalle_tpu.training.checkpoint import CheckpointManager
    restored = CheckpointManager(
        args.checkpoint_dir,
        async_writes=False).restore_params_latest(template)
    if restored is None:
        return None
    params, epoch = restored
    logger.info("serving checkpoint at epoch %d", epoch)
    return params


def _build_pixel_fn(args, cfg):
    """(pixel_fn, degraded_fn) for the overlap worker, or (None, None)
    when no VQGAN checkpoint is configured. ``pixel_fn`` mirrors the
    run_inference pipeline stages; ``degraded_fn`` is the brownout
    variant — VQGAN decode WITHOUT the CLIP rerank, trading candidate
    scoring for latency under sustained saturation."""
    if not args.vqgan_checkpoint:
        return None, None
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_tpu.models.vqgan import (VQGANConfig, decode_codes,
                                        load_taming_checkpoint)
    vq_cfg = VQGANConfig(n_embed=cfg.vocab_image,
                         resolution=cfg.image_grid * 8)
    vq_params = load_taming_checkpoint(
        args.vqgan_checkpoint, vq_cfg,
        allow_unsafe=args.allow_unsafe_pickle)
    decode = jax.jit(lambda c: decode_codes(vq_params, vq_cfg, c))

    score_fn = None
    if args.clip_checkpoint:
        if not args.clip_bpe:
            raise SystemExit("--clip-checkpoint requires --clip-bpe")
        from dalle_tpu.models.clip import (CLIPConfig, CLIPTokenizer,
                                           clip_scores,
                                           load_openai_checkpoint,
                                           resize_for_clip)
        cl_cfg = CLIPConfig()
        cl_params = load_openai_checkpoint(
            args.clip_checkpoint, cl_cfg,
            allow_unsafe=args.allow_unsafe_pickle)
        cl_tok = CLIPTokenizer(args.clip_bpe, cl_cfg.context_length)
        score = jax.jit(lambda im, tok: clip_scores(
            cl_params, cl_cfg, resize_for_clip(im, cl_cfg), tok))

        def score_fn(images):
            # served requests have no caption handy post-tokenization;
            # score against the empty prompt as a fixed aesthetic-ish
            # anchor (rerank across a query's n_images stays meaningful)
            tok = jnp.asarray(cl_tok.encode("")[None])
            return float(np.asarray(score(images, tok))[0, 0])

    def pixel_fn(codes):
        imgs = np.asarray(decode(jnp.asarray(codes[None])))
        out = {"images": imgs[0]}
        if score_fn is not None:
            out["clip_score"] = score_fn(jnp.asarray(imgs))
        return out

    def degraded_fn(codes):
        imgs = np.asarray(decode(jnp.asarray(codes[None])))
        return {"images": imgs[0]}   # brownout: pixels yes, rerank no

    return pixel_fn, degraded_fn


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    # refused here, before anything is built, if nothing decodes the preset
    cfg = decodable_model_from_args(args, "dalle-tpu-server")
    logging.basicConfig(
        level=args.log_level,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    from dalle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    from dalle_tpu.models.decode import SamplingConfig
    from dalle_tpu.serving.engine import DecodeEngine
    from dalle_tpu.serving.metrics import ServingMetrics
    from dalle_tpu.serving.pixels import PixelPipeline
    from dalle_tpu.serving.server import ServingHTTPServer

    serving = dataclass_from_args(ServingConfig, args)
    serving.validate()

    params = _load_params(args, cfg)
    if params is None:
        logger.error("no loadable checkpoint under %s (or pass "
                     "--random-init)", args.checkpoint_dir)
        return 1

    tokenizer = None
    if args.tokenizer_path:
        from dalle_tpu.data.tokenizer import CaptionTokenizer
        tokenizer = CaptionTokenizer.load(args.tokenizer_path)

    metrics = ServingMetrics(n_slots=serving.n_slots,
                             jsonl_path=args.metrics_file,
                             interval_s=serving.metrics_interval_s)
    if args.prime_service_s is not None:
        metrics.prime_service(args.prime_service_s, force=True)
    pixel_fn, degraded_fn = _build_pixel_fn(args, cfg)
    pipeline = (PixelPipeline(pixel_fn, metrics=metrics,
                              degraded_fn=degraded_fn)
                if pixel_fn is not None else None)
    engine = DecodeEngine(
        params, cfg, serving,
        sampling=SamplingConfig(temperature=args.temperature,
                                top_k=args.top_k, top_p=args.top_p),
        pixel_pipeline=pipeline, metrics=metrics).start()

    httpd = ServingHTTPServer((serving.http_host, serving.http_port),
                              engine, tokenizer=tokenizer,
                              request_timeout_s=serving.request_timeout_s)

    # fleet advertising (serving/router.py): this engine's /readyz
    # slice rides a TTL'd DHT record under {prefix}_serving — the
    # router's placement input. The advertiser is stopped BEFORE the
    # DHT is torn down (a publish against a dead native node is a
    # use-after-free, the rendezvous.stop() contract).
    dht = advertiser = None
    if args.advertise:
        from dalle_tpu.serving.router import (DEFAULT_SERVING_TTL,
                                              ServingAdvertiser)
        from dalle_tpu.swarm.dht import DHT
        from dalle_tpu.swarm.identity import Identity
        from dalle_tpu.swarm.metrics import make_validators
        peer = dataclass_from_args(PeerConfig, args)
        # the STANDARD validator chain (task.py wires the same one):
        # the serving record's subkey gains the signed ownership marker
        # validated swarm peers demand — an unsigned record is invisible
        # to every trainer/aux/router whose DHT enforces signatures
        ident = Identity.load_or_create(peer.identity_path)
        dht = DHT(host=peer.host, port=peer.port,
                  initial_peers=list(peer.initial_peers),
                  client_mode=peer.client_mode,
                  identity=ident,
                  record_validators=make_validators(
                      ident, peer.experiment_prefix))
        url = args.advertise_url or (
            f"http://{serving.http_host}:{httpd.server_address[1]}")
        advertiser = ServingAdvertiser(
            dht, peer.experiment_prefix, engine, url,
            ttl=args.advert_ttl or DEFAULT_SERVING_TTL)
        advertiser.publish_once()
        advertiser.start()
        logger.info("advertising %s under '%s_serving' (peer %s)",
                    url, peer.experiment_prefix, dht.peer_id[:12])

    logger.info("=" * 60)
    logger.info("serving %s on http://%s:%d (%d slots, %d-step chunks, "
                "%d prefix buckets%s)", args.preset, serving.http_host,
                httpd.server_address[1], serving.n_slots,
                serving.steps_per_call, engine.n_buckets,
                ", pixel overlap" if pipeline else "")
    logger.info("POST /generate {\"text\"|\"tokens\", \"n_images\", "
                "\"seed\", \"lane\", \"deadline_s\"} | GET /stats | "
                "GET /healthz (live) | GET /readyz (placement)")
    if engine.chaos is not None:
        logger.warning("serve chaos plan ACTIVE (--chaos-plan) — this "
                       "server injects faults on purpose")
    logger.info("=" * 60)

    # SIGTERM (k8s/systemd stop) drains exactly like Ctrl-C: the handler
    # runs on the main thread, so raising here unwinds serve_forever
    import signal

    def _sigterm(signum, frame):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _sigterm)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        logger.info("interrupt: draining engine "
                    "(bounded by drain_timeout_s=%.0fs)",
                    serving.drain_timeout_s)
    finally:
        if advertiser is not None:
            advertiser.stop()
        httpd.server_close()
        engine.stop(drain=True)
        if dht is not None:
            dht.shutdown()
        logger.info("drained; final stats: %s", engine.stats())
    return 0


if __name__ == "__main__":
    sys.exit(main())
