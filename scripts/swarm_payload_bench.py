"""Flagship-payload swarm bench (VERDICT r3 next #2).

The 9-peer scale run proved PROTOCOL correctness on ~64 KiB models; this
bench proves BANDWIDTH behavior: N loopback peers exchange the real
flagship gradient set (~125.6M unique params, ~502 MB f32) through the
full production stack — matchmaking, chunked butterfly all-reduce
(CHUNK_ELEMS frames), SizeAdaptive/PowerSGD codecs, Ed25519 chunk
signatures, ChaCha20-Poly1305 AEAD — and reports per-phase wall time
against the reference's 60 s all-reduce budget (arguments.py:69-74).

Run:  JAX_PLATFORMS=cpu \
      python scripts/swarm_payload_bench.py [n_peers ...] [assist] \
          [--device-codec] [--bits {8,4}] [--ef] [--out FILE]

``--device-codec`` runs every row through the device wire codec
(swarm/device_codec.py, ``codec_backend="device"``): parts are
quantized as jitted whole-part programs and only packed code/scale
buffers cross to the host — encode_s/decode_s then measure the host
wall spent in the device codec hooks (dispatch + the one materialize
pull per part) instead of numpy math.

``--bits 8|4`` PINS the wire codec of both butterfly legs (the r15
in-collective quantization; 4 = the blockwise-u4 stage, ~2x fewer sync
bytes than the r6 u8 wire) instead of SizeAdaptive; ``--ef`` arms the
error-feedback residual legs (requires --bits). Every row reports
``wire_mb`` — actual bytes through DHT.send/post, frames + AEAD
included — which is the sync-byte A/B the r15 gate compares
(``--bits 4 --ef`` vs the plain u8 row). ``--out FILE`` additionally
dumps the row list as JSON (the committed artifact).

Prints one JSON line per configuration (driver-readable) plus the table
SWARM_SCALE.md records. Note the VM has ONE host core: encode/decode of
all N peers serialize here, so these numbers are an UPPER bound on
per-peer codec time for any real fleet (one core per peer).
"""

import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from dalle_tpu.swarm import DHT, Identity  # noqa: E402
from dalle_tpu.swarm import compression  # noqa: E402
from dalle_tpu.swarm.allreduce import (flatten_tensors,  # noqa: E402
                                       run_allreduce)
from dalle_tpu.swarm.matchmaking import make_group  # noqa: E402
from dalle_tpu.swarm.powersgd import (IncompleteRound,  # noqa: E402
                                      PowerSGDCompressor,
                                      average_with_powersgd)


def flagship_grad_arrays(seed: int):
    """Numpy arrays with the flagship's UNIQUE parameter shapes (the
    swarm averages one gradient per unique tensor — weight sharing means
    64 layers but ~125.6M unique elements)."""
    import jax

    from dalle_tpu.config import flagship_model_config
    from dalle_tpu.models.dalle import DALLE, init_params

    cfg = flagship_model_config()
    shapes = jax.eval_shape(
        lambda: init_params(DALLE(cfg), jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(shapes)
    rng = np.random.RandomState(seed)
    arrays = [rng.randn(*l.shape).astype(np.float32) * 0.01
              for l in leaves]
    total = sum(a.size for a in arrays)
    return arrays, total


class PhaseTimers:
    """Global (process-wide) instrumentation of codec + AEAD time plus
    WIRE BYTES (every DHT.send/post payload — frames, signatures and
    AEAD included: the honest sync-byte number the r15 A/B gates on).
    One host core means per-peer attribution is moot — what matters is
    the total CPU each stage burns vs the epoch wall clock."""

    def __init__(self):
        self.encode = 0.0
        self.decode = 0.0
        self.aead = 0.0
        self.wire_bytes = 0
        self._lock = threading.Lock()

    def patch(self):
        from dalle_tpu.swarm import crypto, device_codec

        orig_c, orig_d = compression.compress, compression.decompress
        orig_e, orig_x = crypto.maybe_encrypt, crypto.maybe_decrypt
        dev_orig = (device_codec.compress, device_codec.decompress,
                    device_codec.encode_part, device_codec.part_payload,
                    device_codec.part_decode)

        def timed(orig, attr):
            def wrapper(*a, **kw):
                t0 = time.perf_counter()
                out = orig(*a, **kw)
                with self._lock:
                    setattr(self, attr,
                            getattr(self, attr) + time.perf_counter() - t0)
                return out
            return wrapper

        compression.compress = timed(orig_c, "encode")
        compression.decompress = timed(orig_d, "decode")
        crypto.maybe_encrypt = timed(orig_e, "aead")
        crypto.maybe_decrypt = timed(orig_x, "aead")
        # device codec: encode = dispatch + the one materialize pull per
        # part (inside the first part_payload call); decode = the jitted
        # dequantize paths. Host wall spent in these hooks is the honest
        # "what does the host still pay" number the A/B compares.
        device_codec.compress = timed(dev_orig[0], "encode")
        device_codec.decompress = timed(dev_orig[1], "decode")
        device_codec.encode_part = timed(dev_orig[2], "encode")
        device_codec.part_payload = timed(dev_orig[3], "encode")
        device_codec.part_decode = timed(dev_orig[4], "decode")
        # allreduce imports `compression` as a module and crypto inside
        # the function body, so module-attr patching reaches it

        # wire-byte counters: class-level patch of the two outbound data
        # planes (pushes + mailbox posts) — every loopback node counts
        orig_send, orig_post = DHT.send, DHT.post

        def counting_send(node, addr, tag, payload, *a, **kw):
            with self._lock:
                self.wire_bytes += len(payload)
            return orig_send(node, addr, tag, payload, *a, **kw)

        def counting_post(node, tag, payload, *a, **kw):
            with self._lock:
                self.wire_bytes += len(payload)
            return orig_post(node, tag, payload, *a, **kw)

        DHT.send, DHT.post = counting_send, counting_post

        def restore():
            compression.compress, compression.decompress = orig_c, orig_d
            crypto.maybe_encrypt, crypto.maybe_decrypt = orig_e, orig_x
            (device_codec.compress, device_codec.decompress,
             device_codec.encode_part, device_codec.part_payload,
             device_codec.part_decode) = dev_orig
            DHT.send, DHT.post = orig_send, orig_post
        return restore


def run_threads(fns):
    out = [None] * len(fns)
    errs = []

    def call(i):
        try:
            out[i] = fns[i]()
        except Exception as e:  # noqa: BLE001
            errs.append((i, e))

    ts = [threading.Thread(target=call, args=(i,)) for i in range(len(fns))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errs:
        raise RuntimeError(f"peer failures: {errs}")
    return out


def bench_config(n_peers: int, mode: str, arrays_per_peer, total_elems,
                 budget: float = 60.0, n_assist: int = 0,
                 codec_backend: str = "host", bits=None, ef: bool = False):
    """``n_assist`` weight-0 averaging assistants (swarm/assist.py) join
    the trainers' round as extra part owners at the full flagship
    payload — the M44 mode at realistic scale. ``codec_backend="device"``
    routes every peer's codec through the jitted device path. ``bits``
    pins both wire legs to u8/u4 (the r15 in-collective stage) and
    ``ef`` arms per-peer error-feedback residuals on both legs."""
    n_all = n_peers + n_assist
    nodes = []
    for _ in range(n_all):
        peers = [nodes[0].visible_address] if nodes else []
        nodes.append(DHT(initial_peers=peers, identity=Identity.generate(),
                         rpc_timeout=5.0))
    timers = PhaseTimers()
    restore = timers.patch()
    t_match_s = time.monotonic()
    # min_group_size counts CONTRIBUTORS (assistants don't), so asking
    # for n_all keeps the early-exit quorum unsatisfiable and forces the
    # full window — the 3-member group forms deterministically instead
    # of racing the assistant's announce against the trainers' polls
    groups = run_threads([
        (lambda i=i: make_group(
            nodes[i], f"payload_{mode}", 0,
            weight=1.0 if i < n_peers else 0.0,
            matchmaking_time=4.0, min_group_size=n_all, encrypt=True))
        for i in range(n_all)])
    t_match = time.monotonic() - t_match_s
    assert all(g is not None and g.size == n_all for g in groups)

    compressors = [PowerSGDCompressor(rank=4) for _ in range(n_peers)]
    reports = [dict() for _ in range(n_all)]
    pinned = compression.codec_for_bits(bits)
    pin_kw = {}
    if pinned is not None:
        pin_kw = dict(codec=pinned, gather_codec=pinned)
    efs = [None] * n_all
    if ef:
        from dalle_tpu.swarm.error_feedback import make_pair
        efs = [make_pair() if i < n_peers else None
               for i in range(n_all)]

    def peer(i):
        ef_kw = {} if efs[i] is None else dict(ef_scatter=efs[i][0],
                                               ef_gather=efs[i][1])
        if i >= n_peers:  # averaging assistant: zero template, weight 0
            template = [np.zeros(total_elems, np.float32)]
            return run_allreduce(
                nodes[i], groups[i], f"payload_{mode}", 0, template,
                weight=0.0, allreduce_timeout=budget, report=reports[i],
                codec_backend=codec_backend, **pin_kw)
        if mode == "power_sgd":
            def reduce_fn(tensors, phase):
                rep = {}
                out = run_allreduce(
                    nodes[i], groups[i], f"payload_{mode}_{phase}", 0,
                    tensors, weight=1.0, allreduce_timeout=budget / 2,
                    report=rep, codec_backend=codec_backend)
                reports[i] = rep
                if not rep.get("complete", False):
                    raise IncompleteRound(phase)
                return out
            return average_with_powersgd(
                compressors[i], arrays_per_peer[i], reduce_fn, epoch=0)
        out = run_allreduce(
            nodes[i], groups[i], f"payload_{mode}", 0, arrays_per_peer[i],
            weight=1.0, allreduce_timeout=budget, report=reports[i],
            codec_backend=codec_backend, **pin_kw, **ef_kw)
        return out

    t0 = time.monotonic()
    results = run_threads([lambda i=i: peer(i) for i in range(n_all)])
    wall = time.monotonic() - t0
    restore()
    for n in nodes:
        n.shutdown()

    # correctness: every TRAINER ends with (approximately) the mean of
    # the trainers' data (assistants contribute nothing and collect
    # nothing — their returned value is their own discarded input)
    expected = sum(flatten_tensors(a) for a in arrays_per_peer) / n_peers
    worst = 0.0
    for res in results[:n_peers]:
        flat = flatten_tensors([np.asarray(r) for r in res])
        worst = max(worst, float(np.max(np.abs(flat - expected))))
    scale = float(np.max(np.abs(expected)))

    mb = total_elems * 4 / 1e6
    # slowest peer's per-phase wall times (phases overlap across peers on
    # this one-core VM, so the per-peer view is what a real host sees)
    slowest = max((r.get("phases", {}) for r in reports[:n_peers]),
                  key=lambda p: sum(p.values()), default={})
    label = (f"{mode}, {n_peers} peers"
             + (f" + {n_assist} assist" if n_assist else "")
             + (", device codec" if codec_backend == "device" else "")
             + (f", u{bits} pinned" if bits else "")
             + (" + EF" if ef else ""))
    row = {
        "metric": f"swarm payload allreduce ({label})",
        "payload_mb_f32": round(mb, 1),
        "wire_bits": bits,
        "ef_residuals": ef,
        "wire_mb": round(timers.wire_bytes / 1e6, 1),
        "epoch_wall_s": round(wall, 2),
        "matchmaking_s": round(t_match, 2),
        "encode_s": round(timers.encode, 2),
        "decode_s": round(timers.decode, 2),
        "aead_s": round(timers.aead, 2),
        "complete": all(r.get("complete", False)
                        for r in reports[:n_peers]),
        "slowest_peer_phases": slowest,
        "max_err_vs_mean": round(worst, 5),
        "err_scale": round(scale, 3),
        "within_60s_budget": wall <= 60.0,
    }
    print(json.dumps(row), flush=True)
    return row


def main():
    argv = sys.argv[1:]
    device = "--device-codec" in argv
    ef = "--ef" in argv
    bits = None
    out_path = None
    args = []
    skip = False
    for i, a in enumerate(argv):
        if skip:
            skip = False
            continue
        if a in ("--bits", "--out"):
            if i + 1 >= len(argv):
                raise SystemExit(f"{a} needs a value")
            if a == "--bits":
                if not argv[i + 1].isdigit():
                    raise SystemExit(
                        f"--bits must be 8 or 4 (got {argv[i + 1]!r})")
                bits = int(argv[i + 1])
            else:
                out_path = argv[i + 1]
            skip = True
        elif a not in ("--device-codec", "--ef"):
            args.append(a)
    bad = [a for a in args if not a.isdigit() and a != "assist"]
    if bad:
        raise SystemExit(f"unknown arguments: {bad} "
                         "(expected peer counts, 'assist', "
                         "'--device-codec', '--bits {8,4}', '--ef' "
                         "and/or '--out FILE')")
    if bits not in (None, 4, 8):
        raise SystemExit(f"--bits must be 8 or 4 (got {bits})")
    if ef and bits is None:
        raise SystemExit("--ef requires --bits (EF residual scales need "
                         "one stable pinned codec)")
    backend = "device" if device else "host"
    peer_counts = [int(a) for a in args if a.isdigit()] or [2, 4]
    # the assist and power_sgd rows are fixed 2-trainer configs
    max_n = max(max(peer_counts), 2)
    print("# generating flagship-shaped gradient sets...", file=sys.stderr)
    arrays, total = [], 0
    for i in range(max_n):
        a, total = flagship_grad_arrays(seed=100 + i)
        arrays.append(a)
    print(f"# {total/1e6:.1f}M params = {total*4/1e6:.0f} MB f32 per peer",
          file=sys.stderr)

    rows = []
    for n in peer_counts:
        # the 60 s reference budget is per-PEER compute + wire; this VM
        # serializes all N peers on one core, so give N>2 a proportional
        # budget and report wall/N as the per-peer number a real host sees
        rows.append(bench_config(n, "size_adaptive", arrays[:n], total,
                                 budget=60.0 * max(1, n // 2),
                                 codec_backend=backend, bits=bits, ef=ef))
    if "assist" in args:
        # M44 averaging-assist at the full flagship payload: 2 trainers
        # + 1 weight-0 assistant owning a third of the parts
        rows.append(bench_config(2, "size_adaptive", arrays[:2], total,
                                 budget=90.0, n_assist=1,
                                 codec_backend=backend, bits=bits, ef=ef))
    if bits is None:
        # the PowerSGD row is a different compression family: skip it
        # on pinned-bits runs (the r15 A/B compares uniform codecs)
        rows.append(bench_config(2, "power_sgd", arrays[:2], total,
                                 codec_backend=backend))

    print("\n| mode | peers | payload | wire | epoch | matchmake | "
          "encode | decode | aead |")
    print("|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['metric'].split('(')[1].rstrip(')')} "
              f"| {r['payload_mb_f32']} MB | {r['wire_mb']} MB "
              f"| {r['epoch_wall_s']} s "
              f"| {r['matchmaking_s']} s | {r['encode_s']} s "
              f"| {r['decode_s']} s | {r['aead_s']} s |")
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fh:
            json.dump(rows, fh, indent=1)
            fh.write("\n")
        print(f"# rows -> {out_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
