"""Verified aggregation (swarm/audit.py): the challenge function, the
transcript plane (sign/chunk/post/fetch/strict-open), the replay's
rejection taxonomy, replay determinism (sequential, repeated, and
--jobs-parallel — the drop-set is a pure function of the transcript),
byte-transparency of audit-off AND audit-on honest rounds, live-socket
conviction of wrong-part and omitting owners, the audit worker's
lifecycle, and the hostile-owner soak gate (fast variant tier-1, full
slow-marked).
"""

import concurrent.futures
import hashlib
import json
import threading
import time

import numpy as np
import pytest

from dalle_tpu.swarm import DHT, Identity, compression
from dalle_tpu.swarm.allreduce import (_part_slices, flatten_tensors,
                                       run_allreduce)
from dalle_tpu.swarm.audit import (AUDIT_FAIL_REASON, AUDIT_OMIT_REASON,
                                   AUDIT_TIMEOUT_REASON, AuditPolicy,
                                   AuditWorker, RoundAudit, _audit_ctx,
                                   _audit_tag, audit_round,
                                   challenged_parts, fetch_transcript,
                                   open_transcript, replay_transcript)
from dalle_tpu.swarm.chaos import ByzantineOp, ChaosDHT, FaultPlan
from dalle_tpu.swarm.health import (GOSSIP_REASONS, STRIKE_WEIGHTS,
                                    PeerHealthLedger)
from dalle_tpu.swarm.identity import Ed25519PrivateKey, signed_frame
from dalle_tpu.swarm.matchmaking import make_group
from dalle_tpu.swarm.screening import GradientScreen, ScreenPolicy


# -- the challenge ---------------------------------------------------------

class TestChallenge:
    def test_frac_bounds(self):
        assert challenged_parts("p", 0, 5, 1.0) == {0, 1, 2, 3, 4}
        assert challenged_parts("p", 0, 5, 0.0) == set()
        assert challenged_parts("p", 0, 0, 1.0) == set()

    def test_deterministic_and_round_varying(self):
        a = challenged_parts("p", 3, 64, 0.25)
        b = challenged_parts("p", 3, 64, 0.25)
        assert a == b  # every member derives the identical set
        assert challenged_parts("p", 4, 64, 0.25) != a \
            or challenged_parts("q", 3, 64, 0.25) != a
        # the sample tracks the probability loosely
        assert 4 <= len(a) <= 32

    def test_policy_validation(self):
        with pytest.raises(ValueError):
            AuditPolicy(frac=1.5)
        with pytest.raises(ValueError):
            AuditPolicy(ttl=0)
        with pytest.raises(ValueError):
            AuditPolicy(fetch_retries=0)
        with pytest.raises(ValueError):
            AuditPolicy(chunk_bytes=16)

    def test_new_strike_reasons_registered(self):
        assert STRIKE_WEIGHTS[AUDIT_FAIL_REASON] == 2.0
        assert STRIKE_WEIGHTS[AUDIT_OMIT_REASON] == 2.0
        assert STRIKE_WEIGHTS[AUDIT_TIMEOUT_REASON] == 1.0
        # only the replay verdict gossips: omission is victim-only
        # knowledge, silence is unattributable
        assert AUDIT_FAIL_REASON in GOSSIP_REASONS
        assert AUDIT_OMIT_REASON not in GOSSIP_REASONS
        assert AUDIT_TIMEOUT_REASON not in GOSSIP_REASONS


# -- live-socket harness ---------------------------------------------------

def _det_swarm(n, base=61):
    nodes = []
    for i in range(n):
        peers = [nodes[0].visible_address] if nodes else []
        ident = Identity(Ed25519PrivateKey.from_private_bytes(
            bytes([base + i]) * 32))
        nodes.append(DHT(initial_peers=peers, identity=ident,
                         rpc_timeout=2.0))
    return nodes


def _run_threads(fns, timeout=60):
    results = [None] * len(fns)
    errors = []

    def wrap(i, fn):
        try:
            results[i] = fn()
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=wrap, args=(i, fn))
               for i, fn in enumerate(fns)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
    if errors:
        raise errors[0]
    return results


def _audited_round(nodes, prefix, tensors, *, dhts=None, screen=None,
                   policy=None, mpw=100.0, codec=compression.NONE,
                   audit_on=True, chunk_elems=None, gather_codec=None,
                   efs=None, epoch=0):
    """One full-group round with per-peer RoundAudits armed; returns
    (results[(group, out)], ras, ledgers). ``efs`` (optional
    per-peer (scatter, gather) ErrorFeedback pairs) and
    ``gather_codec`` arm the r15 quantized-wire legs."""
    from dalle_tpu.swarm.allreduce import CHUNK_ELEMS
    n = len(nodes)
    dhts = dhts or list(nodes)
    policy = policy or AuditPolicy(frac=1.0, fetch_timeout=2.0)
    screen = screen or GradientScreen(ScreenPolicy())
    ledgers = [PeerHealthLedger() for _ in range(n)]
    ras = [RoundAudit(prefix, epoch, policy) if audit_on else None
           for _ in range(n)]

    def peer(i):
        g = make_group(dhts[i], prefix, epoch=epoch, weight=1.0,
                       matchmaking_time=2.0, min_group_size=n)
        assert g is not None and g.size == n
        ef_kw = {} if efs is None else dict(ef_scatter=efs[i][0],
                                            ef_gather=efs[i][1])
        return g, run_allreduce(
            dhts[i], g, prefix, epoch, tensors[i], weight=1.0,
            allreduce_timeout=8.0, sender_timeout=1.5, codec=codec,
            ledger=ledgers[i], screen=screen, max_peer_weight=mpw,
            audit=ras[i], gather_codec=gather_codec,
            chunk_elems=chunk_elems or CHUNK_ELEMS, **ef_kw)

    results = _run_threads([lambda i=i: peer(i) for i in range(n)])
    return results, ras, ledgers


def _int_tensors(n, size=400, seed=5):
    rng = np.random.RandomState(seed)
    base = rng.randint(-8, 9, size=size).astype(np.float32)
    return [[base + i] for i in range(n)]


# -- transcript plane ------------------------------------------------------

class TestTranscript:
    @pytest.fixture(scope="class")
    def round5(self):
        nodes = _det_swarm(5)
        try:
            screen = GradientScreen(ScreenPolicy())
            results, ras, ledgers = _audited_round(
                nodes, "tr", _int_tensors(5), screen=screen)
            yield nodes, results, ras, ledgers, screen
        finally:
            for nd in nodes:
                nd.shutdown()

    def test_signed_roundtrip_and_binding(self, round5):
        nodes, results, ras, _led, _screen = round5
        owner_i = next(i for i in range(5)
                       if ras[i].audits_mine and ras[i].posted)
        ra = ras[owner_i]
        blob = ra.build_transcript(nodes[owner_i].identity)
        tr = open_transcript(blob, "tr", 0, ra.my_part,
                             nodes[owner_i].peer_id)
        assert tr is not None
        assert set(tr["order"]) | {ra.group.my_index} >= set(tr["order"])
        # wrong epoch / part / owner: the binding rejects
        assert open_transcript(blob, "tr", 1, ra.my_part,
                               nodes[owner_i].peer_id) is None
        assert open_transcript(blob, "tr", 0, ra.my_part + 1,
                               nodes[owner_i].peer_id) is None
        other = nodes[(owner_i + 1) % 5].peer_id
        assert open_transcript(blob, "tr", 0, ra.my_part, other) is None
        # a flipped byte anywhere kills the signature
        flipped = bytearray(blob)
        flipped[len(flipped) // 2] ^= 1
        assert open_transcript(bytes(flipped), "tr", 0, ra.my_part,
                               nodes[owner_i].peer_id) is None

    def test_fetch_reassembles_chunked_posts(self, round5):
        nodes, results, ras, _led, _screen = round5
        owner_i = next(i for i in range(5) if ras[i].audits_mine)
        ra = ras[owner_i]
        # small chunk_bytes forces multi-chunk posting
        small = AuditPolicy(frac=1.0, chunk_bytes=1024,
                            fetch_timeout=2.0)
        ra2 = RoundAudit("tr2", 0, small)
        ra2.__dict__.update({k: v for k, v in ra.__dict__.items()
                             if k not in ("prefix", "policy")})
        ra2.prefix, ra2.policy = "tr2", small
        assert ra2.post_transcript(nodes[owner_i])
        got = fetch_transcript(
            nodes[(owner_i + 1) % 5], ra.owners[ra.my_part].addr,
            "tr2", 0, ra.my_part, small, group_key=ra.group.group_key)
        assert got == ra2.build_transcript(nodes[owner_i].identity)
        assert open_transcript(got, "tr2", 0, ra.my_part,
                               nodes[owner_i].peer_id) is not None

    def test_unknown_payload_keys_rejected(self, round5):
        import msgpack
        nodes, _res, ras, _led, _screen = round5
        owner_i = next(i for i in range(5) if ras[i].audits_mine)
        ra = ras[owner_i]
        blob = ra.build_transcript(nodes[owner_i].identity)
        tr = open_transcript(blob, "tr", 0, ra.my_part,
                             nodes[owner_i].peer_id)
        payload = msgpack.packb({
            "v": 1, "epoch": 0, "part": ra.my_part, "init": tr["init"],
            "order": tr["order"], "drops": {}, "evidence": {},
            "frames": {}, "extra": 1}, use_bin_type=True)
        forged = signed_frame(nodes[owner_i].identity,
                              _audit_ctx("tr", 0, ra.my_part), b"",
                              payload)
        assert open_transcript(forged, "tr", 0, ra.my_part,
                               nodes[owner_i].peer_id) is None


# -- replay: honest pass + rejection taxonomy ------------------------------

def _replay_kwargs(ra, screen, mpw=100.0):
    return dict(group=ra.group, prefix=ra.prefix, epoch=ra.epoch,
                part=ra.my_part, part_elems=ra.part_sizes[ra.my_part],
                chunk_elems=ra.chunk_elems, codec=ra.codec,
                adaptive_threshold=ra.adaptive_threshold, screen=screen,
                max_peer_weight=mpw)


def _mutated(nodes, ra, mutate):
    """Open the owner's own transcript, apply ``mutate(tr_dict)``, and
    re-sign with the owner's REAL identity — exactly what a lying
    owner can do."""
    import msgpack
    owner_ident = next(nd.identity for nd in nodes
                       if nd.peer_id == ra.owners[ra.my_part].peer_id)
    blob = ra.build_transcript(owner_ident)
    tr = open_transcript(blob, ra.prefix, ra.epoch, ra.my_part,
                         ra.owners[ra.my_part].peer_id)
    raw = {"v": 1, "epoch": ra.epoch, "part": ra.my_part,
           "init": tr["init"], "order": list(tr["order"]),
           "drops": {str(k): v for k, v in tr["drops"].items()},
           "evidence": {str(k): v for k, v in tr["evidence"].items()},
           "frames": {str(k): v for k, v in tr["frames"].items()}}
    mutate(raw)
    payload = msgpack.packb(raw, use_bin_type=True)
    forged = signed_frame(owner_ident,
                          _audit_ctx(ra.prefix, ra.epoch, ra.my_part),
                          b"", payload)
    return open_transcript(forged, ra.prefix, ra.epoch, ra.my_part,
                           ra.owners[ra.my_part].peer_id)


class TestReplay:
    @pytest.fixture(scope="class")
    def round5(self):
        nodes = _det_swarm(5, base=71)
        try:
            screen = GradientScreen(ScreenPolicy())
            results, ras, ledgers = _audited_round(
                nodes, "rp", _int_tensors(5, seed=9), screen=screen)
            yield nodes, results, ras, ledgers, screen
        finally:
            for nd in nodes:
                nd.shutdown()

    def _owner_ra(self, ras):
        return next(ra for ra in ras if ra.audits_mine)

    def test_honest_transcript_replays_bit_exact(self, round5):
        nodes, results, ras, _led, screen = round5
        ra = self._owner_ra(ras)
        tr = _mutated(nodes, ra, lambda raw: None)
        res = replay_transcript(tr, **_replay_kwargs(ra, screen))
        assert res.ok, res.why
        # every member that gathered this part lives with the replay's
        # bytes. Who gathered is counted, not assumed: under six workers a
        # member can time out of the gather by the box's clock, and then
        # it holds nothing to compare and its own output says so (the
        # part's slice of what it put in, not of the average)
        flats = [flatten_tensors(t) for t in _int_tensors(5, seed=9)]
        lo, hi = _part_slices(flats[0].size, 5)[ra.my_part]
        holders = 0
        for i, other in enumerate(ras):
            if other is ra:
                continue
            mine = flatten_tensors(results[i][1])[lo:hi]
            if ra.my_part not in other.gathered:
                assert mine.tobytes() == flats[i][lo:hi].tobytes()
                continue
            holders += 1
            assert res.values.tobytes() \
                == other.gathered[ra.my_part].tobytes()
            assert res.values.tobytes() == mine.tobytes()
        assert holders >= 1

    def test_replay_matches_analytic_average(self, round5):
        nodes, results, ras, _led, screen = round5
        ra = self._owner_ra(ras)
        tr = _mutated(nodes, ra, lambda raw: None)
        res = replay_transcript(tr, **_replay_kwargs(ra, screen))
        flats = [flatten_tensors(t) for t in _int_tensors(5, seed=9)]
        lo, hi = _part_slices(flats[0].size, 5)[ra.my_part]
        want = sum(f[lo:hi] for f in flats) / 5.0
        np.testing.assert_array_equal(res.values, want)

    def test_lying_mutations_rejected(self, round5):
        nodes, _res, ras, _led, screen = round5
        ra = self._owner_ra(ras)
        kw = _replay_kwargs(ra, screen)

        def why(mutate):
            tr = _mutated(nodes, ra, mutate)
            assert tr is not None
            res = replay_transcript(tr, **kw)
            assert not res.ok
            return res.why

        # a duplicate application inflates one sender's influence
        assert why(lambda r: r["order"].append(r["order"][0])) \
            == "duplicate-sender-in-order"
        # claiming an applied sender was ALSO dropped is incoherent
        assert why(lambda r: r["drops"].update(
            {str(r["order"][0]): "screen-outlier"})) \
            == "sender-both-applied-and-dropped"
        # a provable drop (corrupt-chunk) with no offending frame as
        # evidence would let an owner censor anyone with cover
        def fake_corrupt(r):
            s = r["order"].pop()
            r["frames"].pop(str(s), None)
            r["drops"][str(s)] = "corrupt-chunk"
        assert why(fake_corrupt) == "unevidenced-corrupt-drop"
        # claiming an honest sender as a screen outlier fails the
        # screen REPLAY (the deterministic f64 verdict disagrees)
        def fake_screen_drop(r):
            s = r["order"].pop()
            r["drops"][str(s)] = "screen-outlier"
        assert why(fake_screen_drop) == "screen-replay-mismatch"
        # wrong init: claiming a zeros start while the self frames say
        # the owner contributed changes the f32 operation sequence
        def zeros_init(r):
            r["init"] = "zeros"
        assert why(zeros_init) == "wrong-init"
        # dropping the self frames ENTIRELY replays coherently as "the
        # owner contributed nothing" — but the bytes it actually
        # served then disagree, which is the byte-compare's catch
        def no_self(r):
            r["init"] = "zeros"
            r["frames"].pop(str(ra.group.my_index), None)
        tr = _mutated(nodes, ra, no_self)
        res = replay_transcript(tr, **kw)
        honest = replay_transcript(_mutated(nodes, ra, lambda r: None),
                                   **kw)
        assert res.ok and honest.ok
        assert res.values.tobytes() != honest.values.tobytes()
        # an applied sender whose frames were stripped cannot be
        # re-derived
        def strip_frames(r):
            r["frames"].pop(str(r["order"][0]))
        assert why(strip_frames) == "applied-sender-missing-frames"

    def test_fabricated_self_contribution_is_caught(self, round5):
        """The one input an owner CAN mint is its own — a self-segment
        crafted to 'explain' a wrong part is an outlier the replayed
        screen drops, so the claimed keep fails the screen replay."""
        from dalle_tpu.swarm.allreduce import (_chunk_slices, _make_frame,
                                               _sign_ctx)
        nodes, _res, ras, _led, screen = round5
        ra = self._owner_ra(ras)
        owner_pid = ra.owners[ra.my_part].peer_id
        owner_ident = next(nd.identity for nd in nodes
                           if nd.peer_id == owner_pid)
        n = ra.part_sizes[ra.my_part]
        chunks = _chunk_slices(n, ra.chunk_elems)
        ctx = _sign_ctx(ra.prefix, ra.epoch, "scatter", owner_pid)
        fake = (np.ones(n, np.float32) * 1000.0)

        def swap_self(r):
            frames = []
            for ci, (clo, chi) in enumerate(chunks):
                payload = compression.compress(fake[clo:chi],
                                               compression.NONE)
                frames.append(_make_frame(
                    owner_ident, ctx, ra.group.group_hash,
                    ra.group.my_index, 1.0, chi - clo,
                    compression.NONE, payload, chunk=ci,
                    n_chunks=len(chunks)))
            r["frames"][str(ra.group.my_index)] = frames
        tr = _mutated(nodes, ra, swap_self)
        res = replay_transcript(tr, **_replay_kwargs(ra, screen))
        assert not res.ok and res.why == "screen-replay-mismatch"
        assert ra.group.my_index in res.screen_drops

    def test_replay_deterministic_repeated_and_parallel(self, round5):
        """Satellite pin: the drop-set (and bytes) recomputed from a
        transcript are bit-equal across repeated runs AND under
        --jobs-style parallel auditing — the replay is a pure function
        of (transcript, group, config)."""
        nodes, _res, ras, _led, screen = round5
        ra = self._owner_ra(ras)
        tr = _mutated(nodes, ra, lambda raw: None)
        kw = _replay_kwargs(ra, screen)
        ref = replay_transcript(tr, **kw)
        assert ref.ok
        for _ in range(4):
            res = replay_transcript(tr, **kw)
            assert res.screen_drops == ref.screen_drops
            assert res.values.tobytes() == ref.values.tobytes()
        with concurrent.futures.ThreadPoolExecutor(max_workers=4) as ex:
            futs = [ex.submit(replay_transcript, tr, **kw)
                    for _ in range(8)]
            for f in futs:
                res = f.result()
                assert res.screen_drops == ref.screen_drops
                assert res.values.tobytes() == ref.values.tobytes()


# -- byte transparency -----------------------------------------------------

class TestTransparency:
    def test_audit_on_rounds_byte_identical_to_audit_off(self):
        """The tentpole's transparency contract, both directions:
        audit=None rounds are the pre-change protocol, and audit-ON
        honest rounds produce byte-identical averages (retention
        copies bytes, never touches the accumulation)."""
        tensors = _int_tensors(5, seed=13)
        nodes = _det_swarm(5, base=81)
        try:
            on, _ras, led_on = _audited_round(nodes, "ta", tensors,
                                              audit_on=True)
        finally:
            for nd in nodes:
                nd.shutdown()
        nodes = _det_swarm(5, base=81)
        try:
            off, _r2, led_off = _audited_round(nodes, "tb", tensors,
                                               audit_on=False)
        finally:
            for nd in nodes:
                nd.shutdown()
        for i in range(5):
            a = flatten_tensors(on[i][1])
            b = flatten_tensors(off[i][1])
            assert a.tobytes() == b.tobytes()
        assert all(not led.snapshot() for led in led_on + led_off)

    def test_multichunk_round_replays_clean(self):
        """Parts split into many wire chunks (chunk_elems << part
        size): retention, transcript reassembly and replay all work
        per chunk, and a hostile SENDER shipping inconsistent
        in-clamp weights across its chunks cannot frame the honest
        owner — the chunk-0 claim governs on both the live path and
        the replay (the review-found framing attack)."""
        from dalle_tpu.swarm.allreduce import (_chunk_slices, _make_frame,
                                               _parse, _sign_ctx)
        nodes = _det_swarm(5, base=101)
        try:
            screen = GradientScreen(ScreenPolicy())
            results, ras, ledgers = _audited_round(
                nodes, "mc", _int_tensors(5, seed=21), screen=screen,
                chunk_elems=32)
            reports = [audit_round(nodes[i], ras[i], ledgers[i])
                       for i in range(5)]
            for rep, led in zip(reports, ledgers):
                assert not rep["failed"] and not rep["unserved"] \
                    and not rep["omitted"], rep
                assert led.snapshot() == {}
            # now the framing attempt: rewrite one applied sender's
            # NON-ZERO chunk to claim a different (in-clamp) weight
            # and re-sign with that sender's REAL key — the replay
            # must still pass with unchanged values
            ra = next(r for r in ras if r.audits_mine)
            owner_pid = ra.owners[ra.my_part].peer_id
            sender = next(s for s in ra.order)
            sender_pid = ra.group.members[sender].peer_id
            sender_ident = next(nd.identity for nd in nodes
                                if nd.peer_id == sender_pid)
            chunks = _chunk_slices(ra.part_sizes[ra.my_part],
                                   ra.chunk_elems)
            assert len(chunks) > 1
            ctx = _sign_ctx("mc", 0, "scatter", owner_pid)
            honest = _mutated(nodes, ra, lambda r: None)
            kw = _replay_kwargs(ra, screen)
            ref = replay_transcript(honest, **kw)
            assert ref.ok, ref.why

            def twist_weight(r):
                frames = list(r["frames"][str(sender)])
                for i, raw in enumerate(frames):
                    p = _parse(raw, ra.group, chunks, ctx)
                    if p is not None and p[0] == "ok" and p[3] == 1:
                        clo, chi = chunks[1]
                        payload = compression.compress(p[4],
                                                       compression.NONE)
                        frames[i] = _make_frame(
                            sender_ident, ctx, ra.group.group_hash,
                            sender, 9.0, chi - clo, compression.NONE,
                            payload, chunk=1, n_chunks=len(chunks))
                r["frames"][str(sender)] = frames
            twisted = _mutated(nodes, ra, twist_weight)
            res = replay_transcript(twisted, **kw)
            assert res.ok, res.why
            assert res.values.tobytes() == ref.values.tobytes()
        finally:
            for nd in nodes:
                nd.shutdown()

    def test_below_quorum_owner_cannot_mint_over_ceiling_self(self):
        """The docstring's below-quorum defense, both faces: a 2-peer
        round where one OWNER's own data is over the ceiling withholds
        that contribution live (unstruck — the small-swarm rule), and
        a forged transcript claiming such a self-contribution was KEPT
        fails the replay."""
        nodes = _det_swarm(2, base=111)
        big_i = 1
        base = (np.arange(300, dtype=np.float32) % 7 - 3)
        tensors = [[base.copy()], [np.full(300, 1000.0, np.float32)]]
        screen = GradientScreen(ScreenPolicy(abs_norm_ceiling=500.0))
        try:
            results, ras, ledgers = _audited_round(
                nodes, "sq", tensors, screen=screen)
            reports = [audit_round(nodes[i], ras[i], ledgers[i])
                       for i in range(2)]
            # live: every part ends as the honest peer's values alone
            # (big_i's data is withheld everywhere), honest replays
            # pass, nobody is struck
            for i in range(2):
                assert not reports[i]["failed"] \
                    and not reports[i]["unserved"], reports[i]
                assert ledgers[i].snapshot() == {}
                got = flatten_tensors(results[i][1])
                np.testing.assert_array_equal(got,
                                              flatten_tensors(tensors[0]))
            # forged face: rewrite the big owner's transcript to CLAIM
            # it kept its over-ceiling self-contribution
            ra = ras[big_i]
            assert ra.audits_mine and ra.init == "zeros"
            assert ra.drops.get(ra.group.my_index) == "screen-outlier"

            def keep_self(r):
                r["init"] = "self"
                r["drops"].pop(str(ra.group.my_index))
            tr = _mutated(nodes, ra, keep_self)
            res = replay_transcript(tr, **_replay_kwargs(ra, screen))
            assert not res.ok
            assert res.why == "kept-over-ceiling-sender"
        finally:
            for nd in nodes:
                nd.shutdown()

    def test_u8_codec_round_replays_bit_exact(self):
        """The replay reproduces the lossy wire round-trip exactly —
        the u8-quantized gathered bytes ARE the comparison target."""
        nodes = _det_swarm(5, base=41)
        try:
            screen = GradientScreen(ScreenPolicy())
            results, ras, ledgers = _audited_round(
                nodes, "u8", _int_tensors(5, seed=3), screen=screen,
                codec=compression.UNIFORM8BIT)
            reports = [audit_round(nodes[i], ras[i], ledgers[i])
                       for i in range(5)]
        finally:
            for nd in nodes:
                nd.shutdown()
        for rep, led in zip(reports, ledgers):
            assert not rep["failed"] and not rep["unserved"] \
                and not rep["omitted"]
            assert led.snapshot() == {}
            assert len(rep["ok"]) == 4


# -- quantized wire + error feedback (r15) ---------------------------------

class TestQuantizedAudit:
    def test_ef_quantized_rounds_replay_bit_exact_across_epochs(self):
        """The r15 trust-layer carry-over: two consecutive rounds on
        the pinned u8-reduce/u4-gather wire with PERSISTENT per-peer
        error-feedback residuals and a PARTIAL challenge (frac=0.5,
        prefix chosen so the challenged set flips between epochs).
        Unchallenged parts carry their owner's gather residual;
        challenged parts suspend the carry — so every audited part
        must replay bit-exactly even while live residuals exist, and
        honest owners earn zero strikes. Real-valued (codec-inexact)
        gradients: the quantization error is genuinely nonzero."""
        from dalle_tpu.swarm.error_feedback import make_pair
        rng = np.random.RandomState(3)
        nodes = _det_swarm(5, base=121)
        efs = [make_pair() for _ in range(5)]
        policy = AuditPolicy(frac=0.5, fetch_timeout=2.0)
        try:
            gather_resid_seen = False
            for epoch in (0, 1):
                tensors = [[(rng.randn(640) * (1 + i)).astype(np.float32)]
                           for i in range(5)]
                results, ras, ledgers = _audited_round(
                    nodes, "qa0", tensors, policy=policy,
                    codec=compression.UNIFORM8BIT,
                    gather_codec=compression.UNIFORM4BIT,
                    efs=efs, chunk_elems=1024, epoch=epoch)
                assert challenged_parts("qa0", epoch, 5, 0.5), \
                    "prefix must challenge at least one part"
                # every member's replay of every challenged part passes
                for i in range(5):
                    rep = audit_round(nodes[i], ras[i], ledgers[i])
                    assert rep["audited"], rep
                    assert not rep["failed"] and not rep["unserved"] \
                        and not rep["omitted"], (epoch, i, rep)
                    assert ledgers[i].snapshot() == {}
                # all members ended byte-identical (the wire contract)
                flats = [flatten_tensors(r[1]) for r in results]
                for f in flats[1:]:
                    assert flats[0].tobytes() == f.tobytes()
                # the feedback loop is LIVE: scatter residuals are
                # nonzero (real quantization error), and at least one
                # owner carries a nonzero gather residual
                for sc, _ga in efs:
                    r = sc.residual_host()
                    assert r is not None and np.abs(r).max() > 0
                gather_resid_seen = gather_resid_seen or any(
                    ga.residual_host() is not None
                    and np.abs(ga.residual_host()).max() > 0
                    for _sc, ga in efs)
            assert gather_resid_seen
        finally:
            for nd in nodes:
                nd.shutdown()

    def test_unpinned_mixed_codec_round_replays_clean(self):
        """A round whose callers pass an explicit codec WITHOUT
        opting into pinning accepts mixed-codec senders (r14
        semantics) — and the replay must apply the SAME acceptance
        rule: an honest owner that applied a legitimately
        differently-coded frame is never convicted (the review-found
        live-vs-replay asymmetry)."""
        from dalle_tpu.swarm.allreduce import CHUNK_ELEMS
        nodes = _det_swarm(5, base=161)
        rng = np.random.RandomState(9)
        tensors = [[(rng.randn(400) * (1 + i)).astype(np.float32)]
                   for i in range(5)]
        policy = AuditPolicy(frac=1.0, fetch_timeout=2.0)
        screen = GradientScreen(ScreenPolicy())
        ledgers = [PeerHealthLedger() for _ in range(5)]
        ras = [RoundAudit("mxr", 0, policy) for _ in range(5)]
        try:
            def peer(i):
                g = make_group(nodes[i], "mxr", epoch=0, weight=1.0,
                               matchmaking_time=2.0, min_group_size=5)
                assert g is not None and g.size == 5
                # peer 4 runs SizeAdaptive (f16 at these sizes); the
                # rest pass u8 explicitly but UNPINNED
                return g, run_allreduce(
                    nodes[i], g, "mxr", 0, tensors[i], weight=1.0,
                    allreduce_timeout=8.0, sender_timeout=1.5,
                    codec=None if i == 4 else compression.UNIFORM8BIT,
                    ledger=ledgers[i], screen=screen,
                    max_peer_weight=100.0, audit=ras[i],
                    chunk_elems=CHUNK_ELEMS)

            _run_threads([lambda i=i: peer(i) for i in range(5)])
            for i in range(5):
                rep = audit_round(nodes[i], ras[i], ledgers[i])
                assert rep["audited"], rep
                assert not rep["failed"] and not rep["unserved"] \
                    and not rep["omitted"], (i, rep)
                assert ledgers[i].snapshot() == {}
        finally:
            for nd in nodes:
                nd.shutdown()

    def test_replay_uses_the_gather_codec(self):
        """A round whose two legs pin DIFFERENT codecs: the replay
        must re-quantize with the GATHER codec — replaying the same
        transcript under the wrong gather codec mismatches the
        gathered bytes (the codec is load-bearing, not decorative)."""
        nodes = _det_swarm(4, base=141)
        rng = np.random.RandomState(7)
        tensors = [[(rng.randn(512) * (1 + i)).astype(np.float32)]
                   for i in range(4)]
        try:
            results, ras, ledgers = _audited_round(
                nodes, "qg", tensors, codec=compression.UNIFORM8BIT,
                gather_codec=compression.UNIFORM4BIT, chunk_elems=1024)
            auditor = next(
                r for r in ras
                if any(p != r.my_part and p in r.gathered
                       for p in r.audited))
            part = next(p for p in sorted(auditor.audited)
                        if p != auditor.my_part and p in auditor.gathered)
            owner = auditor.owners[part]
            blob = fetch_transcript(
                nodes[ras.index(auditor)], owner.addr, "qg", 0, part,
                auditor.policy, group_key=auditor.group.group_key)
            tr = open_transcript(blob, "qg", 0, part, owner.peer_id)
            assert tr is not None
            right = replay_transcript(
                tr, group=auditor.group, prefix="qg", epoch=0,
                part=part, part_elems=auditor.part_sizes[part],
                chunk_elems=1024, codec=compression.UNIFORM8BIT,
                adaptive_threshold=auditor.adaptive_threshold,
                screen=auditor.screen, max_peer_weight=100.0,
                gather_codec=compression.UNIFORM4BIT)
            assert right.ok
            assert right.values.tobytes() == \
                auditor.gathered[part].tobytes()
            wrong = replay_transcript(
                tr, group=auditor.group, prefix="qg", epoch=0,
                part=part, part_elems=auditor.part_sizes[part],
                chunk_elems=1024, codec=compression.UNIFORM8BIT,
                adaptive_threshold=auditor.adaptive_threshold,
                screen=auditor.screen, max_peer_weight=100.0,
                gather_codec=compression.UNIFORM8BIT)
            assert wrong.ok  # internally consistent transcript...
            assert wrong.values.tobytes() != \
                auditor.gathered[part].tobytes()  # ...wrong bytes
        finally:
            for nd in nodes:
                nd.shutdown()


# -- live conviction -------------------------------------------------------

class TestConviction:
    def test_wrong_part_owner_convicted_by_every_honest_member(self):
        nodes = _det_swarm(5, base=51)
        pids = [nd.peer_id for nd in nodes]
        bad_i = 2
        dhts = list(nodes)
        dhts[bad_i] = ChaosDHT(nodes[bad_i], FaultPlan(
            seed=1, byzantine=(ByzantineOp(kind="wrong_gather_part",
                                           factor=10.0),)))
        try:
            screen = GradientScreen(ScreenPolicy())
            results, ras, ledgers = _audited_round(
                nodes, "wg", _int_tensors(5), dhts=dhts, screen=screen)
            reports = [audit_round(dhts[i], ras[i], ledgers[i],
                                   jobs=2)
                       for i in range(5)]
        finally:
            for nd in nodes:
                nd.shutdown()
        bad_part = next(k for k, m in enumerate(ras[0].owners)
                        if m.peer_id == pids[bad_i])
        for i in range(5):
            if i == bad_i:
                continue
            assert [f["part"] for f in reports[i]["failed"]] == [bad_part]
            assert reports[i]["failed"][0]["why"] \
                == "replayed-bytes-mismatch"
            assert ledgers[i].score(pids[bad_i]) == pytest.approx(2.0)
            # honest owners still audit clean against each other
            assert len(reports[i]["ok"]) == 3

    def test_omitting_owner_convicted_by_its_victim(self):
        nodes = _det_swarm(5, base=31)
        pids = [nd.peer_id for nd in nodes]
        bad_i = 1
        dhts = list(nodes)
        dhts[bad_i] = ChaosDHT(nodes[bad_i], FaultPlan(
            seed=2, byzantine=(ByzantineOp(kind="omit_sender"),)))
        try:
            screen = GradientScreen(ScreenPolicy())
            results, ras, ledgers = _audited_round(
                nodes, "om", _int_tensors(5, seed=7), dhts=dhts,
                screen=screen)
            reports = [audit_round(dhts[i], ras[i], ledgers[i])
                       for i in range(5)]
        finally:
            for nd in nodes:
                nd.shutdown()
        victim = pids.index(min(p for i, p in enumerate(pids)
                                if i != bad_i))
        for i in range(5):
            if i == bad_i:
                continue
            if i == victim:
                assert [o["owner"] for o in reports[i]["omitted"]] \
                    == [pids[bad_i]]
                assert ledgers[i].score(pids[bad_i]) == pytest.approx(2.0)
            else:
                # non-victims have no standing: the omitted set was
                # honestly averaged, their replay passes
                assert not reports[i]["omitted"]
                assert ledgers[i].score(pids[bad_i]) == 0.0

    def test_unserved_transcript_is_an_audit_timeout_strike(self):
        class _DropAuditPosts:
            """An owner that stonewalls the audit: every transcript
            post is silently swallowed."""

            def __init__(self, inner, suppressed):
                self._inner = inner
                self._suppressed = suppressed

            def post(self, tag, payload, expiration_time):
                if tag in self._suppressed:
                    return True
                return self._inner.post(tag, payload, expiration_time)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        nodes = _det_swarm(5, base=21)
        pids = [nd.peer_id for nd in nodes]
        bad_i = 3
        suppressed = {_audit_tag("ns", 0, part, ci)
                      for part in range(5) for ci in range(8)}
        dhts = list(nodes)
        dhts[bad_i] = _DropAuditPosts(nodes[bad_i], suppressed)
        policy = AuditPolicy(frac=1.0, fetch_timeout=0.5,
                             fetch_retries=1)
        try:
            screen = GradientScreen(ScreenPolicy())
            results, ras, ledgers = _audited_round(
                nodes, "ns", _int_tensors(5, seed=11), dhts=dhts,
                screen=screen, policy=policy)
            reports = [audit_round(dhts[i], ras[i], ledgers[i])
                       for i in range(5)]
        finally:
            for nd in nodes:
                nd.shutdown()
        for i in range(5):
            if i == bad_i:
                continue
            assert [u["owner"] for u in reports[i]["unserved"]] \
                == [pids[bad_i]]
            # timeout-weighted and LOCAL: stonewalling converges to a
            # down-ranking without any gossip amplification
            assert ledgers[i].score(pids[bad_i]) == pytest.approx(1.0)


# -- the worker ------------------------------------------------------------

class TestAuditWorker:
    def test_step_drains_and_counts(self):
        nodes = _det_swarm(5, base=11)
        try:
            screen = GradientScreen(ScreenPolicy())
            results, ras, ledgers = _audited_round(
                nodes, "wk", _int_tensors(5, seed=2), screen=screen)
            w = AuditWorker(nodes[0], ledgers[0], jobs=2)
            w.submit(ras[0])
            w.submit(None)                      # ignored
            w.submit(RoundAudit("wk", 9))       # never begun: ignored
            assert w.step() == 1
            assert w.audited == 4 and w.failures == 0
            assert w.unserved == 0 and w.omissions == 0
            assert ledgers[0].snapshot() == {}
        finally:
            for nd in nodes:
                nd.shutdown()

    def test_queue_bound_drops_oldest(self):
        w = AuditWorker(None, None)
        ras = []
        for e in range(AuditWorker.MAX_PENDING + 2):
            ra = RoundAudit("qb", e)
            ra.begun = True
            ras.append(ra)
            w.submit(ra)
        with w._lock:
            epochs = [r.epoch for r in w._pending]
        assert len(epochs) == AuditWorker.MAX_PENDING
        assert epochs[0] == 2  # the two oldest were dropped

    def test_worker_thread_stops_clean(self):
        w = AuditWorker(None, None, period=0.05)
        w.start()
        time.sleep(0.15)
        w.stop()
        assert not w.is_alive()


# -- the hostile-owner soak gate -------------------------------------------

class TestHostileOwnerSoak:
    def test_schedule_is_seed_deterministic(self):
        from scripts.churn_soak import build_hostile_schedule
        a = build_hostile_schedule(seed=4, n_peers=5, epochs=3)
        b = build_hostile_schedule(seed=4, n_peers=5, epochs=3)
        c = build_hostile_schedule(seed=5, n_peers=5, epochs=3)
        assert a == b and a != c
        grads = [x for x in a["attacks"] if x["phase"] == "grads"]
        assert sorted(x["kind"] for x in grads) \
            == ["omit_sender", "wrong_gather_part"]
        assert len({x["peer"] for x in grads}) == 2
        # r16: the same two hostile peers each also attack one aux
        # averaging phase, paired with distinct honest partners
        aux = a["aux"]
        assert set(aux) == {"p", "state"}
        attackers = {x["peer"] for x in grads}
        for pair in aux.values():
            assert pair["attacker"] in attackers
            assert pair["partner"] not in attackers
        assert aux["p"]["partner"] != aux["state"]["partner"]
        phases = sorted(x["phase"] for x in a["attacks"])
        assert phases == ["grads", "grads", "powersgd", "state"]

    def test_fast_soak(self, tmp_path):
        """Tier-1 hostile-owner + REPAIR gate (the r16 repair soak):
        5 peers, FOUR passes over one schedule — control (audits +
        repair + aux phases on: zero strikes, ZERO repairs, bit-exact),
        attack (wrong-part conviction triggers repair and repaired
        survivors match the honest-only analytic reference; the
        PowerSGD-factor and state-averaging owner attacks each convict
        in every honest ledger via a verified proof-carrying receipt,
        at peers holding zero local evidence), nofix (repair OFF == the
        r15 protocol: convicted survivors DIVERGE — the regression
        repair exists to fix), and transparency (audits off == the
        pre-audit protocol)."""
        from scripts.churn_soak import main
        out = tmp_path / "HOSTILE_OWNER_SOAK.json"
        rc = main(["--hostile-owner", "--peers", "5", "--epochs", "3",
                   "--seed", "7", "--matchmaking-time", "1.2",
                   "--allreduce-timeout", "5", "--deadline", "150",
                   "--out", str(out)])
        assert rc == 0, f"hostile-owner soak reported a violation ({out})"
        report = json.loads(out.read_text())
        assert report["pass"] is True and report["violations"] == []
        assert all(not r["first_strike"] for r in report["control"])
        assert all(not r["repairs"].get("applied", 0)
                   for r in report["control"])
        assert all(not any(r["audit_events"].values())
                   for r in report["transparency"])
        honest = [r for r in report["attack"] if not r["attacker"]]
        assert len(honest) == 3
        # convicted ⇒ corrected: every honest member repaired
        assert all(r["repairs"]["applied"] >= 1 for r in honest)
        # r20: with the inline cap forced tiny, every honest peer
        # published its conviction evidence BY REFERENCE and convicted
        # on bundles it FETCHED (digest-checked) from other mailboxes
        assert report["params"]["proof_inline_max"] == 512
        assert all(r["proofs_by_reference"] >= 1 for r in honest)
        assert all(r["proof_fetch"]["ok"] >= 1 for r in honest)
        # r20: the aux pair partners repaired their factor/state
        # averages bit-exactly onto the honest reference
        aux = report["schedule"]["aux"]
        by_index = {r["name"]: r for r in report["attack"]}
        for suffix, pair in aux.items():
            partner = by_index[f"peer{pair['partner']}"]
            assert partner["aux_repairs"].get(suffix, 0) >= 1
            assert partner["aux_repair_clean"].get(suffix) is True
        # r20: the poison phase ran and every audience peer rejected
        # both the unfetchable and the forged by-reference receipt
        assert report["poison"].get("issuer")
        assert report["poison"]["ledger_hits"] == []
        assert all(v >= 2
                   for v in report["poison"]["rejected"].values())
        # and the nofix pass reproduces the r15 divergence the repair
        # closes (honest fingerprints differ from the attack pass's)
        nofix_honest = [r for r in report["nofix"] if not r["attacker"]]
        assert {r["fingerprint"] for r in nofix_honest} \
            != {r["fingerprint"] for r in honest}

    @pytest.mark.slow
    def test_full_soak(self, tmp_path):
        """The full-size hostile-owner soak (defaults-sized windows) —
        slow-marked; `scripts/churn_soak.py --hostile-owner` is the
        same gate from the command line."""
        from scripts.churn_soak import main
        out = tmp_path / "HOSTILE_OWNER_SOAK.json"
        rc = main(["--hostile-owner", "--peers", "5", "--epochs", "6",
                   "--seed", "11", "--deadline", "420",
                   "--out", str(out)])
        assert rc == 0
