"""Collaborative optimizer tests: real peers, loopback sockets, threads.

SURVEY.md §4 strategy: many real peers in one box. Each peer runs its
side of the protocol on its own thread (matchmaking and all-reduce are
blocking calls), exchanging real bytes through the C++ data plane.
"""

import threading
import time

import numpy as np
import optax
import pytest

from dalle_tpu.config import CollabConfig
from dalle_tpu.swarm import DHT, Identity
from dalle_tpu.swarm import compression
from dalle_tpu.swarm.allreduce import (_part_slices, flatten_tensors,
                                       run_allreduce, unflatten_tensors)
from dalle_tpu.swarm.matchmaking import make_group
from dalle_tpu.swarm.progress import ProgressTracker
from dalle_tpu.swarm.state_transfer import (StateServer, deserialize_state,
                                            load_state_from_peers,
                                            serialize_state)


def make_swarm(n, **kwargs):
    nodes = []
    for _ in range(n):
        peers = [nodes[0].visible_address] if nodes else []
        nodes.append(DHT(initial_peers=peers, identity=Identity.generate(),
                         rpc_timeout=2.0, **kwargs))
    return nodes


@pytest.fixture
def swarm3():
    nodes = make_swarm(3)
    yield nodes
    for n in nodes:
        n.shutdown()


def run_threads(fns):
    """Run one callable per peer concurrently; re-raise first error."""
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
        t.join(timeout=60)
    if errors:
        raise errors[0]
    return results


class TestCompression:
    def test_f16_roundtrip(self):
        x = np.random.RandomState(0).randn(1000).astype(np.float32)
        out = compression.decompress(
            compression.compress(x, compression.FLOAT16),
            compression.FLOAT16, x.size)
        np.testing.assert_allclose(out, x, rtol=2e-3, atol=1e-4)

    def test_u8_roundtrip(self):
        x = np.random.RandomState(1).randn(70000).astype(np.float32) * 5
        out = compression.decompress(
            compression.compress(x, compression.UNIFORM8BIT),
            compression.UNIFORM8BIT, x.size)
        # blockwise 8-bit: error bounded by scale/2 = max|block|/254
        err = np.abs(out - x).max()
        assert err <= np.abs(x).max() / 127
        assert out.dtype == np.float32

    def test_u8_odd_sizes_and_zeros(self):
        for n in (1, 255, 256, 257, 5000):
            x = np.zeros(n, np.float32)
            out = compression.decompress(
                compression.compress(x, compression.UNIFORM8BIT),
                compression.UNIFORM8BIT, n)
            np.testing.assert_array_equal(out, x)

    def test_adaptive_dispatch(self):
        assert compression.adaptive_codec(2 ** 16) == compression.FLOAT16
        assert (compression.adaptive_codec(2 ** 16 + 1)
                == compression.UNIFORM8BIT)

    def test_pack_unpack(self):
        x = np.random.RandomState(2).randn(40, 5).astype(np.float32)
        flat, codec = compression.unpack_array(
            compression.pack_array(x, compression.FLOAT16))
        assert codec == compression.FLOAT16
        np.testing.assert_allclose(flat, x.reshape(-1), rtol=2e-3, atol=1e-4)


class TestProgress:
    def test_aggregation_and_readiness(self, swarm3):
        trackers = [ProgressTracker(n, "run", target_batch_size=64,
                                    min_refresh_period=0.0)
                    for n in swarm3]
        trackers[0].report_local_progress(0, 30, force=True)
        trackers[1].report_local_progress(0, 30, force=True)
        g = trackers[2].global_progress(force_refresh=True)
        assert g.samples_accumulated == 30 + 30  # tracker2 itself has 0
        assert g.num_peers >= 2
        assert not g.ready_to_update
        trackers[2].report_local_progress(0, 10, force=True)
        g = trackers[0].global_progress(force_refresh=True)
        assert g.samples_accumulated >= 64
        assert g.ready_to_update

    def test_epoch_is_max(self, swarm3):
        trackers = [ProgressTracker(n, "run2", target_batch_size=1000,
                                    min_refresh_period=0.0)
                    for n in swarm3]
        trackers[0].report_local_progress(2, 5, force=True)
        trackers[1].report_local_progress(1, 5, force=True)
        g = trackers[2].global_progress(force_refresh=True)
        # max over peers, WITHIN the plausible-lead bound: claims may
        # lead the local epoch by at most max_epoch_lead (default 2) —
        # the epoch clock cannot be stolen by one absurd signed claim
        # (tests/test_screening.py TestProgressLeadBound pins the
        # clamp-vs-strike split)
        assert g.epoch == 2
        # samples counted only for peers at the max epoch
        assert g.samples_accumulated == 5
        trackers[0].report_local_progress(9, 5, force=True)
        g = trackers[2].global_progress(force_refresh=True)
        assert g.epoch == 2  # lead 9 > 2: clamped in the aggregate


class TestMatchmaking:
    def test_three_peers_agree(self, swarm3):
        groups = run_threads([
            (lambda n=n: make_group(n, "mm", epoch=0, weight=1.0,
                                    matchmaking_time=3.0, min_group_size=3))
            for n in swarm3])
        assert all(g is not None for g in groups)
        hashes = {g.group_hash for g in groups}
        assert len(hashes) == 1
        assert sorted(g.my_index for g in groups) == [0, 1, 2]
        assert all(g.size == 3 for g in groups)


class TestAllReduce:
    def _weighted_mean(self, tensors_per_peer, weights):
        flats = [flatten_tensors(t) for t in tensors_per_peer]
        num = sum(f * w for f, w in zip(flats, weights))
        return num / sum(weights)

    def test_weighted_average_exact(self, swarm3):
        rng = np.random.RandomState(3)
        shapes = [(33,), (8, 9), (5,)]
        tensors = [[rng.randn(*s).astype(np.float32) for s in shapes]
                   for _ in swarm3]
        weights = [1.0, 2.0, 5.0]

        def peer(i):
            g = make_group(swarm3[i], "ar", epoch=0, weight=weights[i],
                           matchmaking_time=3.0, min_group_size=3)
            assert g is not None and g.size == 3
            return run_allreduce(swarm3[i], g, "ar", 0, tensors[i],
                                 weight=weights[i], allreduce_timeout=10.0,
                                 codec=compression.NONE)

        results = run_threads([lambda i=i: peer(i) for i in range(3)])
        expected_flat = self._weighted_mean(tensors, weights)
        expected = unflatten_tensors(expected_flat, tensors[0])
        for res in results:
            for got, want in zip(res, expected):
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_compressed_average_close(self, swarm3):
        rng = np.random.RandomState(4)
        tensors = [[rng.randn(3000).astype(np.float32)] for _ in swarm3]

        def peer(i):
            g = make_group(swarm3[i], "arc", epoch=1, weight=1.0,
                           matchmaking_time=3.0, min_group_size=3)
            return run_allreduce(swarm3[i], g, "arc", 1, tensors[i],
                                 weight=1.0, allreduce_timeout=10.0)

        results = run_threads([lambda i=i: peer(i) for i in range(3)])
        expected = self._weighted_mean(tensors, [1.0] * 3)
        for res in results:
            np.testing.assert_allclose(res[0], expected, rtol=5e-3,
                                       atol=5e-3)

    def test_lossy_rounds_are_byte_identical(self, swarm3):
        """Part owners apply the same compressed wire bytes they broadcast,
        so all members end a lossy round with byte-identical values (the
        precondition for 'identical updates keep peers bit-synchronized')."""
        rng = np.random.RandomState(11)
        tensors = [[rng.randn(90000).astype(np.float32)] for _ in swarm3]

        def peer(i):
            g = make_group(swarm3[i], "arb", epoch=4, weight=1.0,
                           matchmaking_time=3.0, min_group_size=3)
            return run_allreduce(swarm3[i], g, "arb", 4, tensors[i],
                                 weight=1.0, allreduce_timeout=10.0,
                                 codec=compression.UNIFORM8BIT)

        results = run_threads([lambda i=i: peer(i) for i in range(3)])
        for res in results[1:]:
            np.testing.assert_array_equal(res[0], results[0][0])

    def test_dead_sender_leaves_gather_budget(self, swarm3):
        """One dead group member must not burn the whole round budget in
        the reduce phase: survivors still exchange their averaged parts in
        the gather phase (per-sender timeout + split budget)."""
        rng = np.random.RandomState(12)
        tensors = [[rng.randn(300).astype(np.float32)] for _ in swarm3]

        def peer(i):
            g = make_group(swarm3[i], "arg", epoch=5, weight=1.0,
                           matchmaking_time=3.0, min_group_size=3)
            assert g is not None and g.size == 3
            if i == 2:
                return g, None  # dies silently after matchmaking
            res = run_allreduce(swarm3[i], g, "arg", 5, tensors[i],
                                weight=1.0, allreduce_timeout=6.0,
                                sender_timeout=1.0,
                                codec=compression.NONE)
            return g, res

        results = run_threads([lambda i=i: peer(i) for i in range(3)])
        group = results[0][0]
        flats = [flatten_tensors(t) for t in tensors]
        slices = _part_slices(flats[0].size, 3)
        member_ids = [m.peer_id for m in group.members]
        live_avg = (flats[0] + flats[1]) / 2
        for i in (0, 1):
            _, res = results[i]
            got = flatten_tensors(res)
            other = 1 - i
            other_part = member_ids.index(swarm3[other].peer_id)
            lo, hi = slices[other_part]
            # the *other survivor's* part arrived via gather — under the old
            # shared deadline the stalled reduce left gather no budget and
            # this stayed at the local value
            np.testing.assert_allclose(got[lo:hi], live_avg[lo:hi],
                                       rtol=1e-5, atol=1e-6)

    def test_chunked_parts_average_exact(self, swarm3):
        """Parts larger than chunk_elems travel as multiple independently
        signed+compressed frames (flagship-scale parts exceed the daemon's
        64 MiB frame cap; VERDICT r3 next #2). Force multi-chunk with a
        tiny chunk_elems and check exactness + the complete flag."""
        rng = np.random.RandomState(21)
        # 3 owners, ~433 elems/part, chunk_elems=100 -> 5 chunks/part
        tensors = [[rng.randn(1300).astype(np.float32)] for _ in swarm3]
        weights = [1.0, 3.0, 0.5]
        reports = [dict() for _ in swarm3]

        def peer(i):
            g = make_group(swarm3[i], "arch", epoch=7, weight=weights[i],
                           matchmaking_time=3.0, min_group_size=3)
            assert g is not None and g.size == 3
            return run_allreduce(swarm3[i], g, "arch", 7, tensors[i],
                                 weight=weights[i], allreduce_timeout=10.0,
                                 codec=compression.NONE,
                                 report=reports[i], chunk_elems=100)

        results = run_threads([lambda i=i: peer(i) for i in range(3)])
        expected = self._weighted_mean(tensors, weights)
        for rep, res in zip(reports, results):
            assert rep["complete"]
            np.testing.assert_allclose(flatten_tensors(res), expected,
                                       rtol=1e-5, atol=1e-6)

    def test_chunked_lossy_rounds_byte_identical(self, swarm3):
        """The per-chunk owner-applies-wire-bytes path preserves the
        byte-identity guarantee under chunking + u8 compression."""
        rng = np.random.RandomState(22)
        tensors = [[rng.randn(4096).astype(np.float32)] for _ in swarm3]

        def peer(i):
            g = make_group(swarm3[i], "archb", epoch=8, weight=1.0,
                           matchmaking_time=3.0, min_group_size=3)
            return run_allreduce(swarm3[i], g, "archb", 8, tensors[i],
                                 weight=1.0, allreduce_timeout=10.0,
                                 codec=compression.UNIFORM8BIT,
                                 chunk_elems=512)

        results = run_threads([lambda i=i: peer(i) for i in range(3)])
        for res in results[1:]:
            np.testing.assert_array_equal(res[0], results[0][0])

    def test_peer_dies_after_matchmaking(self, swarm3):
        """A group member that never shows up for the all-reduce is dropped:
        survivors finish fast with the dead peer's weight excluded on their
        own parts (hivemind's ban-and-proceed, arguments.py:69-74)."""
        rng = np.random.RandomState(5)
        tensors = [[rng.randn(300).astype(np.float32)] for _ in swarm3]

        def peer(i):
            g = make_group(swarm3[i], "ard", epoch=2, weight=1.0,
                           matchmaking_time=3.0, min_group_size=3)
            assert g is not None and g.size == 3
            if i == 2:
                return g, None  # dies silently after matchmaking
            res = run_allreduce(swarm3[i], g, "ard", 2, tensors[i],
                                weight=1.0, allreduce_timeout=2.5,
                                codec=compression.NONE)
            return g, res

        t0 = time.monotonic()
        results = run_threads([lambda i=i: peer(i) for i in range(3)])
        assert time.monotonic() - t0 < 20
        group = results[0][0]
        flats = [flatten_tensors(t) for t in tensors]
        slices = _part_slices(flats[0].size, 3)
        dead_id = swarm3[2].peer_id
        member_ids = [m.peer_id for m in group.members]
        dead_part = member_ids.index(dead_id)
        for i in (0, 1):
            _, res = results[i]
            got = flatten_tensors(res)
            my_part = member_ids.index(swarm3[i].peer_id)
            for k, (lo, hi) in enumerate(slices):
                if k == dead_part:
                    # owner died: local fallback (and we can't know what the
                    # dead owner would have sent) — value stays local
                    np.testing.assert_allclose(got[lo:hi], flats[i][lo:hi])
                elif k == my_part:
                    # we own it: average of the two live peers
                    want = (flats[0][lo:hi] + flats[1][lo:hi]) / 2
                    np.testing.assert_allclose(got[lo:hi], want, rtol=1e-5)


class TestClientMode:
    """Outbound-only peers (reference arguments.py:89-92) must still get
    averaged results — via the pull (mailbox) half of the data plane."""

    def test_client_receives_averaged_grads(self):
        nodes = make_swarm(2)
        client = DHT(initial_peers=[nodes[0].visible_address],
                     identity=Identity.generate(), client_mode=True,
                     rpc_timeout=2.0)
        rng = np.random.RandomState(7)
        all_nodes = nodes + [client]
        tensors = [[rng.randn(120).astype(np.float32)] for _ in all_nodes]

        def peer(i):
            cm = all_nodes[i].client_mode
            g = make_group(all_nodes[i], "cmar", epoch=0, weight=1.0,
                           matchmaking_time=3.0, min_group_size=3,
                           client_mode=cm)
            assert g is not None and g.size == 3
            return run_allreduce(all_nodes[i], g, "cmar", 0, tensors[i],
                                 weight=1.0, allreduce_timeout=10.0,
                                 codec=compression.NONE)

        try:
            results = run_threads([lambda i=i: peer(i) for i in range(3)])
            expected = sum(flatten_tensors(t) for t in tensors) / 3
            for res in results:
                np.testing.assert_allclose(flatten_tensors(res), expected,
                                           rtol=1e-5, atol=1e-6)
        finally:
            client.shutdown()
            for n in nodes:
                n.shutdown()

    def test_client_pulls_chunked_parts_from_mailboxes(self):
        """A client-mode peer pulls a multi-chunk averaged part via the
        per-chunk mailbox tags (chunked gather, VERDICT r3 next #2)."""
        nodes = make_swarm(2)
        client = DHT(initial_peers=[nodes[0].visible_address],
                     identity=Identity.generate(), client_mode=True,
                     rpc_timeout=2.0)
        rng = np.random.RandomState(23)
        all_nodes = nodes + [client]
        # 2 owners, 600 elems/part, chunk_elems=128 -> 5 chunks/part
        tensors = [[rng.randn(1200).astype(np.float32)]
                   for _ in all_nodes]

        def peer(i):
            cm = all_nodes[i].client_mode
            g = make_group(all_nodes[i], "cmch", epoch=1, weight=1.0,
                           matchmaking_time=3.0, min_group_size=3,
                           client_mode=cm)
            assert g is not None and g.size == 3
            return run_allreduce(all_nodes[i], g, "cmch", 1, tensors[i],
                                 weight=1.0, allreduce_timeout=10.0,
                                 codec=compression.NONE, chunk_elems=128)

        try:
            results = run_threads([lambda i=i: peer(i) for i in range(3)])
            expected = sum(flatten_tensors(t) for t in tensors) / 3
            for res in results:
                np.testing.assert_allclose(flatten_tensors(res), expected,
                                           rtol=1e-5, atol=1e-6)
        finally:
            client.shutdown()
            for n in nodes:
                n.shutdown()

    def test_client_downloads_state(self):
        nodes = make_swarm(2)
        client = DHT(initial_peers=[nodes[0].visible_address],
                     identity=Identity.generate(), client_mode=True,
                     rpc_timeout=2.0)
        arrays = [np.linspace(0, 1, 20).astype(np.float32)]
        server = StateServer(nodes[0], "cmst", lambda: (3, arrays),
                             announce_period=0.2)
        server.start()
        try:
            deadline = time.monotonic() + 10
            result = None
            while result is None and time.monotonic() < deadline:
                result = load_state_from_peers(client, "cmst", timeout=3.0)
            assert result is not None
            epoch, got = result
            assert epoch == 3
            np.testing.assert_allclose(got[0], arrays[0], atol=1e-3)
        finally:
            server.stop()
            client.shutdown()
            for n in nodes:
                n.shutdown()


class TestStateTransfer:
    def test_roundtrip_serialization(self):
        arrays = [np.random.RandomState(6).randn(10, 3).astype(np.float32),
                  np.arange(7, dtype=np.int32),
                  np.array([1, 200, 255], np.uint8)]
        epoch, out = deserialize_state(serialize_state(5, arrays))
        assert epoch == 5
        np.testing.assert_allclose(out[0], arrays[0], rtol=2e-3, atol=1e-3)
        np.testing.assert_array_equal(out[1], arrays[1])
        np.testing.assert_array_equal(out[2], arrays[2])
        assert out[1].dtype == np.int32 and out[2].dtype == np.uint8

    def test_download_from_server(self, swarm3):
        arrays = [np.full((4, 4), 2.5, np.float32),
                  np.array([9], np.int32)]
        server = StateServer(swarm3[0], "st", lambda: (7, arrays),
                             announce_period=0.2)
        server.start()
        try:
            deadline = time.monotonic() + 10
            result = None
            while result is None and time.monotonic() < deadline:
                result = load_state_from_peers(swarm3[2], "st", timeout=3.0)
            assert result is not None
            epoch, got = result
            assert epoch == 7
            np.testing.assert_allclose(got[0], arrays[0], atol=1e-3)
            np.testing.assert_array_equal(got[1], arrays[1])
        finally:
            server.stop()

    def test_no_server_returns_none(self, swarm3):
        assert load_state_from_peers(swarm3[1], "empty", timeout=1.0) is None

    def test_stale_advertisement_still_served(self, swarm3):
        """Advertised epochs are stale lower bounds: a client demanding a
        newer epoch than any advertisement must still download and get the
        freshest state actually held (previously it gave up immediately)."""
        arrays = [np.full((8,), 1.5, np.float32)]
        server = StateServer(swarm3[0], "stale", lambda: (5, arrays),
                             announce_period=0.2)
        server.start()
        try:
            deadline = time.monotonic() + 10
            result = None
            while result is None and time.monotonic() < deadline:
                result = load_state_from_peers(swarm3[1], "stale",
                                               min_epoch=9, timeout=3.0)
            assert result is not None
            assert result[0] == 5  # freshest available, below min_epoch
        finally:
            server.stop()

    def test_announce_refreshes_on_epoch_change(self, swarm3):
        """The server re-announces as soon as its epoch advances, not a
        full announce_period later (stragglers resync promptly)."""
        epoch_box = {"e": 0}
        arrays = [np.zeros((4,), np.float32)]
        server = StateServer(swarm3[0], "fresh",
                             lambda: (epoch_box["e"], arrays),
                             announce_period=60.0,
                             epoch_fn=lambda: epoch_box["e"])
        server.start()
        try:
            def advertised_epoch():
                entries = swarm3[2].get("fresh_state_servers") or {}
                return max((item.value.get("epoch", -1)
                            for item in entries.values()), default=None)

            deadline = time.monotonic() + 10
            while advertised_epoch() != 0 and time.monotonic() < deadline:
                time.sleep(0.1)
            assert advertised_epoch() == 0
            epoch_box["e"] = 3
            deadline = time.monotonic() + 10
            while advertised_epoch() != 3 and time.monotonic() < deadline:
                time.sleep(0.1)
            assert advertised_epoch() == 3  # well before announce_period
        finally:
            server.stop()


def _make_collab_peer(dht, cfg, seed=0):
    import jax
    import jax.numpy as jnp

    from dalle_tpu.swarm.optimizer import CollaborativeOptimizer
    from dalle_tpu.training.steps import TrainState, make_apply_step

    params = {"w": jnp.ones((16,)) * 0.5, "b": jnp.zeros((4,))}
    tx = optax.sgd(0.1)
    state = TrainState.create(params, tx)
    opt = CollaborativeOptimizer(dht, cfg, state, jax.jit(make_apply_step(tx)))
    opt.tracker.min_refresh_period = 0.05
    return opt


class TestCollaborativeOptimizer:
    def test_two_peers_converge_to_identical_params(self):
        nodes = make_swarm(2)
        cfg = CollabConfig(run_id="co1", target_batch_size=32,
                           matchmaking_time=2.0, allreduce_timeout=10.0,
                           averaging_timeout=20.0, average_state_every=0,
                           grad_compression="none")
        opts = [_make_collab_peer(n, cfg) for n in nodes]
        try:
            import jax.numpy as jnp

            # a swarm-speaking peer arms the whole trust plane: no
            # configuration switches any of it off
            for opt in opts:
                for plane in ("_screen", "_gossip", "_auditor",
                              "_audit_policy", "_repair", "_evidence"):
                    assert getattr(opt, plane) is not None, plane
                assert opt._gossip.verifier is not None

            def run_peer(i):
                opt = opts[i]
                grads = {"w": jnp.full((16,), float(i + 1)),
                         "b": jnp.full((4,), -1.0)}
                deadline = time.monotonic() + 30
                while opt.local_epoch < 1 and time.monotonic() < deadline:
                    opt.step(grads, batch_size=8)
                    time.sleep(0.05)
                return opt.local_epoch

            epochs = run_threads([lambda i=i: run_peer(i) for i in range(2)])
            assert all(e >= 1 for e in epochs)
            p0 = np.asarray(opts[0].state.params["w"])
            p1 = np.asarray(opts[1].state.params["w"])
            np.testing.assert_allclose(p0, p1, rtol=1e-5, atol=1e-6)
            # params actually moved
            assert not np.allclose(p0, 0.5)
        finally:
            for o in opts:
                o.shutdown()
            for n in nodes:
                n.shutdown()

    def test_state_averaging_requantizes_moments(self):
        """Divergent 8-bit moments must be dequantized, averaged, and
        requantized — averaging absmax scales against foreign codes would
        corrupt them (VERDICT r1 weak #4)."""
        import jax
        import jax.numpy as jnp

        from dalle_tpu.ops.quant import dequantize_blockwise, \
            quantize_blockwise
        from dalle_tpu.optim.lamb8bit import lamb8bit
        from dalle_tpu.swarm.optimizer import CollaborativeOptimizer
        from dalle_tpu.training.steps import TrainState, make_apply_step

        nodes = make_swarm(2)
        cfg = CollabConfig(run_id="sa1", target_batch_size=10 ** 9,
                           matchmaking_time=2.0, allreduce_timeout=10.0,
                           averaging_timeout=20.0, average_state_every=1,
                           state_compression="none", grad_compression="none")
        tx = lamb8bit(learning_rate=1e-3, min_8bit_size=512, block_size=256)
        moments = [0.2, 0.6]
        opts = []
        for i, node in enumerate(nodes):
            params = {"w": jnp.full((1024,), 0.5, jnp.float32)}
            state = TrainState.create(params, tx)
            opt_state = state.opt_state._replace(
                mu={"w": quantize_blockwise(
                    jnp.full((1024,), moments[i]), 256, signed=True)})
            state = state.replace(opt_state=opt_state)
            opt = CollaborativeOptimizer(node, cfg, state,
                                         jax.jit(make_apply_step(tx)),
                                         serve_state=False)
            opt.tracker.min_refresh_period = 0.05
            opts.append(opt)
        try:
            run_threads([lambda o=o: o._average_state() for o in opts])
            mus = [np.asarray(dequantize_blockwise(
                o.state.opt_state.mu["w"])) for o in opts]
            want = np.full((1024,), np.mean(moments), np.float32)
            for mu in mus:
                np.testing.assert_allclose(mu, want, rtol=0.02, atol=0.005)
            # lossless round: peers end byte-identical
            np.testing.assert_array_equal(
                np.asarray(opts[0].state.opt_state.mu["w"].codes),
                np.asarray(opts[1].state.opt_state.mu["w"].codes))
            # params untouched by corruption: both still 0.5
            for o in opts:
                np.testing.assert_allclose(
                    np.asarray(o.state.params["w"]), 0.5, atol=1e-6)
        finally:
            for o in opts:
                o.shutdown()
            for n in nodes:
                n.shutdown()

    def test_straggler_resyncs_from_peers(self):
        nodes = make_swarm(2)
        cfg = CollabConfig(run_id="co2", target_batch_size=16,
                           matchmaking_time=1.0, allreduce_timeout=5.0,
                           averaging_timeout=10.0, average_state_every=0,
                           grad_compression="none")
        fast = _make_collab_peer(nodes[0], cfg)
        try:
            import jax.numpy as jnp
            grads = {"w": jnp.ones((16,)), "b": jnp.ones((4,))}
            deadline = time.monotonic() + 20
            while fast.local_epoch < 1 and time.monotonic() < deadline:
                fast.step(grads, batch_size=16)
                time.sleep(0.02)
            assert fast.local_epoch >= 1

            late = _make_collab_peer(nodes[1], cfg)
            try:
                # one step is enough: sees global epoch ahead and resyncs
                deadline = time.monotonic() + 20
                while late.local_epoch < 1 and time.monotonic() < deadline:
                    late.step(grads, batch_size=1)
                    time.sleep(0.05)
                assert late.local_epoch >= 1
                np.testing.assert_allclose(
                    np.asarray(late.state.params["w"]),
                    np.asarray(fast.state.params["w"]), atol=2e-3)
            finally:
                late.shutdown()
        finally:
            fast.shutdown()
            for n in nodes:
                n.shutdown()


class TestRelayAllReduce:
    def test_punched_peers_allreduce_off_relay(self):
        """VERDICT r3 next #7 done-criterion: two listener-less peers
        PUNCH a direct link, then complete a full collaborative epoch —
        and the relay forwards (almost) none of the data-plane bytes."""
        import threading

        from dalle_tpu.swarm import DHT

        relay = DHT(rpc_timeout=2.0)
        clients = [DHT(client_mode=True, rpc_timeout=2.0,
                       initial_peers=[relay.visible_address])
                   for _ in range(2)]
        for c in clients:
            assert c.attach_relay(relay.visible_address)

        results = {}

        def punch(i):
            results[i] = clients[i].punch(
                clients[1 - i].visible_address, timeout=10.0)

        ts = [threading.Thread(target=punch, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(20)
        assert results.get(0) and results.get(1), results

        cfg = CollabConfig(run_id="pnch", target_batch_size=32,
                           matchmaking_time=2.0, allreduce_timeout=10.0,
                           averaging_timeout=20.0, average_state_every=0,
                           grad_compression="none")
        import jax
        import jax.numpy as jnp

        from dalle_tpu.swarm.optimizer import CollaborativeOptimizer
        from dalle_tpu.training.steps import TrainState, make_apply_step

        opts = []
        for dht in clients:
            params = {"w": jnp.ones((16,)) * 0.5}
            tx = optax.sgd(0.1)
            opt = CollaborativeOptimizer(
                dht, cfg, TrainState.create(params, tx),
                jax.jit(make_apply_step(tx)),
                client_mode=True, serve_state=False)
            opt.tracker.min_refresh_period = 0.05
            opts.append(opt)

        try:
            base = relay.relay_traffic_served

            def run_peer(i):
                opt = opts[i]
                grads = {"w": jnp.full((16,), float(i + 1))}
                deadline = time.monotonic() + 30
                while opt.local_epoch < 1 and time.monotonic() < deadline:
                    opt.step(grads, batch_size=8)
                    time.sleep(0.05)
                return opt.local_epoch

            epochs = run_threads([lambda i=i: run_peer(i)
                                  for i in range(2)])
            assert all(e >= 1 for e in epochs), epochs
            p0 = np.asarray(opts[0].state.params["w"])
            p1 = np.asarray(opts[1].state.params["w"])
            np.testing.assert_allclose(p0, p1, rtol=1e-5, atol=1e-6)
            assert not np.allclose(p0, 0.5)
            # the data plane rode the punched link: the relay forwarded
            # no frames for the whole epoch (matchmaking confirmations
            # travel DHT stores + mailbox posts, not relay forwards)
            assert relay.relay_traffic_served == base, (
                relay.relay_traffic_served, base)
        finally:
            for o in opts:
                o.shutdown()
            for n in clients + [relay]:
                n.shutdown()

    def test_two_listenerless_peers_allreduce_through_relay(self):
        """VERDICT r2 next #3 done-criterion: two client-mode peers (no
        listeners at all) complete a full gradient all-reduce THROUGH a
        routable relay peer — the relay forwards contribution pushes,
        averaged-part pushes, and leader confirmations down each peer's
        persistent attachment."""
        from dalle_tpu.swarm import DHT

        relay = DHT(rpc_timeout=2.0)
        clients = [DHT(client_mode=True, rpc_timeout=2.0,
                       initial_peers=[relay.visible_address])
                   for _ in range(2)]
        for c in clients:
            assert c.attach_relay(relay.visible_address)
            assert "/" in c.visible_address

        cfg = CollabConfig(run_id="rly", target_batch_size=32,
                           matchmaking_time=2.0, allreduce_timeout=10.0,
                           averaging_timeout=20.0, average_state_every=0,
                           grad_compression="none")
        # client_mode=True: no all-reduce push listener... except the
        # relay attachment makes these peers fully addressable
        import jax
        import jax.numpy as jnp

        from dalle_tpu.swarm.optimizer import CollaborativeOptimizer
        from dalle_tpu.training.steps import TrainState, make_apply_step

        opts = []
        for dht in clients:
            params = {"w": jnp.ones((16,)) * 0.5, "b": jnp.zeros((4,))}
            tx = optax.sgd(0.1)
            state = TrainState.create(params, tx)
            opt = CollaborativeOptimizer(
                dht, cfg, state, jax.jit(make_apply_step(tx)),
                client_mode=True, serve_state=False)
            opt.tracker.min_refresh_period = 0.05
            opts.append(opt)

        try:
            def run_peer(i):
                opt = opts[i]
                grads = {"w": jnp.full((16,), float(i + 1)),
                         "b": jnp.full((4,), -1.0)}
                deadline = time.monotonic() + 30
                while opt.local_epoch < 1 and time.monotonic() < deadline:
                    opt.step(grads, batch_size=8)
                    time.sleep(0.05)
                return opt.local_epoch

            epochs = run_threads([lambda i=i: run_peer(i) for i in range(2)])
            assert all(e >= 1 for e in epochs), epochs
            p0 = np.asarray(opts[0].state.params["w"])
            p1 = np.asarray(opts[1].state.params["w"])
            np.testing.assert_allclose(p0, p1, rtol=1e-5, atol=1e-6)
            assert not np.allclose(p0, 0.5)  # a real averaged update ran
            # both relay-attached peers owned parts (addr non-empty), so
            # this was a genuine two-owner butterfly, not a solo epoch
        finally:
            for opt in opts:
                opt.shutdown()
            for n in clients + [relay]:
                n.shutdown()
