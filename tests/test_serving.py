"""Continuous-batching engine tests.

The load-bearing invariant: the per-slot-position rewrite of
``decode_step`` must not change numerics — a request decoded by the
engine emits EXACTLY the codes ``generate_images`` samples for the same
key/SamplingConfig. Pinned two ways: a single-slot engine (bit-identical
math, guaranteed), and a multi-slot ragged run where co-tenant slots
share the batch (XLA's batch-tiling wobble is ~1e-6 on logits; the
sampled codes stay exact for these pinned seeds).

Plus: slot recycling, KV-budget admission, metrics accounting, the
pixel-overlap worker, the HTTP front-end, and the thread-lifecycle
discipline (every serving thread daemonized AND reaped by stop()).
"""

import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_init import init_params
from dalle_tpu.config import ServingConfig, tiny_model_config
from dalle_tpu.models.dalle import DALLE
from dalle_tpu.models.decode import (SamplingConfig, bucket_bounds,
                                     generate_images, init_cache,
                                     resolve_buckets)
from dalle_tpu.serving import engine as engine_mod
from dalle_tpu.serving.engine import DecodeEngine
from dalle_tpu.serving.metrics import ServingMetrics, percentiles
from dalle_tpu.serving.pixels import PixelPipeline
from dalle_tpu.serving.scheduler import SlotScheduler, kv_bytes_per_slot
from dalle_tpu.serving.server import ServingHTTPServer

SAM = SamplingConfig(temperature=1.0, top_k=8)

# one flat-cache config + one cycle-structured (scan + wconv) config so
# both decode_step cache layouts run the per-slot path
FLAT = dict(attn_types=("axial_row", "axial_col"), depth=2)
CYCLE = dict(attn_types=("axial_row", "axial_col", "axial_row",
                         "axial_row"), depth=6, shared_block_cycle=4,
             final_conv_block=True, conv_kernel=3)


@pytest.fixture(scope="module")
def flat_setup():
    cfg = tiny_model_config(**FLAT)
    params = init_params(DALLE(cfg), jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def cycle_setup():
    cfg = tiny_model_config(**CYCLE)
    params = init_params(DALLE(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _texts(cfg, n, seed=100):
    return [np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + i), (cfg.text_seq_len,), 2,
        cfg.vocab_text)) for i in range(n)]


def _solo_reference(params, cfg, text, key, buckets):
    codes = generate_images(params, cfg, jnp.asarray(text[None]), key,
                            SAM, buckets=buckets)
    return np.asarray(codes)[0]


class TestEngineParity:
    def test_single_slot_matches_generate_images(self, flat_setup):
        """THE acceptance invariant: one request through the engine ==
        ``generate_images`` for the same seed, code for code. At
        n_slots=1 the per-slot step is bit-identical to the lockstep
        step (same shapes, same ops), so this can never flake."""
        cfg, params = flat_setup
        text = _texts(cfg, 1)[0]
        key = jax.random.PRNGKey(1000)
        ref = _solo_reference(params, cfg, text, key, buckets=4)
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4),
                              sampling=SAM).start()
        try:
            got = engine.submit(text, key).result(timeout=300)
        finally:
            engine.stop()
        np.testing.assert_array_equal(got["codes"], ref)
        assert got["latency_s"] >= got["ttft_s"] >= 0

    def test_single_slot_matches_on_cycle_layout(self, cycle_setup):
        """Same invariant through the cycle-structured cache carry (the
        flagship's layout): scatter writes into the (reps, cycle, B, T,
        H*d) body + the wconv slot."""
        cfg, params = cycle_setup
        text = _texts(cfg, 1)[0]
        key = jax.random.PRNGKey(2000)
        ref = _solo_reference(params, cfg, text, key, buckets=1)
        engine = DecodeEngine(
            params, cfg,
            ServingConfig(n_slots=1, steps_per_call=4, decode_buckets=1),
            sampling=SAM).start()
        try:
            got = engine.submit(text, key).result(timeout=300)
        finally:
            engine.stop()
        np.testing.assert_array_equal(got["codes"], ref)

    def test_ragged_cotenancy_and_recycling_exact(self, flat_setup):
        """5 requests through 2 slots: admissions are ragged (mid-flight
        of other requests), every slot is recycled at least once, and
        EVERY request still emits its solo-reference codes — co-tenants
        cannot perturb each other's samples (pinned seeds)."""
        cfg, params = flat_setup
        texts = _texts(cfg, 5)
        keys = [jax.random.PRNGKey(1000 + i) for i in range(5)]
        refs = [_solo_reference(params, cfg, t, k, buckets=4)
                for t, k in zip(texts, keys)]
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=2, steps_per_call=4),
                              sampling=SAM).start()
        try:
            handles = []
            for i, (t, k) in enumerate(zip(texts, keys)):
                handles.append(engine.submit(t, k))
                time.sleep(0.01 * i)  # stagger: admission lands mid-chunk
            results = [h.result(timeout=300) for h in handles]
        finally:
            engine.stop()
        for res, ref in zip(results, refs):
            np.testing.assert_array_equal(res["codes"], ref)
        stats = engine.stats()
        assert stats["completed"] == 5
        # 5 requests > 2 slots: recycling necessarily happened
        assert stats["admitted"] == 5 and stats["n_slots"] == 2
        assert 0 < stats["mean_occupancy"] <= 1.0


class TestSchedulerAndBuckets:
    def test_engine_reuses_resolve_buckets(self, flat_setup):
        """The engine's bucket count comes FROM resolve_buckets (the
        measured generate_images policy), not a re-derivation."""
        cfg, params = flat_setup
        for n_slots in (1, 4, 8, 12):
            engine = DecodeEngine(params, cfg,
                                  ServingConfig(n_slots=n_slots))
            assert engine.n_buckets == resolve_buckets(None, n_slots)
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=4, decode_buckets=2))
        assert engine.n_buckets == resolve_buckets(2, 4) == 2

    def test_bucket_bounds_match_generate_images(self):
        # ONE definition in models/decode.py, used by BOTH the lockstep
        # scan and the engine's per-chunk visible choice
        assert bucket_bounds(32, 4) == [8, 16, 24, 32]
        assert bucket_bounds(1280, 2) == [640, 1280]
        assert bucket_bounds(32, 1) == [32]

    def test_scheduler_grant(self):
        sched = SlotScheduler(4, bytes_per_slot=100)
        assert sched.max_live == 4
        assert sched.grant(queued=10, live=0, free=4) == 4
        assert sched.grant(queued=1, live=2, free=2) == 1
        assert sched.grant(queued=0, live=2, free=2) == 0
        assert sched.grant(queued=5, live=4, free=0) == 0

    def test_scheduler_admit_burst(self):
        """admit_burst caps the PER-BOUNDARY batch: a cold start against
        a deep queue admits over several chunk boundaries instead of one
        outsized scatter."""
        sched = SlotScheduler(8, bytes_per_slot=100, admit_burst=2)
        assert sched.grant(queued=10, live=0, free=8) == 2
        assert sched.grant(queued=1, live=0, free=8) == 1
        # the burst never lifts the other caps
        assert sched.grant(queued=10, live=7, free=1) == 1
        assert SlotScheduler(8, 100, admit_burst=None).grant(10, 0, 8) == 8

    def test_scheduler_kv_budget(self):
        one_mb = 2 ** 20
        sched = SlotScheduler(8, bytes_per_slot=one_mb, kv_budget_mb=3)
        assert sched.max_live == 3
        assert sched.grant(queued=8, live=2, free=6) == 1
        # budget below one slot still admits one at a time
        assert SlotScheduler(8, one_mb, kv_budget_mb=0).max_live == 1
        # budget above n_slots clamps to n_slots
        assert SlotScheduler(2, one_mb, kv_budget_mb=100).max_live == 2

    def test_kv_bytes_per_slot_matches_cache(self, cycle_setup):
        cfg, _ = cycle_setup
        cache = init_cache(cfg, 1)
        expect = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(cache))
        assert kv_bytes_per_slot(cfg) == expect

    def test_kv_budget_caps_live_slots(self, flat_setup):
        """n_slots=4 but a budget worth ~2 slots: at most 2 requests are
        ever live, everything still completes via recycling."""
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=4, steps_per_call=4),
                              sampling=SAM)
        # tiny caches are ~100 KB/slot and the budget knob rounds whole
        # MB, so inject a scheduler with a synthetic 1 MB/slot size: a
        # 2 MB budget then caps live slots at 2 of the 4
        engine.scheduler = SlotScheduler(4, bytes_per_slot=2 ** 20,
                                         kv_budget_mb=2)
        assert engine.scheduler.max_live == 2
        engine.start()
        max_live_seen = 0
        try:
            handles = [engine.submit(t, jax.random.PRNGKey(i))
                       for i, t in enumerate(_texts(cfg, 4))]
            while not all(h.done() for h in handles):
                live = sum(p is not None for p in engine._slots)
                max_live_seen = max(max_live_seen, live)
                time.sleep(0.005)
            for h in handles:
                assert h.result(timeout=10)["codes"].shape == \
                    (cfg.image_seq_len,)
        finally:
            engine.stop()
        assert max_live_seen <= 2
        assert engine.stats()["completed"] == 4


class TestEngineLifecycle:
    def test_submit_validates_and_bounds(self, flat_setup):
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, queue_capacity=1))
        with pytest.raises(ValueError):
            engine.submit(np.zeros(3, np.int32))
        engine.submit(np.zeros(cfg.text_seq_len, np.int32))
        with pytest.raises(RuntimeError):     # queue full
            engine.submit(np.zeros(cfg.text_seq_len, np.int32))
        engine.stop(drain=False)
        with pytest.raises(RuntimeError):     # stopped
            engine.submit(np.zeros(cfg.text_seq_len, np.int32))

    def test_stop_without_drain_cancels(self, flat_setup):
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg, ServingConfig(n_slots=1))
        handle = engine.submit(np.zeros(cfg.text_seq_len, np.int32))
        engine.stop(drain=False)              # never started: cancel path
        with pytest.raises(RuntimeError, match="cancelled"):
            handle.result(timeout=5)
        assert engine.stats()["cancelled"] == 1

    def test_threads_daemonized_and_reaped(self, flat_setup):
        """The test_thread_lifecycle invariant for the serving stack:
        engine + pixel worker threads are daemons while alive and gone
        after stop()."""
        cfg, params = flat_setup
        before = set(threading.enumerate())
        pipeline = PixelPipeline(lambda codes: {"images": np.zeros(
            (2, 2, 3), np.uint8)})
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4),
                              sampling=SAM,
                              pixel_pipeline=pipeline).start()
        handle = engine.submit(_texts(cfg, 1)[0], jax.random.PRNGKey(3))
        spawned = [t for t in threading.enumerate() if t not in before]
        assert spawned and all(t.daemon for t in spawned), \
            [t.name for t in spawned if not t.daemon]
        assert handle.result(timeout=300)["images"].shape == (2, 2, 3)
        engine.stop()                          # reaps pixel worker too
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and any(
                t.is_alive() for t in spawned):
            time.sleep(0.02)
        leaked = [t.name for t in spawned if t.is_alive()]
        assert not leaked, f"threads outlived stop(): {leaked}"


class TestHotLoop:
    """The r9 zero-sync loop's three load-bearing properties: one chunk
    executable serves every SamplingConfig, the device state is donated
    (the KV cache updates in place), and a novel temperature mid-run
    compiles nothing."""

    def test_chunk_executable_shared_across_sampling(self, flat_setup):
        """Two engines at different temperatures share ONE chunk
        executable: sampling knobs are traced operands, not compile
        keys — `_chunk_fn`'s lru key is (cfg, chunk, visible) and the
        underlying jit cache grows only with shapes/buckets."""
        cfg, params = flat_setup
        engine_mod._chunk_fn.cache_clear()
        text = _texts(cfg, 1)[0]

        def run_one(sampling, seed):
            engine = DecodeEngine(
                params, cfg, ServingConfig(n_slots=1, steps_per_call=4),
                sampling=sampling).start()
            try:
                return engine.submit(
                    text, jax.random.PRNGKey(seed)).result(timeout=300)
            finally:
                engine.stop()

        run_one(SamplingConfig(temperature=1.0, top_k=8), 0)
        info1 = engine_mod._chunk_fn.cache_info()
        bounds = bucket_bounds(cfg.total_seq_len, resolve_buckets(None, 1))
        sizes1 = {v: engine_mod._chunk_fn(cfg, 4, v)._cache_size()
                  for v in bounds}
        run_one(SamplingConfig(temperature=0.31, top_k=0, top_p=0.9), 1)
        info2 = engine_mod._chunk_fn.cache_info()
        sizes2 = {v: engine_mod._chunk_fn(cfg, 4, v)._cache_size()
                  for v in bounds}
        assert info2.misses == info1.misses, (
            "a second SamplingConfig built a NEW chunk program")
        assert sizes2 == sizes1, (
            f"a second SamplingConfig triggered XLA compiles: "
            f"{sizes1} -> {sizes2}")

    def test_temperature_change_midrun_zero_compiles(self, flat_setup):
        """A novel per-request temperature on a RUNNING engine triggers
        zero new compiles (the recompile-per-temperature wall the
        ROADMAP named)."""
        cfg, params = flat_setup
        texts = _texts(cfg, 2)
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=2, steps_per_call=4),
                              sampling=SAM).start()
        try:
            engine.submit(texts[0], jax.random.PRNGKey(0)).result(
                timeout=300)
            sizes1 = {v: engine_mod._chunk_fn(cfg, 4, v)._cache_size()
                      for v in engine._bounds}
            novel = SamplingConfig(temperature=0.427, top_k=5, top_p=0.8)
            engine.submit(texts[1], jax.random.PRNGKey(1),
                          sampling=novel).result(timeout=300)
            sizes2 = {v: engine_mod._chunk_fn(cfg, 4, v)._cache_size()
                      for v in engine._bounds}
        finally:
            engine.stop()
        assert sizes2 == sizes1, (
            f"novel temperature compiled: {sizes1} -> {sizes2}")

    def test_chunk_donates_state_buffers(self, flat_setup):
        """donate_argnums is live: the input EngineState's buffers (the
        KV cache above all) are DELETED after a chunk — the cache
        updates in place instead of reallocating per chunk."""
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=2, steps_per_call=2))
        old = engine._state
        fn = engine_mod._chunk_fn(cfg, 2, cfg.total_seq_len)
        engine._state = fn(params, old)
        jax.block_until_ready(engine._state.pos)
        donated = [old.pos, old.tokens, old.codes,
                   *jax.tree_util.tree_leaves(old.cache)]
        assert all(buf.is_deleted() for buf in donated), (
            "chunk inputs survived the call: donation is not happening")

    def test_admit_donates_and_batches(self, flat_setup):
        """Batched admission initializes K slots in ONE jitted call
        (a (K,) slot vector + (K, text_len) prefix block), also with
        the state donated."""
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=4, steps_per_call=2))
        texts = _texts(cfg, 3)
        keys = [np.asarray(jax.random.PRNGKey(i), np.uint32)
                for i in range(3)]
        pendings = [engine_mod._Pending(
            i, np.asarray(t, np.int32), k,
            engine_mod.RequestHandle(i), SamplingConfig(1.0, 8, 1.0))
            for i, (t, k) in enumerate(zip(texts, keys))]
        old = engine._state
        engine._admit_batch(pendings, [0, 2, 3])
        jax.block_until_ready(engine._state.pos)
        assert old.pos.is_deleted(), "admission did not donate the state"
        pos = np.asarray(engine._state.pos)
        assert pos[0] == 0 and pos[2] == 0 and pos[3] == 0
        assert pos[1] == cfg.total_seq_len       # untouched slot
        np.testing.assert_array_equal(
            np.asarray(engine._state.text)[[0, 2, 3]], np.stack(texts))
        np.testing.assert_array_equal(np.asarray(engine._state.temp),
                                      [1.0, 1.0, 1.0, 1.0])
        assert engine._pos_host[0] == 0 and engine._pos_host[1] == \
            cfg.total_seq_len

    def test_per_request_sampling_cotenancy_exact(self, flat_setup):
        """Per-request SamplingConfig end to end: three co-tenant
        requests with THREE different configs (the engine default, a
        greedy override, a top-p override) each reproduce their own
        generate_images solo reference exactly — one executable, three
        knob settings in flight at once."""
        cfg, params = flat_setup
        texts = _texts(cfg, 3)
        keys = [jax.random.PRNGKey(500 + i) for i in range(3)]
        sams = [SAM, SamplingConfig(temperature=0.0),
                SamplingConfig(temperature=1.0, top_k=0, top_p=0.7)]
        refs = [np.asarray(generate_images(
            params, cfg, jnp.asarray(t[None]), k, s, buckets=4))[0]
            for t, k, s in zip(texts, keys, sams)]
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=2, steps_per_call=4),
                              sampling=SAM).start()
        try:
            handles = [
                engine.submit(texts[0], keys[0]),           # default SAM
                engine.submit(texts[1], keys[1], sampling=sams[1]),
                engine.submit(texts[2], keys[2], sampling=sams[2]),
            ]
            results = [h.result(timeout=300) for h in handles]
        finally:
            engine.stop()
        for res, ref in zip(results, refs):
            np.testing.assert_array_equal(res["codes"], ref)

    def test_submit_rejects_bad_sampling(self, flat_setup):
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg, ServingConfig(n_slots=1))
        text = np.zeros(cfg.text_seq_len, np.int32)
        with pytest.raises(ValueError, match="temperature"):
            engine.submit(text, sampling=SamplingConfig(temperature=-1.0))
        with pytest.raises(ValueError, match="temperature"):
            # inf collapses the finite segment-vocab mask: wrong-segment
            # (negative) codes with no error — must be refused up front
            engine.submit(text,
                          sampling=SamplingConfig(temperature=float("inf")))
        with pytest.raises(ValueError, match="top_k"):
            engine.submit(text, sampling=SamplingConfig(top_k=-2))
        with pytest.raises(ValueError, match="top_k"):
            # the Python API must reject what HTTP rejects: a truncated
            # 3.9 would serve different sampling than requested
            engine.submit(text, sampling=SamplingConfig(top_k=3.9))
        with pytest.raises(ValueError, match="top_p"):
            engine.submit(text, sampling=SamplingConfig(top_p=0.0))
        engine.stop(drain=False)

    def test_bad_engine_default_fails_at_construction(self, flat_setup):
        """An invalid engine-wide default dies at construction (operator
        misconfiguration), not as a 400 on every client request."""
        cfg, params = flat_setup
        with pytest.raises(ValueError, match="temperature"):
            DecodeEngine(params, cfg, ServingConfig(n_slots=1),
                         sampling=SamplingConfig(temperature=-1.0))

    def test_crash_mid_admission_cancels_popped_requests(self, flat_setup):
        """A request popped from the queue but not yet in _slots when
        the loop crashes must still resolve (the registry catch-all) —
        a client in result() must never hang on a dead engine."""
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg, ServingConfig(n_slots=1))

        def boom(admitted, slots):
            raise RuntimeError("synthetic admission crash")

        engine._admit_batch = boom
        engine.start()
        handle = engine.submit(np.zeros(cfg.text_seq_len, np.int32))
        with pytest.raises(RuntimeError, match="cancelled"):
            handle.result(timeout=30)
        engine.stop(drain=False)
        assert engine.stats()["cancelled"] == 1
        with pytest.raises(RuntimeError):      # crashed: submits refused
            engine.submit(np.zeros(cfg.text_seq_len, np.int32))


class TestDrainTimeout:
    def test_drain_timeout_resolves_abandoned_handles(self, flat_setup):
        """stop(drain=True) that hits its bound must RESOLVE the
        abandoned handles with an error payload — a client blocked in
        result() must not hang until its own timeout."""
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4))
        # wedge the loop: the engine thread never serves, exactly like a
        # dispatch stuck behind a hung device. 2s outlives the 0.3s
        # bounded join by 6x but ends before interpreter teardown (a
        # daemon sleeping through exit trips XLA's C++ thread-registry
        # teardown: "terminate called without an active exception")
        engine._serve_loop = lambda: time.sleep(2)
        engine.start()
        handle = engine.submit(np.zeros(cfg.text_seq_len, np.int32))
        t0 = time.monotonic()
        engine.stop(drain=True, timeout=0.3)
        with pytest.raises(RuntimeError, match="abandoned"):
            handle.result(timeout=5)
        # the client unblocked at the drain bound, not at its own timeout
        assert time.monotonic() - t0 < 5.0
        assert engine.stats()["cancelled"] == 1

    def test_abandonment_loses_to_a_real_completion(self, flat_setup):
        """First resolution wins: a handle the engine already resolved
        is NOT overwritten by the abandonment sweep (and the metrics
        ledger counts it once, as completed)."""
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4),
                              sampling=SAM).start()
        try:
            handle = engine.submit(_texts(cfg, 1)[0],
                                   jax.random.PRNGKey(0))
            payload = handle.result(timeout=300)
        finally:
            engine.stop()
        assert not handle._resolve({"error": "late abandonment"})
        assert handle.result(timeout=1)["codes"].shape == \
            (cfg.image_seq_len,)
        assert payload["latency_s"] >= 0
        snap = engine.metrics.snapshot()
        assert snap["completed"] == 1 and snap["cancelled"] == 0

    def test_late_harvest_after_abandonment_skips_ledger(self, flat_setup):
        """The inverse race: the abandonment sweep won, then the wedged
        engine thread limps through a harvest — the request must NOT
        also count as completed (nor fabricate a ~0s latency row)."""
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg, ServingConfig(n_slots=1))
        handle = engine_mod.RequestHandle(0)
        engine.metrics.record_submit(0)
        assert handle._resolve({"error": "abandoned"})
        engine.metrics.record_cancelled(0)
        pending = engine_mod._Pending(
            0, np.zeros(cfg.text_seq_len, np.int32),
            np.zeros(2, np.uint32), handle, SamplingConfig())
        engine._finish_harvest(
            pending, jnp.zeros((cfg.image_seq_len,), jnp.int32))
        snap = engine.metrics.snapshot()
        assert snap["cancelled"] == 1 and snap["completed"] == 0
        with pytest.raises(RuntimeError, match="abandoned"):
            handle.result(timeout=1)

    def test_pixel_worker_skips_abandoned_handles(self):
        """Same contract on the pixel path: an already-resolved handle
        is skipped entirely — no pixel work, no completed/failed count
        on top of the cancelled one."""
        m = ServingMetrics(n_slots=1)
        ran = []
        pipeline = PixelPipeline(lambda codes: (ran.append(1),
                                                {"x": 1})[-1], metrics=m)
        handle = engine_mod.RequestHandle(7)
        m.record_submit(7)
        assert handle._resolve({"error": "abandoned"})
        m.record_cancelled(7)
        pipeline.submit(handle, 7, np.zeros(4, np.int32))
        pipeline.stop(timeout=10)
        assert ran == []
        snap = m.snapshot()
        assert snap["cancelled"] == 1 and snap["completed"] == 0 \
            and snap["failed"] == 0


class TestPixelPipeline:
    def test_failure_fails_request_not_worker(self, flat_setup):
        cfg, params = flat_setup

        calls = {"n": 0}

        def flaky(codes):
            calls["n"] += 1
            if calls["n"] == 1:
                raise ValueError("synthetic pixel failure")
            return {"images": np.ones((2, 2, 3), np.uint8)}

        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4),
                              sampling=SAM,
                              pixel_pipeline=PixelPipeline(flaky)).start()
        try:
            texts = _texts(cfg, 2)
            h1 = engine.submit(texts[0], jax.random.PRNGKey(0))
            h2 = engine.submit(texts[1], jax.random.PRNGKey(1))
            with pytest.raises(RuntimeError, match="pixel stage failed"):
                h1.result(timeout=300)
            assert h2.result(timeout=300)["images"].sum() > 0
            # the failure is a FAILED request, not a completion — the
            # throughput/latency stats stay honest
            snap = engine.metrics.snapshot()
            assert snap["failed"] == 1 and snap["completed"] == 1
        finally:
            engine.stop()

    def test_clean_drain_completes_pixel_queued_requests(self, flat_setup):
        """stop(drain=True) with a request already handed to the pixel
        queue must COMPLETE it (decode finished; the pipeline's drain
        resolves it) — never steal it as 'cancelled at engine stop'."""
        cfg, params = flat_setup
        release = threading.Event()

        def slow_pixels(codes):
            release.wait(10)
            return {"images": np.ones((2, 2, 3), np.uint8)}

        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4),
                              sampling=SAM,
                              pixel_pipeline=PixelPipeline(slow_pixels)
                              ).start()
        handle = engine.submit(_texts(cfg, 1)[0], jax.random.PRNGKey(4))
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline and (
                engine._slots[0] is not None or engine._harvests):
            time.sleep(0.01)       # decode done, job now pixel-queued
        stopper = threading.Thread(
            target=lambda: engine.stop(drain=True, timeout=60))
        stopper.start()
        time.sleep(0.1)            # engine loop exits while pixels wait
        release.set()
        stopper.join(60)
        assert not stopper.is_alive()
        assert handle.result(timeout=10)["images"].sum() > 0
        snap = engine.metrics.snapshot()
        assert snap["completed"] == 1 and snap["cancelled"] == 0

    def test_stop_drains_pending_jobs(self):
        done = []
        slow = PixelPipeline(lambda codes: (time.sleep(0.05),
                                            done.append(1),
                                            {"x": 1})[-1])

        class H:
            def _claim(self):
                return True

            def _deliver(self, payload):
                pass

        for _ in range(4):
            slow.submit(H(), 0, np.zeros(4, np.int32))
        slow.stop(timeout=10)
        assert len(done) == 4, "queued jobs must drain before the reap"


class TestMetrics:
    def test_percentiles(self):
        assert np.isnan(percentiles([], (50.0,))[0])
        assert percentiles([1.0], (50.0,)) == [1.0]
        p50, p95 = percentiles([float(i) for i in range(1, 101)])
        assert 50.0 <= p50 <= 51.0
        assert 95.0 <= p95 <= 96.0

    def test_request_accounting_and_jsonl(self, tmp_path):
        path = tmp_path / "serving.jsonl"
        m = ServingMetrics(n_slots=2, jsonl_path=str(path), interval_s=0.0)
        m._interval_s = 0.0001
        for rid in range(3):
            m.record_submit(rid)
            m.record_admit(rid)
            m.record_first_code(rid)
            row = m.record_complete(rid)
            assert row["latency_s"] >= row["ttft_s"] >= 0
            assert row["queue_wait_s"] >= 0
        m.record_step(live_slots=1, queue_depth=4)
        m.record_step(live_slots=2, queue_depth=0)
        snap = m.snapshot()
        assert snap["completed"] == 3 and snap["submitted"] == 3
        assert snap["mean_occupancy"] == pytest.approx(0.75)
        assert snap["mean_queue_depth"] == pytest.approx(2.0)
        assert snap["max_queue_depth"] == 4
        assert snap["img_per_s"] > 0
        time.sleep(0.001)
        m.maybe_flush()
        rows = [json.loads(line) for line in
                path.read_text().splitlines()]
        assert rows and rows[-1]["completed"] == 3

    def test_cancelled_requests_counted(self):
        m = ServingMetrics(n_slots=1)
        m.record_submit(7)
        m.record_cancelled(7)
        snap = m.snapshot()
        assert snap["cancelled"] == 1 and snap["completed"] == 0


class TestServeBench:
    @pytest.mark.slow
    def test_quick_bench_writes_valid_rows(self, tmp_path):
        """serve_bench --quick end-to-end as a subprocess (fresh JAX
        init + several compiles: minutes — slow-marked, like every
        bench path, so tier-1 stays inside its window). Validates the
        SERVE_BENCH.json row schema the driver reads; --quick numbers
        carry no perf claim."""
        import os
        import subprocess
        import sys
        from pathlib import Path

        repo = Path(__file__).resolve().parent.parent
        out = tmp_path / "SERVE_BENCH.json"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        res = subprocess.run(
            [sys.executable, str(repo / "scripts" / "serve_bench.py"),
             "--quick", "--out", str(out)],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=repo)
        assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-2000:]
        rows = [json.loads(line) for line in
                out.read_text().splitlines()]
        modes = [r["mode"] for r in rows]
        assert modes == ["static", "engine", "summary"]
        for row in rows[:2]:
            assert row["img_per_s"] > 0
            assert "mean_occupancy" in row and "mean_queue_depth" in row
            assert "p95_latency_s" in row
        assert "speedup" in rows[2] and "p95_ok" in rows[2]


class TestEngineLoopBench:
    @pytest.mark.slow
    def test_quick_bench_writes_valid_rows(self, tmp_path):
        """engine_loop_bench --quick as a subprocess (fresh JAX init +
        two chunk-variant compiles: minutes — slow-marked like every
        bench path). Validates the ENGINE_LOOP_BENCH.json row schema;
        --quick numbers carry no perf claim."""
        import os
        import subprocess
        import sys
        from pathlib import Path

        repo = Path(__file__).resolve().parent.parent
        out = tmp_path / "ENGINE_LOOP_BENCH.json"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        res = subprocess.run(
            [sys.executable,
             str(repo / "scripts" / "engine_loop_bench.py"),
             "--quick", "--out", str(out)],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=repo)
        assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-2000:]
        rows = [json.loads(line) for line in
                out.read_text().splitlines()]
        assert [r["mode"] for r in rows] == ["sync", "pipelined",
                                             "summary"]
        for row in rows[:2]:
            assert row["device_ms_per_chunk"] > 0
            assert row["wall_ms_per_chunk"] > 0
            assert "dispatch_gap_ms" in row
            assert "host_overhead_ms_per_chunk" in row
        assert "overhead_removed_ms_per_chunk" in rows[2]
        assert "wall_speedup" in rows[2]


class TestHTTPServer:
    @pytest.fixture()
    def served(self, flat_setup):
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=2, steps_per_call=4),
                              sampling=SAM).start()
        httpd = ServingHTTPServer(("127.0.0.1", 0), engine,
                                  request_timeout_s=300.0)
        thread = threading.Thread(target=httpd.serve_forever, daemon=True)
        thread.start()
        yield cfg, engine, f"http://127.0.0.1:{httpd.server_address[1]}"
        httpd.shutdown()
        httpd.server_close()
        engine.stop()
        thread.join(timeout=10)

    def _post(self, url, payload):
        req = urllib.request.Request(
            url + "/generate", data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            return resp.status, json.loads(resp.read())

    def test_generate_stats_healthz(self, served):
        cfg, engine, url = served
        tokens = _texts(cfg, 1)[0].tolist()
        status, body = self._post(url, {"tokens": tokens, "n_images": 2,
                                        "seed": 11})
        assert status == 200
        assert len(body["results"]) == 2
        for row in body["results"]:
            codes = np.asarray(row["codes"])
            assert codes.shape == (cfg.image_seq_len,)
            assert (codes >= 0).all() and (codes < cfg.vocab_image).all()
            assert row["latency_s"] >= row["ttft_s"]
        # the two images of one query use fold_in(seed, i): distinct
        assert body["results"][0]["codes"] != body["results"][1]["codes"]

        with urllib.request.urlopen(url + "/stats", timeout=30) as resp:
            stats = json.loads(resp.read())
        assert stats["completed"] >= 2 and stats["n_slots"] == 2
        with urllib.request.urlopen(url + "/healthz", timeout=30) as resp:
            health = json.loads(resp.read())
        assert health["ok"] is True

    def test_error_paths(self, served):
        cfg, engine, url = served
        with pytest.raises(urllib.error.HTTPError) as e:
            self._post(url, {"text": "no tokenizer configured"})
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            self._post(url, {})
        assert e.value.code == 400
        # wrong-length token vector is a 400, not a dropped connection
        with pytest.raises(urllib.error.HTTPError) as e:
            self._post(url, {"tokens": [1, 2, 3]})
        assert e.value.code == 400
        # non-numeric tokens (TypeError inside np.asarray) too
        with pytest.raises(urllib.error.HTTPError) as e:
            self._post(url, {"tokens": None})
        assert e.value.code == 400
        # out-of-range seed is a 400, not a handler crash
        with pytest.raises(urllib.error.HTTPError) as e:
            self._post(url, {"tokens": [1] * cfg.text_seq_len,
                             "seed": 2 ** 72})
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(url + "/nope", timeout=30)
        assert e.value.code == 404
        # out-of-range per-request sampling knobs are a 400
        with pytest.raises(urllib.error.HTTPError) as e:
            self._post(url, {"tokens": [1] * cfg.text_seq_len,
                             "temperature": -0.5})
        assert e.value.code == 400
        # non-integral top_k must not silently truncate to a DIFFERENT
        # sampling config than the client asked for
        with pytest.raises(urllib.error.HTTPError) as e:
            self._post(url, {"tokens": [1] * cfg.text_seq_len,
                             "top_k": 3.9})
        assert e.value.code == 400

    def test_per_request_sampling_over_http(self, served):
        """The POST body's sampling knobs reach the engine: a greedy
        (temperature 0) request is deterministic — same seed, same
        codes — while the stochastic default keeps its own stream."""
        cfg, engine, url = served
        tokens = _texts(cfg, 1)[0].tolist()
        status, a = self._post(url, {"tokens": tokens, "seed": 3,
                                     "temperature": 0.0})
        status_b, b = self._post(url, {"tokens": tokens, "seed": 3,
                                       "temperature": 0.0})
        assert status == status_b == 200
        assert a["results"][0]["codes"] == b["results"][0]["codes"]
        ref = np.asarray(generate_images(
            engine._params, cfg,
            jnp.asarray(np.asarray(tokens, np.int32)[None]),
            jax.random.fold_in(jax.random.PRNGKey(3), 0),
            SamplingConfig(temperature=0.0), buckets=4))[0]
        np.testing.assert_array_equal(a["results"][0]["codes"], ref)

    def test_queue_full_maps_to_429(self, flat_setup):
        """submit()'s backpressure rejection is an HTTP 429 (retryable),
        NOT a generic failure: an unstarted engine with queue_capacity=1
        fills on the first sibling of a 2-image query."""
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, queue_capacity=1))
        httpd = ServingHTTPServer(("127.0.0.1", 0), engine,
                                  request_timeout_s=5.0)
        thread = threading.Thread(target=httpd.serve_forever, daemon=True)
        thread.start()
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                self._post(url, {"tokens": _texts(cfg, 1)[0].tolist(),
                                 "n_images": 2})
            assert e.value.code == 429
        finally:
            httpd.shutdown()
            httpd.server_close()
            engine.stop(drain=False)
            thread.join(timeout=10)

    def test_stopping_engine_maps_to_503(self, flat_setup):
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg, ServingConfig(n_slots=1))
        engine.stop(drain=False)        # engine gone before the request
        httpd = ServingHTTPServer(("127.0.0.1", 0), engine,
                                  request_timeout_s=5.0)
        thread = threading.Thread(target=httpd.serve_forever, daemon=True)
        thread.start()
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                self._post(url, {"tokens": _texts(cfg, 1)[0].tolist()})
            assert e.value.code == 503
        finally:
            httpd.shutdown()
            httpd.server_close()
            thread.join(timeout=10)
