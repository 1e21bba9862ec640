"""Serving-plane chaos + overload SLO tests.

The load-bearing invariants, mirroring `tests/test_chaos.py` for the
swarm:

- `ServeFaultPlan` parsing is STRICT (a typoed plan must not pass as an
  inert green soak) and its decisions are seed-deterministic.
- The seam is bit-transparent when disabled: an engine (and the HTTP
  front-end) with an inert ServeChaos attached produces the same codes
  as one with no seam at all.
- Priority lanes admit high before low with a bounded low-lane bypass;
  deadline shedding refuses work BEFORE decode is spent and sheds
  queued work whose deadline became unmeetable.
- Mid-decode cancellation frees the slot within one call boundary,
  never double-resolves a handle, and the recycled slot's next occupant
  still reproduces its solo reference bit-exactly.
- The front-end's timeout path CANCELS (the r8→r11 slot leak), and
  /healthz (liveness) is split from /readyz (readiness + overload
  telemetry).
- The fast overload soak (`scripts/overload_soak.py --quick` shape)
  holds all its oracles in tier-1.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_init import init_params
from dalle_tpu.config import ServingConfig, tiny_model_config
from dalle_tpu.models.dalle import DALLE
from dalle_tpu.models.decode import SamplingConfig, generate_images
from dalle_tpu.serving import engine as engine_mod
from dalle_tpu.serving.chaos import (ChaosInjectedError, Flood, ServeChaos,
                                     ServeFaultPlan, ServeFaultRule,
                                     maybe_wrap_serving)
from dalle_tpu.serving.engine import DeadlineShedError, DecodeEngine
from dalle_tpu.serving.metrics import ServingMetrics
from dalle_tpu.serving.pixels import PixelPipeline
from dalle_tpu.serving.scheduler import LANES, SlotScheduler
from dalle_tpu.serving.server import ServingHTTPServer

SAM = SamplingConfig(temperature=1.0, top_k=8)
FLAT = dict(attn_types=("axial_row", "axial_col"), depth=2)


@pytest.fixture(scope="module")
def flat_setup():
    cfg = tiny_model_config(**FLAT)
    params = init_params(DALLE(cfg), jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture()
def slowed_chunks(monkeypatch):
    """Pace every chunk dispatch by 20 ms (numerics untouched): at
    steps_per_call=1 a tiny-config request takes ~0.7 s across ~32 call
    boundaries, so mid-decode events (cancel, front-end timeout)
    deterministically land while the slot is still live — no reliance
    on this box's wobbling decode speed."""
    real = engine_mod._chunk_fn

    def slow(cfg, n_steps, visible):
        fn = real(cfg, n_steps, visible)

        def wrapped(params, state):
            time.sleep(0.02)
            return fn(params, state)

        return wrapped

    monkeypatch.setattr(engine_mod, "_chunk_fn", slow)


def _texts(cfg, n, seed=100):
    return [np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + i), (cfg.text_seq_len,), 2,
        cfg.vocab_text)) for i in range(n)]


class TestPlanParsing:
    def test_unknown_keys_and_ops_raise(self):
        with pytest.raises(ValueError, match="unknown plan key"):
            ServeFaultPlan.from_dict({"seeed": 1})
        with pytest.raises(ValueError, match="unknown rule key"):
            ServeFaultPlan.from_dict({"rules": [{"stall": 0.1}]})
        with pytest.raises(ValueError, match="unknown serve fault op"):
            ServeFaultPlan.from_dict({"rules": [{"ops": ["send"]}]})
        with pytest.raises(ValueError, match="unknown flood key"):
            ServeFaultPlan.from_dict({"floods": [{"t": 1, "burst": 2}]})

    def test_value_validation(self):
        with pytest.raises(ValueError, match="fail must be a probability"):
            ServeFaultRule(fail=1.5)
        with pytest.raises(ValueError, match="stall_s"):
            ServeFaultRule(stall_s=(0.5,))
        with pytest.raises(ValueError, match="stall_s"):
            ServeFaultRule(stall_s=(0.5, 0.1))
        with pytest.raises(ValueError, match="half_close only fires"):
            ServeFaultRule(ops=("pixel",), half_close=0.5)
        with pytest.raises(ValueError, match="start_s <= end_s"):
            ServeFaultRule(start_s=5.0, end_s=1.0)
        with pytest.raises(ValueError, match="burst"):
            Flood(at_s=0.0, burst=0)
        with pytest.raises(ValueError, match="at_s"):
            Flood(at_s=-1.0, burst=2)
        with pytest.raises(ValueError, match="crash_at_admission"):
            ServeFaultPlan(crash_at_admission=0)
        with pytest.raises(ValueError, match="crash_at_admission"):
            ServeFaultPlan.from_dict({"crash_at_admission": -3})

    def test_roundtrip_and_enabled(self):
        plan = ServeFaultPlan.from_json(
            '{"seed": 7, "rules": [{"ops": ["pixel"], "fail": 0.5}], '
            '"floods": [{"at_s": 1.0, "burst": 4}], '
            '"crash_at_admission": 3}')
        assert plan.enabled and plan.seed == 7
        assert plan.crash_at_admission == 3
        again = ServeFaultPlan.from_json(plan.to_json())
        assert again == plan
        assert not ServeFaultPlan().enabled
        assert ServeFaultPlan(crash_at_admission=1).enabled

    def test_maybe_wrap_disabled_paths(self):
        assert maybe_wrap_serving(None) is None
        assert maybe_wrap_serving("") is None
        assert maybe_wrap_serving('{"seed": 9}') is None  # inert plan
        wrapped = maybe_wrap_serving(
            '{"rules": [{"ops": ["pixel"], "fail": 1.0}]}')
        assert isinstance(wrapped, ServeChaos)


class TestDeterminism:
    def _pixel_verdicts(self, seed, n=32):
        chaos = ServeChaos(ServeFaultPlan(
            seed=seed, rules=(ServeFaultRule(ops=("pixel",), fail=0.5),)))
        out = []
        for rid in range(n):
            try:
                chaos.on_pixel(rid)
                out.append(False)
            except ChaosInjectedError:
                out.append(True)
        return out

    def test_same_seed_same_schedule(self):
        a, b = self._pixel_verdicts(11), self._pixel_verdicts(11)
        assert a == b
        assert any(a) and not all(a)   # p=0.5 over 32 draws: both kinds

    def test_per_channel_counter_advances(self):
        chaos = ServeChaos(ServeFaultPlan(
            seed=3, rules=(ServeFaultRule(ops=("pixel",), fail=0.5),)))
        verdicts = []
        for _ in range(16):            # SAME rid: the channel index moves
            try:
                chaos.on_pixel(0)
                verdicts.append(False)
            except ChaosInjectedError:
                verdicts.append(True)
        assert any(verdicts) and not all(verdicts)

    def test_flood_fires_exactly_once(self):
        chaos = ServeChaos(ServeFaultPlan(
            floods=(Flood(at_s=0.0, burst=3), Flood(at_s=9999.0, burst=5))))
        assert chaos.flood_due() == 3
        assert chaos.flood_due() == 0
        # the ledger records what the engine actually LANDED (the
        # capacity-capped count), not the planned burst
        assert "flood" not in chaos.injected
        chaos.note_flood(2)
        assert chaos.injected["flood"] == 2


class TestBitTransparency:
    def test_engine_output_identical_with_inert_seam(self, flat_setup):
        """The acceptance pin: an engine with a constructed-but-inert
        ServeChaos attached emits EXACTLY the codes of a sealess engine
        (both equal to the generate_images reference)."""
        cfg, params = flat_setup
        text = _texts(cfg, 1)[0]
        key = jax.random.PRNGKey(77)
        ref = np.asarray(generate_images(
            params, cfg, jnp.asarray(text[None]), key, SAM, buckets=4))[0]

        def run(chaos):
            eng = DecodeEngine(params, cfg,
                               ServingConfig(n_slots=1, steps_per_call=4),
                               sampling=SAM, chaos=chaos).start()
            try:
                return eng.submit(text, key).result(timeout=300)["codes"]
            finally:
                eng.stop()

        clean = run(None)
        seamed = run(ServeChaos(ServeFaultPlan(seed=5)))
        np.testing.assert_array_equal(clean, ref)
        np.testing.assert_array_equal(seamed, ref)

    def test_http_stream_identical_with_inert_seam(self, flat_setup):
        """HTTP face of the same pin: status, headers shape and body
        agree byte-for-byte once the wall-clock timing row (different
        across ANY two runs, seam or not) is normalized."""
        cfg, params = flat_setup
        tokens = _texts(cfg, 1)[0].tolist()

        def serve_once(chaos):
            eng = DecodeEngine(params, cfg,
                               ServingConfig(n_slots=1, steps_per_call=4),
                               sampling=SAM, chaos=chaos).start()
            httpd = ServingHTTPServer(("127.0.0.1", 0), eng,
                                      request_timeout_s=300.0)
            th = threading.Thread(target=httpd.serve_forever, daemon=True)
            th.start()
            url = f"http://127.0.0.1:{httpd.server_address[1]}"
            try:
                req = urllib.request.Request(
                    url + "/generate",
                    data=json.dumps({"tokens": tokens, "seed": 3}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=300) as resp:
                    status, ctype = resp.status, resp.headers[
                        "Content-Type"]
                    raw = resp.read()
            finally:
                httpd.shutdown()
                httpd.server_close()
                eng.stop()
                th.join(timeout=10)
            body = json.loads(raw)
            for row in body["results"]:
                for k in ("latency_s", "ttft_s", "queue_wait_s"):
                    row[k] = 0.0
            return status, ctype, json.dumps(body).encode()

        assert serve_once(None) == serve_once(
            ServeChaos(ServeFaultPlan(seed=5)))


class TestLaneScheduler:
    def test_grant_lanes_priority_and_total(self):
        sched = SlotScheduler(4, bytes_per_slot=100)
        assert sched.grant_lanes([3, 3], live=0, free=4) == [3, 1]
        assert sched.grant_lanes([6, 2], live=0, free=4) == [4, 0]
        assert sched.grant_lanes([0, 3], live=2, free=2) == [0, 2]
        with pytest.raises(ValueError, match="one entry per lane"):
            sched.grant_lanes([1], live=0, free=1)

    def test_burst_cap_applies_across_lanes(self):
        sched = SlotScheduler(8, 100, admit_burst=2)
        assert sched.grant_lanes([3, 3], live=0, free=8) == [2, 0]
        assert sum(sched.grant_lanes([1, 5], live=0, free=8)) == 2

    def test_kv_budget_clamp_with_high_queue(self):
        one_mb = 2 ** 20
        sched = SlotScheduler(8, one_mb, kv_budget_mb=3)
        assert sched.max_live == 3
        # the budget is lane-blind: a saturated high lane eats the
        # whole clamp
        assert sched.grant_lanes([5, 5], live=0, free=8) == [3, 0]
        assert sched.grant_lanes([5, 5], live=3, free=5) == [0, 0]

    def test_low_lane_bounded_bypass(self):
        sched = SlotScheduler(1, 100, low_lane_bypass=3)
        # 3 starved boundaries (high takes the only slot each time)...
        for _ in range(3):
            assert sched.grant_lanes([2, 2], live=0, free=1) == [1, 0]
        # ...then the bypass reserves the slot for low, and resets
        assert sched.grant_lanes([2, 2], live=0, free=1) == [0, 1]
        assert sched.grant_lanes([2, 2], live=0, free=1) == [1, 0]

    def test_zero_grant_boundary_starves_nobody(self):
        sched = SlotScheduler(1, 100, low_lane_bypass=2)
        for _ in range(10):           # no free slot: nothing to bypass
            assert sched.grant_lanes([2, 2], live=1, free=0) == [0, 0]
        assert sched.grant_lanes([2, 2], live=0, free=1) == [1, 0]

    def test_bypass_disabled_is_strict_priority(self):
        sched = SlotScheduler(1, 100, low_lane_bypass=None)
        for _ in range(20):
            assert sched.grant_lanes([2, 2], live=0, free=1) == [1, 0]

    def test_predict_completion_boundaries(self):
        sched = SlotScheduler(4, 100)
        # empty engine: one wave exactly
        assert sched.predict_completion_s(0, 0, 2.0) == 2.0
        # a full wave ahead: two waves
        assert sched.predict_completion_s(4, 0, 2.0) == 4.0
        assert sched.predict_completion_s(0, 4, 2.0) == 4.0
        # one under the wave boundary stays in the earlier wave
        assert sched.predict_completion_s(3, 0, 2.0) == 2.0
        # kv clamp shrinks the wave size
        clamped = SlotScheduler(4, 2 ** 20, kv_budget_mb=2)
        assert clamped.predict_completion_s(2, 0, 2.0) == 4.0

    def test_lane_priority_end_to_end(self, flat_setup):
        """3 low requests queued first, 1 high submitted last: the high
        request is admitted at the FIRST boundary (shortest queue wait)
        and every request still completes."""
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4),
                              sampling=SAM)
        texts = _texts(cfg, 4)
        lows = [engine.submit(texts[i], jax.random.PRNGKey(i), lane="low")
                for i in range(3)]
        high = engine.submit(texts[3], jax.random.PRNGKey(3), lane="high")
        engine.start()
        try:
            high_row = high.result(timeout=300)
            low_rows = [h.result(timeout=300) for h in lows]
        finally:
            engine.stop()
        assert high_row["lane"] == "high"
        assert high_row["queue_wait_s"] < min(
            r["queue_wait_s"] for r in low_rows)
        snap = engine.metrics.snapshot()
        assert snap["completed"] == 4


class TestDeadlineShed:
    def test_submit_shed_before_any_decode(self, flat_setup):
        """With a measured cadence that predicts a miss, submit raises
        DeadlineShedError and nothing is queued or decoded."""
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg, ServingConfig(n_slots=1))
        with engine.metrics._lock:     # inject a measured cadence
            engine.metrics._service_ema_s = 10.0
        text = np.zeros(cfg.text_seq_len, np.int32)
        with pytest.raises(DeadlineShedError, match="shed"):
            engine.submit(text, deadline_s=5.0)
        # malformed deadlines are a 400-class ValueError, NOT a shed —
        # bad input must not inflate the overload telemetry
        with pytest.raises(ValueError, match="deadline_s"):
            engine.submit(text, deadline_s=0.0)
        with pytest.raises(ValueError, match="deadline_s"):
            engine.submit(text, deadline_s=-5.0)
        snap = engine.metrics.snapshot()
        assert snap["shed"] == 1 and snap["submitted"] == 0
        assert snap["lanes"]["high"]["shed"] == 1
        # boundary condition: predicted == deadline is NOT shed
        # (strictly-greater — never refuse work that can exactly win)
        h = engine.submit(text, deadline_s=10.0)
        assert h is not None
        with pytest.raises(ValueError, match="finite"):
            engine.submit(text, deadline_s=float("inf"))
        with pytest.raises(ValueError, match="lane"):
            engine.submit(text, lane="turbo")
        engine.stop(drain=False)

    def test_queued_deadline_expiry_sheds_at_boundary(self, flat_setup):
        """A request accepted optimistically (no cadence yet) whose
        deadline passes while queued is shed at the first boundary —
        before its decode burns a slot."""
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4))
        handle = engine.submit(np.zeros(cfg.text_seq_len, np.int32),
                               deadline_s=0.05, lane="low")
        time.sleep(0.2)                # deadline passes pre-start
        engine.start()
        with pytest.raises(RuntimeError, match="shed"):
            handle.result(timeout=30)
        engine.stop()
        snap = engine.metrics.snapshot()
        assert snap["shed"] == 1 and snap["shed_queued"] == 1
        assert snap["completed"] == 0 and snap["cancelled"] == 0

    def test_shed_maps_to_429_over_http(self, flat_setup):
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg, ServingConfig(n_slots=1))
        with engine.metrics._lock:
            engine.metrics._service_ema_s = 50.0
        httpd = ServingHTTPServer(("127.0.0.1", 0), engine,
                                  request_timeout_s=5.0)
        th = threading.Thread(target=httpd.serve_forever, daemon=True)
        th.start()
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            req = urllib.request.Request(
                url + "/generate",
                data=json.dumps(
                    {"tokens": [1] * cfg.text_seq_len,
                     "deadline_s": 2.0}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=30)
            assert e.value.code == 429
            assert json.loads(e.value.read())["shed"] is True
        finally:
            httpd.shutdown()
            httpd.server_close()
            engine.stop(drain=False)
            th.join(timeout=10)


class TestCancel:
    def test_cancel_queued_resolves_immediately(self, flat_setup):
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg, ServingConfig(n_slots=1))
        handle = engine.submit(np.zeros(cfg.text_seq_len, np.int32))
        assert engine.cancel(handle.request_id) is True
        with pytest.raises(RuntimeError, match="cancelled by client"):
            handle.result(timeout=5)
        assert engine.cancel(handle.request_id) is False   # idempotent
        assert engine.cancel(99999) is False               # unknown
        snap = engine.metrics.snapshot()
        assert snap["cancelled"] == 1 and snap["cancelled_mid_decode"] == 0
        engine.stop(drain=False)

    def test_mid_decode_cancel_frees_slot_and_parity(self, flat_setup,
                                                     slowed_chunks):
        """THE acceptance pin: cancelling a live request returns its
        slot to the scheduler within one call boundary, and the next
        occupant of that recycled slot still reproduces its solo
        reference bit-exactly (cancellation leaves no residue)."""
        cfg, params = flat_setup
        texts = _texts(cfg, 2)
        key_b = jax.random.PRNGKey(1)
        ref_b = np.asarray(generate_images(
            params, cfg, jnp.asarray(texts[1][None]), key_b, SAM,
            buckets=4))[0]
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=1),
                              sampling=SAM).start()
        try:
            h_a = engine.submit(texts[0], jax.random.PRNGKey(0))
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and engine._slots[0] is None:
                time.sleep(0.002)
            assert engine._slots[0] is not None, "A never admitted"
            h_b = engine.submit(texts[1], key_b)
            assert engine.cancel(h_a.request_id) is True
            with pytest.raises(RuntimeError, match="cancelled"):
                h_a.result(timeout=30)
            got_b = h_b.result(timeout=300)
        finally:
            engine.stop()
        np.testing.assert_array_equal(got_b["codes"], ref_b)
        snap = engine.metrics.snapshot()
        assert snap["cancelled"] == 1 and snap["cancelled_mid_decode"] == 1
        assert snap["completed"] == 1

    def test_cancel_after_completion_is_noop(self, flat_setup):
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4),
                              sampling=SAM).start()
        try:
            handle = engine.submit(_texts(cfg, 1)[0], jax.random.PRNGKey(2))
            payload = handle.result(timeout=300)
        finally:
            engine.stop()
        assert engine.cancel(handle.request_id) is False
        assert handle.result(timeout=1)["codes"].shape == \
            (cfg.image_seq_len,)
        assert payload["latency_s"] >= 0
        snap = engine.metrics.snapshot()
        assert snap["completed"] == 1 and snap["cancelled"] == 0

    def test_cancel_never_double_resolves(self, flat_setup):
        """The r9 _claim/_deliver discipline on the cancel path: a
        harvest limping in after a cancel resolved the handle must not
        deliver a second payload or feed the completion ledger."""
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg, ServingConfig(n_slots=1))
        handle = engine_mod.RequestHandle(0)
        engine.metrics.record_submit(0)
        pending = engine_mod._Pending(
            0, np.zeros(cfg.text_seq_len, np.int32),
            np.zeros(2, np.uint32), handle, SamplingConfig())
        assert handle._resolve({"error": "cancelled by client"})
        engine.metrics.record_cancelled(0, mid_decode=True)
        engine._finish_harvest(
            pending, jnp.zeros((cfg.image_seq_len,), jnp.int32))
        snap = engine.metrics.snapshot()
        assert snap["cancelled"] == 1 and snap["completed"] == 0
        with pytest.raises(RuntimeError, match="cancelled"):
            handle.result(timeout=1)


class TestServerTimeoutCancel:
    def test_504_reclaims_the_slot(self, flat_setup, slowed_chunks):
        """The satellite fix: the front-end's request timeout used to
        504 while the request kept decoding (a leaked slot for the full
        decode). Now the timeout cancels mid-decode and the slot is
        free for the next request."""
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=1),
                              sampling=SAM).start()
        httpd = ServingHTTPServer(("127.0.0.1", 0), engine,
                                  request_timeout_s=0.2)
        th = threading.Thread(target=httpd.serve_forever, daemon=True)
        th.start()
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            req = urllib.request.Request(
                url + "/generate",
                data=json.dumps(
                    {"tokens": _texts(cfg, 1)[0].tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=60)
            assert e.value.code == 504
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and (
                    engine._slots[0] is not None):
                time.sleep(0.01)
            assert engine._slots[0] is None, \
                "timed-out request still owns its slot"
            snap = engine.metrics.snapshot()
            assert snap["cancelled"] >= 1
            assert snap["completed"] == 0
        finally:
            httpd.shutdown()
            httpd.server_close()
            engine.stop(drain=False)
            th.join(timeout=10)


class TestBrownout:
    def test_hysteresis_and_hold(self, flat_setup):
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg, ServingConfig(
            n_slots=1, queue_capacity=10, brownout_high_frac=0.5,
            brownout_low_frac=0.25, brownout_hold_s=0.05))
        engine._update_brownout(5)       # at threshold: hold starts
        assert not engine.brownout_active
        time.sleep(0.06)
        engine._update_brownout(5)       # held long enough: engages
        assert engine.brownout_active
        engine._update_brownout(3)       # between low and high: stays
        assert engine.brownout_active
        engine._update_brownout(2)       # at/below low frac: disengages
        assert not engine.brownout_active
        engine._update_brownout(10)      # dip reset the hold timer
        assert not engine.brownout_active

    def test_brownout_trims_images_over_http(self, flat_setup):
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4,
                                            brownout_max_images=1),
                              sampling=SAM).start()
        httpd = ServingHTTPServer(("127.0.0.1", 0), engine,
                                  request_timeout_s=300.0)
        th = threading.Thread(target=httpd.serve_forever, daemon=True)
        th.start()
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            engine._brownout = True      # force: the trim is the pin
            req = urllib.request.Request(
                url + "/generate",
                data=json.dumps({"tokens": _texts(cfg, 1)[0].tolist(),
                                 "n_images": 3, "seed": 4}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as resp:
                body = json.loads(resp.read())
            assert body["brownout"] is True
            assert len(body["results"]) == 1
            # the surviving image is fold_in(seed, 0): parity unchanged
            ref = np.asarray(generate_images(
                params, cfg, jnp.asarray(_texts(cfg, 1)[0][None]),
                jax.random.fold_in(jax.random.PRNGKey(4), 0), SAM,
                buckets=4))[0]
            np.testing.assert_array_equal(body["results"][0]["codes"], ref)
            snap = engine.metrics.snapshot()
            assert snap["browned"] == 1
        finally:
            httpd.shutdown()
            httpd.server_close()
            engine.stop()
            th.join(timeout=10)


class TestPixelChaos:
    def test_injected_pixel_failure_fails_request_not_worker(
            self, flat_setup):
        cfg, params = flat_setup
        chaos = ServeChaos(ServeFaultPlan(
            seed=1, rules=(ServeFaultRule(ops=("pixel",), fail=1.0),)))
        engine = DecodeEngine(
            params, cfg, ServingConfig(n_slots=1, steps_per_call=4),
            sampling=SAM, chaos=chaos,
            pixel_pipeline=PixelPipeline(
                lambda codes: {"x": 1})).start()
        try:
            texts = _texts(cfg, 2)
            h1 = engine.submit(texts[0], jax.random.PRNGKey(0))
            h2 = engine.submit(texts[1], jax.random.PRNGKey(1))
            for h in (h1, h2):
                with pytest.raises(RuntimeError, match="chaos"):
                    h.result(timeout=300)
        finally:
            engine.stop()
        # the worker survived the first injected failure to fail the
        # second request too — and the ledger counts both as failed
        snap = engine.metrics.snapshot()
        assert snap["failed"] == 2 and snap["completed"] == 0
        assert chaos.injected["pixel_fail"] == 2

    def test_pixel_stall_delays_but_completes(self, flat_setup):
        cfg, params = flat_setup
        chaos = ServeChaos(ServeFaultPlan(
            seed=1, rules=(ServeFaultRule(ops=("pixel",),
                                          stall_s=(0.05, 0.05)),)))
        engine = DecodeEngine(
            params, cfg, ServingConfig(n_slots=1, steps_per_call=4),
            sampling=SAM, chaos=chaos,
            pixel_pipeline=PixelPipeline(
                lambda codes: {"x": 1})).start()
        try:
            got = engine.submit(_texts(cfg, 1)[0],
                                jax.random.PRNGKey(0)).result(timeout=300)
        finally:
            engine.stop()
        assert got["x"] == 1
        assert chaos.injected.get("stall", 0) >= 1


class TestFloodAndAdmitCrash:
    def test_flood_consumes_capacity_not_ledger(self, flat_setup):
        cfg, params = flat_setup
        chaos = ServeChaos(ServeFaultPlan(
            floods=(Flood(at_s=0.0, burst=3),)))
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4,
                                            queue_capacity=8),
                              sampling=SAM, chaos=chaos).start()
        try:
            text = _texts(cfg, 1)[0]
            key = jax.random.PRNGKey(0)
            ref = np.asarray(generate_images(
                params, cfg, jnp.asarray(text[None]), key, SAM,
                buckets=4))[0]
            got = engine.submit(text, key).result(timeout=300)
        finally:
            engine.stop()
        np.testing.assert_array_equal(got["codes"], ref)
        snap = engine.metrics.snapshot()
        assert snap["flood_injected"] == 3
        assert snap["submitted"] == 1 and snap["completed"] == 1
        assert chaos.injected["flood"] == 3

    def test_crash_at_admission_cancels_cleanly(self, flat_setup):
        """The engine-thread-crash seam: the first admission batch
        raises inside the _admitting window; the crash-path sweep must
        resolve the handle (no orphan) and the engine must fail fast
        afterwards."""
        cfg, params = flat_setup
        chaos = ServeChaos(ServeFaultPlan(crash_at_admission=1))
        engine = DecodeEngine(params, cfg, ServingConfig(n_slots=1),
                              chaos=chaos).start()
        handle = engine.submit(np.zeros(cfg.text_seq_len, np.int32))
        with pytest.raises(RuntimeError, match="cancelled"):
            handle.result(timeout=30)
        with pytest.raises(RuntimeError):      # crashed: fail fast
            engine.submit(np.zeros(cfg.text_seq_len, np.int32))
        assert engine.alive is False
        assert chaos.injected["admit_crash"] == 1
        snap = engine.metrics.snapshot()
        assert snap["cancelled"] == 1
        engine.stop(drain=False)


class TestReadiness:
    def test_healthz_liveness_and_readyz_telemetry(self, flat_setup):
        cfg, params = flat_setup
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4),
                              sampling=SAM).start()
        httpd = ServingHTTPServer(("127.0.0.1", 0), engine,
                                  request_timeout_s=300.0)
        th = threading.Thread(target=httpd.serve_forever, daemon=True)
        th.start()
        url = f"http://127.0.0.1:{httpd.server_address[1]}"

        def get(path):
            try:
                with urllib.request.urlopen(url + path,
                                            timeout=30) as resp:
                    return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        try:
            status, health = get("/healthz")
            assert status == 200 and health == {"ok": True}
            status, ready = get("/readyz")
            assert status == 200 and ready["ready"] is True
            for key in ("draining", "queue_full", "brownout",
                        "queue_depth_by_lane", "shed", "browned",
                        "cancelled_mid_decode", "goodput_img_per_s"):
                assert key in ready, key
            assert set(ready["queue_depth_by_lane"]) == set(LANES)
            status, stats = get("/stats")
            assert status == 200
            for key in ("lanes", "shed", "browned", "goodput_img_per_s",
                        "cancelled_mid_decode", "queue_depth_by_lane"):
                assert key in stats, key
            # a stopped engine is not live and not ready
            engine.stop()
            status, health = get("/healthz")
            assert status == 503 and health["ok"] is False
            status, ready = get("/readyz")
            assert status == 503 and ready["ready"] is False
        finally:
            httpd.shutdown()
            httpd.server_close()
            engine.stop(drain=False)
            th.join(timeout=10)


class TestOverloadSoak:
    def _args(self, **kw):
        import argparse
        # load 3x (vs the CLI's 2x default): the tier-1 gate must stay
        # green when the box runs FASTER during the soak than during
        # calibration (2-4x wobble, memory/CHAOS.md) — the
        # overload-engaged oracle needs the backlog to exist even then,
        # and the 8s p99 floor already absorbs the slow direction
        base = dict(requests=8, slots=2, steps_per_call=4, load=3.0,
                    queue_capacity=10, seed=0, request_timeout_s=60.0,
                    high_deadline_s=None, high_deadline_factor=12.0,
                    low_deadline_factor=2.5, plan=None, quick=True)
        base.update(kw)
        return argparse.Namespace(**base)

    # the two oracles that compare seconds measured here with a deadline
    # calibrated moments before, on a box whose speed wobbles 2-4x: a
    # number from the CPU box is a count or a byte compare, never a speed
    # (ROADMAP Design 13). The fast gate prints them; ``test_full_soak``
    # and the committed OVERLOAD_SOAK.json go on holding every oracle.
    ON_THE_CLOCK = ("high_lane_p99", "overload_engaged_shed")

    def test_fast_soak_all_oracles_hold(self):
        """Tier-1 gate for `scripts/overload_soak.py`: a seeded 2x-
        overload trace against the fault-plan-wrapped server ends with
        every oracle that is a count or a byte compare green (accounting,
        bit-exact parity, goodput, zero orphans); the two on the clock
        are printed."""
        import sys
        from pathlib import Path
        sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                               / "scripts"))
        import overload_soak
        report = overload_soak.run_soak(self._args())
        oracles = report["oracles"]
        assert set(self.ON_THE_CLOCK) < set(oracles), report
        print({k: oracles[k] for k in self.ON_THE_CLOCK},
              report["outcomes"])
        failed = [k for k, v in oracles.items()
                  if not v and k not in self.ON_THE_CLOCK]
        assert not failed, (failed, report["outcomes"],
                            report["server_stats"])

    @pytest.mark.slow
    def test_full_soak(self, tmp_path):
        """The full-size soak as a subprocess (the committed
        OVERLOAD_SOAK.json shape); slow-marked like every bench/soak
        path (pytest.ini)."""
        import os
        import subprocess
        import sys
        from pathlib import Path
        repo = Path(__file__).resolve().parent.parent
        out = tmp_path / "OVERLOAD_SOAK.json"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        res = subprocess.run(
            [sys.executable, str(repo / "scripts" / "overload_soak.py"),
             "--out", str(out)],
            capture_output=True, text=True, timeout=1800, env=env,
            cwd=repo)
        assert res.returncode == 0, \
            res.stdout[-3000:] + res.stderr[-2000:]
        report = json.loads(out.read_text())
        assert report["ok"] and all(report["oracles"].values())
