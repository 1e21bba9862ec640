"""Fleet-routing tests (serving/router.py): DHT serving records, the
placement brain, failover, stale-record exclusion, and the tier-1 fast
router smoke (pytest.ini names TestRouterSmoke in the tier-1 set).

DHT-backed tests run real loopback peers (the test_swarm strategy);
placement-logic tests drive Router with synthetic record providers so
every decision is deterministic.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from dalle_init import init_params
from dalle_tpu.config import ServingConfig, tiny_model_config
from dalle_tpu.models.dalle import DALLE
from dalle_tpu.models.decode import (SamplingConfig, generate_images,
                                     resolve_buckets)
from dalle_tpu.serving.engine import DecodeEngine
from dalle_tpu.serving.prefix_cache import prompt_fingerprint
from dalle_tpu.serving.router import (Router, RouterHTTPServer,
                                      ServingAdvertiser, advertise_serving,
                                      discover_engines, engine_record,
                                      request_fingerprint, serving_key)
from dalle_tpu.serving.server import ServingHTTPServer
from dalle_tpu.swarm import DHT, Identity
from dalle_tpu.swarm.dht import get_dht_time

SAM = SamplingConfig(temperature=1.0, top_k=8)
FLAT = dict(attn_types=("axial_row", "axial_col"), depth=2)


@pytest.fixture(scope="module")
def flat_setup():
    cfg = tiny_model_config(**FLAT)
    params = init_params(DALLE(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _text(cfg, seed=100):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (cfg.text_seq_len,), 2,
        cfg.vocab_text))


def _solo(params, cfg, text, key, buckets=None):
    buckets = buckets or resolve_buckets(None, 2)
    return np.asarray(generate_images(
        params, cfg, np.asarray(text)[None], key, SAM,
        buckets=buckets))[0]


def _rec(pid="e", url="http://u", depth=0, live=0, max_live=2,
         cap=64, service=1.0, draining=False, age=0.0):
    return {"url": url, "t": get_dht_time() - age, "queue_depth": depth,
            "live_slots": live, "max_live": max_live,
            "queue_capacity": cap, "service_ema_s": service,
            "draining": draining}


def _post(url, body, timeout=120):
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


class TestServingRecords:
    def test_advertise_discover_roundtrip(self, flat_setup):
        """An engine's record reaches a second peer through a real
        loopback DHT, identity-bound, carrying the /readyz slice."""
        cfg, params = flat_setup
        a = DHT(identity=Identity.generate())
        b = DHT(initial_peers=[a.visible_address],
                identity=Identity.generate())
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=2, steps_per_call=4),
                              sampling=SAM)
        try:
            rec = engine_record(engine, "http://127.0.0.1:9")
            assert advertise_serving(a, "t", rec, ttl=30)
            found = discover_engines(b, "t")
            assert a.peer_id in found
            got = found[a.peer_id]
            assert got["url"] == "http://127.0.0.1:9"
            for key in ("queue_depth", "live_slots", "max_live",
                        "service_ema_s", "goodput_img_per_s",
                        "draining", "brownout", "prefix_hits"):
                assert key in got, key
        finally:
            engine.stop()
            a.shutdown()
            b.shutdown()

    def test_expired_record_vanishes_from_discovery(self, flat_setup):
        """A TTL-expired serving record is gone from discover — a dead
        engine ages out of the table within one TTL."""
        cfg, params = flat_setup
        a = DHT(identity=Identity.generate())
        b = DHT(initial_peers=[a.visible_address],
                identity=Identity.generate())
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4),
                              sampling=SAM)
        try:
            advertise_serving(a, "t", engine_record(engine, "http://u"),
                              ttl=1.0)
            assert a.peer_id in (discover_engines(b, "t") or {})
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if a.peer_id not in (discover_engines(b, "t") or {}):
                    break
                time.sleep(0.25)
            assert a.peer_id not in (discover_engines(b, "t") or {})
        finally:
            engine.stop()
            a.shutdown()
            b.shutdown()

    def test_record_without_url_dropped(self, flat_setup):
        a = DHT(identity=Identity.generate())
        try:
            a.store(serving_key("t"), a.peer_id, {"t": get_dht_time()},
                    expiration_time=get_dht_time() + 30)
            assert a.peer_id not in (discover_engines(a, "t") or {})
        finally:
            a.shutdown()

    def test_advertiser_republishes_and_stops_clean(self, flat_setup):
        cfg, params = flat_setup
        a = DHT(identity=Identity.generate())
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4),
                              sampling=SAM)
        adv = ServingAdvertiser(a, "t", engine, "http://u", ttl=1.5)
        try:
            assert adv.daemon
            adv.start()
            deadline = time.monotonic() + 10
            t0 = None
            while time.monotonic() < deadline:
                found = discover_engines(a, "t") or {}
                if a.peer_id in found:
                    t0 = found[a.peer_id]["t"]
                    break
                time.sleep(0.1)
            assert t0 is not None
            # a LATER publish supersedes (the republishing loop runs)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                found = discover_engines(a, "t") or {}
                if a.peer_id in found and found[a.peer_id]["t"] > t0:
                    break
                time.sleep(0.1)
            assert found[a.peer_id]["t"] > t0
        finally:
            adv.stop()
            assert not adv.is_alive()
            engine.stop()
            a.shutdown()


class TestPlacement:
    def test_least_predicted_completion_wins(self):
        recs = {"a": _rec("a", depth=6, live=2),   # 5 waves
                "b": _rec("b", depth=0, live=0)}   # 1 wave
        r = Router(lambda: recs, refresh_s=99)
        r.refresh_once()
        assert [p for p, _ in r.candidates()] == ["b", "a"]

    def test_inflight_counts_before_records_refresh(self):
        """Router-placed work not yet visible in the (stale) records
        still loads the prediction — a burst spreads instead of piling
        onto the engine the last refresh liked."""
        recs = {"a": _rec("a"), "b": _rec("b")}
        r = Router(lambda: recs, refresh_s=99)
        r.refresh_once()
        placed = []
        for _ in range(6):
            pid = r.candidates()[0][0]
            placed.append(pid)
            r.note_placed(pid, 1)
        assert set(placed) == {"a", "b"}

    def test_affinity_pins_duplicates_until_load_beats_it(self):
        recs = {"a": _rec("a"), "b": _rec("b")}
        r = Router(lambda: recs, refresh_s=99)
        r.refresh_once()
        fp = prompt_fingerprint(np.arange(16, dtype=np.int32))
        home = r.candidates(fp)[0][0]
        # idle fleet: the home is stable
        assert all(r.candidates(fp)[0][0] == home for _ in range(4))
        # pile load on the home: affinity must yield to the wave model
        for _ in range(8):
            r.note_placed(home, 1)
        assert r.candidates(fp)[0][0] != home

    def test_draining_and_full_engines_unplaceable(self):
        recs = {"a": _rec("a", draining=True),
                "b": _rec("b", depth=64, cap=64),
                "c": _rec("c")}
        r = Router(lambda: recs, refresh_s=99)
        r.refresh_once()
        assert [p for p, _ in r.healthy()] == ["c"]

    def test_stale_record_never_placed_to(self):
        """The acceptance case: a record older than record_max_age_s —
        an engine that stopped republishing — is excluded even though
        the provider still returns it."""
        recs = {"fresh": _rec("fresh"),
                "stale": _rec("stale", age=120.0)}
        r = Router(lambda: recs, refresh_s=99, record_max_age_s=30.0)
        r.refresh_once()
        assert [p for p, _ in r.candidates()] == ["fresh"]

    def test_refresh_failure_keeps_last_good_table(self):
        state = {"fail": False}

        def fetch():
            if state["fail"]:
                raise RuntimeError("dht down")
            return {"a": _rec("a")}

        r = Router(fetch, refresh_s=99)
        r.refresh_once()
        state["fail"] = True
        with pytest.raises(RuntimeError):
            r.refresh_once()
        assert [p for p, _ in r.healthy()] == ["a"]

    def test_unmeasured_engine_rides_fleet_fallback_service(self):
        """An engine with no service EMA yet must not look infinitely
        fast next to a measured one."""
        recs = {"new": _rec("new", depth=4, service=None),
                "old": _rec("old", depth=0, service=2.0)}
        r = Router(lambda: recs, refresh_s=99)
        r.refresh_once()
        assert r.candidates()[0][0] == "old"

    def test_request_fingerprint_matches_engine_pool_key(self):
        toks = list(range(2, 18))
        assert request_fingerprint({"tokens": toks}) == \
            prompt_fingerprint(np.asarray(toks, np.int32))
        assert request_fingerprint({"text": "a cat"}) is not None
        assert request_fingerprint({}) is None


class TestFailover:
    def _serve(self, engine):
        httpd = ServingHTTPServer(("127.0.0.1", 0), engine)
        th = threading.Thread(target=httpd.serve_forever, daemon=True)
        th.start()
        return httpd, th, f"http://127.0.0.1:{httpd.server_address[1]}"

    def test_engine_dies_mid_request_retried_elsewhere(self, flat_setup):
        """THE failover case: the placed engine stops mid-request (its
        outstanding handles resolve with the typed stopped marker →
        503); the router retries on the surviving engine and the client
        gets the exact solo codes. Nothing is orphaned on the dead
        engine. The admit-stall chaos seam holds the request in the
        dying engine until the engine is told to stop (the stall's end
        is the test's to give, not 0.6 s of the box's clock), so the
        order of the race is fixed whatever the load."""
        from dalle_tpu.serving.chaos import ServeChaos, ServeFaultPlan
        cfg, params = flat_setup
        text = _text(cfg)
        stalled = threading.Event()

        class HeldAtAdmit(ServeChaos):
            def _stall(self, rule, roll):
                self._count("stall")
                stalled.set()
                with dying._cv:         # stop() notifies under it
                    dying._cv.wait_for(lambda: dying._stopping, timeout=60)

        chaos = HeldAtAdmit(ServeFaultPlan.from_dict(
            {"seed": 0, "rules": [{"ops": ["admit"],
                                   "stall_s": [0.6, 0.6]}]}))
        dying = DecodeEngine(params, cfg,
                             ServingConfig(n_slots=2, steps_per_call=4),
                             sampling=SAM, chaos=chaos).start()
        backup = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=2, steps_per_call=4),
                              sampling=SAM).start()
        h1, t1, u1 = self._serve(dying)
        h2, t2, u2 = self._serve(backup)
        table = {"a-dying": dict(_rec("a-dying", url=u1)),
                 "b-backup": dict(_rec("b-backup", url=u2, depth=50))}
        router = Router(lambda: {k: dict(v, t=get_dht_time())
                                 for k, v in table.items()},
                        refresh_s=0.1).start()
        router.refresh_once()
        rh = RouterHTTPServer(("127.0.0.1", 0), router,
                              request_timeout_s=60)
        rth = threading.Thread(target=rh.serve_forever, daemon=True)
        rth.start()
        rurl = f"http://127.0.0.1:{rh.server_address[1]}"
        try:
            result = {}

            def client():
                result["status"], result["reply"] = _post(
                    rurl, {"tokens": text.tolist(), "seed": 5})

            t = threading.Thread(target=client, daemon=True)
            t.start()
            assert stalled.wait(60)   # inside the admit stall: counted
            assert chaos.injected == {"stall": 1}
            table["b-backup"]["queue_depth"] = 0   # backup now best
            dying.stop(drain=False)   # the engine dies mid-request
            t.join(timeout=90)
            assert not t.is_alive()
            assert result["status"] == 200
            codes = np.asarray(result["reply"]["results"][0]["codes"],
                               np.int32)
            assert np.array_equal(
                codes,
                _solo(params, cfg, text,
                      jax.random.fold_in(jax.random.PRNGKey(5), 0)))
            assert router.stats()["ledger"]["failovers"] >= 1
            # nothing orphaned on the dead engine
            assert all(h.done() for h in dying._handles.values())
            assert not any(dying._slots)
        finally:
            rh.shutdown()
            rh.server_close()
            router.stop()
            for h in (h1, h2):
                h.shutdown()
                h.server_close()
            dying.stop(drain=False)
            backup.stop(drain=False)
            for th in (t1, t2, rth):
                th.join(timeout=10)

    def test_router_client_vanish_severs_the_attempt(self, flat_setup):
        """A client that hangs up while the router waits on an engine
        must not leave the engine decoding for nobody: the router's
        EOF probe severs the engine connection, the engine's own
        vanished-client probe cancels the work, and the router ledger
        records the client_gone terminal."""
        import socket as socket_mod
        from dalle_tpu.serving.chaos import ServeChaos, ServeFaultPlan
        cfg, params = flat_setup
        text = _text(cfg)
        chaos = ServeChaos(ServeFaultPlan.from_dict(
            {"seed": 0, "rules": [{"ops": ["admit"],
                                   "stall_s": [0.8, 0.8]}]}))
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=2, steps_per_call=4),
                              sampling=SAM, chaos=chaos).start()
        h, th, url = self._serve(engine)
        router = Router(lambda: {"e": dict(_rec("e", url=url),
                                           t=get_dht_time())},
                        refresh_s=0.1).start()
        router.refresh_once()
        rh = RouterHTTPServer(("127.0.0.1", 0), router,
                              request_timeout_s=60)
        rth = threading.Thread(target=rh.serve_forever, daemon=True)
        rth.start()
        try:
            body = json.dumps({"tokens": text.tolist(),
                               "seed": 3}).encode()
            raw = (b"POST /generate HTTP/1.1\r\nHost: r\r\n"
                   b"Content-Type: application/json\r\n"
                   b"Content-Length: " + str(len(body)).encode()
                   + b"\r\n\r\n" + body)
            s = socket_mod.create_connection(
                ("127.0.0.1", rh.server_address[1]), timeout=10)
            s.sendall(raw)
            time.sleep(0.3)        # inside the engine's admit stall
            s.close()              # the client vanishes
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                led = router.stats()["ledger"]
                if led["client_gone"] == 1 and not any(engine._slots) \
                        and all(hd.done()
                                for hd in engine._handles.values()):
                    break
                time.sleep(0.1)
            led = router.stats()["ledger"]
            assert led["client_gone"] == 1, led
            assert not router.stats()["inflight"]
            # the engine's work was cancelled, not decoded for nobody
            assert not any(engine._slots)
            assert all(hd.done() for hd in engine._handles.values())
        finally:
            rh.shutdown()
            rh.server_close()
            router.stop()
            h.shutdown()
            h.server_close()
            engine.stop(drain=False)
            for t in (th, rth):
                t.join(timeout=10)

    def test_all_engines_down_clean_503(self):
        r = Router(lambda: {}, refresh_s=99)
        r.refresh_once()
        rh = RouterHTTPServer(("127.0.0.1", 0), r, request_timeout_s=5)
        th = threading.Thread(target=rh.serve_forever, daemon=True)
        th.start()
        url = f"http://127.0.0.1:{rh.server_address[1]}"
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(url, {"tokens": [1, 2], "seed": 0})
            assert exc.value.code == 503
            assert json.loads(exc.value.read())["error"] \
                == "no engine available"
            # /readyz agrees: nothing placeable
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(url + "/readyz", timeout=5)
            assert exc.value.code == 503
            assert r.stats()["ledger"]["no_engine"] == 1
        finally:
            rh.shutdown()
            rh.server_close()
            r.stop()
            th.join(timeout=10)

    def test_unreachable_engine_fails_over(self, flat_setup):
        """A record pointing at a dead port (the engine process is
        gone but its record lingers fresh): connection refused →
        next-best engine serves."""
        cfg, params = flat_setup
        text = _text(cfg)
        live = DecodeEngine(params, cfg,
                            ServingConfig(n_slots=2, steps_per_call=4),
                            sampling=SAM).start()
        h, th, url = self._serve(live)
        # the live engine starts 3 waves deep so the ghost is STRICTLY
        # preferred (beyond the affinity slack): the request must try
        # the dead port first and fail over
        recs = {"a-ghost": _rec("a-ghost", url="http://127.0.0.1:9"),
                "b-live": _rec("b-live", url=url, depth=6)}
        router = Router(lambda: {k: dict(v, t=get_dht_time())
                                 for k, v in recs.items()},
                        refresh_s=99).start()
        router.refresh_once()
        rh = RouterHTTPServer(("127.0.0.1", 0), router,
                              request_timeout_s=60)
        rth = threading.Thread(target=rh.serve_forever, daemon=True)
        rth.start()
        try:
            status, reply = _post(
                f"http://127.0.0.1:{rh.server_address[1]}",
                {"tokens": text.tolist(), "seed": 9})
            assert status == 200
            assert np.array_equal(
                np.asarray(reply["results"][0]["codes"], np.int32),
                _solo(params, cfg, text,
                      jax.random.fold_in(jax.random.PRNGKey(9), 0)))
            assert router.stats()["ledger"]["failovers"] == 1
        finally:
            rh.shutdown()
            rh.server_close()
            router.stop()
            h.shutdown()
            h.server_close()
            live.stop(drain=False)
            for t in (th, rth):
                t.join(timeout=10)


class TestRouterBench:
    @pytest.mark.slow
    def test_quick_router_bench_writes_valid_rows(self, tmp_path):
        """scripts/serve_bench.py --router --quick emits the three
        ROUTER_BENCH.json rows (single / router / summary) with the
        per-row TTFT hit/miss split. Slow-marked like every bench path
        (pytest.ini); numbers are not meaningful at --quick."""
        import os
        import subprocess
        import sys
        from pathlib import Path
        repo = Path(__file__).resolve().parent.parent
        out = tmp_path / "ROUTER_BENCH.json"
        proc = subprocess.run(
            [sys.executable, str(repo / "scripts" / "serve_bench.py"),
             "--router", "--quick", "--out", str(out)],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, proc.stderr[-2000:]
        rows = [json.loads(line) for line in
                out.read_text().splitlines() if line.strip()]
        modes = [r["mode"] for r in rows]
        assert modes == ["single", "router", "summary"]
        router_row = rows[1]
        assert "prefix_hits" in router_row
        assert router_row["router_ledger"]["requests"] \
            == router_row["completed"]
        assert "speedup" in rows[2]


class TestRouterSmoke:
    def test_fast_router_smoke(self, flat_setup):
        """The tier-1 router gate (pytest.ini): two engines with
        prefix pools behind the router, a duplicate-heavy trace —
        every reply bit-equal to its solo reference, duplicates land
        warm, the router ledger closes, no threads leak."""
        cfg, params = flat_setup
        buckets = resolve_buckets(None, 2)
        threads_before = set(threading.enumerate())
        engines, servers, sthreads, urls = [], [], [], []
        for _ in range(2):
            e = DecodeEngine(
                params, cfg,
                ServingConfig(n_slots=2, steps_per_call=4,
                              prefix_cache_mb=4.0),
                sampling=SAM).start()
            hs = ServingHTTPServer(("127.0.0.1", 0), e)
            t = threading.Thread(target=hs.serve_forever, daemon=True)
            t.start()
            engines.append(e)
            servers.append(hs)
            sthreads.append(t)
            urls.append(f"http://127.0.0.1:{hs.server_address[1]}")

        def fetch():
            return {f"eng{i}": engine_record(engines[i], urls[i])
                    for i in range(2)}

        router = Router(fetch, refresh_s=0.2).start()
        router.refresh_once()
        rh = RouterHTTPServer(("127.0.0.1", 0), router,
                              request_timeout_s=120)
        rth = threading.Thread(target=rh.serve_forever, daemon=True)
        rth.start()
        rurl = f"http://127.0.0.1:{rh.server_address[1]}"
        try:
            texts = [_text(cfg, 200), _text(cfg, 201)]
            trace = [0, 1, 0, 0, 1, 0]      # duplicate-heavy
            rows = []
            for i, ti in enumerate(trace):
                status, reply = _post(
                    rurl, {"tokens": texts[ti].tolist(), "seed": i})
                assert status == 200
                rows.append(reply["results"][0])
            for i, (ti, row) in enumerate(zip(trace, rows)):
                assert np.array_equal(
                    np.asarray(row["codes"], np.int32),
                    _solo(params, cfg, texts[ti],
                          jax.random.fold_in(jax.random.PRNGKey(i), 0),
                          buckets))
            assert sum(1 for r in rows if r.get("prefix_hit")) >= 2
            led = router.stats()["ledger"]
            assert led["requests"] == len(trace)
            assert led["completed"] == len(trace)
            assert led["requests"] == led["completed"] \
                + led["relayed_errors"] + led["no_engine"] \
                + led["client_gone"]
        finally:
            rh.shutdown()
            rh.server_close()
            router.stop()
            for hs in servers:
                hs.shutdown()
                hs.server_close()
            for e in engines:
                e.stop()
            for t in sthreads + [rth]:
                t.join(timeout=10)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            leaked = [t for t in threading.enumerate()
                      if t not in threads_before and t.is_alive()]
            if not leaked:
                break
            time.sleep(0.1)
        assert not leaked, leaked
