"""Flight recorder + exposition tests (dalle_tpu/obs, OBSERVABILITY.md).

The contracts pinned here, in order of load-bearing-ness:

- **transparency**: recorder OFF is the uninstrumented path (the
  disabled span is one shared singleton — zero allocation), and
  recorder ON never touches the data: an engine with a tracer emits
  bit-identical codes, an allreduce with the report dict produces
  byte-identical averages.
- **overhead budget**: total recording cost (spans recorded x measured
  per-span cost) stays under a fixed percent of the engine run and of
  a real loopback allreduce round. The budget multiplies two numbers
  measured in the SAME process run, so it holds on a loaded 2-core box
  where wall-vs-wall A/B comparisons flake.
- **the failure-dump path**: a forced oracle failure in a churn-soak
  SUBPROCESS emits SOAK_FLIGHT.json whose last-round spans identify
  the injected fault's peer and phase, plus the always-on merged
  cross-peer timeline artifact.
- **exposition**: /metrics parses as Prometheus text and agrees with
  the /stats ledger (same snapshot source), histograms are cumulative
  and monotone.
- **fetch_metrics edges**: a peer republishing under a new epoch
  supersedes (never double-counts) its prior record; a bound-but-stale
  subkey is dropped, not crashed; pre-r16 records (no proof counters)
  still validate.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from dalle_tpu.config import ServingConfig, tiny_model_config
from dalle_tpu.models.dalle import DALLE, init_params
from dalle_tpu.models.decode import SamplingConfig
from dalle_tpu.obs.exposition import (MetricsRegistry, parse_text,
                                      serving_source, tracer_source)
from dalle_tpu.obs.trace import (NULL_SPAN, Tracer, load_jsonl,
                                 merge_rows, span)
from dalle_tpu.serving.engine import DecodeEngine
from dalle_tpu.serving.server import ServingHTTPServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SAM = SamplingConfig(temperature=1.0, top_k=8)


@pytest.fixture(scope="module")
def flat_setup():
    cfg = tiny_model_config(attn_types=("axial_row", "axial_col"),
                            depth=2)
    params = init_params(DALLE(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _text(cfg, seed=3):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (cfg.text_seq_len,), 2,
        cfg.vocab_text))


# -- tracer core ----------------------------------------------------------

class TestTracer:
    def test_span_records_duration_trace_and_attrs(self):
        t = Tracer(peer="p0")
        with t.span("swarm", "matchmaking", "run:grads:7", group=3) as sp:
            sp.set(extra=1)
        t.event("serving", "submit", "req:9", lane="high")
        rows = t.dump()
        assert [r["phase"] for r in rows] == ["matchmaking", "submit"]
        assert rows[0]["trace"] == "run:grads:7"
        assert rows[0]["dur_s"] >= 0 and rows[0]["a"] == {"group": 3,
                                                          "extra": 1}
        assert rows[1]["dur_s"] == 0.0 and rows[1]["peer"] == "p0"

    def test_span_annotates_error_and_reraises(self):
        t = Tracer()
        with pytest.raises(ValueError):
            with t.span("swarm", "allreduce", "r:0"):
                raise ValueError("boom")
        (row,) = t.dump()
        assert row["a"]["error"] == "ValueError"

    def test_disabled_span_is_the_shared_singleton(self):
        """The zero-allocation proof: span(None, ...) returns the SAME
        object every time — the disabled path builds nothing."""
        a = span(None, "swarm", "x", "t", attr=1)
        b = span(None, "serving", "y", "u")
        assert a is NULL_SPAN and b is NULL_SPAN
        # a library caller's tracer=None: the trace-less form too
        assert span(None, "train", "collab/step", samples=8) is NULL_SPAN
        with a as sp:
            assert sp.set(anything=1) is NULL_SPAN

    def test_spans_nest_and_carry_parent_and_trace(self):
        """A row says which step or round it belongs to and which span
        caused it; a span opened with no trace is its parent's."""
        t = Tracer(peer="p")
        with t.span("train", "loop/step", "step:7"):
            with t.span("train", "collab/step") as inner:
                assert inner.trace == "step:7"
                t.event("train", "jit/compile", program="f")
                with t.span("train", "collab/accumulate", samples=8):
                    assert [s.phase for s in t.open_spans()] == [
                        "loop/step", "collab/step", "collab/accumulate"]
            with t.span("swarm", "apply", "run:grads:3"):
                pass
        assert t.open_spans() == []
        with t.span("train", "setup/dht"):
            pass
        rows = {r["phase"]: r for r in t.dump()}
        assert "parent" not in rows["loop/step"]
        assert rows["collab/step"]["parent"] == "loop/step"
        assert rows["collab/accumulate"]["parent"] == "collab/step"
        assert rows["jit/compile"]["parent"] == "collab/step"
        assert rows["jit/compile"]["dur_s"] == 0.0
        assert {rows[p]["trace"] for p in (
            "loop/step", "collab/step", "collab/accumulate",
            "jit/compile")} == {"step:7"}
        # an explicit trace (a round id) wins over the parent's
        assert rows["apply"]["trace"] == "run:grads:3"
        assert rows["apply"]["parent"] == "loop/step"
        assert rows["setup/dht"]["trace"] == "-"
        assert t.closed("train", "loop/step") == 1
        assert t.closed("train", "jit/compile") == 0   # events: not spans
        # each thread has its own stack: another thread's span has no
        # parent here
        seen = []
        with t.span("train", "loop/step", "step:8"):
            worker = threading.Thread(
                target=lambda: seen.append(list(t.open_spans())))
            worker.start()
            worker.join(timeout=10)
        assert seen == [[]]

    def test_span_is_also_a_profiler_annotation_inside_the_row(self):
        """With an annotation factory a live span enters
        ``<plane>/<phase>`` in the profiler, inside the row's own clock
        reads; rows from pre-measured walls have no event."""
        log = []
        ticks = iter(range(100))

        class Note:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                log.append(("enter", self.name, next(ticks)))

            def __exit__(self, *exc):
                log.append(("exit", self.name, next(ticks)))

        t = Tracer(annotate=Note, clock=lambda: float(next(ticks)))
        with t.span("train", "loop/step", "step:1"):
            with t.span("train", "collab/step"):
                pass
        t.add("swarm", "apply", "r:0", 0.0, 1.0)
        t.event("train", "jit/compile")
        assert [(kind, name) for kind, name, _ in log] == [
            ("enter", "train/loop/step"), ("enter", "train/collab/step"),
            ("exit", "train/collab/step"), ("exit", "train/loop/step")]
        when = {(kind, name): tick for kind, name, tick in log}
        for row in t.dump():
            name = f"{row['plane']}/{row['phase']}"
            if ("enter", name) in when:
                assert row["t0"] < when["enter", name]
                assert when["exit", name] < row["t0"] + row["dur_s"]

    def test_obs_imports_and_records_without_jax(self):
        """``dalle_tpu.obs`` is stdlib-only (``scripts/trace_report.py``
        runs on a box with nothing installed): the annotation factory is
        injected by the entry point, never imported."""
        code = (
            "import sys\n"
            "sys.modules['jax'] = None\n"      # any import of it raises
            "import dalle_tpu.obs as obs\n"
            "import dalle_tpu.obs.compiles\n"
            "t = obs.configure(peer='x')\n"
            "with obs.span(t, 'train', 'loop/step', 'step:1'):\n"
            "    with t.span('train', 'collab/step'):\n"
            "        pass\n"
            "rows = obs.default_tracer().dump()\n"
            "assert [r['phase'] for r in rows] == "
            "['collab/step', 'loop/step'], rows\n"
            "assert rows[0]['parent'] == 'loop/step'\n"
            "assert 'jax' not in [m for m in sys.modules "
            "if sys.modules[m] is not None]\n")
        done = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0, done.stderr[-2000:]

    def test_ring_byte_cap_evicts_oldest(self):
        t = Tracer(ring_bytes=2048)
        for i in range(200):
            t.event("swarm", "apply", f"r:{i}")
        rows = t.dump()
        assert t.ring_evictions > 0
        assert len(rows) < 200
        # oldest evicted, newest kept, order preserved
        assert rows[-1]["trace"] == "r:199"
        traces = [int(r["trace"].split(":")[1]) for r in rows]
        assert traces == sorted(traces)

    def test_last_rounds_keeps_n_distinct_traces(self):
        t = Tracer()
        for e in range(6):
            t.event("swarm", "matchmaking", f"r:{e}")
            t.event("swarm", "apply", f"r:{e}")
        last = t.last_rounds(2)
        assert {r["trace"] for r in last} == {"r:4", "r:5"}
        assert len(last) == 4

    def test_jsonl_sink_roundtrip_and_torn_line(self, tmp_path):
        path = str(tmp_path / "p0.jsonl")
        t = Tracer(peer="p0", sink_path=path)
        t.event("swarm", "apply", "r:0", n=1)
        t.event("swarm", "apply", "r:1")
        t.flush()
        with open(path, "a") as fh:
            fh.write('{"torn": ')  # crash mid-append
        rows = load_jsonl(path)
        assert [r["trace"] for r in rows] == ["r:0", "r:1"]
        assert rows[0]["a"] == {"n": 1}

    def test_merge_rows_orders_by_trace_then_peer(self):
        a = [{"peer": "p1", "trace": "r:1", "t0": 5.0, "phase": "x"},
             {"peer": "p1", "trace": "r:0", "t0": 9.0, "phase": "x"}]
        b = [{"peer": "p0", "trace": "r:1", "t0": 2.0, "phase": "x"}]
        merged = merge_rows([a, b])
        assert [(r["trace"], r["peer"]) for r in merged] == [
            ("r:0", "p1"), ("r:1", "p0"), ("r:1", "p1")]

    def test_merge_rows_natural_orders_numeric_epochs(self):
        """Round 10 sorts AFTER round 9 (lexicographic order would put
        run:grads:10 before run:grads:2 and misorder every timeline
        past epoch 9)."""
        rows = [{"peer": "p", "trace": f"run:grads:{e}", "t0": float(e),
                 "phase": "x"} for e in (10, 2, 9, 11, 1)]
        merged = merge_rows([rows])
        assert [r["trace"].rsplit(":", 1)[1] for r in merged] == [
            "1", "2", "9", "10", "11"]

    def test_histogram_is_cumulative_and_monotone(self):
        t = Tracer()
        for d in (0.0005, 0.003, 0.003, 0.2, 40.0):
            t.add("swarm", "allreduce", "r:0", 0.0, d)
        # events are markers, not latencies: they ride the ring but
        # never the phase histograms (trace_report's treatment)
        t.event("swarm", "allreduce", "r:0")
        t.event("serving", "submit", "req:1")
        assert ("serving", "submit") not in t.histogram_snapshot()
        h = t.histogram_snapshot()[("swarm", "allreduce")]
        counts = [c for _le, c in h["buckets"]]
        assert counts == sorted(counts)          # cumulative
        assert h["buckets"][-1] == ("+Inf", 5)   # total in +Inf
        assert h["count"] == 5
        assert abs(h["sum"] - 40.2065) < 1e-6


# -- overhead budget ------------------------------------------------------

def _per_span_cost_s(n: int = 4000) -> float:
    t = Tracer(ring_bytes=64 * 1024)
    t0 = time.perf_counter()
    for i in range(n):
        t.add("serving", "chunk", "engine", 0.0, 0.001, live=2)
    return (time.perf_counter() - t0) / n


def _per_live_span_cost_s(tracer: Tracer, n: int = 4000) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        with tracer.span("train", "loop/step", "step:1"):
            with tracer.span("train", "collab/step", samples=8):
                pass
    return (time.perf_counter() - t0) / (2 * n)


class TestOverheadBudget:
    #: recording cost must stay under this fraction of the measured
    #: work it observes (the CI budget the issue pins)
    BUDGET_FRAC = 0.05

    def test_annotated_span_cost_with_no_profiler_session(self):
        """A trainer's span is a ring row and a ``TraceAnnotation``: with
        no profiler session running the two together stay under 20 us a
        span (a dozen a step against a step of seconds). Best of three,
        so that one descheduling on a loaded box does not fail it."""
        tracer = Tracer(ring_bytes=64 * 1024,
                        annotate=jax.profiler.TraceAnnotation)
        cost = min(_per_live_span_cost_s(tracer) for _ in range(3))
        assert cost <= 20e-6, f"{cost * 1e6:.1f} us a span"
        assert tracer.open_spans() == []

    def test_per_span_cost_is_bounded(self):
        # generous absolute ceiling (~100x the typical few-us cost) so
        # the pin survives the 2-core box's scheduling noise
        assert _per_span_cost_s() < 5e-4

    def test_engine_chunk_loop_overhead_within_budget(self, flat_setup):
        """Spans recorded during a real engine run x measured per-span
        cost <= BUDGET_FRAC of the run's wall. Both factors come from
        this process, so the bound is load-independent."""
        cfg, params = flat_setup
        tracer = Tracer(peer="engine")
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=2, steps_per_call=4),
                              sampling=SAM, tracer=tracer).start()
        try:
            t0 = time.perf_counter()
            handles = [engine.submit(_text(cfg, s), jax.random.PRNGKey(s))
                       for s in (11, 12, 13)]
            for h in handles:
                h.result(timeout=300)
            wall = time.perf_counter() - t0
        finally:
            engine.stop()
        assert tracer.spans_recorded > 0
        overhead = tracer.spans_recorded * _per_span_cost_s()
        assert overhead <= self.BUDGET_FRAC * wall, (
            f"recording cost {overhead:.4f}s exceeds "
            f"{self.BUDGET_FRAC:.0%} of the {wall:.3f}s engine run "
            f"({tracer.spans_recorded} spans)")
        # the request timeline actually materialized
        phases = {r["phase"] for r in tracer.dump()}
        assert {"submit", "admit", "first_code", "harvest", "complete",
                "chunk"} <= phases

    def test_allreduce_round_overhead_within_budget(self):
        """Same budget against one real 2-peer loopback round with the
        soak harness's span set around it."""
        from dalle_tpu.swarm import DHT, compression
        from dalle_tpu.swarm.identity import Ed25519PrivateKey, Identity
        from dalle_tpu.swarm.matchmaking import make_group
        from dalle_tpu.swarm.allreduce import run_allreduce
        from dalle_tpu.obs.trace import span as obs_span

        nodes = []
        for i in range(2):
            peers = [nodes[0].visible_address] if nodes else []
            ident = Identity(Ed25519PrivateKey.from_private_bytes(
                bytes([61 + i]) * 32))
            nodes.append(DHT(initial_peers=peers, identity=ident,
                             rpc_timeout=2.0))
        tracers = [Tracer(peer=f"p{i}") for i in range(2)]
        grads = np.arange(2048, dtype=np.float32)
        results = [None, None]
        errors = []

        def peer(i):
            try:
                tr = tracers[i]
                with obs_span(tr, "swarm", "matchmaking", "obs:0"):
                    g = make_group(nodes[i], "obs", epoch=0, weight=1.0,
                                   matchmaking_time=3.0,
                                   min_group_size=2)
                assert g is not None and g.size == 2
                with obs_span(tr, "swarm", "allreduce", "obs:0",
                              group=g.size):
                    out = run_allreduce(
                        nodes[i], g, "obs", 0, [grads], weight=1.0,
                        allreduce_timeout=10.0,
                        codec=compression.UNIFORM8BIT, chunk_elems=512)
                results[i] = out[0]
            except BaseException as e:  # noqa: BLE001 - surfaced below
                errors.append(e)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=peer, args=(i,))
                   for i in range(2)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            for n in nodes:
                n.shutdown()
        assert not errors, errors
        wall = time.perf_counter() - t0
        np.testing.assert_array_equal(results[0], results[1])
        spans = sum(t.spans_recorded for t in tracers)
        assert spans == 4
        overhead = spans * _per_span_cost_s()
        assert overhead <= self.BUDGET_FRAC * wall, (
            f"{overhead:.5f}s of recording vs {wall:.3f}s round")


# -- transparency ---------------------------------------------------------

class TestTransparency:
    def test_engine_codes_identical_with_and_without_tracer(
            self, flat_setup):
        """Recorder ON observes, never perturbs: same seed, same codes,
        bit for bit — and OFF is the same code path minus the
        `is None` tests, so both sides of the pin hold."""
        cfg, params = flat_setup
        text, key = _text(cfg, 21), jax.random.PRNGKey(77)

        def run(tracer):
            engine = DecodeEngine(
                params, cfg, ServingConfig(n_slots=1, steps_per_call=4),
                sampling=SAM, tracer=tracer).start()
            try:
                return engine.submit(text, key).result(timeout=300)
            finally:
                engine.stop()

        off = run(None)
        on = run(Tracer(peer="e"))
        np.testing.assert_array_equal(off["codes"], on["codes"])

    def test_allreduce_bytes_identical_with_and_without_report(self):
        """The optimizer requests the wire report only when tracing —
        this pins that the report dict is write-only telemetry: averaged
        bytes are identical either way."""
        from dalle_tpu.swarm import DHT, compression
        from dalle_tpu.swarm.identity import Ed25519PrivateKey, Identity
        from dalle_tpu.swarm.matchmaking import make_group
        from dalle_tpu.swarm.allreduce import run_allreduce

        rng = np.random.RandomState(5)
        tensors = [rng.randn(1024).astype(np.float32) for _ in range(2)]

        def round_once(with_report):
            nodes = []
            for i in range(2):
                peers = [nodes[0].visible_address] if nodes else []
                ident = Identity(Ed25519PrivateKey.from_private_bytes(
                    bytes([71 + i]) * 32))
                nodes.append(DHT(initial_peers=peers, identity=ident,
                                 rpc_timeout=2.0))
            results = [None, None]
            errors = []

            def peer(i):
                try:
                    g = make_group(nodes[i], "tp", epoch=0, weight=1.0,
                                   matchmaking_time=3.0,
                                   min_group_size=2)
                    assert g is not None and g.size == 2
                    rep = {} if with_report else None
                    results[i] = run_allreduce(
                        nodes[i], g, "tp", 0, [tensors[i]], weight=1.0,
                        allreduce_timeout=10.0,
                        codec=compression.UNIFORM8BIT, chunk_elems=256,
                        report=rep)[0]
                    if with_report:
                        assert "phases" in rep and rep["complete"]
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=peer, args=(i,))
                       for i in range(2)]
            try:
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=60)
            finally:
                for n in nodes:
                    n.shutdown()
            assert not errors, errors
            return results

        without = round_once(with_report=False)
        with_rep = round_once(with_report=True)
        for a, b in zip(without, with_rep):
            np.testing.assert_array_equal(a, b)


# -- exposition -----------------------------------------------------------

class TestExposition:
    def test_render_escapes_and_types(self):
        reg = MetricsRegistry()
        reg.register("x", lambda: [
            {"name": "dalle_test_ops", "type": "counter",
             "help": "ops", "samples": [("_total", {}, 3)]},
            {"name": "dalle_test_gauge", "type": "gauge",
             "samples": [("", {"k": 'a"b\nc\\d'}, 1.5)]},
        ])
        text = reg.render()
        assert "# TYPE dalle_test_ops counter" in text
        assert "dalle_test_ops_total 3" in text
        assert '{k="a\\"b\\nc\\\\d"}' in text
        parsed = parse_text(text)
        assert parsed["dalle_test_ops_total"][""] == 3.0

    def test_failing_source_degrades_not_500(self):
        reg = MetricsRegistry()
        reg.register("bad", lambda: (_ for _ in ()).throw(
            RuntimeError("dead plane")))
        # malformed FAMILY (missing "samples") must lose only its own
        # source's lines, never the page — the guard covers rendering
        reg.register("malformed", lambda: [
            {"name": "dalle_half", "type": "gauge",
             "samples": [("", {}, 2)]},
            {"name": "dalle_broken", "type": "gauge"}])
        reg.register("good", lambda: [
            {"name": "dalle_ok", "type": "gauge",
             "samples": [("", {}, 1)]}])
        text = reg.render()
        assert "dalle_ok 1" in text
        assert "dalle_half" not in text  # its source failed mid-render

    def test_http_metrics_agrees_with_stats_ledger(self, flat_setup):
        """THE exposition identity: /metrics counters == the /stats
        JSON ledger (one snapshot source), and the text parses as
        Prometheus format including the span histograms."""
        cfg, params = flat_setup
        tracer = Tracer(peer="engine")
        engine = DecodeEngine(params, cfg,
                              ServingConfig(n_slots=1, steps_per_call=4),
                              sampling=SAM, tracer=tracer).start()
        httpd = ServingHTTPServer(("127.0.0.1", 0), engine,
                                  request_timeout_s=300.0)
        thread = threading.Thread(target=httpd.serve_forever,
                                  daemon=True)
        thread.start()
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            body = json.dumps(
                {"tokens": _text(cfg, 31).tolist(), "seed": 5}).encode()
            req = urllib.request.Request(
                url + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as resp:
                assert resp.status == 200
            with urllib.request.urlopen(url + "/metrics",
                                        timeout=30) as resp:
                assert resp.headers["Content-Type"].startswith(
                    "text/plain")
                metrics = parse_text(resp.read().decode())
            with urllib.request.urlopen(url + "/stats",
                                        timeout=30) as resp:
                stats = json.loads(resp.read())
        finally:
            httpd.shutdown()
            httpd.server_close()
            engine.stop()
            thread.join(timeout=10)
        for key in ("submitted", "admitted", "completed", "cancelled",
                    "failed", "shed"):
            assert metrics[f"dalle_serving_{key}_total"][""] \
                == stats[key], key
        assert stats["submitted"] == stats["completed"] == 1
        # per-lane family carries the lane label
        assert metrics["dalle_serving_lane_completed_total"][
            '{lane="high"}'] == 1.0
        # span-derived histogram rode along (engine had a tracer)
        assert any(k.startswith("dalle_phase_latency_seconds")
                   for k in metrics)
        buckets = metrics["dalle_phase_latency_seconds_bucket"]
        chunk = {k: v for k, v in buckets.items() if 'phase="chunk"' in k}
        assert chunk, buckets
        assert max(chunk.values()) == metrics[
            "dalle_phase_latency_seconds_count"][
                '{phase="chunk",plane="serving"}']


# -- trace_report ---------------------------------------------------------

class TestTraceReport:
    def _rows(self):
        rows = []
        for epoch in range(4):
            for peer, dur in (("p0", 0.1), ("p1", 0.1), ("p2", 0.9)):
                rows.append({"v": 1, "peer": peer, "plane": "swarm",
                             "phase": "allreduce",
                             "trace": f"run:{epoch}",
                             "t0": 100.0 + epoch * 10, "dur_s": dur})
        # one silent gap inside p0's own timeline of run:0
        rows.append({"v": 1, "peer": "p0", "plane": "swarm",
                     "phase": "apply", "trace": "run:0",
                     "t0": 100.1 + 5.0, "dur_s": 0.01})
        return rows

    def test_phase_table_stragglers_and_gaps(self, tmp_path):
        from scripts.trace_report import build_report
        rows = self._rows()
        by_peer = {}
        for r in rows:
            by_peer.setdefault(r["peer"], []).append(r)
        files = []
        for peer, prs in by_peer.items():
            p = tmp_path / f"{peer}.jsonl"
            p.write_text("".join(json.dumps(r) + "\n" for r in prs))
            files.append(str(p))
        rep = build_report(sorted(files), gap_s=1.0, rounds=True)
        assert rep["peers"] == ["p0", "p1", "p2"]
        ph = rep["phases"]["swarm:allreduce"]
        assert ph["n"] == 12 and abs(ph["p50_s"] - 0.1) < 1e-9
        assert ph["max_s"] == 0.9
        # p2 drags EVERY round: straggler attribution names it
        assert rep["stragglers"]["straggles_by_peer"] == {"p2": 4}
        assert rep["stragglers"]["worst"]["peer"] == "p2"
        # the silent window inside p0's run:0 timeline is detected
        assert any(g["peer"] == "p0" and g["trace"] == "run:0"
                   and g["gap_s"] > 1.0 for g in rep["gaps"])
        assert {r["trace"] for r in rep["rounds"]} == {
            f"run:{e}" for e in range(4)}


# -- fetch_metrics aggregation edges (satellite) --------------------------

class _Item:
    def __init__(self, value):
        self.value = value


class _StubDHT:
    """Just enough of the DHT surface for fetch_metrics: a canned
    subkey map + a canned identity binding."""

    peer_id = "me"

    def __init__(self, entries, bound):
        self._entries = entries
        self._bound = bound

    def get(self, key):
        return self._entries

    def bound_peer_id(self, subkey):
        return self._bound.get(subkey)


class TestFetchMetricsEdges:
    def _record(self, peer_id, epoch, **over):
        row = {"peer_id": peer_id, "epoch": epoch,
               "samples_per_second": 8.0, "samples_accumulated": 64,
               "loss": 2.5, "mini_steps": 4}
        row.update(over)
        return row

    def test_republish_under_new_epoch_supersedes(self):
        """One peer, two publishes (epoch 1 then 2) through a REAL DHT
        node: the subkey is the peer id, so the second record replaces
        the first — fetch returns exactly one record at the new epoch
        and the aux aggregate counts ONE alive peer."""
        from dalle_tpu.cli.run_aux_peer import aggregate
        from dalle_tpu.swarm import DHT, Identity
        from dalle_tpu.swarm.metrics import (LocalMetrics, fetch_metrics,
                                             publish_metrics)
        node = DHT(identity=Identity.generate(), rpc_timeout=2.0)
        try:
            for epoch in (1, 2):
                assert publish_metrics(
                    node, "exp",
                    LocalMetrics(**self._record(
                        node.peer_id, epoch, proofs_published=epoch)))
            got = fetch_metrics(node, "exp")
            assert len(got) == 1, "stale epoch-1 record double-counted"
            assert got[0].epoch == 2
            assert got[0].proofs_published == 2
            agg = aggregate(got)
            assert agg["alive_peers"] == 1 and agg["epoch"] == 2
            assert agg["proofs_published"] == 2
        finally:
            node.shutdown()

    def test_bound_but_stale_subkey_dropped_not_crashed(self):
        """Records whose subkey still binds an identity but whose VALUE
        is stale garbage (schema drift, truncated payload, identity
        mismatch) are skipped defensively — never a crash, never a
        forged identity in the aggregate."""
        from dalle_tpu.swarm.metrics import fetch_metrics
        entries = {
            b"good": _Item(self._record("pA", 3)),
            b"malformed": _Item({"epoch": "NaN-garbage"}),
            b"truncated": _Item(None),
            b"mismatch": _Item(self._record("pEvil", 3)),
            b"unbound": _Item(self._record("pB", 3)),
        }
        bound = {b"good": "pA", b"malformed": "pM",
                 b"truncated": "pT", b"mismatch": "pC"}
        got = fetch_metrics(_StubDHT(entries, bound), "exp")
        assert [m.peer_id for m in got] == ["pA"]

    def test_pre_r16_record_without_proof_counters_validates(self):
        from dalle_tpu.swarm.metrics import LocalMetrics
        m = LocalMetrics(**self._record("old", 1))
        assert m.proofs_published == 0
        assert m.proofs_convicted == 0 and m.proofs_rejected == 0

    def test_aggregate_sums_robustness_counters(self):
        from dalle_tpu.cli.run_aux_peer import aggregate
        from dalle_tpu.swarm.metrics import LocalMetrics
        ms = [LocalMetrics(**self._record(
            f"p{i}", 2, proofs_published=i, proofs_convicted=1,
            parts_audited=10)) for i in range(3)]
        agg = aggregate(ms)
        assert agg["proofs_published"] == 3
        assert agg["proofs_convicted"] == 3
        assert agg["parts_audited"] == 30


# -- the failure-dump path (subprocess, the CI satellite) ------------------

class TestFailureDump:
    def test_forced_oracle_failure_emits_flight_dump(self, tmp_path):
        """churn_soak --inject-oracle-failure in a SUBPROCESS: exit 1,
        SOAK_FLIGHT.json's last-round spans identify the injected
        fault's peer and phase, and the merged cross-peer timeline
        artifact exists and is consumable by trace_report."""
        out = tmp_path / "CHURN.json"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "churn_soak.py"),
             "--peers", "2", "--epochs", "2", "--kills", "0",
             "--joins", "0", "--seed", "5",
             "--matchmaking-time", "0.6", "--allreduce-timeout", "4",
             "--deadline", "90", "--out", str(out),
             "--inject-oracle-failure"],
            cwd=str(tmp_path), env=env, capture_output=True, text=True,
            timeout=180)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        flight = json.loads((tmp_path / "SOAK_FLIGHT.json").read_text())
        assert flight["violations"], "no oracle violation recorded"
        # the last-round spans name the injected fault's peer AND phase
        faults = [r for r in flight["timeline"]
                  if r["phase"] == "fault_injected"]
        assert faults, flight["timeline"]
        assert faults[0]["peer"] == "peer0"
        assert faults[0]["a"]["target_phase"] == "apply"
        assert faults[0]["trace"].endswith(":1")  # the final round
        # the always-on merged timeline artifact, cross-peer
        trace_path = tmp_path / "CHURN_TRACE.jsonl"
        rows = load_jsonl(str(trace_path))
        assert {r["peer"] for r in rows} == {"peer0", "peer1"}
        from scripts.trace_report import build_report
        rep = build_report([str(trace_path)])
        assert "swarm:allreduce" in rep["phases"]
        report = json.loads(out.read_text())
        assert report["pass"] is False
        assert report["artifacts"]["flight"].endswith("SOAK_FLIGHT.json")
        # flight-ring excerpts never bloat the persisted report
        assert all("_spans" not in p for p in report["peers"])


# -- state transfer spans -------------------------------------------------

class TestStateTransferSpans:
    def test_fetch_and_serve_share_the_nonce_trace(self):
        """A state download records a state_fetch span on the client
        and a state_serve span on the server under the SAME
        nonce-derived trace id — the cross-peer correlation needs no
        clock agreement."""
        from dalle_tpu.swarm import DHT, Identity
        from dalle_tpu.swarm.state_transfer import (StateServer,
                                                    load_state_from_peers)
        a = DHT(identity=Identity.generate(), rpc_timeout=2.0)
        b = DHT(initial_peers=[a.visible_address],
                identity=Identity.generate(), rpc_timeout=2.0)
        tr_srv, tr_cli = Tracer(peer="srv"), Tracer(peer="cli")
        state = [np.arange(32, dtype=np.float32)]
        server = StateServer(a, "xfer", lambda: (7, state),
                             announce_period=0.5, tracer=tr_srv).start()
        try:
            result = load_state_from_peers(b, "xfer", timeout=20.0,
                                           tracer=tr_cli)
            assert result is not None and result[0] == 7
            np.testing.assert_array_equal(result[1][0], state[0])
        finally:
            server.stop()
            b.shutdown()
            a.shutdown()
        fetch = [r for r in tr_cli.dump() if r["phase"] == "state_fetch"]
        assert fetch and fetch[-1]["a"]["ok"] is True
        deadline = time.monotonic() + 5.0
        serve = []
        while not serve and time.monotonic() < deadline:
            serve = [r for r in tr_srv.dump()
                     if r["phase"] == "state_serve"]
            time.sleep(0.05)
        assert serve, "server recorded no state_serve span"
        assert serve[-1]["trace"] == fetch[-1]["trace"]
        assert serve[-1]["trace"].startswith("xfer:xfer:")
