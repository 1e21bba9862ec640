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

import collections
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

from dalle_init import init_params
from dalle_tpu.config import ServingConfig, tiny_model_config
from dalle_tpu.models.dalle import DALLE
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

    def test_a_steps_two_edges_stay_under_300_us(self, caplog):
        """What the late-step recorder adds to every step: the span it
        opens and closes for the loop, the kernel's counters, the
        threads' clocks, the compile counter, the device's statistics,
        the step's rows read back from the ring and the median of 32.
        Under 300 us a step (the shortest step of the benchmark is 1.24
        s); best of three."""
        from dalle_tpu.obs import compiles as C
        from dalle_tpu.obs.late import LateSteps
        tracer = Tracer(annotate=jax.profiler.TraceAnnotation)
        device = jax.local_devices()[0]
        allocs = iter(range(1, 10_000))

        def device_memory():              # the call, and a count that moves
            return dict(device.memory_stats() or {"bytes_in_use": 1},
                        num_allocs=next(allocs))
        rec = LateSteps(tracer, compiles=C.CompileCounter(tracer),
                        device_memory=device_memory)
        rec.start()
        try:
            def steps(n, first):
                t0 = time.perf_counter()
                for i in range(first, first + n):
                    with rec.step(i) as row:
                        for phase in ("loop/batch_fetch",
                                      "loop/grad_dispatch", "loop/loss_wait",
                                      "loop/hook", "collab/step"):
                            with tracer.span("train", phase):
                                pass
                        row.set(moe_dense_calls=0.0)
                return (time.perf_counter() - t0) / n
            steps(40, 1)                      # fill the history
            with_it = min(steps(300, 100 + 300 * k) for k in range(3))
        finally:
            import logging
            with caplog.at_level(logging.INFO, logger="dalle_tpu.obs.late"):
                rec.stop()

        def bare(n):
            t0 = time.perf_counter()
            for i in range(n):
                with tracer.span("train", "loop/step", f"step:{i}") as row:
                    for phase in ("loop/batch_fetch", "loop/grad_dispatch",
                                  "loop/loss_wait", "loop/hook",
                                  "collab/step"):
                        with tracer.span("train", phase):
                            pass
                    row.set(moe_dense_calls=0.0)
            return (time.perf_counter() - t0) / n
        without = min(bare(300) for _ in range(3))
        added = with_it - without
        assert added <= 300e-6, f"{added * 1e6:.0f} us a step"
        said = [r.getMessage() for r in caplog.records]
        assert any(" of 940 steps" in line for line in said), said
        assert next(allocs) > 900            # every edge asked the device

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
        assert rep["late_steps"] == []

    def test_late_steps_are_listed_under_the_gaps(self, tmp_path, capsys):
        """The operator's reader of ``loop/late_step`` events: step,
        excess, where, cause, and the line the trainer logged."""
        from scripts import trace_report
        attrs = {"step_s": 3.361, "usual_s": 1.794, "excess_s": 1.567,
                 "where": "loop/loss_wait", "where_excess_s": 1.566,
                 "pulse_missed_s": 1.55, "pulse_lock_waits": 1,
                 "process_cpu_s": 0.01,
                 "machine_ran_s": 3.36, "throttled_s": 0.0,
                 "invol_switches": 0, "vol_switches": 2,
                 "stacks": "peer.jsonl.stacks", "cause": "process_stopped"}
        rows = [{"v": 1, "peer": "p0", "plane": "train",
                 "phase": "loop/step", "trace": "step:812", "t0": 50.0,
                 "dur_s": 3.361},
                {"v": 1, "peer": "p0", "plane": "train",
                 "phase": "loop/late_step", "trace": "step:812",
                 "t0": 53.361, "dur_s": 0.0, "a": attrs},
                # a swarm event of the same name is no late step
                {"v": 1, "peer": "p0", "plane": "swarm",
                 "phase": "loop/late_step", "trace": "run:0", "t0": 1.0,
                 "dur_s": 0.0}]
        path = tmp_path / "p0.jsonl"
        path.write_text("".join(json.dumps(r) + "\n" for r in rows))
        (late,) = trace_report.build_report([str(path)])["late_steps"]
        assert (late["peer"], late["trace"], late["excess_s"],
                late["where"], late["cause"]) == (
            "p0", "step:812", 1.567, "loop/loss_wait", "process_stopped")
        line = ("step:812 took 3.361 s where 1.794 is usual (+1.567 s in "
                "loop/loss_wait): process_stopped: pulse missed 1.55 s, "
                "process CPU 0.01 s, machine ran 3.36 s, throttled 0.00 s, "
                "0 involuntary / 2 voluntary switches; stacks in "
                "peer.jsonl.stacks")
        assert late["line"] == line
        assert trace_report.main([str(path)]) == 0
        assert f"  late step: p0 {line}" in capsys.readouterr().out


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


# -- the late-step recorder (obs/late.py) -----------------------------------

class _World:
    """A clock and the kernel's counters, moved by hand: what a step of
    the loop sees at its two edges."""

    def __init__(self):
        self.now = 1000.0
        self.host = {"process_cpu_s": 5.0, "vol_switches": 100,
                     "invol_switches": 3, "major_faults": 0,
                     "steal_s": 0.0, "machine_ran_s": 500.0,
                     "throttled_s": 0.0}
        self.threads = {"MainThread": 2.0, "wire": 0.5}
        self.pulse = [0.0, 0]         # missed s, lock waits
        self.compiled = [0, 0.0]
        self.switches_a_wait = 1      # 0: a kernel that counts none

    def clock(self):
        return self.now

    def read_host(self):
        return dict(self.host, threads=dict(self.threads))

    def read_pulse(self):
        return tuple(self.pulse)

    def cost(self):                       # the compile counter's reading
        return tuple(self.compiled)

    def pass_(self, seconds, machine=True, main_cpu=0.0):
        self.now += seconds
        if machine:
            self.host["machine_ran_s"] += seconds
        self.host["vol_switches"] += self.switches_a_wait
        self.host["process_cpu_s"] += main_cpu
        self.threads["MainThread"] += main_cpu


def _recorder(world, **kw):
    from dalle_tpu.obs.late import LateSteps
    tracer = Tracer(peer="late", clock=world.clock)
    return tracer, LateSteps(tracer, compiles=world, host=world.read_host,
                             pulse=world.read_pulse, clock=world.clock,
                             slow_attributes=("moe_dense_calls",), **kw)


def _a_step(world, tracer, rec, n, extra=None, attrs=None):
    """One step as the loop runs it; ``extra`` = (phase, what happens in
    it beside its usual milliseconds)."""
    phase, happens = extra or (None, None)

    def spend(name, seconds):
        with tracer.span("train", name):
            world.pass_(seconds, main_cpu=0.001)
            if name == phase:
                happens()
    if phase == "between_steps":           # before the step's span opens
        happens()
    with rec.step(n) as row:
        spend("loop/batch_fetch", 0.001)
        spend("loop/grad_dispatch", 0.002)
        spend("loop/loss_wait", 1.0)
        row.set(**(attrs or {"moe_dense_calls": 0.0,
                             "moe_sum_spills": float(n % 3)}))
        spend("loop/hook", 0.001)
        with tracer.span("train", "collab/step"):
            spend("collab/accumulate", 0.004)
            spend("collab/global_step", 0.0)
            world.pass_(0.002)


def _late_events(tracer):
    rows = tracer if isinstance(tracer, list) else tracer.dump()
    return [r for r in rows if r["phase"] == "loop/late_step"]


def _cases():
    """name -> (the phase the excess is in, what happens there, the
    attributes of the step, the cause and a few attributes of the record;
    None = not late)."""
    def stall(w, s, **kw):
        return lambda: w.pass_(s, **kw)

    def compiled(w):
        w.pass_(2.0, main_cpu=1.9)
        w.compiled[0] += 1
        w.compiled[1] += 1.5

    def collected(w, rec):
        rec._on_gc("start", {})
        w.pass_(0.4, main_cpu=0.4)
        rec._on_gc("stop", {})

    def stopped(w):
        w.pass_(2.0)
        w.pulse[0] += 1.95
        w.pulse[1] += 1
        w.host["throttled_s"] += 1.9

    def paused(w):
        w.pass_(2.0, machine=False)
        w.pulse[0] += 1.98

    def held_by_a_busy_thread(w):
        w.pass_(2.0)
        w.pulse[0] += 1.9
        w.host["process_cpu_s"] += 1.9
        w.threads["wire"] += 1.9

    def held_by_a_native_call(w):
        w.pass_(2.0)
        w.pulse[0] += 1.9
        w.pulse[1] += 380

    def starved_by_native_threads(w):
        w.pass_(0.3)
        w.pulse[0] += 0.3
        w.host["process_cpu_s"] += 2.4        # eight native threads

    def main_thread_worked(w):
        w.pass_(0.5, main_cpu=0.5)
        w.pulse[0] += 0.4                     # it kept the lock meanwhile
        w.pulse[1] += 90

    def hook_called_native_code(w):
        w.pass_(0.04)                         # the profiler's start
        w.pulse[0] += 0.021
        w.host["process_cpu_s"] += 0.03

    return {
        "compile": ("collab/accumulate", compiled, None, "compile",
                    {"where": "collab/step", "compiles": 1,
                     "hook_or_after": 1}),
        "model": ("loop/loss_wait", lambda w: w.pass_(0.5),
                  {"moe_dense_calls": 3.0, "moe_sum_spills": 1.0}, "model",
                  {"moe_dense_calls": 3.0, "moe_dense_calls_usual": 0.0,
                   "slower_lowering": "moe_dense_calls"}),
        "gc": ("loop/hook", collected, None, "gc",
               {"where": "loop/hook", "gc_n": 1, "gc_s": 0.4}),
        "machine_stopped": ("loop/loss_wait", paused, None,
                            "machine_stopped", {"pulse_missed_s": 1.98}),
        "process_stopped": ("loop/loss_wait", stopped, None,
                            "process_stopped",
                            {"throttled_s": 1.9, "where": "loop/loss_wait",
                             "pulse_lock_waits": 1, "hook_or_after": 0}),
        "interpreter_held-a_thread_burned": (
            "loop/loss_wait", held_by_a_busy_thread, None,
            "interpreter_held",
            {"busiest_thread": "wire", "busiest_thread_cpu_s": 1.9}),
        "interpreter_held-a_native_call_kept_the_lock": (
            "loop/loss_wait", held_by_a_native_call, None,
            "interpreter_held", {"pulse_lock_waits": 380}),
        "host-the_hook": ("loop/hook", lambda w: w.pass_(0.3), None, "host",
                          {"where": "loop/hook", "where_excess_s": 0.3,
                           "hook_or_after": 1}),
        "host-the_main_thread_worked": (
            "loop/grad_dispatch", main_thread_worked, None, "host",
            {"busiest_thread": "MainThread", "where": "loop/grad_dispatch"}),
        "host-a_native_call_of_the_hook_kept_the_lock": (
            "loop/hook", hook_called_native_code, None, "host",
            {"where": "loop/hook", "busiest_thread": "native threads"}),
        "device_or_runtime": ("loop/loss_wait", lambda w: w.pass_(0.3), None,
                              "device_or_runtime",
                              {"where": "loop/loss_wait",
                               "pulse_missed_s": 0.0}),
        "device_or_runtime-traced_run_waits_in_dispatch": (
            "loop/grad_dispatch", lambda w: w.pass_(0.3), None,
            "device_or_runtime", {"where": "loop/grad_dispatch",
                                  "hook_or_after": 0}),
        "process_stopped-between_two_steps": (
            "between_steps", stopped, None, "process_stopped",
            {"where": "between_steps", "between_s": 2.0,
             "where_excess_s": 2.0, "hook_or_after": 0}),
        "host-between_two_steps": (
            "between_steps", lambda w: w.pass_(0.3), None, "host",
            {"where": "between_steps", "between_s": 0.3}),
        "unknown": ("loop/loss_wait", starved_by_native_threads, None,
                    "unknown", {"busiest_thread": "native threads"}),
        "round_work_is_not_late": ("collab/global_step",
                                   lambda w: w.pass_(3.0), None, None, {}),
        "quiet_scatter_is_not_late": ("loop/loss_wait",
                                      lambda w: w.pass_(0.004), None, None,
                                      {}),
    }


class TestLateSteps:
    @pytest.mark.parametrize("case", sorted(_cases()))
    def test_cause_table(self, case):
        """Every row of ``obs.late.CAUSES`` on an injected clock and
        injected counters: six quiet steps, then one in which the case
        happens."""
        phase, happens, attrs, cause, shown = _cases()[case]
        world = _World()
        tracer, rec = _recorder(world)
        for n in range(1, 7):
            _a_step(world, tracer, rec, n)
        assert _late_events(tracer) == []
        import inspect
        args = (world, rec)[:len(inspect.signature(happens).parameters)]
        _a_step(world, tracer, rec, 7, (phase, lambda: happens(*args)),
                attrs)
        _a_step(world, tracer, rec, 8)
        events = _late_events(tracer)
        if cause is None:
            assert events == []
            return
        (event,) = events
        a = event["a"]
        assert event["trace"] == "step:7" and event["dur_s"] == 0.0
        assert a["cause"] == cause, a
        assert a["usual_s"] == pytest.approx(1.01, abs=1e-6)
        assert a["excess_s"] == pytest.approx(
            a["step_s"] + a.get("between_s", 0.0) - 1.01, abs=1e-5)
        for key, value in shown.items():
            assert a[key] == (pytest.approx(value, abs=1e-6)
                              if isinstance(value, float) else value), key
        assert all(isinstance(v, (int, float, str)) for v in a.values())
        # the event follows its step's row, on the ring's clock
        rows = tracer.dump()
        at = rows.index(event)
        assert rows[at - 1]["phase"] == "loop/step"
        assert rows[at - 1]["trace"] == "step:7"
        assert event["t0"] == pytest.approx(
            rows[at - 1]["t0"] + rows[at - 1]["dur_s"])

    def test_the_table_is_the_nine_causes_in_order(self):
        from dalle_tpu.obs import late
        assert [name for name, _, _ in late.CAUSES] == [
            "compile", "model", "gc", "machine_stopped", "process_stopped",
            "interpreter_held", "host", "device_or_runtime", "unknown"]
        doc = open(os.path.join(REPO, "OBSERVABILITY.md")).read()
        for name, _, _ in late.CAUSES:
            assert f"`{name}`" in doc, name

    @pytest.mark.parametrize("late_at", [1, 2, 3, 4])
    def test_the_first_four_steps_are_never_late(self, late_at):
        world = _World()
        tracer, rec = _recorder(world)
        for n in range(1, 5):
            _a_step(world, tracer, rec, n,
                    ("loop/loss_wait", lambda: world.pass_(5.0))
                    if n == late_at else None)
        assert _late_events(tracer) == []
        _a_step(world, tracer, rec, 5,
                ("loop/loss_wait", lambda: world.pass_(5.0)))
        assert [e["trace"] for e in _late_events(tracer)] == ["step:5"]

    def test_sources_in_name_only_are_left_out(self, caplog):
        """A sandbox's kernel (the chip machine's, PERF.md section 6, PR
        35): ``/proc/stat`` does not tick, ``getrusage`` counts no switch
        and no fault, the allocator no allocation. Judged once, over the
        first four steps: their keys are left out from then on like those
        of a file that is absent, the device is not asked again, no stall
        is laid on a machine whose clock never moved, and the line says
        what ``process_stopped`` then cannot tell."""
        import logging
        world = _World()
        world.switches_a_wait = 0
        asked = []

        def device_memory():
            asked.append(world.now)
            return {"bytes_in_use": 7 << 30, "num_allocs": 1234}
        tracer, rec = _recorder(world, device_memory=device_memory)
        rec.start()
        with caplog.at_level(logging.INFO, logger="dalle_tpu.obs.late"):
            for n in range(1, 7):
                _a_step(world, tracer, rec, n)
                world.host["machine_ran_s"] = 500.0
                if n == 3:
                    world.host["vol_switches"] += 2   # a stray one, no count
            assert len(asked) == 5                # the start, four steps
            (judged,) = [r.getMessage() for r in caplog.records]
            assert judged.endswith(
                "left out of the records: invol_switches, machine_ran_s, "
                "major_faults, mem_allocs_delta, mem_in_use_delta, "
                "pulse_lock_waits, steal_s, vol_switches")
            caplog.clear()

            def frozen():
                world.pass_(2.0, machine=False)
                world.pulse[0] += 1.97
                world.host["invol_switches"] += 1     # too late to count
            _a_step(world, tracer, rec, 7, ("loop/loss_wait", frozen))
        rec.stop()
        (event,) = _late_events(tracer)
        a = event["a"]
        assert a["cause"] == "process_stopped", a
        assert not {"machine_ran_s", "steal_s", "vol_switches",
                    "invol_switches", "major_faults", "pulse_lock_waits",
                    "mem_in_use_delta", "mem_allocs_delta"} & set(a)
        (said,) = [r.getMessage() for r in caplog.records]
        assert "machine ran" not in said and "switches" in said
        assert "switches are not counted here (a native call that slept " \
            "with the interpreter lock reads the same)" in said
        assert "involuntary" not in said

    def test_a_source_that_moved_in_the_first_steps_is_kept(self):
        """Judged once: a counter that counted over the first four steps
        and says 0 for a later one has said something."""
        world = _World()
        held = {"bytes_in_use": 1000, "num_allocs": 0}

        def device_memory():
            if world.now < 1004.5:
                held["num_allocs"] += 3
            return dict(held)
        tracer, rec = _recorder(world, device_memory=device_memory)
        rec.start()
        for n in range(1, 7):
            _a_step(world, tracer, rec, n)
        world.switches_a_wait = 0
        _a_step(world, tracer, rec, 7,
                ("loop/loss_wait", lambda: world.pass_(0.3)))
        rec.stop()
        a = _late_events(tracer)[0]["a"]
        assert (a["mem_allocs_delta"], a["mem_in_use_delta"],
                a["vol_switches"], a["major_faults"]) == (0, 0, 0, 0)
        assert a["machine_ran_s"] == pytest.approx(a["step_s"], abs=1e-3)

    def test_a_beat_that_is_overdue_counts_as_far_as_it_is(self):
        """A stall that ends with its step: the step's close reads the
        pulse before the pulse has run again, and does not wait for it."""
        from dalle_tpu.obs.late import BEAT_S, Pulse
        now = [100.0]
        pulse = Pulse(clock=lambda: now[0])            # never started
        assert pulse.read() == (0.0, 0)
        now[0] += BEAT_S + 0.001                       # ordinary scheduling
        assert pulse.read()[0] == 0.0
        now[0] = 100.0 + BEAT_S + 1.5
        assert pulse.read()[0] == pytest.approx(1.5)

    def test_stacks_are_taken_after_beats_that_came_on_time(self):
        """Once a step, when it has been open 1.25 x the usual in beats
        the pulse did not miss: not during a stall (this thread does not
        run) and not at the first beat after it (every thread is back in
        a wait, the dump would name nothing)."""
        import io
        from dalle_tpu.obs.late import Pulse
        now = [100.0]
        out = io.StringIO()
        pulse = Pulse(clock=lambda: now[0], stacks=out)    # never started
        pulse.opened("step:7", 0.5)
        now[0] += 2.1                  # a stall of 2 s, and its first beat
        pulse._missed_s += 2.0
        assert pulse._stacks_due(now[0]) is None       # it beat for 0.1 s
        now[0] += 0.45
        step = pulse._stacks_due(now[0])
        assert step is not None
        frames = pulse._take_stacks(step[0], now[0] - step[1])
        # this thread took them itself, as the pulse does in earnest
        assert frames.startswith("MainThread: late.py:")
        assert frames.split("; ")[0].endswith(" _take_stacks")
        dump = out.getvalue()
        assert " in test_stacks_are_taken_after_beats_that_came_on_" in dump
        assert dump.startswith("Late step (step:7 open 2.550 s):\n"
                               "Thread MainThread (most recent call first)")
        assert "late-step-pulse" not in dump and "pytest" in dump
        pulse._frames = frames
        assert pulse._stacks_due(now[0] + 5.0) is None     # once a step
        assert pulse.closed() == frames and pulse.closed() is None
        pulse.opened("step:8", 0.5)
        assert pulse._stacks_due(now[0] + 0.4) is None
        assert pulse._stacks_due(now[0] + 0.6)[0] == "step:8"

    def test_a_step_that_raised_is_not_compared(self, caplog):
        import logging
        world = _World()
        tracer, rec = _recorder(world)
        for n in range(1, 7):
            _a_step(world, tracer, rec, n)

        def dies():
            world.pass_(4.0)
            raise KeyError("the window is over")
        with pytest.raises(KeyError):
            _a_step(world, tracer, rec, 7, ("loop/hook", dies))
        assert _late_events(tracer) == []
        assert tracer.dump()[-1]["a"]["error"] == "KeyError"
        with caplog.at_level(logging.INFO, logger="dalle_tpu.obs.late"):
            rec.stop()
        assert [r.getMessage() for r in caplog.records] == [
            "late steps: 0 of 6 steps, +0.000 s in all"]

    def test_one_warning_in_thirty_seconds_and_none_is_lost(self, caplog):
        """The first late step logs its line at once; those of the next
        30 s are held back and named by the next line, or by ``stop``."""
        import logging
        world = _World()
        tracer, rec = _recorder(world)
        slow = ("loop/loss_wait", lambda: world.pass_(0.3))
        with caplog.at_level(logging.WARNING, logger="dalle_tpu.obs.late"):
            for n in range(1, 7):
                _a_step(world, tracer, rec, n)
            for n in (7, 8, 9):
                _a_step(world, tracer, rec, n, slow)
            said = [r.getMessage() for r in caplog.records]
            assert len(said) == 1 and said[0].startswith(
                "step:7 took 1.310 s where 1.010 is usual (+0.300 s in "
                "loop/loss_wait): device_or_runtime: pulse missed 0.00 s, "
                "process CPU 0.01 s, machine ran 1.31 s, throttled 0.00 s")
            assert "0 involuntary / 8 voluntary switches" in said[0]
            for n in range(10, 40):
                _a_step(world, tracer, rec, n)
            _a_step(world, tracer, rec, 40, slow)
            _a_step(world, tracer, rec, 41, slow)
            rec.stop()
        said = [r.getMessage() for r in caplog.records]
        assert len(said) == 3
        assert said[1].startswith("step:40 took") and said[1].endswith(
            "(2 more held back since the last line: step:8 +0.300 s in "
            "loop/loss_wait: device_or_runtime; step:9 +0.300 s in "
            "loop/loss_wait: device_or_runtime)")
        assert said[2] == ("1 more late steps since the last line: step:41 "
                           "+0.300 s in loop/loss_wait: device_or_runtime")
        assert len(_late_events(tracer)) == 5          # the ring has all

    def test_stop_says_the_runs_sum(self, caplog):
        """The operator's end-of-run line: the WARNINGs are one in 30 s,
        this counts every late step since ``start``."""
        import logging
        world = _World()
        tracer, rec = _recorder(world)
        rec.start()
        for n in range(1, 13):
            _a_step(world, tracer, rec, n, {
                7: ("loop/loss_wait", lambda: world.pass_(0.3)),
                9: ("loop/hook", lambda: world.pass_(0.5)),
                11: ("loop/loss_wait", lambda: world.pass_(0.2))}.get(n))
        with caplog.at_level(logging.INFO, logger="dalle_tpu.obs.late"):
            rec.stop()
            rec.stop()                                 # says it once
        assert [r.getMessage() for r in caplog.records
                if r.levelno == logging.INFO] == [
            "late steps: 3 of 12 steps, +1.000 s in all, "
            "2 device_or_runtime, 1 host"]

    # -- real threads, the real kernel ------------------------------------

    @staticmethod
    def _real(tmp_path=None, **kw):
        from dalle_tpu.obs.late import LateSteps
        tracer = Tracer(peer="late")
        stacks = str(tmp_path / "peer.jsonl.stacks") if tmp_path else None
        return tracer, LateSteps(tracer, stacks_path=stacks, **kw)

    @staticmethod
    def _run(tracer, rec, steps, during, quiet_s=0.06):
        """``steps`` real steps of ``quiet_s``; ``during[n]`` = (phase,
        what to do in it). Returns the late-step events of those steps:
        on a loaded box a quiet step of 60 ms can come 5 ms late too."""
        rec.start()
        try:
            for n in range(1, steps + 1):
                phase, act = during.get(n, (None, None))
                with rec.step(n):
                    with tracer.span("train", "loop/loss_wait"):
                        time.sleep(quiet_s)
                        if phase == "loop/loss_wait":
                            act()
                    with tracer.span("train", "loop/hook"):
                        if phase == "loop/hook":
                            act()
        finally:
            rec.stop()
        assert not [t for t in threading.enumerate()
                    if t.name == "late-step-pulse"]
        return [e for e in _late_events(tracer)
                if int(e["trace"].split(":")[1]) in during]

    def test_a_thread_that_keeps_the_interpreter_lock(self, tmp_path):
        """A thread inside a native call that does not release the lock
        (``ctypes.PyDLL``): the pulse misses the hold and wakes every 5
        ms for the lock meanwhile. No stacks from inside the hold: the
        pulse cannot run during it (the C watchdog that could catch it
        mid-call is not safe to arm, ``obs/late.py``). Every limit is from
        what this run measured itself (the hold's own seconds and edges,
        the step's and the usual step's seconds), none from how long the
        box takes: under six workers a 400 ms sleep is not 400 ms, and the
        step's quiet part alone can pass 1.25 x the usual step, so that the
        stacks fall due at the hold's very edges."""
        import ctypes
        import re

        from dalle_tpu.obs.late import BEAT_S, LOCK_WAIT_S
        libc = ctypes.PyDLL(None)
        held = []

        def keeps_the_lock():
            t0 = time.perf_counter()
            libc.usleep(400_000)
            held.append((t0, time.perf_counter()))

        def act():
            thread = threading.Thread(target=keeps_the_lock, name="keeper")
            woken = rec._pulse.read()[1]
            thread.start()
            thread.join()
            # the phase stays open until the pulse has run again and
            # counted its wake-ups (or for the hold's length again): a
            # step that closes first has the missed seconds, an overdue
            # beat counting as far as it is, and not yet the count
            ((began, ended),) = held
            while rec._pulse.read()[1] == woken \
                    and time.perf_counter() < 2 * ended - began:
                pass
        tracer, rec = self._real(tmp_path)
        (event,) = self._run(tracer, rec, 8, {7: ("loop/loss_wait", act)})
        a = event["a"]
        assert event["trace"] == "step:7" and a["where"] == "loop/loss_wait"
        ((began, ended),) = held
        hold = ended - began
        # the pulse missed the hold, but for the beat under way when it
        # began, and no more than the step it was in
        assert hold - 2 * BEAT_S <= a["pulse_missed_s"] <= a["step_s"]
        # ... awake every 5 ms to ask for the lock, for most of the hold
        assert a["pulse_lock_waits"] * LOCK_WAIT_S >= hold / 2
        assert a["process_cpu_over_s"] < hold / 4   # nobody burned CPU
        assert a["cause"] == "interpreter_held", a
        # the excess is the hold, less what the usual step takes over its
        # own 60 ms of sleep, within the step
        assert hold - (a["usual_s"] - 0.06) - 1e-3 <= a["excess_s"] \
            <= a["step_s"] + a.get("between_s", 0.0)
        # a dump, if the step was open long enough in beats for one, was
        # taken before the call or after it (the tracer's clock is the
        # pulse's): never with the keeper inside it
        (step,) = [r for r in tracer.dump() if r["phase"] == "loop/step"
                   and r["trace"] == "step:7"]
        dump = (tmp_path / "peer.jsonl.stacks").read_text()
        for open_s in re.findall(r"Late step \(step:7 open ([0-9.]+) s\)",
                                 dump):
            taken = step["t0"] + float(open_s)
            assert not began + BEAT_S < taken < ended - BEAT_S, (
                taken - began, hold)
        if "keeps_the_lock" in a.get("stacks", ""):
            assert "keeps_the_lock" in dump     # the record's is that dump's

    def test_a_hook_that_sleeps_while_the_pulse_beats(self, tmp_path):
        tracer, rec = self._real(tmp_path)
        (event,) = self._run(tracer, rec, 8,
                             {7: ("loop/hook", lambda: time.sleep(0.3))})
        a = event["a"]
        assert a["where"] == "loop/hook" and a["cause"] == "host", a
        assert a["where_excess_s"] == pytest.approx(0.3, abs=0.05)
        assert a["pulse_missed_s"] < 0.1
        stacks = tmp_path / "peer.jsonl.stacks"
        assert a["stacks"].startswith(f"{stacks}: MainThread: test_obs.py:")
        assert "<lambda>" in a["stacks"]             # the sleeping frame
        dump = stacks.read_text()
        assert dump.startswith("Late step (step:7 open 0.")
        assert dump.count("Late step (") == 1        # once a step

    def test_a_process_that_was_stopped(self, tmp_path):
        """``SIGSTOP`` for half a second inside a step, from outside: no
        thread of the process runs, the machine does."""
        import signal
        code = (
            "import json, sys, time\n"
            "sys.modules['jax'] = None\n"
            "from dalle_tpu.obs.trace import Tracer\n"
            "from dalle_tpu.obs.late import LateSteps\n"
            "tracer = Tracer(peer='child')\n"
            f"rec = LateSteps(tracer, stacks_path={str(tmp_path / 's')!r})\n"
            "rec.start()\n"
            "for n in range(1, 9):\n"
            "    if n == 7:\n"
            "        print('now', flush=True)\n"
            "    with rec.step(n):\n"
            "        with tracer.span('train', 'loop/loss_wait'):\n"
            "            time.sleep(0.2)\n"
            "rec.stop()\n"
            "print(json.dumps([r for r in tracer.dump()\n"
            "                  if r['phase'] == 'loop/late_step']))\n")
        child = subprocess.Popen([sys.executable, "-c", code], cwd=REPO,
                                 stdout=subprocess.PIPE, text=True)
        try:
            assert child.stdout.readline().strip() == "now"
            time.sleep(0.05)
            child.send_signal(signal.SIGSTOP)
            time.sleep(0.5)
            child.send_signal(signal.SIGCONT)
            out, _ = child.communicate(timeout=60)
        finally:
            child.kill()
        assert child.returncode == 0
        (event,) = [e for e in json.loads(out.strip().splitlines()[-1])
                    if e["trace"] == "step:7"]   # a loaded box may add one
        a = event["a"]
        assert a["cause"] == "process_stopped", a
        # the step's sleep ran out while the process stood still: the
        # excess is the stop less what was left of the sleep (0.15 s)
        assert a["excess_s"] == pytest.approx(0.35, abs=0.1)
        assert a["pulse_missed_s"] == pytest.approx(0.5, abs=0.1)
        assert a["pulse_lock_waits"] <= 5 and a["process_cpu_s"] < 0.1
        assert a["machine_ran_s"] == pytest.approx(a["step_s"], abs=0.06)

    def test_every_source_missing_leaves_its_keys_out(self, tmp_path):
        """No ``/proc/stat``, no ``/proc/pressure``, no ``cpu.stat``, a
        backend without ``memory_stats``: a record all the same."""
        from dalle_tpu.obs.late import HostCounters
        nothing = str(tmp_path / "nothing")
        tracer, rec = self._real(
            host=HostCounters(proc_stat=nothing, pressure=nothing,
                              cpu_stat=None),
            device_memory=lambda: None)
        (event,) = self._run(tracer, rec, 8,
                             {7: ("loop/hook", lambda: time.sleep(0.3))})
        a = event["a"]
        assert a["cause"] == "host" and a["where"] == "loop/hook"
        gone = {"machine_ran_s", "steal_s", "psi_cpu_s", "psi_io_s",
                "psi_mem_s", "throttled_s", "mem_in_use_delta",
                "mem_allocs_delta"}
        assert not gone & set(a), gone & set(a)
        assert {"process_cpu_s", "vol_switches", "invol_switches",
                "major_faults", "pulse_missed_s", "gc_s", "gc_n"} <= set(a)

    def test_what_the_device_holds_is_read_through_the_callable(self):
        held = {"bytes_in_use": 1000, "num_allocs": 10,
                "peak_bytes_in_use": 1000, "largest_alloc_size": 7}

        def device_memory():
            held["num_allocs"] += 1          # an allocator that counts
            return dict(held)

        def grows():
            held["bytes_in_use"] = 5000
            held["num_allocs"] += 4
            time.sleep(0.3)
        tracer, rec = self._real(device_memory=device_memory)
        (event,) = self._run(tracer, rec, 8, {7: ("loop/hook", grows)})
        a = event["a"]
        assert (a["mem_in_use_delta"], a["mem_allocs_delta"]) == (4000, 5)
        assert not [k for k in a if k.startswith("mem_peak")]
        # no trace file: the stacks go to standard error
        assert a["stacks"].startswith("standard error: MainThread: ")

    def test_a_file_that_feeds_only_absent_keys_is_not_read_again(self):
        from dalle_tpu.obs.late import HostCounters
        read = HostCounters()
        if "machine_ran_s" not in read():
            pytest.skip("no /proc/stat here")
        read.leave_out({"vol_switches"})
        assert "machine_ran_s" in read()
        read.leave_out({"machine_ran_s", "steal_s"})
        assert not {"machine_ran_s", "steal_s"} & set(read())

    def test_the_kernels_counters_as_they_are_here(self):
        """What this machine has is read and only grows."""
        from dalle_tpu.obs.late import HostCounters
        read = HostCounters()
        first = read()
        sum(i * i for i in range(200_000))
        second = read()
        assert second["process_cpu_s"] > first["process_cpu_s"]
        assert "MainThread" in second["threads"]
        for key, value in first.items():
            if key != "threads":
                assert second[key] >= value, key
        if os.path.exists("/proc/stat"):
            assert second["machine_ran_s"] > 0

    def test_the_recorder_imports_and_records_without_jax(self):
        code = (
            "import sys, time\n"
            "sys.modules['jax'] = None\n"
            "import dalle_tpu.obs as obs\n"
            "from dalle_tpu.obs.late import LateSteps\n"
            "t = obs.configure(peer='x')\n"
            "rec = LateSteps(t)\n"
            "rec.start()\n"
            "for n in range(1, 9):\n"
            "    with rec.step(n) as row:\n"
            "        with t.span('train', 'loop/hook'):\n"
            "            time.sleep(0.3 if n == 7 else 0.02)\n"
            "rec.stop()\n"
            "late = [r for r in t.dump() if r['phase'] == 'loop/late_step']\n"
            "late = [r for r in late if r['trace'] == 'step:7']\n"
            "assert len(late) == 1 and late[0]['a']['cause'] == 'host', late\n"
            "assert 'jax' not in [m for m in sys.modules "
            "if sys.modules[m] is not None]\n")
        done = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0, done.stderr[-2000:]
        assert "step:7 took" in done.stderr          # the WARNING's line


class TestSelfSeconds:
    """The compile counter's seconds two ways (``obs/compiles.py``): JAX's
    own, in which a jitted function traced inside another is in the outer's
    seconds too, and the self form, whose parts sum."""

    INNER_S, OUTER_S, ASIDE_S = 0.12, 0.08, 0.35

    @pytest.fixture()
    def counted(self):
        """``outer`` = OUTER_S of Python + two calls of jitted ``inner``
        (INNER_S), traced once inside ``setup/train_state``; meanwhile a
        second thread, started from inside ``outer``'s trace and waited
        for there, traces ``aside`` (ASIDE_S)."""
        import jax.numpy as jnp

        from dalle_tpu.obs import compiles
        tracer = Tracer(peer="self")
        counter = compiles.install(tracer)
        installed_at = time.perf_counter()

        @jax.jit
        def self_inner(x):
            time.sleep(self.INNER_S)
            return x + 1

        @jax.jit
        def self_aside(x):
            time.sleep(self.ASIDE_S)
            return x - 1

        @jax.jit
        def self_outer(x):
            other = threading.Thread(
                target=lambda: self_aside.lower(jnp.ones(3)))
            other.start()
            time.sleep(self.OUTER_S)
            y = self_inner(self_inner(x))
            other.join()
            return y * 2

        try:
            with tracer.span("train", "setup/train_state", "setup"):
                self_outer(jnp.ones(3)).block_until_ready()
            first = counter.snapshot()
            self_outer(jnp.ones(3)).block_until_ready()     # cached
            (span_row,) = [r for r in tracer.dump()
                           if r["phase"] == "setup/train_state"]
            yield {"first": first, "second": counter.snapshot(),
                   "span_s": span_row["dur_s"], "cost": counter.cost(),
                   "since_install": time.perf_counter() - installed_at}
        finally:
            compiles.install(None)

    def test_the_inclusive_keys_read_as_before(self, counted):
        by = counted["first"]["by_program"]
        inner, outer = by["self_inner"], by["self_outer"]
        # the second call of ``inner`` inside the one trace costs nothing
        assert inner["trace_n"] == 2
        assert inner["trace_s"] == pytest.approx(self.INNER_S, rel=0.25)
        assert outer["trace_n"] == 1 and outer["compile_n"] == 1
        # ``outer``'s seconds hold ``inner``'s: their sum is over the wall
        assert outer["trace_s"] >= inner["trace_s"] + self.OUTER_S
        for row in by.values():
            assert set(row) == {
                "trace_n", "trace_s", "trace_self_s", "lower_n", "lower_s",
                "lower_self_s", "compile_n", "compile_s", "cache_hits",
                "cache_misses"}

    def test_self_seconds_sum_to_the_wall(self, counted):
        by = counted["first"]["by_program"]
        inner, outer = by["self_inner"], by["self_outer"]
        assert inner["trace_self_s"] == pytest.approx(inner["trace_s"],
                                                      rel=0.05)
        # the parts of the one trace sum to its wall, within 5%
        assert inner["trace_self_s"] + outer["trace_self_s"] == \
            pytest.approx(outer["trace_s"], rel=0.05)
        assert outer["trace_self_s"] == pytest.approx(
            outer["trace_s"] - inner["trace_s"], rel=0.05)

    def test_another_threads_trace_is_not_taken_out(self, counted):
        """``aside`` was traced on a second thread while ``outer``'s trace
        waited for it: its seconds ended inside ``outer``'s interval and
        stay in ``outer``'s self seconds."""
        by = counted["first"]["by_program"]
        aside, outer, inner = (by[f"self_{name}"]
                               for name in ("aside", "outer", "inner"))
        assert aside["trace_self_s"] == pytest.approx(self.ASIDE_S, rel=0.25)
        # ``outer``'s trace lasted as long as the thread it waited for
        assert outer["trace_s"] >= self.ASIDE_S
        assert outer["trace_self_s"] >= self.ASIDE_S - 1.25 * self.INNER_S
        # a thread has no open span of the first's: the span's tally holds
        # the parts of the one trace, which sum to its wall, and no more
        span = counted["first"]["by_span"]["setup/train_state"]
        assert span["trace_self_s"] == pytest.approx(outer["trace_s"],
                                                     rel=0.05)

    def test_no_span_and_no_process_holds_more_self_seconds_than_wall(
            self, counted):
        from dalle_tpu.obs import compiles
        first = counted["first"]
        span = first["by_span"]["setup/train_state"]
        assert span["trace_self_s"] <= counted["span_s"]
        assert compiles.self_seconds(span) <= counted["span_s"]
        # inclusive seconds make no such promise: the same span, summed
        assert span["trace_s"] > span["trace_self_s"]
        # a process's threads trace side by side: each thread's self
        # seconds stay under the wall, here the first's
        total = counted["second"]["total"]
        aside = counted["second"]["by_program"]["self_aside"]
        assert compiles.self_seconds(total) - compiles.self_seconds(aside) \
            <= counted["since_install"]
        # what the late-step recorder reads at a step's edge is the self form
        compiled, seconds = counted["cost"]
        assert compiled == total["compile_n"]
        assert seconds == pytest.approx(compiles.self_seconds(total))

    def test_a_cached_second_call_costs_nothing(self, counted):
        first, second = counted["first"], counted["second"]
        for name in ("self_outer", "self_inner", "self_aside"):
            assert second["by_program"][name] == first["by_program"][name]


@pytest.fixture(scope="module")
def late_loop(tmp_path_factory):
    """A tiny-preset ``train_loop`` on the CPU with a trace file: its hook
    sleeps once (step 7) and swaps the grad step for a new ``jax.jit`` of
    it once (step 9, so that step 10 traces and compiles again). CPU steps
    of milliseconds scatter by more than 5 ms on a loaded box, so the
    floor is raised for the run."""
    import logging

    from dalle_tpu.obs import compiles, late
    from dalle_tpu.training.loop import train_loop
    from dalle_tpu.training.steps import make_grad_step
    from tests.test_trainer_spans import _make_task
    tmp = tmp_path_factory.mktemp("late")
    trace_file = tmp / "peer.jsonl"
    records = []

    class Keep(logging.Handler):
        def emit(self, record):
            records.append(record)
    keep = Keep(logging.INFO)
    log = logging.getLogger("dalle_tpu.obs.late")
    log.addHandler(keep)
    level = log.level
    log.setLevel(logging.INFO)
    floor, late.LATE_FLOOR_S = late.LATE_FLOOR_S, 0.25
    slept = []

    def on_step(n, loss):
        if n == 7:
            t0 = time.perf_counter()
            time.sleep(0.5)
            slept.append(time.perf_counter() - t0)
        if n == 9:
            task.__dict__["grad_step"] = jax.jit(make_grad_step(task.model))
    try:
        with _make_task(tmp, trace_file=str(trace_file)) as task:
            train_loop(task, max_steps=12, warmup_steps=1,
                       publish_metrics_records=False, on_step=on_step)
            task.tracer.flush()
            threads = [t.name for t in threading.enumerate()]
            yield {"rows": task.tracer.dump(), "said": [
                r.getMessage() for r in records
                if r.levelno == logging.WARNING], "threads": threads,
                "told": [r.getMessage() for r in records
                         if r.levelno == logging.INFO],
                "trace_file": trace_file, "slept": slept[0]}
    finally:
        late.LATE_FLOOR_S = floor
        log.removeHandler(keep)
        log.setLevel(level)
        compiles.install(None)


class TestLateStepsInTheLoop:
    def test_one_event_for_the_step_whose_hook_slept(self, late_loop):
        """The limits are the run's own: what the hook's sleep took by the
        hook's clock, and the step's and the usual step's seconds."""
        events = _late_events(late_loop["rows"])
        late = {e["trace"]: e["a"] for e in events}
        # one event a late step, the two the loop made late among them; a
        # loaded box may make another step late by itself: not in the hook
        assert len(late) == len(events) and {"step:7", "step:10"} <= set(late)
        assert [t for t, b in late.items() if b["where"] == "loop/hook"] == [
            "step:7"]
        a = late["step:7"]
        assert a["where"] == "loop/hook" and a["cause"] == "host", a
        # the hook's own median is microseconds: its excess is the sleep,
        # as long as the sleep was on this box and no longer than the hook
        slept = late_loop["slept"]
        assert slept - 1e-3 <= a["where_excess_s"] <= 1.1 * slept
        # the step's moves with the load on the box: the step took the
        # sleep at least, so its excess is the sleep less what the usual
        # step takes, within the step
        assert slept - a["usual_s"] <= a["excess_s"] \
            <= a["step_s"] + a.get("between_s", 0.0)
        # taken once, when the step had been open 1.25 x the usual: in
        # the hook's sleep, or on a loaded box still in the loss's wait
        assert a["stacks"].startswith(
            str(late_loop["trace_file"]) + ".stacks: MainThread: ")
        assert a["hook_or_after"] == 1
        # the run's sum counts what the ring holds, cause by cause
        total = sum(b["excess_s"] for b in late.values())
        causes = collections.Counter(b["cause"] for b in late.values())
        assert causes["compile"] == 1 and causes["host"] >= 1
        assert late_loop["told"][-1] == (
            f"late steps: {len(late)} of 12 steps, +{total:.3f} s in all"
            + "".join(f", {n} {cause}" for cause, n in sorted(
                causes.items(), key=lambda kv: -kv[1])))

    def test_a_forced_recompile_is_named(self, late_loop):
        event = _late_events(late_loop["rows"])[1]
        a = event["a"]
        assert a["cause"] == "compile", a
        assert a["compiles"] >= 1 and a["compile_s"] >= a["excess_s"] / 2

    def test_one_warning_and_the_held_back_one_at_the_end(self, late_loop):
        first, last = late_loop["said"]
        assert first.startswith("step:7 took ") and ": host: " in first
        assert "in loop/hook" in first and "stacks in " in first
        assert last.startswith("1 more late steps since the last line: "
                               "step:10 +")
        assert last.endswith(": compile")

    def test_the_event_is_in_the_trace_file_and_the_report(self, late_loop,
                                                           capsys):
        from scripts import trace_report
        rows = load_jsonl(str(late_loop["trace_file"]))
        assert [r["trace"] for r in _late_events(rows)] == ["step:7",
                                                            "step:10"]
        rep = trace_report.build_report([str(late_loop["trace_file"])])
        assert [(s["trace"], s["where"], s["cause"])
                for s in rep["late_steps"]] == [
            ("step:7", "loop/hook", "host"),
            ("step:10", rep["late_steps"][1]["where"], "compile")]
        assert trace_report.main([str(late_loop["trace_file"])]) == 0
        out = capsys.readouterr().out
        assert "late step: " in out and " step:7 took " in out
        assert ": host: " in out

    def test_an_edge_asks_one_device_on_a_host_of_four(self, monkeypatch):
        """``TrainingTask`` hands the memory account the fullest local
        device, chosen once; the late-step recorder reads the account's
        last edge and asks no device at all."""
        import types

        from dalle_tpu import task as task_module

        class Device:
            def __init__(self, held):
                self.held, self.asked = held, 0

            def memory_stats(self):
                self.asked += 1
                return {"bytes_in_use": self.held, "num_allocs": self.asked}
        devices = [Device(held) for held in (3, 9, 5, 1)]
        monkeypatch.setattr(task_module.jax, "local_devices", lambda: devices)
        row = types.SimpleNamespace(set=lambda **a: None)
        me = types.SimpleNamespace(
            tracer=Tracer(peer="four"), compiles=None,
            family=types.SimpleNamespace(SLOW_STEP_ATTRIBUTES=()),
            collab_cfg=types.SimpleNamespace(trace_file=None),
            _bytes_on_read_device=lambda tree, itemsize=None: (0, 0))
        me._read_device = task_module.TrainingTask._read_device.func(me)
        me.memory = task_module.TrainingTask.memory.func(me)
        late = task_module.TrainingTask.late_steps.func(me)
        me.memory.start()
        for _ in range(5):
            assert late.device_memory()["bytes_in_use"] == 9
        assert [d.asked for d in devices] == [1, 2, 1, 1]
        me.memory.close_step(row)
        assert late.device_memory()["num_allocs"] == 3

    def test_the_pulse_and_the_callback_are_gone_after_the_loop(self,
                                                                late_loop):
        import gc
        assert "late-step-pulse" not in late_loop["threads"]
        assert not [c for c in gc.callbacks
                    if getattr(c, "__self__", None).__class__.__name__
                    == "LateSteps"]

    def test_they_are_gone_when_the_loop_raises(self, tmp_path, caplog):
        import logging
        from dalle_tpu.obs import compiles
        from dalle_tpu.training.loop import train_loop
        from tests.test_trainer_spans import _make_task

        class Over(Exception):
            pass

        def on_step(n, loss):
            if n == 6:
                assert [t for t in threading.enumerate()
                        if t.name == "late-step-pulse"]
                raise Over()
        try:
            with _make_task(tmp_path) as task:
                with pytest.raises(Over), caplog.at_level(
                        logging.INFO, logger="dalle_tpu.obs.late"):
                    train_loop(task, warmup_steps=1,
                               publish_metrics_records=False,
                               on_step=on_step)
                assert [r.getMessage() for r in caplog.records
                        if " of 5 steps" in r.getMessage()]
        finally:
            compiles.install(None)
        assert not [t for t in threading.enumerate()
                    if t.name == "late-step-pulse"]
