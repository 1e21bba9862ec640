"""The three readers of the program's own record (``benchmark/reducers/
program_span.py``, ``program_counter.py``, ``program_idle.py``) on a
hand-made flight ring and a hand-made trace with known answers: medians
over the window's steps, set-up seconds of nested spans, the loop's self
share, the compile counter by span and by program, and the device's idle
time named by leaf spans across the two clocks — with the clock check
refusing a ring that does not line up."""
import pytest

from benchmark import trace as T
from benchmark.harness import RunContext
from benchmark.reducers import program_counter, program_idle, program_span
from dalle_tpu.obs import compiles
from dalle_tpu.obs import trace as obs_trace

OFFSET_NS = 1_700_000_000_123_456_789     # profiler clock - perf_counter
US = 1e-6


def add_step(tracer, n, at, grad_s, self_s=0.004):
    """One step of the loop as the program records it: children first (a
    row is written when its span closes), then the step."""
    def row(phase, t0, t1, parent):
        tracer.add("train", phase, f"step:{n}", at + t0, t1 - t0,
                   parent=parent)
    t = 0.001
    row("loop/batch_fetch", t, t + 0.001, "loop/step")
    row("loop/grad_dispatch", t + 0.001, t + 0.001 + grad_s, "loop/step")
    t += 0.001 + grad_s
    row("loop/loss_wait", t, t + 0.001, "loop/step")
    row("loop/hook", t + 0.001, t + 0.002, "loop/step")
    c = t + 0.003                        # 1 ms of the loop's own time
    row("collab/accumulate", c + 0.001, c + 0.005, "collab/step")
    row("collab/progress", c + 0.005, c + 0.006, "collab/step")
    row("collab/decide", c + 0.006, c + 0.009, "collab/step")
    row("collab/step", c, c + 0.010, "loop/step")
    end = c + 0.010 + self_s - 0.002     # + the rest of its own time
    tracer.add("train", "loop/step", f"step:{n}", at, end)
    return at + c                        # when collab/step opened


@pytest.fixture()
def ring():
    """Six steps: two of set-up (slow), two of the window, two traced."""
    tracer = obs_trace.configure(peer="bench-test")
    tracer.add("train", "setup/train_state", "setup", 10.0, 8.0)
    # the optimizer opens the node inside its own span: they nest
    tracer.add("train", "setup/dht", "setup", 30.5, 0.5,
               parent="setup/collab_optimizer")
    tracer.add("train", "setup/collab_optimizer", "setup", 30.0, 2.0)
    tracer.add("train", "setup/warmup", "setup", 40.0, 3.0)
    tracer.event("train", "jit/compile", "setup", program="grad_step")
    opened = {}
    for n, grad_s in ((1, 5.0), (2, 0.9), (3, 0.5), (4, 0.7), (5, 0.6),
                      (6, 0.6)):
        opened[n] = add_step(tracer, n, 100.0 * n, grad_s)
    yield tracer, opened
    obs_trace._default = None


def ctx_for(trace=None, traced_steps=2, n_intervals=2):
    return RunContext(values={"n_intervals": n_intervals}, trace=trace,
                      traced_steps=traced_steps)


def test_step_medians_read_the_windows_steps(ring):
    ctx = ctx_for()
    grad = program_span.read(
        ctx, phases=["loop/grad_dispatch", "loop/loss_wait"],
        how="step_median")
    assert grad == pytest.approx((0.501 + 0.701) / 2)     # steps 3 and 4
    assert program_span.read(ctx, ["collab/step"], "step_median") \
        == pytest.approx(0.010)
    assert program_span.read(ctx, ["collab/accumulate"], "step_median") \
        == pytest.approx(0.004)
    assert program_span.read(ctx, ["collab/progress"], "step_median") \
        == pytest.approx(0.001)
    # with the traced steps unknown the window is the last two steps
    assert program_span.read(ctx_for(traced_steps=0),
                             ["loop/grad_dispatch"], "step_median") \
        == pytest.approx(0.6)


def test_self_share_is_what_no_child_covers(ring):
    # a step is 1 ms + fetch 1 + grad + wait 1 + hook 1 + 1 own + collab
    # 10 + 2 own: 4 ms of its own in all
    share = program_span.read(ctx_for(), ["loop/step"], "self_pct")
    spans = [0.017 + 0.5, 0.017 + 0.7]
    assert share == pytest.approx(100 * 0.004 / (sum(spans) / 2))


def test_setup_seconds_count_nested_spans_once(ring):
    ctx = ctx_for()
    assert program_span.read(ctx, ["setup/train_state"], "open_seconds") \
        == pytest.approx(8.0)
    assert program_span.read(
        ctx, ["setup/dht", "setup/collab_optimizer"], "open_seconds") \
        == pytest.approx(2.0)
    assert program_span.read(ctx, ["setup/warmup"], "open_seconds") \
        == pytest.approx(3.0)
    assert program_span.read(ctx, ["setup/nothing"], "open_seconds") is None


def test_no_ring_nothing_to_read():
    obs_trace._default = None
    assert program_span.read(ctx_for(), ["collab/step"], "step_median") \
        is None
    assert program_idle.read(ctx_for()) is None


def test_counter_by_span_by_program_and_after_the_first_step():
    tracer = obs_trace.Tracer(peer="bench-test")
    counter = compiles.install(tracer)
    try:
        trace_ev = "/jax/core/compile/jaxpr_trace_duration"
        lower_ev = "/jax/core/compile/jaxpr_to_mlir_module_duration"
        compile_ev = "/jax/core/compile/backend_compile_duration"
        with tracer.span("train", "setup/train_state", "setup"):
            counter.on_duration(trace_ev, 1.0, fun_name="normal")
            counter.on_duration(lower_ev, 0.5, fun_name="jit(normal)")
            counter.on_duration(compile_ev, 0.25, fun_name="jit(normal)")
            counter.on_event("/jax/compilation_cache/cache_hits")
        counter.on_duration(trace_ev, 7.0, fun_name="grad_step")
        counter.on_duration(lower_ev, 3.0, fun_name="jit(grad_step)")
        counter.on_duration(compile_ev, 40.0, fun_name="jit(grad_step)")
        ctx = ctx_for()
        assert program_counter.read(ctx, span="setup/train_state") == 1.75
        assert program_counter.read(ctx, program="grad_step",
                                    kinds=["trace", "lower"]) == 10.0
        assert program_counter.read(ctx, span="setup/never") is None
        assert program_counter.read(ctx, after_first_step=True) == 0.0
        add_step(tracer, 1, 100.0, 0.5)
        counter.on_duration(compile_ev, 0.1, fun_name="jit(_concat_f32)")
        assert program_counter.read(ctx, after_first_step=True) == 1.0
        assert counter.snapshot()["by_span"]["setup/train_state"][
            "cache_hits"] == 1
    finally:
        compiles.install(None)
    assert program_counter.read(ctx_for(), after_first_step=True) == 0.0


def ns(seconds):
    return int(round(seconds * 1e9)) + OFFSET_NS


def hand_trace(opened, skew_s=0.0):
    """The traced steps 5 and 6 on the profiler's clock: the harness's
    span 5 us around each ``collab/step`` row, and a device that is busy
    but for three gaps in step 5: 2 ms inside ``collab/accumulate`` (a
    leaf), 0.5 ms in ``collab/step``'s own time before it, 1 ms in the
    loop's own time after ``collab/step``."""
    lo, hi = 500.0, 600.7
    c = opened[5]
    gaps = [(c + 0.0002, c + 0.0007), (c + 0.002, c + 0.004),
            (c + 0.0105, c + 0.0115)]
    busy, at = [], lo
    for g0, g1 in gaps:
        busy.append(["fusion.1", ns(at), ns(g0) - ns(at)])
        at = g1
    busy.append(["fusion.2", ns(at), ns(hi) - ns(at)])
    host = [["bench/traced_window", ns(lo), ns(hi) - ns(lo)]]
    for n, skew in ((5, 0.0), (6, skew_s)):
        t0 = opened[n] + skew
        host.append(["bench/collab_step", ns(t0 - 5 * US),
                     ns(t0 + 0.010 + 5 * US) - ns(t0 - 5 * US)])
    return T.Reduced({"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": busy}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]})


def test_idle_time_is_named_by_leaf_spans_across_the_clocks(ring):
    _, opened = ring
    reduced = hand_trace(opened)
    anchors = [(s, s + d) for name, s, d in reduced.spans]
    rows = [(opened[n], opened[n] + 0.010) for n in (3, 4, 5, 6)]
    # the rows sit 5 us inside their spans at both ends
    assert program_idle.fit_offset(anchors, rows) == pytest.approx(
        OFFSET_NS, abs=1500)
    named = program_idle.read(ctx_for(trace=reduced))
    assert named == pytest.approx(100 * 2.0 / 3.5, rel=1e-3)


@pytest.mark.parametrize("skew_us", [120, -120])
def test_clock_check_refuses_a_ring_that_does_not_line_up(ring, skew_us):
    """One offset has to put every traced ``collab/step`` row inside its
    harness span to 50 us: a ring whose second step sits 120 us off is
    not on the trace's clock, and nothing is reported."""
    _, opened = ring
    reduced = hand_trace(opened, skew_s=skew_us * US)
    assert program_idle.read(ctx_for(trace=reduced)) is None
    # fewer rows than harness spans: nothing to fit on
    anchors = [(s, s + d) for name, s, d in reduced.spans]
    assert program_idle.fit_offset(anchors, [(1.0, 1.01)]) is None
    assert program_idle.fit_offset([], [(1.0, 1.01)]) is None
