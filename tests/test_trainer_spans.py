"""The trainer times itself (OBSERVABILITY.md, plane ``train``): the
always-on ring of a ``TrainingTask`` records set-up, every step of
``train_loop`` and what ``collab.step`` does inside it; the compile counter
names what JAX compiled and when; the kernels keep their call site's name
under ``per_shard``; the model's unnamed parts carry device scopes."""

import logging
import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from dalle_tpu.config import (CollabConfig, OptimizerConfig, PeerConfig,
                              TrainerConfig, tiny_model_config)
from dalle_tpu.obs import compiles
from dalle_tpu.obs.trace import Tracer, default_tracer


def _make_task(tmp_path, **collab):
    from dalle_tpu.task import TrainingTask
    return TrainingTask(
        tiny_model_config(),
        OptimizerConfig(learning_rate=3e-3, warmup_steps=2, total_steps=100),
        TrainerConfig(per_device_batch=2, seed=0),
        CollabConfig(run_id=f"sp-{tmp_path.name}", target_batch_size=1 << 30,
                     matchmaking_time=0.5, allreduce_timeout=5.0,
                     averaging_timeout=10.0, average_state_every=0, **collab),
        PeerConfig(identity_path=str(tmp_path / "id.pem")))


#: every name of the span table that a solo run of the loop reaches
SOLO_PHASES = {
    "setup/dht", "setup/collab_optimizer", "setup/train_state",
    "setup/warmup", "loop/step", "loop/batch_fetch", "loop/grad_dispatch",
    "loop/loss_wait", "loop/hook", "collab/step", "collab/accumulate",
    "collab/progress", "collab/decide"}


class TestTrainLoopRing:
    @pytest.fixture(scope="class")
    def rows(self, tmp_path_factory):
        from dalle_tpu.training.loop import train_loop
        seen = []
        # another file's task of these shapes, earlier in this process,
        # would leave the accumulate compiled and step 1 nothing to count
        jax.clear_caches()
        # ... and the call sites it traced would be in the sites' record,
        # which is the process's: this task's own, from an empty one
        from dalle_tpu.ops.pallas import lowering
        record, lowering._RECORD = lowering._RECORD, {}
        try:
            with _make_task(tmp_path_factory.mktemp("ring")) as task:
                assert task.tracer is default_tracer()
                assert task.tracer.sink_path is None        # ring only
                assert task.collab_optimizer.tracer is task.tracer
                train_loop(task, max_steps=3, warmup_steps=1,
                           publish_metrics_records=False,
                           on_step=lambda n, loss: seen.append(n))
                assert task.tracer.ring_evictions == 0
                assert task.compiles is compiles.installed()
                yield task.tracer.dump(), task.compiles.snapshot()
        finally:
            lowering._RECORD = record
        compiles.install(None)

    def test_ring_only_tracer_records_the_solo_path(self, rows):
        ring, _ = rows
        train = [r for r in ring if r["plane"] == "train"]
        assert {r["phase"] for r in train if r["dur_s"] > 0} >= SOLO_PHASES
        setup = {r["trace"] for r in train
                 if r["phase"].startswith("setup/")}
        assert setup == {"setup"}

    def test_warmup_row_says_where_the_gradient_is_reduced(self, rows):
        """The engagement record of training/steps._accumulate_grads: the
        plan the step took for its sum over dp, on the row of the span in
        which the step is traced, and in trace_report's output."""
        from scripts.trace_report import setup_facts
        ring, _ = rows
        warm, = [r for r in ring if r["phase"] == "setup/warmup"]
        dp = jax.device_count()
        assert warm["a"]["grad_reduction"] == f"over dp={dp}: once per step"
        shown, = [a for k, a in setup_facts(ring).items()
                  if k.endswith("setup/warmup")]
        assert shown["grad_reduction"] == warm["a"]["grad_reduction"]

    def test_warmup_row_says_what_the_attention_layers_lowered_to(self,
                                                                  rows):
        """The engagement record of the lane-dense attention kernels
        (models/attention.attn_layout_record), set on the warm-up row once
        the step is traced: on the CPU no layer takes a Mosaic kernel, and
        the row says so."""
        from scripts.trace_report import setup_facts
        ring, _ = rows
        warm, = [r for r in ring if r["phase"] == "setup/warmup"]
        assert re.fullmatch(r"lane-dense 128: 0 of \d+ layers",
                            warm["a"]["attn_layout"])
        shown, = [a for k, a in setup_facts(ring).items()
                  if k.endswith("setup/warmup")]
        assert shown["attn_layout"] == warm["a"]["attn_layout"]

    def test_warmup_row_says_how_the_layer_scan_runs(self, rows):
        """The engagement record of the layer scan's conditional slots
        (models/transformer.layer_loop_record): a pure function of the
        model's configuration, on the warm-up row and in trace_report's
        output. The tiny preset's four dense layers build no scan."""
        from scripts.trace_report import setup_facts
        ring, _ = rows
        warm, = [r for r in ring if r["phase"] == "setup/warmup"]
        assert warm["a"]["layer_loop"] == "unrolled"
        shown, = [a for k, a in setup_facts(ring).items()
                  if k.endswith("setup/warmup")]
        assert shown["layer_loop"] == warm["a"]["layer_loop"]

    def test_rows_carry_their_step_and_the_span_that_caused_them(self, rows):
        ring, _ = rows
        steps = [r for r in ring if r["phase"] == "loop/step"]
        assert [r["trace"] for r in steps] == ["step:1", "step:2", "step:3"]
        assert all("parent" not in r for r in steps)
        by_phase = {}
        for r in ring:
            if r["trace"] == "step:2":
                by_phase[r["phase"]] = r
        for child in ("loop/batch_fetch", "loop/grad_dispatch",
                      "loop/loss_wait", "loop/hook", "collab/step"):
            assert by_phase[child]["parent"] == "loop/step", child
        for child in ("collab/accumulate", "collab/progress",
                      "collab/decide"):
            assert by_phase[child]["parent"] == "collab/step", child
        # children lie inside their parent on the one clock
        step, inner = by_phase["loop/step"], by_phase["collab/step"]
        assert step["t0"] <= inner["t0"]
        assert inner["t0"] + inner["dur_s"] <= step["t0"] + step["dur_s"] \
            + 2e-6

    def test_compiles_are_counted_by_program_and_by_span(self, rows):
        ring, counted = rows
        # the warm-up is where this task's grad step (a new jit: never
        # cached by an earlier test of the process) is traced and compiled
        warm = counted["by_span"]["setup/warmup"]
        assert warm["compile_n"] >= 1 and warm["trace_s"] > 0
        grad = counted["by_program"]["grad_step"]
        assert grad["trace_n"] >= 1 and grad["lower_s"] > 0
        # the first step compiles the accumulate; no later step compiles
        assert counted["after_first_step"] == []
        events = [r for r in ring if r["phase"] == "jit/compile"]
        assert events and all(r["dur_s"] == 0 for r in events)
        first_step = [r for r in events if r["trace"] == "step:1"]
        assert first_step and all(r["parent"] == "collab/accumulate"
                                  for r in first_step)


    def test_set_up_is_accounted_once_when_the_first_step_closes(self, rows):
        """One ``setup/account`` event, written between the first step's
        close and the second's start: the wall from the first set-up
        span's start, the spans' walls, what of it was JAX's machinery
        (parts that sum, so under the wall) and what no span names."""
        ring, _ = rows
        (event,) = [r for r in ring if r["phase"] == compiles.ACCOUNT_EVENT]
        assert event["dur_s"] == 0 and event["trace"] == "setup"
        assert "parent" not in event
        first, second = [r for r in ring if r["phase"] == "loop/step"][:2]
        closed = first["t0"] + first["dur_s"]
        assert closed <= event["t0"] <= second["t0"]
        a = event["a"]
        start = min(r["t0"] for r in ring if r["phase"].startswith("setup/")
                    and r["dur_s"] > 0)
        assert a["wall_s"] == pytest.approx(closed - start, abs=2e-3)
        walls = {r["phase"]: r["dur_s"] for r in ring if r["dur_s"] > 0}
        assert set(a["spans"]) == {"dht", "collab_optimizer", "train_state",
                                   "warmup"}
        for what, seconds in a["spans"].items():
            assert seconds == pytest.approx(walls[f"setup/{what}"], abs=2e-3)
        assert a["first_step_s"] == pytest.approx(first["dur_s"], abs=2e-3)
        # the optimizer builds the node and the state inside its own span
        # and the loop follows at once: the spans and the step cover it
        assert 0 <= a["unnamed_s"] < 0.05 * a["wall_s"]
        jit = a["trace_self_s"] + a["lower_self_s"] + a["compile_s"]
        assert 0 < jit <= a["wall_s"]
        assert a["grad_step_trace_s"] > 0 and a["grad_step_lower_s"] > 0
        assert a["sites"] == []         # a CPU mesh: no site took a kernel
        line = compiles.account_line(a)
        assert line.startswith("set-up to the first step's close took ")
        assert "train_state" in line and "none traced" in line

    def test_a_steady_step_writes_the_rows_it_wrote_before(self, rows):
        """What PR 54 added runs while JAX traces or once at the first
        step's close: a step after the first writes nine rows, as on the
        parent (its span, four children with the hook's, ``collab/step``
        and its three parts)."""
        ring, _ = rows
        by_step = {n: [r["phase"] for r in ring if r["trace"] == f"step:{n}"]
                   for n in (2, 3)}
        assert len(by_step[2]) == len(by_step[3]) == 9, by_step
        assert sorted(by_step[2]) == sorted(by_step[3])
        assert not [r for r in ring if r["phase"].startswith("trace/")
                    and r["trace"].startswith("step:")]

    def test_the_benchmarks_readers_find_the_set_up_metrics(self, rows):
        """The five per-layer metrics of PR 54 on the ring and the counter
        of a real (tiny) run, read as ``harness.run_cell`` reads them: the
        counter's two are numbers, each under the span or the wall it is
        part of; no site took a kernel on the CPU mesh, so the sites' spans
        give nothing to read and their count is 0."""
        from benchmark.harness import RunContext
        from benchmark.manifest import Manifest, reducer
        ring, _ = rows
        ctx = RunContext(values={}, traced_steps=0)
        files = {m["name"]: m for m in Manifest().cell(
            "flagship-train-solo").per_layer}
        read = lambda name: reducer(files[name]["reducer"])(
            ctx, **files[name]["params"])
        (event,) = [r for r in ring if r["phase"] == compiles.ACCOUNT_EVENT]
        assert 0 < read("state_init_jit_self_s") <= read("task_state_init_s")
        assert read("state_init_jit_self_s") <= read("state_init_jit_s")
        assert 0 < read("setup_jit_self_s") <= event["a"]["wall_s"]
        assert read("kernel_sites_trace_s") is None
        assert read("kernel_sites_again_trace_s") is None
        assert read("kernel_site_again_calls") == 0.0


def test_the_account_is_one_event_and_one_line_a_counter(caplog):
    """On a hand-made ring: nested set-up spans count once, a harness's
    own seconds between the task and the loop are ``unnamed_s``, the five
    sites whose tracing took longest are named, and a second call says
    nothing."""
    tracer = Tracer(peer="acct")
    counter = compiles.CompileCounter(tracer)
    assert counter.account_setup() is None       # no step has closed
    counter.reset(tracer)
    tracer.add("train", "setup/dht", "setup", 10.5, 0.5,
               parent="setup/collab_optimizer")
    tracer.add("train", "setup/train_state", "setup", 11.0, 6.0,
               parent="setup/collab_optimizer")
    tracer.add("train", "setup/collab_optimizer", "setup", 10.0, 8.0)
    tracer.add("train", "setup/warmup", "setup", 30.0, 3.0)
    counter.on_duration("/jax/core/compile/jaxpr_trace_duration", 4.0,
                        fun_name="grad_step")
    counter.on_duration("/jax/core/compile/backend_compile_duration", 2.0,
                        fun_name="jit(grad_step)")
    tracer.add("train", "loop/step", "step:1", 33.5, 1.5)
    by_site = {f"site {i}": {"calls": 3, "keys": 1, "trace_s": float(i),
                             "again_n": 2, "again_s": i / 2.0}
               for i in range(7)}
    snapshot = counter.snapshot
    counter.snapshot = lambda: dict(snapshot(), by_site=by_site)
    with caplog.at_level(logging.INFO, logger="dalle_tpu.obs.compiles"):
        account = counter.account_setup()
        assert counter.account_setup() is None
    assert account["wall_s"] == 25.0
    assert account["spans"] == {"collab_optimizer": 8.0, "dht": 0.5,
                                "train_state": 6.0, "warmup": 3.0}
    assert account["first_step_s"] == 1.5
    assert account["unnamed_s"] == pytest.approx(25.0 - 8.0 - 3.0 - 1.5)
    assert account["trace_self_s"] == 4.0 and account["compile_s"] == 2.0
    assert account["grad_step_trace_s"] == 4.0
    assert [s[0] for s in account["sites"]] == [f"site {i}"
                                                for i in (6, 5, 4, 3, 2)]
    assert account["sites"][0] == ["site 6", 6.0, 3, 1, 3.0]
    (event,) = [r for r in tracer.dump()
                if r["phase"] == compiles.ACCOUNT_EVENT]
    assert event["a"] == account and event["trace"] == "setup"
    (line,) = [r.getMessage() for r in caplog.records
               if r.name == "dalle_tpu.obs.compiles"]
    assert line == compiles.account_line(account)
    assert "took 25.0 s" in line and "unnamed 12.5" in line
    assert "site 6 6.0 s in 3 calls on 1 keys (3.0 s of it over again)" \
        in line
    # a task after this one counts anew and is accounted again
    counter.reset(tracer)
    assert counter.account_setup() is not None


def test_compile_counter_names_a_retraced_function_and_warns(caplog):
    tracer = Tracer(peer="t")
    counter = compiles.install(tracer)
    try:
        def leaky(x):
            return x * 2 + 1
        step = jax.jit(leaky)
        small, large = jnp.ones(3), jnp.ones(5)   # compiled out here
        before = counter.snapshot()["total"]["compile_n"]
        with tracer.span("train", "loop/step", "step:1"):
            step(small).block_until_ready()
        assert counter.snapshot()["after_first_step"] == []
        with caplog.at_level(logging.WARNING, logger="dalle_tpu.obs.compiles"):
            with tracer.span("train", "loop/step", "step:2"):
                step(large).block_until_ready()   # a new shape
        counted = counter.snapshot()
        assert counted["by_program"]["leaky"]["trace_n"] == 2
        assert counted["by_span"]["loop/step"]["compile_n"] == 2
        assert counted["total"]["compile_n"] == before + 2
        (late,) = counted["after_first_step"]
        assert "leaky" in late[0] and late[2] == "step:2"
        (warning,) = [r for r in caplog.records
                      if r.name == "dalle_tpu.obs.compiles"]
        assert "leaky" in warning.getMessage()
        assert "step:2" in warning.getMessage()
        events = [r for r in tracer.dump() if r["phase"] == "jit/compile"
                  and r["trace"] != "-"]       # the inputs' had no span
        assert [r["trace"] for r in events] == ["step:1", "step:2"]
        assert all(r["parent"] == "loop/step" and "leaky" in r["a"]["program"]
                   for r in events)
    finally:
        compiles.install(None)


class TestOptimizerSpans:
    def test_accumulates_open_between_the_rounds_hop_spans(self):
        """The r19 overlap proof without the observer effect: while an
        overlapped round is in flight, ``collab/accumulate`` spans —
        recorded at dispatch, the recorder never waits for the device —
        name the round and open between its first and last hop span."""
        import optax

        from dalle_tpu.swarm.optimizer import CollaborativeOptimizer
        from dalle_tpu.training.steps import TrainState, make_apply_step
        from tests.test_overlap import make_swarm, run_threads
        nodes = make_swarm(2)
        cfg = CollabConfig(run_id="ovs", target_batch_size=32,
                           matchmaking_time=1.5, allreduce_timeout=10.0,
                           averaging_timeout=20.0, average_state_every=0,
                           grad_compression="none",
                           delay_optimizer_step=True)
        # a payload whose hops take long enough on loopback for the
        # training thread's steps to fall between them
        n = 1 << 19
        tx = optax.sgd(0.1)
        tracers = [Tracer(peer=f"p{i}") for i in range(2)]
        peers = [CollaborativeOptimizer(
            node, cfg, TrainState.create({"w": jnp.ones((n,))}, tx),
            jax.jit(make_apply_step(tx)), tracer=tracer)
            for node, tracer in zip(nodes, tracers)]
        grads = {"w": jnp.full((n,), 0.5)}

        def drive(opt):
            opt.tracker.min_refresh_period = 0.05

            def run():
                deadline = time.monotonic() + 45
                while opt.local_epoch < 1 and time.monotonic() < deadline:
                    opt.step(grads, batch_size=8)
                    time.sleep(0.002)
                return opt.local_epoch
            return run

        try:
            assert run_threads([drive(o) for o in peers]) == [1, 1]
        finally:
            for opt in peers:
                opt.shutdown()
            for node in nodes:
                node.shutdown()
        round_id = "ovs:grads:0"
        for tracer in tracers:
            rows = tracer.dump()
            hops = [r for r in rows if r["trace"] == round_id
                    and r["phase"].startswith("ar_hop_")]
            assert hops, sorted({r["phase"] for r in rows})
            lo = min(r["t0"] for r in hops)
            hi = max(r["t0"] + r["dur_s"] for r in hops)
            during = [r for r in rows if r["phase"] == "collab/accumulate"
                      and r.get("a", {}).get("round") == round_id]
            assert during
            assert any(lo < r["t0"] < hi for r in during)
            assert all(r["parent"] == "collab/step" for r in during)
            assert any(r["phase"] == "collab/reconcile" for r in rows)
            assert any(r["phase"] == "collab/launch_round" for r in rows)


def test_per_shard_opens_the_call_sites_scope_inside_the_body():
    """XLA names a Mosaic call after the innermost name-stack component at
    the ``pallas_call``. Under ``per_shard`` that has to be the call
    site's name, not ``shard_map``."""
    from dalle_tpu.parallel.mesh import TOKENS_SPEC, make_mesh, per_shard
    mesh = make_mesh(dp=2, fsdp=2, tp=2)

    def innermost(scope):
        def kernel_site(x):
            return jnp.sin(x)

        def module(x):
            with jax.named_scope("attn"):
                return per_shard(kernel_site, mesh, (TOKENS_SPEC,),
                                 TOKENS_SPEC, scope=scope)(x)
        jaxpr = jax.make_jaxpr(module)(jnp.ones((8, 4, 4)))
        (outer,) = [e for e in jaxpr.eqns if e.primitive.name == "shard_map"]
        (sin,) = [e for e in outer.params["jaxpr"].eqns
                  if e.primitive.name == "sin"]
        return str(sin.source_info.name_stack).split("/")

    assert innermost("attn")[-1] == "attn"
    assert innermost(None)[-1] != "attn"       # what the scope is for
    # one device: the function itself, nothing between it and its caller
    fn = lambda x: x   # noqa: E731
    assert per_shard(fn, None, (P(),), P(), scope="attn") is fn


def test_device_scopes_reach_the_lowered_grad_step():
    """``embed``, ``head``, ``ce`` and ``grad_accumulate`` name what no
    flax module names; they are in the scope paths of the lowered text."""
    from dalle_tpu.models.dalle import (CE_SCOPE, DALLE, EMBED_SCOPE,
                                        HEAD_SCOPE, init_params)
    from dalle_tpu.training.steps import (GRAD_ACCUMULATE_SCOPE,
                                          make_grad_step)
    cfg = tiny_model_config()
    model = DALLE(cfg)
    params = jax.eval_shape(
        lambda: init_params(model, jax.random.PRNGKey(0)))
    batch = {"text": jax.ShapeDtypeStruct((4, cfg.text_seq_len), jnp.int32),
             "image": jax.ShapeDtypeStruct((4, cfg.image_seq_len), jnp.int32)}
    text = jax.jit(make_grad_step(model, accum_steps=2)).lower(
        params, batch).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]+)"', text))
    for scope in (EMBED_SCOPE, HEAD_SCOPE, CE_SCOPE, GRAD_ACCUMULATE_SCOPE):
        assert any(scope in path.split("/") for path in paths), scope
    # the backward pass keeps the forward's scope under its transpose
    assert any(path.startswith("transpose(") and HEAD_SCOPE in path.split("/")
               for path in paths)
    assert f"{GRAD_ACCUMULATE_SCOPE}/add" in paths
