"""The memory account (``dalle_tpu/obs/memory.py``, OBSERVABILITY.md "A full
chip says what holds it"): the owners weighed from the trees, the three
samples of a step on its ``loop/step`` row, where the process's peak last
rose, the record near the limit and the one at a failed allocation. The
allocator, the clock and the weigher are injected, as ``LateSteps``' tests
inject theirs; the loop tests run the tiny preset on the CPU, whose
backend has no allocator statistics, behind a fake one."""

import logging
import types

import jax
import numpy as np
import pytest

from dalle_tpu.obs import compiles, memory
from dalle_tpu.obs.memory import MemoryAccount
from dalle_tpu.obs.trace import Tracer

GRID = ("--shared-block-cycle", "4", "--attn-types", "axial_row", "axial_col",
        "axial_row", "axial_row", "--final-conv-block", "--depth", "10",
        "--scan-unroll", "2", "--conv-kernel", "3")
SPARSE = ("--hidden-size", "64", "--num-heads", "4", "--num-kv-heads", "2",
          "--head-dim", "16", "--expert-width", "32", "--num-experts", "8",
          "--experts-per-token", "2", "--experts-held", "4",
          "--expert-offset", "2", "--vocab-size", "96", "--window", "8",
          "--image-grid", "4", "--vocab-text", "48", "--vocab-image", "48",
          "--dtype", "float32", "--head-chunk", "16")
#: a tiny preset of each of the benchmark's four configurations
TINY = {
    "flagship": ("--preset", "tiny", *GRID),
    "xl": ("--preset", "tiny", *GRID, "--dim", "96", "--heads", "6",
           "--vocab-image", "64"),
    "smallthinker21b": ("--preset", "smallthinker21b", *SPARSE,
                        "--num-hidden-layers", "4", "--text-seq-len", "12"),
    "trinitymini": ("--preset", "trinitymini", *SPARSE,
                    "--num-hidden-layers", "5", "--text-seq-len", "16",
                    "--dense-width", "96"),
}


def make_task(tmp_path, flags=("--preset", "tiny")):
    from dalle_tpu.cli import run_trainer
    from dalle_tpu.task import TrainingTask
    args = run_trainer.build_parser().parse_args(
        [*flags, "--per-device-batch", "1", "--grad-accum-steps", "2",
         "--target-batch-size", str(1 << 30), "--seed", "7",
         "--identity-path", str(tmp_path / "id.pem")])
    return TrainingTask(*run_trainer.configs_from_args(args))


def held_by(device, tree):
    """Bytes of ``tree``'s shards on ``device``, from the shards' own
    data: not how ``TrainingTask`` reckons them."""
    return sum(shard.data.nbytes for leaf in jax.tree.leaves(tree)
               for shard in leaf.addressable_shards
               if shard.device == device)


class Allocator:
    """A fake ``memory_stats``: the owners' sum plus what a test says is
    held besides at each call (``held``, the last value repeating), a peak
    that only grows, and the calls counted."""

    def __init__(self, account=None, held=(0,), limit=10 ** 15, base=None,
                 unit=1):
        self.account, self.held, self.limit = account, list(held), limit
        self.base, self.calls, self.peak, self.unit = base, 0, 0, unit

    def __call__(self):
        extra = self.held[min(self.calls, len(self.held) - 1)]
        self.calls += 1
        base = self.base if self.base is not None else \
            self.account.owned_sum
        used = base + extra * self.unit
        self.peak = max(self.peak, used)
        return {"bytes_in_use": used, "bytes_reserved": 500 * self.unit,
                "peak_bytes_in_use": self.peak, "bytes_limit": self.limit,
                "largest_alloc_size": 64}


MIB = 2 ** 20


def gib(attrs):
    """A step's account as its row holds it: GiB to six places."""
    return {k: round(v * MIB / 2 ** 30, 6) for k, v in attrs.items()}


def events(tracer, phase):
    return [r for r in tracer.dump() if r["phase"] == phase]


# -- owners: exact, from the trees -------------------------------------------

@pytest.mark.parametrize("config", sorted(TINY))
def test_the_owners_are_the_trees_bytes_on_the_read_device(config, tmp_path):
    """Through ``TrainingTask`` and ``train_loop``, for a tiny preset of
    each of the four configurations: every owner's bytes are its leaves'
    shards on the read device, the count is the parameters', and the two
    ``memory/owners`` rows and the warm-up's sentence say so."""
    from dalle_tpu.training.loop import train_loop
    kept = {}
    try:
        with make_task(tmp_path, TINY[config]) as task:
            grad_step = task.grad_step

            def keeping(params, batch):
                kept["batch"], kept["out"] = batch, grad_step(params, batch)
                return kept["out"]
            task.__dict__["grad_step"] = keeping
            train_loop(task, max_steps=2, warmup_steps=1,
                       publish_metrics_records=False)
            device, account = task._read_device, task.memory
            state = task.collab_optimizer.state
            want = {"params": held_by(device, state.params),
                    "optimizer": held_by(device, state.opt_state),
                    "accumulator": held_by(
                        device, task.collab_optimizer._grad_acc),
                    "step_output": held_by(device, kept["out"]),
                    "batch": held_by(device, kept["batch"])}
            assert account.owned == want
            assert all(want.values())
            count = sum(x.size for x in jax.tree.leaves(state.params))
            assert account.parameters == count
            first, second = events(task.tracer, memory.OWNERS_EVENT)
            assert first["trace"] == "setup" and second["trace"] == "step:1"
            assert set(first["a"]) == {"params", "optimizer", "owned",
                                       "parameters", "bytes_per_param"}
            assert second["a"] == dict(
                want, owned=sum(want.values()), parameters=count,
                bytes_per_param=round(sum(want.values()) / count, 4))
            warm, = events(task.tracer, "setup/warmup")
            said = warm["a"]["memory_layout"]
            assert said.startswith(f"{count / 1e6:.1f} M parameters: "
                                   "params 4.00 B, optimizer ")
            assert said.endswith(
                f"= {sum(want.values()) / count:.2f} B a parameter "
                f"resident ({sum(want.values()) / 1e9:.2f} GB)")
            for row in events(task.tracer, "loop/step"):
                assert row["a"]["mem_state_bytes_per_param"] == \
                    second["a"]["bytes_per_param"]
    finally:
        compiles.install(None)


def test_a_leaf_elsewhere_weighs_nothing_and_an_itemsize_reweighs():
    from dalle_tpu import task as task_module
    here, there = jax.local_devices()[:2]
    me = types.SimpleNamespace(_read_device=here)
    weigh = lambda *a, **k: \
        task_module.TrainingTask._bytes_on_read_device(me, *a, **k)
    tree = {"here": jax.device_put(np.zeros((3, 5), np.float16), here),
            "there": jax.device_put(np.zeros((7,), np.float32), there),
            "host": 3.0}
    assert weigh(tree) == (30, 22)
    assert weigh(tree, itemsize=4) == (60, 22)


# -- a step's samples --------------------------------------------------------

def account_with(held, limit=10 ** 15, base=None, clock=None, unit=1):
    tracer = Tracer(peer="mem")
    account = MemoryAccount(
        tracer, tree_bytes=lambda tree, itemsize=None: tree,
        **({"clock": clock} if clock else {}))
    account.device_memory = Allocator(account, held, limit, base, unit)
    account._absent = None
    account.own(params=(4000 * unit, 1000), optimizer=(2000 * unit, 0))
    return tracer, account


def run_steps(tracer, account, n, first=1, unit=1):
    for i in range(first, first + n):
        with tracer.span("train", "loop/step", f"step:{i}") as row:
            account.after_grad((4000 * unit, 0), (10 * unit, 0))
            account.settled()
            account.after_accumulate((4000 * unit, 0))
            account.close_step(row)
    return [r["a"] for r in events(tracer, "loop/step")]


def test_the_four_samples_land_on_their_steps_row():
    """What the allocator held beyond the owners at the loop's start, then
    after the grad step's dispatch, after the wait for the loss, after the
    accumulate and at the edge of two steps: each on the row of its step,
    the transient and the unowned bytes measured from the settled
    reading, the edge the one the late-step recorder is handed."""
    tracer, account = account_with([0, 70, 300, 4300, 4005, 1900, 307, 4307,
                                    4007], unit=MIB)
    account.start()
    assert account.edge["bytes_in_use"] == 6000 * MIB
    one, two = run_steps(tracer, account, 2, unit=MIB)
    with_step = 4000 + 2000 + 4000 + 10
    owned = with_step + 4000
    per_param = owned * MIB / 1000
    assert one == dict(gib({
        "mem_after_grad": with_step + 70, "mem_settled": with_step + 300,
        "mem_after_accumulate": owned + 4300, "mem_edge": owned + 4005,
        "mem_step_max": owned + 4300, "mem_reserved": 500,
        # the first accumulate makes the accumulator: it counts here
        "mem_accumulate_transient": 4000 + 4300 - 300, "mem_unowned": 300}),
        mem_state_bytes_per_param=per_param)
    assert two == dict(gib({
        "mem_after_grad": owned + 1900, "mem_settled": owned + 307,
        "mem_after_accumulate": owned + 4307, "mem_edge": owned + 4007,
        "mem_step_max": owned + 4307, "mem_reserved": 500,
        "mem_accumulate_transient": 4000, "mem_unowned": 307}),
        mem_state_bytes_per_param=per_param)
    assert tuple(one) == ("mem_state_bytes_per_param",) + memory.SAMPLES + (
        "mem_step_max", "mem_reserved", "mem_unowned",
        "mem_accumulate_transient")
    assert set(one) == set(memory.STEP_ATTRIBUTES)
    assert account.edge["bytes_in_use"] == (owned + 4007) * MIB


def test_the_peak_is_noted_once_a_rise_and_never_in_a_flat_loop():
    tracer, account = account_with(
        [0, 0, 900, 0] + [0] * 12 + [0, 0, 1200, 0], base=5000)
    account.read("setup/train_state closed")
    with tracer.span("train", "setup/warmup", "setup"):
        account.read("setup/warmup opened")
        account.read("setup/warmup ran")          # the check's 900 above
    account.start()
    run_steps(tracer, account, 3)                 # flat: nothing
    rose, = events(tracer, memory.PEAK_EVENT)
    assert rose["a"] == {"from": 5000, "to": 5900, "span": "setup/warmup",
                         "at": "setup", "read_at": "setup/warmup ran",
                         "since": "setup/warmup opened"}
    run_steps(tracer, account, 1, first=4)        # one rise, in step 4
    _, late = events(tracer, memory.PEAK_EVENT)
    assert (late["a"]["from"], late["a"]["to"]) == (5900, 6200)
    assert late["a"]["at"] == "step:4" and late["a"]["span"] == "loop/step"
    assert late["a"]["read_at"] == "collab/accumulate returned"


def test_near_the_limit_one_event_a_step_and_one_warning_in_thirty_seconds(
        caplog):
    now = [100.0]
    tracer, account = account_with([0], limit=14600, clock=lambda: now[0])
    account.start()
    with caplog.at_level(logging.WARNING, logger="dalle_tpu.obs.memory"):
        run_steps(tracer, account, 3)
        now[0] += memory.WARN_EVERY_S + 1
        run_steps(tracer, account, 1, first=4)
    near = events(tracer, memory.NEAR_EVENT)
    assert [r["trace"] for r in near] == ["step:1", "step:2", "step:3",
                                          "step:4"]
    a = near[0]["a"]
    assert a["span"] == "loop/step" and a["owned"] == 14010
    assert a["bytes_in_use"] == 14010 and a["bytes_limit"] == 14600
    assert a["mem_after_accumulate"] == a["mem_edge"] == 14010   # bytes
    assert a["params"] == 4000
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 2
    assert said[0].startswith("step:1: the device holds over 95% of its "
                              "0.00 GB: owners params 0.00, optimizer ")
    assert "settled 0.00, after the accumulate" in said[0]
    assert "now in use" in said[0]
    # under the limit: nothing
    quiet, account = account_with([0], limit=10 ** 9)
    account.start()
    run_steps(quiet, account, 2)
    assert events(quiet, memory.NEAR_EVENT) == []


def test_an_absent_allocator_is_judged_once_and_never_asked_again():
    for answer in (None, {}, {"bytes_in_use": 0}):
        asked = []
        tracer = Tracer(peer="cpu")
        account = MemoryAccount(
            tracer, device_memory=lambda: asked.append(1) or answer,
            tree_bytes=lambda tree, itemsize=None: tree)
        account.state_built((4000, 1000), (2000, 0))
        account.start()
        rows = run_steps(tracer, account, 3)
        assert len(asked) == 1
        assert account.edge is None
        # the owners need no allocator
        assert rows[0] == {"mem_state_bytes_per_param": 14.01}
        assert len(events(tracer, memory.OWNERS_EVENT)) == 2
    account = MemoryAccount(Tracer(peer="none"))
    account.start()
    account.own(params=object())
    assert account.layout() == "no owner weighed"


# -- a failed allocation -----------------------------------------------------

OOM = ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
       "memory in memory space hbm. Used 17.09G of 15.75G hbm.\nmore")


class Exhausted(RuntimeError):
    pass


def test_a_failed_allocation_is_on_record_and_the_error_goes_on(tmp_path,
                                                                caplog):
    """A grad step that raises the runtime's error at the loop's fourth
    step: one ``memory/exhausted`` row naming owners, samples, span and
    program, one ERROR, and ``train_loop`` raises the same exception."""
    from dalle_tpu.training.loop import train_loop
    raised = Exhausted(OOM)
    try:
        with make_task(tmp_path) as task:
            account = task.memory
            account.device_memory = Allocator(account, [0, 0, 0, 3])
            account._absent = None
            grad_step, calls = task.grad_step, []

            def failing(params, batch):
                calls.append(1)
                if len(calls) == 5:                 # one warm-up, then four
                    raise raised
                return grad_step(params, batch)
            task.__dict__["grad_step"] = failing
            with pytest.raises(Exhausted) as caught, caplog.at_level(
                    logging.ERROR, logger="dalle_tpu.obs.memory"):
                train_loop(task, warmup_steps=1,
                           publish_metrics_records=False)
            assert caught.value is raised
            row, = events(task.tracer, memory.EXHAUSTED_EVENT)
            a = row["a"]
            assert row["trace"] == a["at"] == "step:4"
            assert a["span"] == "loop/grad_dispatch"
            assert a["program"] == "grad_step"
            assert a["message"] == OOM.splitlines()[0]
            assert a["owned"] == account.owned_sum
            assert {"params", "optimizer", "accumulator", "step_output",
                    "batch", "parameters", "bytes_per_param"} <= set(a)
            # the fourth step got as far as its grad step's dispatch: the
            # samples are the third's
            assert a["mem_settled"] == account.owned_sum + 3
            assert a["mem_edge"] == a["mem_step_max"]
            assert a["bytes_in_use"] == account.owned_sum + 3
            said, = [r.getMessage() for r in caplog.records]
            assert said.startswith("step:4: the device could not allocate "
                                   "in loop/grad_dispatch (program "
                                   "grad_step): owners params ")
            assert "the runtime said: RESOURCE_EXHAUSTED: XLA:TPU" in said
            assert len(events(task.tracer, "loop/step")) == 4
    finally:
        compiles.install(None)


def test_another_error_leaves_no_record(tmp_path):
    from dalle_tpu.training.loop import train_loop

    def on_step(n, loss):
        raise Exhausted("the hook's own")
    try:
        with make_task(tmp_path) as task:
            with pytest.raises(Exhausted):
                train_loop(task, warmup_steps=1, on_step=on_step,
                           publish_metrics_records=False)
            assert events(task.tracer, memory.EXHAUSTED_EVENT) == []
    finally:
        compiles.install(None)


def test_a_warm_up_that_cannot_allocate_is_on_record_too(tmp_path):
    from dalle_tpu.training.loop import train_loop
    try:
        with make_task(tmp_path) as task:
            def failing(params, batch):
                raise Exhausted(OOM)
            task.__dict__["grad_step"] = failing
            with pytest.raises(Exhausted):
                train_loop(task, warmup_steps=1,
                           publish_metrics_records=False)
            row, = events(task.tracer, memory.EXHAUSTED_EVENT)
            assert row["a"]["span"] == "setup/warmup"
            assert row["a"]["at"] == row["trace"] == "setup"
            assert row["a"]["params"] == task.memory.owned["params"]
    finally:
        compiles.install(None)


def test_a_program_is_named_only_if_the_counter_has_counted_it():
    tracer = Tracer(peer="c")
    counter = compiles.CompileCounter(tracer)
    account = MemoryAccount(tracer, compiles=counter)
    for phase in ("collab/accumulate", "loop/batch_fetch"):
        try:
            with tracer.span("train", "loop/step", "step:9"):
                with tracer.span("train", phase):
                    raise Exhausted(OOM)
        except Exhausted as exc:
            assert account.exhausted(exc)
        counter.on_duration(
            "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.1,
            fun_name="jit(accumulate_grads)")
    try:
        with tracer.span("train", "loop/step", "step:10"):
            with tracer.span("train", "collab/step"):
                with tracer.span("train", "collab/accumulate"):
                    raise Exhausted(OOM)
    except Exhausted as exc:
        assert account.exhausted(exc)
    assert not account.exhausted(ValueError("another"))
    assert [(r["a"]["span"], r["a"].get("program"))
            for r in events(tracer, memory.EXHAUSTED_EVENT)] == [
        ("collab/accumulate", None), ("loop/batch_fetch", None),
        ("collab/accumulate", "accumulate_grads")]


# -- the repair the account exposed ------------------------------------------

def test_the_accumulate_writes_over_the_accumulator_it_is_given(tmp_path):
    """``CollaborativeOptimizer._accumulate`` donates its first operand:
    after a step the accumulator's old buffers are deleted (the allocator
    never holds two), the gradients are not, and the sum is bit for bit
    the undonated program's."""
    import jax.numpy as jnp

    from dalle_tpu.swarm.optimizer import accumulate_grads
    try:
        with make_task(tmp_path) as task:
            opt = task.collab_optimizer
            batch = next(task.batches())
            grads, _ = task.grad_step(opt.state.params, batch)
            opt.step(grads, batch_size=2)
            first = jax.tree.leaves(opt._grad_acc)
            # copies on the device: a host view of a CPU buffer keeps it
            # alive, and the runtime then copies instead of donating
            kept = [a + 0 for a in first]
            more, _ = task.grad_step(opt.state.params, batch)
            opt.step(more, batch_size=3)
            assert all(a.is_deleted() for a in first)
            assert not any(g.is_deleted() for g in jax.tree.leaves(more))
            assert opt.local_samples == 5
            plain = jax.jit(accumulate_grads)(
                jax.tree.unflatten(jax.tree.structure(opt._grad_acc), kept),
                more, 3.0)
            for got, want in zip(jax.tree.leaves(opt._grad_acc),
                                 jax.tree.leaves(plain)):
                assert got.dtype == jnp.float32
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))
    finally:
        compiles.install(None)


def test_every_attribute_and_event_is_in_the_guide():
    import os
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "OBSERVABILITY.md")).read()
    for name in memory.STEP_ATTRIBUTES + (
            memory.OWNERS_EVENT, memory.PEAK_EVENT, memory.NEAR_EVENT,
            memory.EXHAUSTED_EVENT, "memory_layout", *memory.PROGRAMS.values()):
        assert f"`{name}`" in doc, name
