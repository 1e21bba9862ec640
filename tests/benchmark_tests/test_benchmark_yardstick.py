"""The seam between the harness and an architecture: every configuration
resolves a yardstick that keeps the contract of ``yardsticks/dalle.py``, an
unknown one is an error, and harness and reducers really read through it —
a second yardstick, written here into a throw-away root, changes
``correct``, the reference loss and the FLOPs behind ``mfu_pct`` with not
one edit to a file of the benchmark."""
import json
import os
import subprocess
import sys
from pathlib import Path

import benchmark_checks as checks
import pytest

from benchmark import manifest as M
from benchmark.harness import RunContext, peaks_for

MAN = M.Manifest()
YARDSTICKS = sorted(p.stem for p in (MAN.dir / "yardsticks").glob("*.py"))
REHEARSE = Path(__file__).parent / "benchmark_rehearse.py"

# what the test adds as a file of its own: the dalle yardstick with a loss
# twice and FLOPs three times as large
TWICE = '''"""dalle, with 2 x the loss and gradients and 3 x the FLOPs (a test's)."""
from benchmark.manifest import Manifest

_dalle = Manifest().yardstick("dalle")
tokens_per_sample = _dalle.tokens_per_sample
attention_min_seconds_per_sample = _dalle.attention_min_seconds_per_sample


def loss_and_grads(params, text, image, model, checkpoint_blocks=False):
    import jax
    loss, grads = _dalle.loss_and_grads(params, text, image, model,
                                        checkpoint_blocks)
    return 2 * loss, jax.tree.map(lambda g: 2 * g, grads)


def train_flops_per_sample(model):
    return 3 * _dalle.train_flops_per_sample(model)
'''


@pytest.mark.parametrize("config", sorted(MAN.configs))
def test_configuration_resolves_a_yardstick_that_counts(config):
    checks.configuration_resolves_a_yardstick_that_counts(MAN, config)


@pytest.mark.parametrize("name", YARDSTICKS)
def test_yardstick_module_keeps_the_contract(name):
    checks.yardstick_module_keeps_the_contract(MAN, name)


def test_absent_key_is_dalle_and_an_unknown_name_is_an_error(tmp_path):
    import benchmark_rehearse
    cell = benchmark_rehearse.tiny_root(tmp_path / "plain")
    assert "yardstick" not in cell.config
    assert Path(cell.yardstick.__file__) == (
        tmp_path / "plain/benchmark/yardsticks/dalle.py")
    with pytest.raises(M.BenchFailure, match=r"no yardstick 'nope'.*dalle"):
        benchmark_rehearse.tiny_root(tmp_path / "nope", yardstick="nope")
    for bad in ("../dalle", "", None):
        with pytest.raises(M.BenchFailure):
            MAN.yardstick(bad)


class _Trace:
    """Stands for a reduced trace in which the matched kernels ran 2 s."""

    @staticmethod
    def seconds_matching(pattern, scope=None):
        return 2.0


def _ctx(cell, **kw):
    return RunContext(model=cell.config["model"], yardstick=cell.yardstick,
                      chips=1, peaks=peaks_for("TPU v5 lite"),
                      values={"train_tokens_per_s": 1000.0}, trace=_Trace(),
                      traced_steps=3, samples_per_step=4, **kw)


def test_reducers_read_the_cells_yardstick(tmp_path):
    import benchmark_rehearse
    plain = benchmark_rehearse.tiny_root(tmp_path / "plain")
    (tmp_path / "twice/benchmark/yardsticks").mkdir(parents=True)
    (tmp_path / "twice/benchmark/yardsticks/twice.py").write_text(TWICE)
    twice = benchmark_rehearse.tiny_root(tmp_path / "twice",
                                         yardstick="twice")
    model = plain.config["model"]
    assert twice.yardstick.train_flops_per_sample(model) \
        == 3 * plain.yardstick.train_flops_per_sample(model)
    mfu = M.reducer("mfu")
    assert mfu(_ctx(twice)) == pytest.approx(3 * mfu(_ctx(plain)))
    # mfu by hand: flops a token x tokens/s over the peak
    y = plain.yardstick
    assert mfu(_ctx(plain)) == pytest.approx(
        100 * y.train_flops_per_sample(model) / y.tokens_per_sample(model)
        * 1000.0 / 197e12)
    # the one kernel-roofline reducer, told which function to ask
    roof = next(m for m in plain.per_layer if m["name"] == "attn_roofline")
    assert roof["reducer"] == "kernel_roofline"
    least = y.attention_min_seconds_per_sample(model,
                                               peaks_for("TPU v5 lite"))
    got = M.reducer("kernel_roofline")(_ctx(plain), **roof["params"])
    assert got == pytest.approx(100 * least["seconds"] * 3 * 4 / 2.0)
    with pytest.raises(AttributeError):
        M.reducer("kernel_roofline")(_ctx(plain), pattern="x",
                                     least="no_such_function")


def test_harness_judges_by_the_configurations_yardstick(tmp_path):
    """The whole of ``run_cell`` on the CPU with the tiny configuration
    pointed at a yardstick file this test wrote: the system is sound and
    the yardstick says twice its loss, so the run is not ``correct``."""
    ydir = tmp_path / "root/benchmark/yardsticks"
    ydir.mkdir(parents=True)
    (ydir / "twice.py").write_text(TWICE)
    env = dict(os.environ, JAX_PLATFORMS="cpu", SECS="2",
               PYTHONPATH=str(M.ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, str(REHEARSE), "1", "0", str(tmp_path), "float32",
         "twice"], cwd=M.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1].split(":", 1)[1])
    assert result["correct"] is False and result["failed"] == 0
    ref = next(json.loads(line)["reference_check"] for line in lines
               if line.startswith('{"reference_check"'))
    assert ref["reference_loss"] == pytest.approx(2 * ref["loss"], rel=1e-4)
    assert ref["loss_rel_err"] == pytest.approx(0.5, rel=1e-3)
    assert ref["grad_rel_l2_max"] == pytest.approx(0.5, rel=1e-2)
    # nothing under the copied benchmark/ but the file the test wrote differs
    for sub in ("layer_metrics", "yardsticks"):
        for copied in (tmp_path / "root/benchmark" / sub).iterdir():
            if copied.name not in ("twice.py", "__pycache__"):
                assert copied.read_bytes() == (
                    MAN.dir / sub / copied.name).read_bytes()
