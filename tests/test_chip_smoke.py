"""chip_smoke.py's plumbing on the CPU, and the compile-cache placement rule.

The smoke itself only means something on the TPU; what is checked here is
that its driver function spawns and tears down the aux peer, refuses the
wrong backend, turns a missing kernel into a failure, and (slow-marked,
like every subprocess cotrain) carries the tiny preset through three really
exchanged epochs with the kernels in interpret mode.
"""

import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from dalle_tpu.utils import compile_cache  # noqa: E402


@pytest.fixture(autouse=True)
def cache_option_restored():
    """The smoke and the helper place the process-wide compile cache; the
    rest of the suite must not inherit that."""
    before = getattr(jax.config, compile_cache.JAX_OPTION)
    yield before
    jax.config.update(compile_cache.JAX_OPTION, before)


@pytest.fixture
def children(monkeypatch):
    """Every process the smoke starts, for the teardown assertions."""
    started = []
    popen = subprocess.Popen

    def recording_popen(*args, **kwargs):
        started.append(popen(*args, **kwargs))
        return started[-1]

    monkeypatch.setattr(chip_smoke.subprocess, "Popen", recording_popen)
    return started


def test_no_chip_no_result_and_the_child_is_stopped(tmp_path, capsys,
                                                   children):
    with pytest.raises(chip_smoke.SmokeFailure, match="default_backend"):
        chip_smoke.main(["--out-dir", str(tmp_path)])    # needs "tpu"
    assert '"ok"' not in capsys.readouterr().out
    assert len(children) == 1
    assert children[0].poll() is not None, "aux peer left running"
    assert not (tmp_path / "trainer_epochs.jsonl").exists()


def test_missing_kernel_family_fails_the_census():
    """A program lowered for the TPU names its Mosaic calls; a family
    absent from it (its dispatcher gave way to XLA) is a failure."""
    from dalle_tpu.ops.pallas.quant_kernels import quantize_blocks_pallas

    text = jax.jit(quantize_blocks_pallas).trace(
        jax.ShapeDtypeStruct((8, 512), jnp.float32)).lower(
            lowering_platforms=("tpu",)).as_text()
    census = chip_smoke.kernel_census(text)
    assert census["_quant_kernel"] == text.count("tpu_custom_call") == 1
    chip_smoke.check_kernels("apply step", census,
                             chip_smoke.APPLY_STEP_KERNELS)
    with pytest.raises(chip_smoke.SmokeFailure, match="axial attention"):
        chip_smoke.check_kernels("grad step", census,
                                 chip_smoke.GRAD_STEP_KERNELS)


@pytest.mark.slow
def test_tiny_preset_exchanges_three_epochs(tmp_path, children):
    device = chip_smoke.run_smoke(
        tmp_path, preset="tiny", per_device_batch=1, grad_accum_steps=2,
        matchmaking_time=1.5, require_backend=None, interpret_kernels=True,
        # "auto" is the host codec on a CPU peer; the smoke insists on the
        # device codec, whose XLA twin runs here
        trainer_args=("--wire-codec-backend", "device",
                      "--warmup-batches", "1", "--allreduce-timeout", "20"))
    assert device == {"platform": "cpu", "kind": "cpu", "count": 8}
    rows = (tmp_path / "trainer_epochs.jsonl").read_text().splitlines()
    assert len(rows) == 3
    assert children[0].poll() is not None, "aux peer left running"
    from dalle_tpu.models import attention
    assert attention._PALLAS_INTERPRET is False     # restored


class TestCompileCachePlacement:
    def test_environment_places_the_cache(self, monkeypatch, tmp_path,
                                          cache_option_restored):
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # nothing set in code: JAX reads the variable itself
        assert (getattr(jax.config, compile_cache.JAX_OPTION)
                == cache_option_restored)

    def test_default_is_inside_the_checkout(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        path = compile_cache.enable_compile_cache()
        assert path == str(REPO / compile_cache.DEFAULT_DIRNAME)
        assert getattr(jax.config, compile_cache.JAX_OPTION) == path
