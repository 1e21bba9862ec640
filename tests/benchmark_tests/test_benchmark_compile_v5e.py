"""The reference check's program compiled for the real chip from the
sandbox (no chip attached): it has to fit one v5e next to the trainer's
state. All in one file and behind fixtures, so that only the worker given
this file loads the TPU compiler."""
import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.manifest import Manifest


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_persistent_cache():
    # a compile for a described chip is written to the cache but cannot be
    # read back without one; keep the test silent and the cache clean
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _dalle_configurations():
    """The configurations judged by the ``dalle`` yardstick, whose program
    this file compiles; another architecture's PR brings its own file."""
    man = Manifest()
    return sorted(
        name for name, entry in man.configs.items()
        if json.loads((man.root / entry["file"]).read_text()).get(
            "yardstick", "dalle") == "dalle")


@pytest.mark.slow
@pytest.mark.parametrize("config", _dalle_configurations())
def test_reference_check_program_fits_one_v5e(config, one_chip,
                                              no_persistent_cache):
    from dalle_tpu.cli.run_trainer import MODEL_PRESETS
    from dalle_tpu.models.dalle import DALLE, init_params
    man = Manifest()
    reference = man.yardstick("dalle")
    on_file = json.loads(
        (man.root / man.configs[config]["file"]).read_text())
    model = on_file["model"]
    shapes = jax.eval_shape(lambda: init_params(
        DALLE(MODEL_PRESETS[on_file["preset"]]()), jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), shapes)
    text = jax.ShapeDtypeStruct((1, model["text_seq_len"]), jnp.int32,
                                sharding=one_chip)
    image = jax.ShapeDtypeStruct((1, model["image_grid"] ** 2), jnp.int32,
                                 sharding=one_chip)
    masks = {k: jax.ShapeDtypeStruct(m.shape, jnp.bool_, sharding=one_chip)
             for k, m in reference.masks_for(model).items()}
    compiled = reference.make_loss_and_grads(
        model, checkpoint_blocks=True).lower(params, text, image,
                                             masks).compile()
    mem = compiled.memory_analysis()
    need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    # one sequence at a time (reference.loss_and_grads); beside it live
    # the train state, the system's gradients and the running sum of the
    # reference's (XL: 0.7 + 1.4 + 1.4 GB) on a 16 GB chip
    assert need < 10e9, need
