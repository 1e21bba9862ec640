"""The Mosaic census (``harness.mosaic_census``): a configuration's
``mosaic_kernels`` name roles as regular expressions, not how the program
splits a backward pass. On lowered text: a fused attention backward fills
the sparse lists' role as today's two kernels do, a forward alone or an
attention on XLA is ``missing``, a kernel no entry names is ``unlisted`` and
fails nothing, a plain name matches only itself. On the program's own grad
step, lowered for a TPU from the sandbox (no chip, no compile): it fills
every role of its configuration, however it splits a role and whatever else
it runs; with the kernels of attention's backward renamed to one it still
does, and with attention on ``dense_causal_attention`` it does not."""
import json
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import mosaic_census
from benchmark.manifest import Manifest
from dalle_tpu.cli.run_trainer import MODEL_PRESETS

MAN = Manifest()
SPARSE = ("smallthinker21b", "trinitymini")
ATTENTION_ROLES = ["_causal_fwd_kernel", r"_causal_(?!fwd_)\w+"]


def roles(config):
    on_file = json.loads((MAN.root / MAN.configs[config]["file"]).read_text())
    return on_file["mosaic_kernels"]


def lowered_text(*names):
    """What ``harness.kernel_census`` reads of a lowered program: one
    Mosaic custom call a name."""
    return "\n".join(
        f'stablehlo.custom_call @tpu_custom_call(%{i}) {{kernel_name = '
        f'"{name}"}}' for i, name in enumerate(names))


# what else each sparse list names, one kernel a role
OTHERS = {"smallthinker21b": ["_gmm_kernel", "_tgmm_kernel",
                              "_token_sum_kernel"],
          "trinitymini": ["_gmm_kernel", "_tgmm_kernel", "_token_sum_kernel",
                          "_head_norm_fwd_kernel", "_head_norm_bwd_kernel"]}
TODAY = ["_causal_fwd_kernel", "_causal_dq_kernel", "_causal_dkv_kernel"]
FUSED = ["_causal_fwd_kernel", "_causal_bwd_kernel"]


@pytest.mark.parametrize("config", SPARSE)
@pytest.mark.parametrize("attention", [FUSED, TODAY],
                         ids=["one_fused_backward", "todays_split"])
def test_the_sparse_lists_take_any_split_of_the_backward(config, attention):
    assert set(ATTENTION_ROLES) <= set(roles(config))
    census = mosaic_census(lowered_text(*attention, *OTHERS[config]),
                           roles(config))
    assert census["missing"] == [] and census["unlisted"] == {}
    assert set(census["found"]) == set(attention) | set(OTHERS[config])


@pytest.mark.parametrize("config", SPARSE)
@pytest.mark.parametrize("attention, missing", [
    (["_causal_fwd_kernel"], ATTENTION_ROLES[1:]),
    ([], ATTENTION_ROLES),
    # the backward alone: the forward's plain name matches only itself
    (["_causal_dq_kernel", "_causal_dkv_kernel"], ATTENTION_ROLES[:1]),
], ids=["forward_only", "no_causal_kernel", "backward_only"])
def test_an_attention_direction_off_mosaic_is_missing(config, attention,
                                                      missing):
    census = mosaic_census(lowered_text(*attention, *OTHERS[config]),
                           roles(config))
    assert census["missing"] == missing
    assert census["unlisted"] == {}


@pytest.mark.parametrize("config", SPARSE)
def test_a_kernel_no_entry_names_is_unlisted_and_fails_nothing(config):
    text = lowered_text(*FUSED, *OTHERS[config], "_rotary_norm_kernel",
                        "_rotary_norm_kernel")
    census = mosaic_census(text, roles(config))
    assert census["missing"] == []
    assert census["unlisted"] == {"_rotary_norm_kernel": 2}
    assert census["found"]["_rotary_norm_kernel"] == 2


@pytest.mark.parametrize("config, absent, present", [
    ("flagship", "_bwd_kernel", "_win_bwd_kernel"),
    ("flagship", "_fwd_kernel", "_ff_fwd_kernel"),
    ("xl", "_bwd_kernel", "_win_bwd_kernel"),
])
def test_a_plain_name_matches_only_itself(config, absent, present):
    names = [r for r in roles(config) if r != absent]
    assert present in names
    census = mosaic_census(lowered_text(*names), roles(config))
    assert census["missing"] == [absent] and census["unlisted"] == {}
    whole = mosaic_census(lowered_text(*roles(config)), roles(config))
    assert whole["missing"] == [] and whole["unlisted"] == {}


def test_an_empty_list_names_nothing():
    census = mosaic_census(lowered_text("_fwd_kernel"), [])
    assert census == {"found": {"_fwd_kernel": 1}, "missing": [],
                      "unlisted": {"_fwd_kernel": 1}}


# -- the program's own step -------------------------------------------------

def grad_step_text(config, monkeypatch, **overrides):
    """The configuration's grad step at its cell's batch, lowered for a TPU
    without one: the dispatchers are told the backend is a TPU, as the
    compile tests tell them, and nothing is compiled."""
    from dalle_tpu.models import family
    from dalle_tpu.parallel.mesh import make_mesh
    from dalle_tpu.training.steps import make_grad_step
    cell = MAN.cell(next(w["name"] for w in MAN.data["workloads"]
                         if w["config"] == config and w["chips"] == 1))
    cfg = MODEL_PRESETS[cell.config["preset"]](**overrides)
    fam = family(cfg)
    model = fam.build(cfg, make_mesh(devices=jax.devices()[:1]))
    params = jax.eval_shape(
        lambda: fam.init_params(model, jax.random.PRNGKey(0)))
    accum = cell.traffic["grad_accum_steps"]
    rows = cell.traffic["per_device_batch"] * accum
    batch = {"text": jax.ShapeDtypeStruct((rows, cfg.text_seq_len),
                                          jnp.int32),
             "image": jax.ShapeDtypeStruct((rows, cfg.image_seq_len),
                                           jnp.int32)}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = jax.jit(make_grad_step(model, accum_steps=accum))
    return step.trace(params, batch).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("config", sorted(MAN.configs))
def test_the_programs_step_fills_every_role(config, monkeypatch):
    """``missing`` alone is held: a kernel the program gains, or another
    split of a role, is the program's to choose and shows under
    ``unlisted`` in the run's record, where the next benchmark PR reads
    it."""
    text = grad_step_text(config, monkeypatch)
    census = mosaic_census(text, roles(config))
    assert census["missing"] == [], census
    if config in SPARSE:
        # the rehearsal of a fused backward: whatever kernels fill the
        # backward's role today, under one other ``_causal_`` name, no file
        # of the benchmark edited
        backward = [name for name in census["found"]
                    if re.fullmatch(ATTENTION_ROLES[1], name)]
        assert backward
        for name in backward:
            text = text.replace(f'"{name}"', '"_causal_rehearsed_bwd_kernel"')
        fused = mosaic_census(text, roles(config))
        assert fused["missing"] == []
        assert fused["unlisted"] == census["unlisted"]
        assert fused["found"]["_causal_rehearsed_bwd_kernel"] == sum(
            census["found"][name] for name in backward)


@pytest.mark.parametrize("config", SPARSE)
def test_attention_on_the_xla_lowering_fails_the_census(config, monkeypatch):
    """The dispatcher gives way, as it does for a head size that is no lane
    tile (the configuration's own knob: nothing inside the program is
    patched, so the test holds however a later PR arranges the dispatch):
    every layer takes the dense lowering and no ``_causal_`` kernel is
    left. At a 1024-token sequence, so that the dense mask the lowered text
    holds stays small; the choice does not read the length."""
    text = grad_step_text(config, monkeypatch, head_dim=64, text_seq_len=768,
                          image_grid=16)
    census = mosaic_census(text, roles(config))
    assert set(ATTENTION_ROLES) <= set(census["missing"])
    assert not [name for name in census["found"]
                if name.startswith("_causal_")]
    # the expert layer does not read the head size: its roles stay filled
    assert "_gmm_kernel" in census["found"]
