"""KV-cached decode tests: teacher-forced cached decode must reproduce the
training forward's logits exactly; sampling produces valid codes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_init import init_params
from dalle_tpu.config import tiny_model_config
from dalle_tpu.models.dalle import DALLE
from dalle_tpu.models.decode import (SamplingConfig, decode_step,
                                     generate_images, init_cache,
                                     layer_params, resolve_buckets,
                                     sample_logits)


def _setup(**overrides):
    cfg = tiny_model_config(**overrides)
    model = DALLE(cfg)
    params = init_params(model, jax.random.PRNGKey(0))
    # zero-init biases would make the decode-vs-training parity blind to a
    # dropped bias add (exactly the r4 FF-bias decode bug): perturb every
    # bias leaf so both paths must apply them identically
    key = jax.random.PRNGKey(99)

    def _noise_bias(path, leaf):
        if any(getattr(p, "key", None) == "bias" for p in path):
            k = jax.random.fold_in(key, abs(hash(str(path))) % (2 ** 31))
            return leaf + 0.05 * jax.random.normal(k, leaf.shape,
                                                   leaf.dtype)
        return leaf

    params = jax.tree_util.tree_map_with_path(_noise_bias, params)
    rng = jax.random.PRNGKey(7)
    text = jax.random.randint(rng, (2, cfg.text_seq_len), 2, cfg.vocab_text)
    image = jax.random.randint(rng, (2, cfg.image_seq_len), 0,
                               cfg.vocab_image)
    return cfg, model, params, text, image


# configurations covering the zoo + weight sharing (incl. the scan path)
CONFIGS = [
    dict(),                                              # full attention
    dict(attn_types=("axial_row", "axial_col"), depth=4),
    dict(attn_types=("axial_row", "axial_col", "axial_row", "axial_row"),
         depth=10, shared_block_cycle=4, final_conv_block=True,
         conv_kernel=3),                                 # scan + wconv
]


class TestCachedDecodeExactness:
    @pytest.mark.parametrize("overrides", CONFIGS)
    def test_matches_training_forward(self, overrides):
        cfg, model, params, text, image = _setup(**overrides)
        _, _, logits_full = model.apply(params, text, image,
                                        return_logits=True)

        labels = np.concatenate([np.asarray(text),
                                 np.asarray(image) + cfg.vocab_text], 1)
        inputs = np.concatenate(
            [np.full((2, 1), cfg.vocab_total), labels[:, :-1]], 1)

        cache = init_cache(cfg, batch=2)
        step = jax.jit(lambda c, ids, p: decode_step(params, cfg, c,
                                                     ids, p))
        got = []
        for p in range(cfg.total_seq_len):
            logits_p, cache = step(cache, jnp.asarray(inputs[:, p]),
                                   jnp.asarray(p))
            got.append(np.asarray(logits_p))
        got = np.stack(got, axis=1)
        np.testing.assert_allclose(got, np.asarray(logits_full),
                                   rtol=2e-4, atol=2e-4)

    def test_layer_params_covers_schedule(self):
        cfg, _, params, _, _ = _setup(
            depth=10, shared_block_cycle=4, final_conv_block=True,
            attn_types=("axial_row", "axial_col", "axial_row", "axial_row"),
            conv_kernel=3)
        layers = layer_params(params, cfg)
        assert len(layers) == cfg.depth
        # weight sharing: layer 0 and layer 4 read the same arrays
        assert layers[0]["attn"]["q"]["kernel"] is \
            layers[4]["attn"]["q"]["kernel"]
        assert layers[-1]["attn_type"] == "conv_like"


class TestSampling:
    def test_temperature_zero_is_argmax(self):
        logits = jnp.asarray([[1.0, 3.0, 2.0], [0.5, 0.1, 0.9]])
        out = sample_logits(jax.random.PRNGKey(0), logits,
                            SamplingConfig(temperature=0.0))
        np.testing.assert_array_equal(np.asarray(out), [1, 2])

    def test_top_k_restricts_support(self):
        logits = jnp.asarray([[0.0, 5.0, 4.0, -1.0]])
        cfgs = SamplingConfig(temperature=1.0, top_k=2)
        hits = {int(sample_logits(jax.random.PRNGKey(i), logits, cfgs)[0])
                for i in range(50)}
        assert hits <= {1, 2}

    def test_top_p_restricts_support(self):
        logits = jnp.asarray([[10.0, 9.0, -10.0, -10.0]])
        cfgs = SamplingConfig(temperature=1.0, top_p=0.9)
        hits = {int(sample_logits(jax.random.PRNGKey(i), logits, cfgs)[0])
                for i in range(50)}
        assert hits <= {0, 1}

    def test_generate_produces_valid_codes(self):
        cfg, model, params, text, _ = _setup(
            attn_types=("axial_row", "axial_col"), depth=2)
        codes = jax.jit(lambda t, r: generate_images(
            params, cfg, t, r, SamplingConfig(temperature=1.0, top_k=8)))(
                text, jax.random.PRNGKey(3))
        codes = np.asarray(codes)
        assert codes.shape == (2, cfg.image_seq_len)
        assert (codes >= 0).all() and (codes < cfg.vocab_image).all()
        # deterministic under the same seed
        codes2 = np.asarray(generate_images(
            params, cfg, text, jax.random.PRNGKey(3),
            SamplingConfig(temperature=1.0, top_k=8)))
        np.testing.assert_array_equal(codes, codes2)

    def test_greedy_decode_matches_forward_chain(self):
        """Greedy generation must equal iterating the full forward with
        argmax — the cache cannot change the distribution."""
        cfg, model, params, text, _ = _setup(depth=2)
        codes = np.asarray(generate_images(
            params, cfg, text, jax.random.PRNGKey(0),
            SamplingConfig(temperature=0.0)))
        # replay: feed the generated codes through the training forward and
        # check each position's argmax reproduces the generated code
        _, _, logits = model.apply(params, text, jnp.asarray(codes),
                                   return_logits=True)
        pred = np.asarray(jnp.argmax(logits[:, cfg.text_seq_len:], -1))
        np.testing.assert_array_equal(pred - cfg.vocab_text, codes)

def test_resolve_buckets_thresholds():
    """The measured adaptive bucket policy (DECODE_BENCH.json r4:
    B<=8 peaks at 4 buckets, B>=12 at 2; the threshold interpolates the
    B=8/B=16 crossover). The serving engine REUSES this function for its
    visible-bucket count (test_serving pins that), so these thresholds
    are a shared contract, not a generate_images detail."""
    for batch in range(1, 9):
        assert resolve_buckets(None, batch) == 4
    for batch in (9, 11, 12, 16, 64):
        assert resolve_buckets(None, batch) == 2
    # an explicit bucket count always wins over the adaptive choice
    assert resolve_buckets(1, 4) == 1
    assert resolve_buckets(7, 16) == 7


def test_prefix_buckets_do_not_change_samples():
    """Bucketed decode (statically truncated cache reads) must produce
    the IDENTICAL sample sequence to the single full-length scan — the
    truncation only skips cache rows the mask already forbids."""
    cfg, model, params, text, image = _setup(
        attn_types=("axial_row", "axial_col", "axial_row", "axial_row"),
        depth=10, shared_block_cycle=4, final_conv_block=True,
        conv_kernel=3)
    from dalle_tpu.models.decode import SamplingConfig, generate_images

    rng = jax.random.PRNGKey(11)
    sam = SamplingConfig(temperature=1.0, top_k=8)
    one = generate_images(params, cfg, text, rng, sam, buckets=1)
    four = generate_images(params, cfg, text, rng, sam, buckets=4)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(four))
