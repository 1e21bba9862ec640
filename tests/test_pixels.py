"""VQGAN decoder + CLIP reranker: the pixel half of the inference pipeline.

The reference decodes sampled codes with a taming-transformers VQGAN and
reranks with OpenAI CLIP (``inference/run_inference.py:122-138``). These
tests prove (a) the Flax decoders run and are deterministic, (b) the torch
checkpoint mappers produce exactly the parameter trees the Flax modules
expect (round-trip through a synthetic torch state dict with the real key
schema), and (c) the CLIP BPE tokenizer implements byte-level BPE correctly
against a hand-computable merges table.
"""

import gzip
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.models.clip import (CLIPModel, CLIPTokenizer, clip_scores,
                                   map_openai_state_dict, resize_for_clip,
                                   tiny_clip_config)
from dalle_tpu.models.vqgan import (VQGANDecoder, decode_codes,
                                    map_taming_state_dict,
                                    tiny_vqgan_config)

torch = pytest.importorskip("torch")


# ---------------------------------------------------------------------------
# VQGAN
# ---------------------------------------------------------------------------

def test_vqgan_decodes_codes_to_pixels():
    cfg = tiny_vqgan_config()
    model = VQGANDecoder(cfg)
    codes = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.n_embed,
                                         (2, cfg.code_grid ** 2)), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), codes)
    imgs = decode_codes(params, cfg, codes)
    assert imgs.shape == (2, cfg.resolution, cfg.resolution, 3)
    assert imgs.dtype == jnp.uint8
    again = decode_codes(params, cfg, codes)
    np.testing.assert_array_equal(np.asarray(imgs), np.asarray(again))


def _fake_taming_state_dict(cfg, flax_params):
    """Build a torch state dict with taming-transformers' key schema whose
    values are the given flax params (conv kernels transposed back), so
    loading it must reproduce the flax tree exactly."""
    sd = {}
    p = flax_params["params"]

    def put_conv(torch_name, leaf):
        sd[f"{torch_name}.weight"] = torch.tensor(
            np.transpose(np.asarray(leaf["kernel"]), (3, 2, 0, 1)))
        sd[f"{torch_name}.bias"] = torch.tensor(np.asarray(leaf["bias"]))

    def put_norm(torch_name, leaf):
        sd[f"{torch_name}.weight"] = torch.tensor(np.asarray(leaf["scale"]))
        sd[f"{torch_name}.bias"] = torch.tensor(np.asarray(leaf["bias"]))

    def put_resnet(torch_prefix, blk):
        put_norm(f"{torch_prefix}.norm1", blk["norm1"])
        put_conv(f"{torch_prefix}.conv1", blk["conv1"])
        put_norm(f"{torch_prefix}.norm2", blk["norm2"])
        put_conv(f"{torch_prefix}.conv2", blk["conv2"])
        if "nin_shortcut" in blk:
            put_conv(f"{torch_prefix}.nin_shortcut", blk["nin_shortcut"])

    def put_attn(torch_prefix, blk):
        put_norm(f"{torch_prefix}.norm", blk["norm"])
        for nm in ("q", "k", "v", "proj_out"):
            put_conv(f"{torch_prefix}.{nm}", blk[nm])

    sd["quantize.embed.weight"] = torch.tensor(np.asarray(p["codebook"]))
    put_conv("post_quant_conv", p["post_quant_conv"])
    put_conv("decoder.conv_in", p["conv_in"])
    put_resnet("decoder.mid.block_1", p["mid_block_1"])
    put_attn("decoder.mid.attn_1", p["mid_attn_1"])
    put_resnet("decoder.mid.block_2", p["mid_block_2"])
    for i_level in range(len(cfg.ch_mult)):
        for i_block in range(cfg.num_res_blocks + 1):
            key = f"up_{i_level}_block_{i_block}"
            if key in p:
                put_resnet(f"decoder.up.{i_level}.block.{i_block}", p[key])
            akey = f"up_{i_level}_attn_{i_block}"
            if akey in p:
                put_attn(f"decoder.up.{i_level}.attn.{i_block}", p[akey])
        ukey = f"up_{i_level}_upsample"
        if ukey in p:
            put_conv(f"decoder.up.{i_level}.upsample.conv", p[ukey])
    put_norm("decoder.norm_out", p["norm_out"])
    put_conv("decoder.conv_out", p["conv_out"])
    return sd


def test_taming_checkpoint_mapping_roundtrip():
    cfg = tiny_vqgan_config()
    model = VQGANDecoder(cfg)
    codes = jnp.zeros((1, cfg.code_grid ** 2), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), codes)
    sd = _fake_taming_state_dict(cfg, params)
    mapped = map_taming_state_dict(sd, cfg)

    flat_ref = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_map = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(jnp.asarray, mapped))[0]
    assert [k for k, _ in flat_map] == [k for k, _ in flat_ref]
    for (path, a), (_, b) in zip(flat_map, flat_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   err_msg=str(path))
    # and the mapped params actually run
    imgs = decode_codes(mapped, cfg, codes)
    assert imgs.shape == (1, cfg.resolution, cfg.resolution, 3)


# ---------------------------------------------------------------------------
# CLIP
# ---------------------------------------------------------------------------

def test_clip_scores_shapes_and_selfconsistency():
    cfg = tiny_clip_config()
    model = CLIPModel(cfg)
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.rand(3, cfg.image_size, cfg.image_size, 3),
                         jnp.float32)
    tokens = jnp.asarray(rng.randint(1, cfg.vocab_size, (2, cfg.context_length)),
                         jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), images, tokens)
    scores = clip_scores(params, cfg, images, tokens)
    assert scores.shape == (3, 2)
    assert np.all(np.abs(np.asarray(scores)) <= 1.0 + 1e-5)  # cosine range
    # identical images must tie
    images2 = jnp.concatenate([images[:1], images[:1]], axis=0)
    s2 = np.asarray(clip_scores(params, cfg, images2, tokens))
    np.testing.assert_allclose(s2[0], s2[1], atol=1e-6)


def test_clip_resize_uint8():
    cfg = tiny_clip_config()
    imgs = (np.random.RandomState(0).rand(2, 8, 8, 3) * 255).astype(np.uint8)
    out = resize_for_clip(jnp.asarray(imgs), cfg)
    assert out.shape == (2, cfg.image_size, cfg.image_size, 3)
    assert float(out.max()) <= 1.0 and float(out.min()) >= 0.0


def _fake_openai_state_dict(cfg, flax_params):
    sd = {}
    p = flax_params["params"]
    sd["visual.conv1.weight"] = torch.tensor(np.transpose(
        np.asarray(p["patch_embed"]["kernel"]), (3, 2, 0, 1)))
    sd["visual.class_embedding"] = torch.tensor(
        np.asarray(p["class_embedding"]))
    sd["visual.positional_embedding"] = torch.tensor(
        np.asarray(p["vision_pos"]))
    sd["visual.proj"] = torch.tensor(np.asarray(p["vision_proj"]))
    sd["token_embedding.weight"] = torch.tensor(
        np.asarray(p["token_embedding"]))
    sd["positional_embedding"] = torch.tensor(np.asarray(p["text_pos"]))
    sd["text_projection"] = torch.tensor(np.asarray(p["text_proj"]))
    sd["logit_scale"] = torch.tensor(np.asarray(p["logit_scale"]))

    def put_ln(torch_name, leaf):
        sd[f"{torch_name}.weight"] = torch.tensor(np.asarray(leaf["scale"]))
        sd[f"{torch_name}.bias"] = torch.tensor(np.asarray(leaf["bias"]))

    put_ln("visual.ln_pre", p["ln_pre"])
    put_ln("visual.ln_post", p["ln_post"])
    put_ln("ln_final", p["ln_final"])

    def put_block(torch_prefix, blk, width):
        put_ln(f"{torch_prefix}.ln_1", blk["ln_1"])
        put_ln(f"{torch_prefix}.ln_2", blk["ln_2"])
        attn = blk["attn"]
        ws, bs = [], []
        for nm in ("query", "key", "value"):
            k = np.asarray(attn[nm]["kernel"]).reshape(width, width)
            ws.append(k.T)
            bs.append(np.asarray(attn[nm]["bias"]).reshape(width))
        sd[f"{torch_prefix}.attn.in_proj_weight"] = torch.tensor(
            np.concatenate(ws, axis=0))
        sd[f"{torch_prefix}.attn.in_proj_bias"] = torch.tensor(
            np.concatenate(bs, axis=0))
        out_k = np.asarray(attn["out"]["kernel"]).reshape(width, width)
        sd[f"{torch_prefix}.attn.out_proj.weight"] = torch.tensor(out_k.T)
        sd[f"{torch_prefix}.attn.out_proj.bias"] = torch.tensor(
            np.asarray(attn["out"]["bias"]))
        sd[f"{torch_prefix}.mlp.c_fc.weight"] = torch.tensor(
            np.asarray(blk["mlp_fc"]["kernel"]).T)
        sd[f"{torch_prefix}.mlp.c_fc.bias"] = torch.tensor(
            np.asarray(blk["mlp_fc"]["bias"]))
        sd[f"{torch_prefix}.mlp.c_proj.weight"] = torch.tensor(
            np.asarray(blk["mlp_proj"]["kernel"]).T)
        sd[f"{torch_prefix}.mlp.c_proj.bias"] = torch.tensor(
            np.asarray(blk["mlp_proj"]["bias"]))

    for i in range(cfg.vision_layers):
        put_block(f"visual.transformer.resblocks.{i}",
                  p[f"vision_block_{i}"], cfg.vision_width)
    for i in range(cfg.text_layers):
        put_block(f"transformer.resblocks.{i}",
                  p[f"text_block_{i}"], cfg.text_width)
    return sd


def test_openai_checkpoint_mapping_preserves_scores():
    """Round-trip: flax params -> torch state dict (openai schema) ->
    mapper -> identical CLIP scores."""
    cfg = tiny_clip_config()
    model = CLIPModel(cfg)
    rng = np.random.RandomState(1)
    images = jnp.asarray(rng.rand(2, cfg.image_size, cfg.image_size, 3),
                         jnp.float32)
    tokens = jnp.asarray(rng.randint(1, cfg.vocab_size,
                                     (2, cfg.context_length)), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(2), images, tokens)
    sd = _fake_openai_state_dict(cfg, params)
    mapped = jax.tree.map(jnp.asarray, map_openai_state_dict(sd, cfg))
    want = np.asarray(clip_scores(params, cfg, images, tokens))
    got = np.asarray(clip_scores(mapped, cfg, images, tokens))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# CLIP BPE tokenizer
# ---------------------------------------------------------------------------

def _write_merges(tmp_path, merges):
    path = tmp_path / "merges.txt.gz"
    buf = io.StringIO()
    buf.write("#version: 0.2\n")
    for a, b in merges:
        buf.write(f"{a} {b}\n")
    with gzip.open(path, "wt", encoding="utf-8") as f:
        f.write(buf.getvalue())
    return str(path)


def test_clip_bpe_tokenizer_merges(tmp_path):
    # merge 'l'+'o' -> 'lo', then 'lo'+'w</w>' -> 'low</w>'
    path = _write_merges(tmp_path, [("l", "o"), ("lo", "w</w>")])
    tok = CLIPTokenizer(path, context_length=8)
    ids = tok.encode("low")
    sot = tok.encoder["<|startoftext|>"]
    eot = tok.encoder["<|endoftext|>"]
    assert ids[0] == sot
    assert tok.encoder["low</w>"] in ids.tolist()
    assert eot in ids.tolist()
    # an unmergeable word falls back to byte tokens with </w> on the last
    ids2 = tok.encode("ox")
    assert tok.encoder["o"] in ids2.tolist()
    assert tok.encoder["x</w>"] in ids2.tolist()
    # padding and fixed length
    assert ids.shape == (8,) and ids2.shape == (8,)


def test_clip_bpe_eot_is_argmax(tmp_path):
    """encode_text locates the EOT embedding via argmax over ids — EOT must
    be the largest id the tokenizer ever emits."""
    path = _write_merges(tmp_path, [("l", "o")])
    tok = CLIPTokenizer(path, context_length=8)
    ids = tok.encode("lo x")
    assert ids.max() == tok.encoder["<|endoftext|>"]


def test_clip_tokenizer_truncation_keeps_eot(tmp_path):
    """encode_text locates the EOT embedding via argmax over ids, so
    truncation must keep EOT as the final token."""
    path = _write_merges(tmp_path, [])
    tok = CLIPTokenizer(str(path), context_length=6)
    ids = tok.encode("a very long caption that overflows the context")
    assert ids.shape == (6,)
    assert ids[-1] == tok.encoder["<|endoftext|>"]
    assert ids.max() == tok.encoder["<|endoftext|>"]
