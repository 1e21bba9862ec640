"""The DALL-E text-to-image autoregressive model, TPU-native.

Capability parity with the dalle-pytorch model the reference instantiates at
``task.py:61-86`` of learning-at-home/dalle: a decoder-only transformer over
``[text tokens || VQGAN image codes]`` with the attention zoo, weight-shared
blocks, rotary embeddings, tied input/output embeddings
(``share_input_output_emb=True``, ``task.py:82``), and the weighted
text/image cross-entropy loss (dalle-pytorch's ``loss_img_weight``).

Sequence layout. The model scores the unshifted token sequence
``S = [text_0..text_{Tt-1}, img_0..img_{Ti-1}]``: position ``p`` receives the
*previous* token's embedding (BOS at p=0) and predicts ``S_p``. Keeping
positions aligned with token coordinates (rather than physically shifting the
sequence) lets every attention mask be indexed by the coordinates of the token
being predicted, which is exactly the causal-validity condition for axial and
conv-like sparsity.

Vocabulary. One tied table over ``vocab_text + vocab_image (+1 BOS)``; image
ids are offset by ``vocab_text``. Text positions may only predict text ids and
image positions only image ids (segment logit masking, as dalle-pytorch does).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dalle_tpu.config import ModelConfig
from dalle_tpu.models.transformer import Transformer
from dalle_tpu.parallel.mesh import sum_over_manual_data_axes


# Device scopes (``jax.named_scope``) for what no flax module names: they
# put ``embed`` / ``head`` / ``ce`` into the scope path of the operations
# they enclose, in the lowered program and in a profiler trace
# (``run_trainer --profile-dir``). Metadata only: the compiled program is
# the same.
EMBED_SCOPE, HEAD_SCOPE, CE_SCOPE = "embed", "head", "ce"


def _segment_nll(h: jax.Array, table: jax.Array, targets: jax.Array,
                 head_chunk: int = 0) -> jax.Array:
    """Per-token negative log-likelihood of ``targets`` under the tied-head
    logits ``h @ table^T``, (B, T) out.

    ``head_chunk > 0`` streams the logsumexp over vocabulary chunks so the
    (B, T, V) logits tensor never materializes in HBM (the chunk body is
    rematerialized in backward, trading one extra head-matmul pass for the
    logits' round-trips). Identical values either way.
    """
    v = table.shape[0]
    if head_chunk <= 0 or v <= head_chunk:
        with jax.named_scope(HEAD_SCOPE):
            logits = jnp.einsum("btd,vd->btv", h, table.astype(h.dtype),
                                preferred_element_type=jnp.float32)
        with jax.named_scope(CE_SCOPE):
            return -jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1),
                targets[..., None], axis=-1)[..., 0]

    # the target logit, without the full logits tensor: gather the target
    # rows of the table and contract against h
    with jax.named_scope(HEAD_SCOPE):
        tgt_rows = jnp.take(table, targets, axis=0).astype(h.dtype)  # B,T,D
        target_logit = jnp.einsum("btd,btd->bt", h, tgt_rows,
                                  preferred_element_type=jnp.float32)

    pad = (-v) % head_chunk
    tbl = jnp.pad(table, ((0, pad), (0, 0))) if pad else table
    chunks = tbl.reshape(-1, head_chunk, tbl.shape[1]).astype(h.dtype)
    n_chunks = chunks.shape[0]
    # padded rows are all-zero -> logit 0; mask them out of the logsumexp
    valid0 = jnp.arange(head_chunk)[None, :] < (
        v - jnp.arange(n_chunks)[:, None] * head_chunk)

    @jax.checkpoint
    def body(carry, xs):
        m, l = carry
        chunk, valid = xs
        with jax.named_scope(HEAD_SCOPE):
            s = jnp.einsum("btd,vd->btv", h, chunk,
                           preferred_element_type=jnp.float32)
        with jax.named_scope(CE_SCOPE):
            s = jnp.where(valid[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            l = l * jnp.exp(m - m_new) + jnp.sum(
                jnp.exp(s - m_new[..., None]), axis=-1)
        return (m_new, l), None

    b, t = h.shape[0], h.shape[1]
    m0 = jnp.full((b, t), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, t), jnp.float32)
    (m, l), _ = jax.lax.scan(body, (m0, l0), (chunks, valid0))
    with jax.named_scope(CE_SCOPE):
        lse = m + jnp.log(l)
        return lse - target_logit


class DALLE(nn.Module):
    cfg: ModelConfig
    # Device mesh the model is trained on. The fused Pallas kernels run
    # per shard of it (parallel/mesh.per_shard — GSPMD cannot partition a
    # Mosaic kernel), and with cfg.sequence_parallel != "none" the
    # attention ops become shard_map programs over its sp axis
    # (parallel/sequence.py). None = one device. Parameter shapes do not
    # depend on it.
    mesh: Any = None

    def setup(self):
        cfg = self.cfg
        cfg.validate()
        pdt = jnp.dtype(cfg.param_dtype)
        emb_init = nn.initializers.normal(stddev=0.02)
        # +1 row for BOS (input-only, never predicted), then padded up to a
        # multiple of 128 so the vocab axis tiles TPU lanes and stays
        # divisible under tp sharding (see parallel/sharding.py rules).
        rows = -(-(cfg.vocab_total + 1) // 128) * 128
        self.token_emb = self.param(
            "token_emb", emb_init, (rows, cfg.dim), pdt)
        self.text_pos_emb = self.param(
            "text_pos_emb", emb_init, (cfg.text_seq_len, cfg.dim), pdt)
        # Axial (row + col) learned position embedding for the image grid.
        self.img_row_emb = self.param(
            "img_row_emb", emb_init, (cfg.image_grid, cfg.dim), pdt)
        self.img_col_emb = self.param(
            "img_col_emb", emb_init, (cfg.image_grid, cfg.dim), pdt)
        self.transformer = Transformer(cfg, mesh=self.mesh)
        if not cfg.tied_embeddings:
            self.lm_head = nn.Dense(
                cfg.vocab_total, use_bias=False,
                dtype=jnp.dtype(cfg.dtype), param_dtype=pdt)

    @property
    def bos_id(self) -> int:
        return self.cfg.vocab_total

    def combined_ids(self, text_tokens: jax.Array,
                     image_tokens: jax.Array) -> jax.Array:
        """[text || image+vocab_text] combined-vocabulary id sequence."""
        return jnp.concatenate(
            [text_tokens, image_tokens + self.cfg.vocab_text], axis=1)

    def positional(self) -> jax.Array:
        """(T, dim) learned positional embedding: text pos + image axial."""
        cfg = self.cfg
        img_pos = (self.img_row_emb[:, None, :] +
                   self.img_col_emb[None, :, :]).reshape(
                       cfg.image_seq_len, cfg.dim)
        return jnp.concatenate([self.text_pos_emb, img_pos], axis=0)

    def backbone(self, input_ids: jax.Array) -> jax.Array:
        """Embed (previous-token) ids, add positions, run the stack.

        input_ids: (B, T) ids in the combined vocabulary (+BOS), already
        shifted so position p holds the token preceding S_p.
        """
        cfg = self.cfg
        with jax.named_scope(EMBED_SCOPE):
            x = jnp.take(self.token_emb, input_ids, axis=0)
            x = x + self.positional()[None]
            x = x.astype(jnp.dtype(cfg.dtype))
        return self.transformer(x)

    def logits_from_hidden(self, h: jax.Array) -> jax.Array:
        """Tied-embedding head + segment masking, in float32."""
        cfg = self.cfg
        with jax.named_scope(HEAD_SCOPE):
            if cfg.tied_embeddings:
                table = self.token_emb[: cfg.vocab_total].astype(h.dtype)
                logits = jnp.einsum("btd,vd->btv", h, table,
                                    preferred_element_type=jnp.float32)
            else:
                logits = self.lm_head(h).astype(jnp.float32)
            # Text positions predict text ids; image positions image ids.
            t = h.shape[1]
            is_text_pos = (jnp.arange(t) < cfg.text_seq_len)[None, :, None]
            is_text_vocab = (jnp.arange(cfg.vocab_total) < cfg.vocab_text)[
                None, None, :]
            valid = jnp.logical_not(
                jnp.logical_xor(is_text_pos, is_text_vocab))
            return jnp.where(valid, logits, -1e9)

    def __call__(self, text_tokens: jax.Array, image_tokens: jax.Array,
                 loss_mask: Optional[jax.Array] = None,
                 return_logits: bool = False):
        """Weighted next-token cross-entropy (and optionally logits).

        text_tokens: (B, text_seq_len) int32; image_tokens: (B, image_seq_len)
        int32 VQGAN codes. loss_mask: optional (B, T) multiplier (e.g. to
        exclude caption padding).
        """
        cfg = self.cfg
        labels = self.combined_ids(text_tokens, image_tokens)
        bos = jnp.full((labels.shape[0], 1), self.bos_id, labels.dtype)
        input_ids = jnp.concatenate([bos, labels[:, :-1]], axis=1)

        h = self.backbone(input_ids)

        if return_logits or not cfg.tied_embeddings:
            # the untied head must be trained through the same lm_head the
            # eval/decode path reads, so it takes the full-vocab route
            logits = self.logits_from_hidden(h)
            with jax.named_scope(CE_SCOPE):
                logp = jax.nn.log_softmax(logits, axis=-1)
                token_ll = jnp.take_along_axis(
                    logp, labels[..., None], axis=-1)[..., 0]
            nll = -token_ll
            nll_text = nll[:, : cfg.text_seq_len]
            nll_img = nll[:, cfg.text_seq_len:]
        else:
            # Segment-split head: text positions only ever predict text ids
            # and image positions image ids (the segment masking of
            # logits_from_hidden), so scoring each segment against its own
            # vocabulary slice computes identical losses with ~3x fewer
            # logits and no mask pass over the full-vocab tensor.
            table = self.token_emb
            h_text = h[:, : cfg.text_seq_len]
            h_img = h[:, cfg.text_seq_len:]
            nll_text = _segment_nll(
                h_text, table[: cfg.vocab_text], text_tokens,
                cfg.head_chunk)
            nll_img = _segment_nll(
                h_img, table[cfg.vocab_text: cfg.vocab_total],
                image_tokens, cfg.head_chunk)

        if loss_mask is not None:
            mask_text = loss_mask[:, : cfg.text_seq_len]
            mask_img = loss_mask[:, cfg.text_seq_len:]
            nll_text = nll_text * mask_text
            nll_img = nll_img * mask_img
            denom_text, denom_img = mask_text.sum(), mask_img.sum()
        else:
            denom_text = nll_text.shape[0] * cfg.text_seq_len
            denom_img = nll_img.shape[0] * cfg.image_seq_len
        # The loss is normalised over the WHOLE (micro)batch. Where this
        # trace holds one data shard of it (training/steps.py accumulates
        # under a shard_map manual over dp) the denominators are summed
        # over the shards, two scalars, and the loss returned is this
        # shard's share: the shares add up to the batch's loss.
        denom_text, denom_img = sum_over_manual_data_axes(
            (denom_text, denom_img))
        if loss_mask is not None:
            denom_text = jnp.maximum(denom_text, 1.0)
            denom_img = jnp.maximum(denom_img, 1.0)
        loss_text = nll_text.sum() / denom_text
        loss_img = nll_img.sum() / denom_img
        w = cfg.loss_img_weight
        loss = (loss_text + w * loss_img) / (1.0 + w)
        aux = {"loss": loss, "loss_text": loss_text, "loss_img": loss_img}
        if return_logits:
            return loss, aux, logits
        return loss, aux


def init_params(model: DALLE, rng: jax.Array,
                batch: int = 2) -> "flax.core.FrozenDict":
    cfg = model.cfg
    if model.mesh is not None:
        # the per-shard kernels split the batch over dp x fsdp, so even
        # the dummy init batch must divide over the data shards
        shards = model.mesh.shape["dp"] * model.mesh.shape["fsdp"]
        batch = -(-batch // shards) * shards
    text = jnp.zeros((batch, cfg.text_seq_len), jnp.int32)
    image = jnp.zeros((batch, cfg.image_seq_len), jnp.int32)
    return model.init(rng, text, image)


def build(cfg: ModelConfig, mesh=None) -> DALLE:
    return DALLE(cfg, mesh=mesh)


def engagement_records(cfg: ModelConfig, mesh=None) -> dict:
    """The ``setup/warmup`` row's attributes: how the layer scan runs the
    body, and which attention layers' traced calls took the kernel."""
    from dalle_tpu.models.attention import attn_layout_record
    from dalle_tpu.models.transformer import layer_loop_record
    return {"layer_loop": layer_loop_record(cfg),
            "attn_layout": attn_layout_record(cfg, mesh)}


def step_attributes(cfg) -> tuple:
    """Entries of the step's aux that go onto every ``loop/step`` row:
    none."""
    return ()


# those of them that count a slower lowering (obs/late.py's cause
# "model"): none
SLOW_STEP_ATTRIBUTES = ()


def param_count(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
