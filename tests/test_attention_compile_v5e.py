"""One attention layer's gradient compiled for the real chip from the
sandbox (no chip attached), at the benchmark cells' shapes: between the
q/k/v projections and the out projection no array with a minor dimension
of ``head_dim`` may exist — a 64-minor bf16 array is tiled half empty, and
used to cost a heads-major transpose, six slices, six pads and three f32
adds a layer beside; and the blockwise causal attention's backward at
the cells' shapes and at the longest lengths its rule admits. All in one
file and behind fixtures, so that only the worker given this file loads
the TPU compiler."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dalle_tpu.config import (ATTN_AXIAL_COL, ATTN_AXIAL_ROW,
                              ATTN_CONV_LIKE, ATTN_FULL,
                              flagship_model_config, xl_model_config)

MICRO = 4            # the flagship cells' microbatch


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


def _layer_gradient_hlo(cfg, attn_type, one_chip, monkeypatch):
    """d(sum(attn(x)^2))/d(params, x) for one ``ZooAttention`` layer,
    compiled for one v5e: the Mosaic kernels' names in the lowered module
    (where the benchmark's census reads them) and the optimised HLO."""
    from dalle_tpu.models.transformer import ZooAttention, _make_rot
    from dalle_tpu.ops.pallas import lowering

    # the dispatcher asks the backend whether Mosaic is there: here it is
    # the described chip's compiler, whatever the process runs on
    monkeypatch.setattr(lowering, "mosaic", lambda: True)
    mod = ZooAttention(cfg, attn_type, name="attn")
    t = cfg.total_seq_len
    params = jax.eval_shape(lambda: mod.init(
        jax.random.PRNGKey(0), jnp.zeros((1, t, cfg.dim), jnp.bfloat16),
        _make_rot(cfg)))
    params = jax.tree.map(lambda p: jax.ShapeDtypeStruct(
        p.shape, p.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((MICRO, t, cfg.dim), jnp.bfloat16,
                             sharding=one_chip)

    def loss(p, x):
        y = mod.apply(p, x, _make_rot(cfg))
        return jnp.sum(y.astype(jnp.float32) ** 2)

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x)
    names = sorted(re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()))
    return names, lowered.compile().as_text()


_ARRAY = re.compile(r"\b(?:pred|[suf]\d+|bf16)\[([\d,]+)\]")
_PRODUCED = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (\S+) (copy|pad|slice|transpose)\(")


def _minor(shape: str) -> int:
    return int(shape.rsplit(",", 1)[-1])


@pytest.mark.parametrize("preset,attn_type", [
    ("flagship", ATTN_AXIAL_ROW), ("flagship", ATTN_AXIAL_COL),
    ("flagship", ATTN_CONV_LIKE), ("flagship", ATTN_FULL),
    ("xl", ATTN_AXIAL_ROW)])
def test_no_head_dim_minor_array_around_the_kernels(
        preset, attn_type, one_chip, no_persistent_cache, monkeypatch):
    cfg = {"flagship": flagship_model_config, "xl": xl_model_config}[preset]()
    d = cfg.head_dim
    names, text = _layer_gradient_hlo(cfg, attn_type, one_chip, monkeypatch)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # text and image rows in ONE call a direction (it was two)
    assert len(calls) == 2, len(calls)
    assert names == (["_win_bwd_kernel", "_win_fwd_kernel"]
                     if attn_type in (ATTN_CONV_LIKE, ATTN_FULL)
                     else ["_bwd_kernel", "_fwd_kernel"])
    for call in calls:
        head = call.split("backend_config")[0]   # results, operand layouts
        arrays = [s for s in _ARRAY.findall(head) if "," in s]
        assert len(arrays) >= 5, call[:300]     # results and operands
        assert not [s for s in arrays if _minor(s) == d], call[:300]
        # q/k/v, the context and the gradients are the projections' own
        # (B, T, H*d) arrays
        assert f"{MICRO},{cfg.total_seq_len},{cfg.dim}" in arrays
    # and nothing around them moves a head_dim-minor array either
    narrow = [m.group(0)[:160] for m in map(_PRODUCED.match,
                                            text.splitlines())
              if m and any("," in s and _minor(s) == d
                           for s in _ARRAY.findall(m.group(1)))]
    assert not narrow, narrow


@pytest.mark.parametrize("preset, micro, scope, calls", [
    ("trinitymini", 1, "qk_norm", 4), ("smallthinker21b", 2, "rotary", 4)])
def test_the_per_head_work_on_queries_and_keys_stays_on_the_lanes(
        preset, micro, scope, calls, one_chip, no_persistent_cache,
        monkeypatch):
    """One window layer's attention (``trinitymini``: norm, then rotary;
    ``smallthinker21b``: rotary) at its cell's shapes: the per-head work is
    two Mosaic calls a direction named ``qk_norm`` or ``rotary`` (not
    ``attn``: the attention roofline must not count them), no (B, T,
    heads, 128) array exists, and XLA is left nothing of the rotary: no
    cosine or sine of a table as wide as the array, no padded shifted
    copy (``apply_rotary_lanes``' (..., H*d - 64) slices)."""
    from dalle_tpu import config
    from dalle_tpu.models import sparse_lm
    from dalle_tpu.ops.pallas import lowering

    monkeypatch.setattr(lowering, "mosaic", lambda: True)
    cfg = getattr(config, f"{preset}_model_config")()
    mod = sparse_lm.Attention(cfg, config.LAYER_WINDOW_ROPE, name="attn")
    a = jax.ShapeDtypeStruct((micro, cfg.total_seq_len, cfg.hidden_size),
                             jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
        jax.eval_shape(lambda: mod.init(jax.random.PRNGKey(0),
                                        jnp.zeros(a.shape, a.dtype))))

    def loss(p, a):
        return jnp.sum(mod.apply(p, a).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, a).compile().as_text()
    names = sorted(
        re.match(r"\s*(?:ROOT )?%?([\w\-]+?)[.\d]* =", line).group(1)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)
    # beside them the blockwise attention's forward and its one backward
    assert names.count(scope) == calls and len(names) == calls + 2, names
    assert all("attn" in n for n in names if n != scope), names
    t, d = cfg.total_seq_len, cfg.head_dim
    for heads in (cfg.num_heads, cfg.num_kv_heads):
        assert f"{t},{heads},{d}]" not in text
        assert f",{heads * d - d // 2}]" not in text
        assert lowering.why_not(
            "head norm + rotary" if cfg.qk_norm else "rotary",
            (t, heads * d, d)) is None
    tables = [line.split("=")[1].split()[0] for line in text.splitlines()
              if re.search(r" (cosine|sine)\(", line)]
    assert tables and all(_minor(s.split("]")[0]) == d for s in tables), \
        tables


@pytest.mark.parametrize("batch, lanes, normed", [
    (1, 4096, True), (1, 512, True),         # trinitymini's q and k
    (2, 3584, False), (2, 512, False),       # smallthinker21b's
    (1, 4096, False)])
def test_the_per_head_pass_compiles_in_both_directions(
        batch, lanes, normed, one_chip, no_persistent_cache):
    """``head_norm_kernels.per_head`` and its gradient alone at the cells'
    local shapes: the lane rotate by half a head lowers and the tiles fit
    VMEM, with the norm and without."""
    from dalle_tpu.ops.pallas import head_norm_kernels as K

    tokens = 8192
    assert K.fits(tokens, lanes, K.LANES) is None

    def both(x, scale, tables, w):
        y, vjp = jax.vjp(lambda x, scale: K.per_head(
            x, scale if normed else None, tables, 1e-5, K.LANES), x, scale)
        return y, vjp(w)

    x = jax.ShapeDtypeStruct((batch, tokens, lanes), jnp.bfloat16,
                             sharding=one_chip)
    scale = jax.ShapeDtypeStruct((K.LANES,), jnp.float32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((tokens, K.LANES), jnp.float32,
                                 sharding=one_chip)
    lowered = jax.jit(both).lower(x, scale, (table, table), x)
    assert {"_head_norm_fwd_kernel", "_head_norm_bwd_kernel"} <= set(
        re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()))
    lowered.compile()


@pytest.fixture(scope="module")
def latent_layer(one_chip):
    """One latent layer's gradient at ``joyaiflash``'s cell's shapes,
    compiled once for the two tests below (about 20 s): the configuration
    and the optimised HLO."""
    from jax.experimental.compilation_cache import compilation_cache

    from dalle_tpu import config
    from dalle_tpu.models import sparse_lm
    from dalle_tpu.ops.pallas import lowering

    cfg = config.joyaiflash_model_config()
    mod = sparse_lm.LatentAttention(cfg, name="attn")
    a = jax.ShapeDtypeStruct((1, cfg.total_seq_len, cfg.hidden_size),
                             jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
        jax.eval_shape(lambda: mod.init(jax.random.PRNGKey(0),
                                        jnp.zeros(a.shape, a.dtype))))

    def loss(p, a):
        return jnp.sum(mod.apply(p, a).astype(jnp.float32) ** 2)

    # as ``monkeypatch`` and ``no_persistent_cache`` do for one test
    mosaic = lowering.mosaic
    cached = jax.config.jax_enable_compilation_cache
    lowering.mosaic = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, a).compile().as_text()
    finally:
        lowering.mosaic = mosaic
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    return cfg, text


def _custom_calls(text):
    """The optimised HLO's Mosaic calls: (name without its number, line)."""
    return [(re.match(r"\s*(?:ROOT )?%?([\w\-]+?)[.\d]* =", line).group(1),
             line) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def test_latent_attentions_rotary_is_one_pass_where_q_b_wrote_it(
        latent_layer):
    """One latent layer's gradient at ``joyaiflash``'s cell's shapes: the
    rotary of the queries' 2 048 lanes and of the one 64-wide key is two
    Mosaic calls a direction named ``rotary`` (not ``attn``: the attention
    roofline reads the latent kernels alone), the queries' read as column
    block 2 of ``q_b``'s (1, 8 192, 6 144) output and the key as the first
    half of lane tile 4 of ``kv_a``'s (1, 8 192, 576), and XLA is left the
    tables of one lane tile: no cosine or sine as wide as the rotated
    array, no padded shifted copy of it (``rotary_interleaved_lanes``'
    (..., 2 047) slices), no copy of the columns in front of a kernel."""
    from dalle_tpu.models import sparse_lm
    from dalle_tpu.ops.pallas import lowering

    cfg, text = latent_layer
    t, heads, rope = cfg.total_seq_len, cfg.num_heads, cfg.qk_rope_head_dim
    names = sorted(name for name, _ in _custom_calls(text))
    calls = [line for name, line in _custom_calls(text) if name == "rotary"]
    # beside them the latent kernels' forward and their one backward
    assert names.count("rotary") == 4 and len(names) == 6, names
    assert all("attn" in n for n in names if n != "rotary"), names
    # (asked of the record as the fixture's trace left it: no gate here)
    for lanes in (heads * rope, rope):
        assert lowering.recorded("rotary", sparse_lm._pair_key(
            t, lanes, rope))["why_not"] is None
    # each forward call reads its projection's whole output where it lies
    for whole, part in ((heads * (cfg.qk_nope_head_dim + rope), heads * rope),
                        (cfg.kv_lora_rank + rope, rope)):
        reads = [c for c in calls
                 if f"bf16[1,{t},{whole}]" in c.split("custom-call(")[1]]
        assert len(reads) == 1 and f"bf16[1,{t},{part}]" in \
            reads[0].split("custom-call(")[0], reads
    assert f",{heads * rope - 1}]" not in text and f",{rope - 1}]" not in text
    tables = [line.split("=")[1].split()[0] for line in text.splitlines()
              if re.search(r" (cosine|sine)\(", line)]
    assert tables and all(_minor(s.split("]")[0]) <= 128 for s in tables), \
        tables
    # the rotary parts exist as the pass wrote them and as nothing else:
    # no slice or copy of q_b's last columns, nor of kv_a's
    moved = [m.group(0)[:160] for m in map(_PRODUCED.match, text.splitlines())
             if m and any(f"bf16[1,{t},{lanes}]" in m.group(1)
                          for lanes in (heads * rope, rope))]
    assert not moved, moved


def test_latent_attentions_kernels_read_what_q_b_and_kv_b_wrote(
        latent_layer):
    """The same compile: the forward and the backward kernel take ``q_b``'s
    (1, 8 192, 6 144) and ``kv_b``'s (1, 8 192, 8 192) outputs themselves,
    the second twice (``k_nope`` at column block ``j``, ``v`` at ``16 +
    j``), so XLA cuts no ``q_nope``, ``k_nope`` or ``v`` out of them (no
    ``slice`` or ``copy`` of either, in any fusion); the backward takes the
    forward's output where ``delta`` = rowsum(do * o) stood, and nothing
    holds that in the statistics' (1, 16, 8 192, 128) layout but the
    statistics; ``dk_nope`` and ``dv`` go side by side into ``kv_b``'s
    two backward products as the kernel wrote them (no concatenate is a
    row of its own)."""
    from dalle_tpu.models import sparse_lm
    from dalle_tpu.ops.pallas import lowering

    cfg, text = latent_layer
    t, heads = cfg.total_seq_len, cfg.num_heads
    q_b = f"bf16[1,{t},{heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)}]"
    kv_b = f"bf16[1,{t},{heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)}]"
    out = f"bf16[1,{t},{heads * cfg.v_head_dim}]"
    fwd, bwd = (line for name, line in _custom_calls(text)
                if name != "rotary")
    for call in (fwd, bwd):
        operands = call.split("operand_layout_constraints={")[1]
        assert operands.count(q_b + "{") == 1, operands
        assert operands.count(kv_b + "{") == 2, operands
    # q_nope, k_nope, v (and dout, out in the backward) as arrays of
    # their own would be operands of v's width: the forward has none
    assert out not in fwd.split("operand_layout_constraints={")[1]
    assert bwd.split("operand_layout_constraints={")[1].count(out) == 2
    # whatever the entry computation holds of either width is read by
    # Mosaic calls and by nothing else: no slice, copy or fusion
    entry = text.split("ENTRY")[1].splitlines()
    wrote = [m.group(1) for m in (re.match(
        r"\s*(?:ROOT )?(%[\w.\-]+) = (\S+?)\{", line) for line in entry)
        if m and m.group(2) in (q_b, kv_b)]
    assert len(wrote) == 2, wrote
    readers = {line.strip() for line in entry for name in wrote
               if re.search(re.escape(name) + r"[,)]",
                            line.split(" = ", 1)[-1])}
    # the queries' rotary pass and the two kernels
    assert len(readers) == 3 and all(
        'custom_call_target="tpu_custom_call"' in line for line in readers), \
        [line[:160] for line in readers]
    stats = f"f32[1,{heads // 2},{t},128]"
    holds = [line.strip()[:120] for line in text.splitlines()
             if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = " + re.escape(stats), line)]
    assert len(holds) == 1 and "get-tuple-element" in holds[0], holds
    assert " concatenate(" not in text.split("ENTRY")[1]
    assert lowering.recorded("latent attention", sparse_lm._latent_key(
        t, heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.v_head_dim)) == {
            "why_not": None, "sliced": None}


@pytest.mark.parametrize("batch, lanes, before", [
    (1, 6144, 4096), (1, 576, 512),          # joyaiflash's q_b and kv_a
    (1, 64, 0), (2, 1024, 0)])               # a key alone; half the heads
def test_the_pair_pass_compiles_in_both_directions(
        batch, lanes, before, one_chip, no_persistent_cache):
    """``head_norm_kernels.pair_rotary`` and its gradient alone at the
    cell's local shapes: the two lane rotates lower at 128 lanes and at
    the key's 64, a block of ``kv_a``'s last lane tile ends past the array,
    and the tiles fit VMEM."""
    from dalle_tpu.ops.pallas import head_norm_kernels as K

    tokens = 8192
    assert K.pairs_fit(tokens, lanes - before, 64) is None

    def both(x, tables, w):
        y, vjp = jax.vjp(lambda x: K.pair_rotary(x, tables, before), x)
        return y, vjp(w)

    x, w = (jax.ShapeDtypeStruct((batch, tokens, n), jnp.bfloat16,
                                 sharding=one_chip)
            for n in (lanes, lanes - before))
    table = jax.ShapeDtypeStruct((tokens, K.LANES), jnp.float32,
                                 sharding=one_chip)
    lowered = jax.jit(both).lower(x, (table, table), w)
    assert "_pair_rotary_kernel" in re.findall(r'kernel_name = "([^"]+)"',
                                               lowered.as_text())
    lowered.compile()


@pytest.mark.parametrize("batch, tokens, heads, window, dtype, kernel", [
    (2, 8192, 28, 4096, jnp.bfloat16, "_causal_bwd_kernel"),    # the cells'
    (1, 8192, 32, None, jnp.bfloat16, "_causal_bwd_kernel"),
    # the longest lengths ``fused_backward_fits`` admits: the chip's
    # compiler has to take what the rule says fits
    (1, 25600, 32, 2048, jnp.bfloat16, "_causal_bwd_kernel"),
    (1, 14848, 32, None, jnp.float32, "_causal_bwd_kernel"),
    (1, 26112, 32, 2048, jnp.bfloat16, "_causal_dkv_kernel"),   # one past
])
def test_the_blockwise_backward_the_rule_chooses_compiles(
        batch, tokens, heads, window, dtype, kernel, one_chip,
        no_persistent_cache):
    """``causal_attention``'s gradient alone, 4 key-value heads: one
    kernel a tile where a key-value head's ``dk`` and ``dv`` fit VMEM, the
    ``dq`` and ``dk``/``dv`` kernels past that, and either compiles."""
    from dalle_tpu.ops.pallas import causal_attention_kernels as K

    fused = kernel == "_causal_bwd_kernel"
    assert (K.fused_backward_fits(tokens, heads // 4,
                                  jnp.dtype(dtype).itemsize) is None) == fused

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(K.causal_attention(
            *a, window).astype(jnp.float32)), (0, 1, 2))(q, k, v)

    q, kv = (jax.ShapeDtypeStruct((batch, tokens, n * K.LANES), dtype,
                                  sharding=one_chip) for n in (heads, 4))
    lowered = jax.jit(grads).lower(q, kv, kv)
    assert (kernel in lowered.as_text()) and (
        "_causal_dq_kernel" in lowered.as_text()) != fused
    lowered.compile()


# -- heads of 256 lanes and a rotary of their first lanes (qwen3next80b) ------

@pytest.mark.parametrize("tokens, kernel", [
    # past what the one kernel holds (the cell's own length takes the one
    # kernel: its compile is the real step's, on the chip and in
    # test_benchmark_qwen3next.py's slow case)
    (16384, "_causal_dkv_kernel"),
])
def test_the_256_wide_backward_the_rule_chooses_compiles_for_a_v5e(
        tokens, kernel, one_chip, no_persistent_cache):
    """``causal_attention``'s gradient alone at 16 query over 2 key-value
    heads of 256 lanes, bfloat16: the chip's compiler takes what
    ``fused_backward_fits(lanes=256)`` says fits VMEM, and the two kernels
    past it."""
    from dalle_tpu.ops.pallas import causal_attention_kernels as K

    hd = K.WIDE
    fused = kernel == "_causal_bwd_kernel"
    assert (K.fused_backward_fits(tokens, 8, 2, lanes=hd) is None) == fused

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(K.causal_attention(
            *a, None, K.BLOCK, False, hd).astype(jnp.float32)),
            (0, 1, 2))(q, k, v)

    q, kv = (jax.ShapeDtypeStruct((1, tokens, n * hd), jnp.bfloat16,
                                  sharding=one_chip) for n in (16, 2))
    lowered = jax.jit(grads).lower(q, kv, kv)
    assert (kernel in lowered.as_text()) and (
        "_causal_dq_kernel" in lowered.as_text()) != fused
    lowered.compile()


@pytest.mark.parametrize("lanes", [4096, 512])      # the cell's q and k
def test_the_turned_head_pass_compiles_in_both_directions_for_a_v5e(
        lanes, one_chip, no_persistent_cache):
    """``head_norm_kernels.per_head`` with the norm and the rotary of a
    256-wide head's first 64 lanes, and its gradient, at the cell's local
    shapes: the two lane rotates of one lane tile lower and the tiles fit
    VMEM."""
    from dalle_tpu.ops.pallas import head_norm_kernels as K
    tokens, hd, turned = 8192, 256, 64
    assert K.fits(tokens, lanes, hd) is None

    def both(x, scale, tables, w):
        y, vjp = jax.vjp(lambda x, scale: K.per_head(
            x, scale, tables, 1e-6, hd, False, turned), x, scale)
        return y, vjp(w)

    x = jax.ShapeDtypeStruct((1, tokens, lanes), jnp.bfloat16,
                             sharding=one_chip)
    scale = jax.ShapeDtypeStruct((hd,), jnp.float32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((tokens, K.LANES), jnp.float32,
                                 sharding=one_chip)
    lowered = jax.jit(both).lower(x, scale, (table,) * 3, x)
    assert {"_head_norm_fwd_kernel", "_head_norm_bwd_kernel"} <= set(
        re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()))
    lowered.compile()


# -- the gated delta rule's kernel pair (qwen3next80b) ------------------------

@pytest.mark.parametrize("tokens", [8192])          # the cell's local sample
def test_the_delta_rules_kernels_compile_in_both_directions_for_a_v5e(
        tokens, one_chip, no_persistent_cache):
    """``delta_rule_kernels.rule`` and its gradient at the cell's local
    shape (1 x 8 192 tokens, 16 query/key heads x 128 serving 32 value
    heads x 128, chunks of 64, bfloat16): the forward that keeps a state a
    grid step and the backward lower, and the chip's compiler takes the
    (64 x 64) tables, the products with noughts and ones and the blocks
    that ``vmem_bytes`` counts."""
    from dalle_tpu.ops.pallas import delta_rule_kernels as K
    key_heads, heads, dk, dv, chunk = 16, 32, 128, 128, 64
    assert K.fits(tokens, key_heads, heads, dk, dv, chunk, 2) is None

    def both(q, k, v, g, beta, w):
        o, vjp = jax.vjp(lambda *a: K.rule(*a, key_heads=key_heads,
                                           chunk=chunk), q, k, v, g, beta)
        return o, vjp(w)

    narrow, wide = (jax.ShapeDtypeStruct((1, tokens, n * K.LANES),
                                         jnp.bfloat16, sharding=one_chip)
                    for n in (key_heads, heads))
    row = jax.ShapeDtypeStruct((1, tokens, heads), jnp.float32,
                               sharding=one_chip)
    lowered = jax.jit(both).lower(narrow, narrow, wide, row, row, wide)
    assert {"_delta_rule_fwd_kernel", "_delta_rule_bwd_kernel"} <= set(
        re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()))
    lowered.compile()
