"""A sparse decoder-only language model on the trainer's normal path.

Eight configurations' equations, each mechanism read from a field of the
configuration and none from a preset's name. What ``SparseLMConfig``
describes (its defaults: SmallThinker-21BA3B-Instruct, PowerInfer): every
layer is

    a   = rmsnorm(x)
    r   = a . W_r                        the router, in f32, BEFORE attention
    h   = x + attention(a) . W_o         grouped key-value heads; per layer
                                         either full causal with no
                                         positions, or a causal window with
                                         rotary (``cfg.layer_kinds``)
    m   = rmsnorm(h)
    S   = the k largest of r;  p = softmax(r_S)
    out = h + sum_{e in S, e held here} p_e . W_down,e(relu(W_gate,e m) * W_up,e m)

then a final RMSNorm, an untied head and next-token cross-entropy.

What ``AfmoeLMConfig`` describes (its defaults: Trinity-Mini, arcee-ai,
``model_type`` ``afmoe``), with x0 = E[ids] * sqrt(hidden) (``mup_enabled``):

    a     = rmsnorm(x)
    q,k,v,g = a.W_q, a.W_k, a.W_v, a.W_g   ``attention_gate``: no bias
    q,k   = rmsnorm(q), rmsnorm(k)         ``qk_norm``: over each head's
                                           head_dim, before rotary
    h     = x + rmsnorm((attention(q,k,v) * sigmoid(g)) . W_o)
    m     = rmsnorm(h)                     ``sandwich_norms``: both results
                                           are normed before they join x
    dense layer (the leading ``num_dense_layers``):
      f   = W_down(silu(W_gate m) * W_up m)            width ``dense_width``
    expert layer:
      s   = sigmoid(m . W_r)               f32; the router reads m
      S   = the k largest of s + b         b: ``router_bias``, zeros, a leaf
                                           no gradient reaches
      p_e = route_scale * s_e / (sum_S s + 1e-20)
      f   = shared(m) + sum_{e in S, e held here} p_e . expert_e(m)
    out   = h + rmsnorm(f)

(:class:`GatedBlock` is the dense layer's block and the shared expert, which
every token takes and every rank computes alike.)

What ``JoyAILMConfig`` describes (its defaults: JoyAI-LLM-Flash,
jdopensource, ``model_type`` ``joyai_llm_flash``): ``AfmoeLMConfig``'s dense
and expert layers with two norms a layer, no gate, no head norms and no
embedding scale, around **latent attention** (:class:`LatentAttention`,
the layers of kind ``full_rope``: every one of the preset's):

    c_q   = rmsnorm(a . W_qa)              ``q_lora_rank``
    [q_nope ; q_rope] = c_q . W_qb         H x ``qk_nope_head_dim``, then
                                           H x ``qk_rope_head_dim``
    [c_kv ; k_rope] = a . W_kva            ``kv_lora_rank`` + ONE rotary key
    [k_nope ; v] = rmsnorm(c_kv) . W_kvb   H x nope, then H x ``v_head_dim``
    q_rope, k_rope <- rotary on interleaved pairs (x_2i, x_2i+1)
    s_h   = (q_nope,h . k_nope,h + q_rope,h . k_rope) / sqrt(nope + rope)
    h     = x + concat_h(softmax(s_h) v_h) . W_o       causal, whole sequence

and, with ``num_nextn_predict_layers`` 1, a **prediction module**
(:class:`PredictionModule`, parameters under ``mtp``) after the final norm:
``h'_i = [rmsnorm(E[t_{i+1}]) ; rmsnorm(z_i)] . W_eh`` of the main model's
normed last state ``z``, one more expert layer, a final norm of its own,
the same embedding and the same head, cross-entropy against ``t_{i+2}``
over the T - 2 positions that have one. The step's ``loss`` is ``loss_main
+ mtp_loss_weight * loss_mtp``; both ride beside it in the step's aux.

What ``Lfm2MoeLMConfig`` describes (its defaults: LFM2-8B-A1B, LiquidAI,
``model_type`` ``lfm2_moe``): ``AfmoeLMConfig``'s dense and expert layers
(no shared expert, ``route_scale`` 1) with two norms a layer, no gate and no
embedding scale, around an operator that ``cfg.layer_kinds`` names a layer:

    short_conv (:class:`ShortConv`, parameters under ``conv``):
      [B ; C ; u] = a . W_in             hidden -> 3 x hidden, no bias
      z_t = sum_{j<K} taps[j] * (B * u)_{t-(K-1)+j}   depthwise, causal
                                         (noughts before t = 0), K =
                                         ``conv_kernel``; * is elementwise
      h   = x + (C * z) . W_out
    full_rope (:class:`Attention`; no ``kv_lora_rank``):
      q,k = rmsnorm(q), rmsnorm(k)       ``qk_norm``, over a head's 64 lanes
      q,k <- rotary (rotate-half, all of ``head_dim``, position = index)
      h   = x + attention(q, k, v) . W_o  causal over the whole sequence,
                                         grouped key-value heads

and the head is the embedding's table (``tied_embeddings``: one leaf, which
gets the sum of both uses' gradients; :func:`_streamed_nll` contracts it
where it lies). ``full_rope`` is thus any class's kind: which attention runs
it is read from ``cfg.kv_lora_rank``.

What ``KeyeLMConfig`` describes (its defaults: the language model of
Keye-VL-2.0-30B-A3B, Kwai-Keye, ``model_type`` ``KeyeVL2``):
``AfmoeLMConfig``'s gated-SiLU experts behind a softmax router over the chosen that reads the
post-attention norm (no dense layer, no shared expert, two norms a layer)
around grouped-query attention **over the keys an indexer chose**, in
every layer (kind ``selected_rope``; ``sg`` = stop_gradient):

    q,k   = rmsnorm(q), rmsnorm(k)       ``qk_norm``, over a head's 128 lanes
    q,k  <- rotary (rotate-half), frequency pair i of 64 reading position
            row 0 for i < 16, row 1 for 16 <= i < 40, row 2 after
            (``mrope_section`` [16, 24, 24]; :func:`position_tables`)
    indexer (:class:`Indexer`, parameters under ``attn/indexer``):
      qI  = sg(a) . W_qI (``index_heads`` J x ``index_head_dim`` e)
      kI  = layernorm(sg(a) . W_kI)      ONE head of e
      w   = sg(a) . W_w                  J
      qI, kI <- rotary (rotate-half over e, position row 0)
      I[t,s] = (J e)^-1/2 sum_j w[t,j] relu(qI[t,j] . kI[s])        f32
      S_t = the ``index_topk`` largest I[t,s] over s <= t (every s <= t
            where t < ``index_topk``; ties to the lower s)
    o_h[t] = sum_{s in S_t} softmax_{s in S_t}(q_h[t] . k_g(h)[s] / sqrt(d))
             v_g(h)[s];   h = x + concat_h(o_h) . W_o
    L_I  += mean_t KL(sg(1/H sum_h P_h[t, .]) || softmax_{s in S_t} I[t, s])

The step's ``loss`` is ``loss_main + indexer_loss_weight * loss_indexer``
(``L_I`` summed over the layers); both ride beside it in the step's aux
with ``sparse_selected_pct`` (chosen pairs over causal pairs, the layers'
median). By construction ``loss_main``'s gradient on the indexer's leaves
is nought, and ``loss_indexer``'s on every other leaf. The three position
rows are an input of the layer stack (:func:`field_positions` makes them
from the two fields' lengths: a text token's three are its index, an image
token's the text's length, + its row, + its column; nothing else knows the
rule). **How a layer runs it** (:func:`selected_attend`; the ``setup/warmup``
row's ``sparse_layout`` says it): a kernel writes the scores of the causal
band's tiles, one (T, T) f32 array a sequence
(ops/pallas/indexer_kernels.py); a query's threshold is found by counting, a
bit a pass, in a second kernel there that keeps a block of queries' scores
in VMEM over its passes (``index_select``: no sort; :func:`select_keys`,
the same sets by four bits a pass in ``index_chunk``-row chunks of XLA
code, is the dense lowering's selection and 6 times as slow on the v5e,
XLA's ``top_k`` of 2 048 among 8 192 50 times); the selection is ONE (T, T)
f32 array ``sel``
that holds a chosen pair's score and the kernels' mask value elsewhere, which
the blockwise kernels read a tile of beside ``q``, ``k`` and ``v``
(``causal_attention_kernels.selected_*``: the band's every tile is
visited, so the step's time does not depend on the selection); the heads'
mean probability is made a tile at a time by a kernel from the forward's
statistics, which in the forward pass sums each tile into the loss's rows
where it is made (a row's KL, log-sum-exp and count: no (T, T) array of
the mean there) and in the backward pass writes the mean alone, a second
(T, T) f32 array; the rows' log-sum-exp is kept a layer with the
statistics; the loss's gradient to ``qI``, ``kI`` and ``w`` is one kernel
that makes the scores' cotangent tile by tile. All of it is one derivative
rule
(:func:`_selected_kernels`) that makes the backward's arrays in the
backward pass between two barriers, so that one layer's are alive at a
time. No (T, T) array exists per head. Scopes ``attn/indexer/proj``,
``attn/indexer/scores`` (``scores[mosaic]``, forward and gradient),
``attn/indexer/select`` (``select[mosaic]``), ``attn/indexer/align``
(``align[mosaic]``, both forms, and nothing else), ``attn/qk_norm``,
``attn[mosaic]``.

What ``NemotronHLMConfig`` describes (its defaults: the 52-layer stack of
Nemotron-Labs-TwoTower-30B-A3B-Base-BF16, nvidia, ``model_type``
``nemotron_h``): **every layer is one part behind one norm**
(:class:`OnePartLayer`), ``x' = x + part(rmsnorm(x))``, the part named by
``cfg.layer_kinds``; no positions anywhere, no bias but the convolution's:

    mamba2 (:class:`Mamba2Mixer`, parameters under ``ssm``; H heads of P,
    G groups of state N, K taps):
      [z ; xBC ; dt] = a . W_in          hidden -> H P + (H P + 2 G N) + H
      xBC <- silu(sum_{j<K} taps[j] * xBC_{t-(K-1)+j} + bias)   depthwise,
                                         causal, noughts before t = 0
      x (T, H, P), B (T, G, N), C (T, G, N) = split(xBC); head h reads
                                         group h // (H / G)
      D_t,h = softplus(dt_t,h + dt_bias_h);  A_h = -exp(A_log_h)
      S_t = exp(D_t A) S_{t-1} + D_t x_t B_t^T;  y_t = S_t C_t + D_h x_t
                                         f32, S (P, N) a head, S_{-1} = 0
      y <- rmsnorm over each group's H P / G lanes of (y * silu(z))
      part = y . W_out                   H P -> hidden
    full_nope (:class:`Attention`): grouped key-value heads, causal over
      the whole sequence, no rotary
    experts (:class:`ExpertLayer`): the sigmoid router of ``AfmoeLMConfig``
      (bias, norm, scale) on the layer's one normed input m, and
      f = shared(m) + sum_{e in S, e held here} p_e . W_down,e relu(W_up,e m)^2
      two products an expert, NOT gated (``expert_gated`` false,
      ``hidden_act`` ``relu2``; :class:`UngatedBlock` is the shared expert)

**How the recurrence runs** (the site "ssm scan", :func:`ssm_scan`;
``ssm_layout`` on the ``setup/warmup`` row says which lowering ran): in
chunks of ``ssm_chunk`` tokens. Inside a chunk the masked (chunk x chunk)
form a head, ``y_i += sum_{j<=i} exp(cs_i - cs_j) (C_i . B_j) D_j x_j`` with
``cs`` the running sum of ``D A`` inside the chunk; across chunks the
carried (P, N) state, ``S_c = exp(cs_last) S_{c-1} + sum_j exp(cs_last -
cs_j) D_j x_j B_j^T``, and ``y_i += exp(cs_i) C_i . S_{c-1}``. ``D``, the
decays, their running sums and the states are f32; the products' operands
are in ``cfg.dtype`` with f32 accumulation. Nothing of (T, T) and no state a
token exists, forward, replay or backward. Where the local shapes are lane
tiles (whole chunks of whole tiles, a state of whole tiles, a group's heads
whole tiles: ``ssm_scan_kernels.fits``) it runs as a forward and a backward
Mosaic kernel (ops/pallas/ssm_scan_kernels.py: a chunk's form is made, used
and dropped in VMEM and the states are carried there; the layer's replay is
the forward call that keeps the state each chunk starts from, which the
backward kernel walks in reverse); anywhere else (no Mosaic backend, a
ragged tail, the tests' tiny widths) as XLA code, :func:`chunked_scan`,
whose backward is plain differentiation of the chunked form.

**The way into and out of the scan is a pass a direction** (the sites "ssm
taps" and "ssm gate norm", :func:`ssm_taps` and :func:`ssm_gate_norm`;
ops/pallas/ssm_pass_kernels.py): between ``in_proj`` and ``out_proj`` nothing
is sliced out of ``in_proj``'s (B, T, 2 H P + 2 G N + H) output and no (T, W)
f32 array reaches HBM. The taps pass reads the ``xBC`` columns where the
projection wrote them, with the K - 1 tokens before a tile as a small second
block, and writes ``x``, ``B`` and ``C`` as three arrays, the scan's
operands; its backward makes the pre-activation again from the raw columns
and sums ``d taps`` and ``d bias`` in f32. The gate-and-norm pass reads the
scan's ``y`` and the ``z`` columns in place; a group's sum over its lanes is
a product with ones. Each pass's cotangent of ``in_proj``'s output is its own
columns padded with noughts, which XLA adds up inside the projection's
backward products. Where a predicate refuses (``ssm_pass_kernels.taps_fit`` /
``gate_norm_fit``: parts or groups of no whole lane tiles, a part that is no
column block of ``in_proj``'s output, tokens of no whole sublane tiles; no
Mosaic backend) the stage is XLA code, :func:`causal_taps_silu` /
:func:`gated_group_norm` on slices. Scopes ``ssm/in_proj``, ``ssm/conv``
(``taps[mosaic]``, or XLA code), ``ssm/scan`` (``D``; then the decays, both
forms and ``D x``: ``scan[mosaic]``, or XLA code; the softplus of the step
sizes and the rows' transposes are XLA code either way), ``ssm/gate_norm``
(``gate_norm[mosaic]``, or XLA code), ``ssm/out_proj`` (never under ``attn``
or ``conv``).

What ``Qwen3NextLMConfig`` describes (its defaults:
Qwen3-Next-80B-A3B-Instruct, Qwen, ``model_type`` ``qwen3_next``):
``AfmoeLMConfig``'s two-part layer (two norms, no dense layer, a softmax
router over the chosen that reads the post-attention norm, gated-SiLU
experts) whose operator ``cfg.layer_kinds`` names a layer, no bias anywhere:

    gated_delta (:class:`GatedDeltaMixer`, parameters under ``gdn``; G
    query/key heads of dk, H value heads of dv, value head h reading
    query/key head h // (H / G); K taps):
      [q ; k ; v], z, [b ; a] = a . W_qkv, a . W_z, a . W_ba
                                         hidden -> 2 G dk + H dv, H dv, 2 H
      [q ; k ; v] <- silu(sum_{j<K} taps[j] * [q ; k ; v]_{t-(K-1)+j})
                                         depthwise, causal, no bias
      q^ = q / sqrt(sum q^2 + 1e-6) / sqrt(dk); k^ = k / sqrt(sum k^2 + 1e-6)
      beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias)
      S_t = e^{g_t} S_{t-1} + k^_t (beta_t (v_t - (e^{g_t} S_{t-1})^T k^_t))^T
      o_t = S_t^T q^_t                   f32, S (dk, dv) a value head
      y = rmsnorm over each head's dv lanes of o, THEN times silu(z)
      h = x + y . W_out
    full_rope (:class:`Attention`): heads of 256 lanes, two lane tiles;
      q, k = rmsnorm(q), rmsnorm(k) over a head's lanes (``qk_norm``); the
      rotary turns a head's first ``partial_rotary_factor`` x ``head_dim``
      = 64 lanes (rotate-half, halves of 32, a head of 64's frequencies) and
      leaves the other 192; the context times sigmoid(a . W_gate)
      (``attention_gate``)
    the expert block: ``f = sigmoid(m . w_g) shared(m) + sum_{e in S, e held
      here} p_e . expert_e(m)`` (``shared_expert_gate``: the leaf
      ``ff/shared_gate``, a vector; under the scope ``ff/shared``)

**How the rule runs** (the site "delta rule", :func:`delta_rule`;
``gdn_layout`` on the ``setup/warmup`` row says which lowering ran): in
chunks of ``delta_chunk`` tokens (:func:`chunked_delta_rule`). The delta
rule reads the state back before it writes, so what a chunk writes with an
empty state is the solution of a unit lower-triangular system a head: with
``cs`` the running sum of ``g`` inside the chunk and ``A_ij = beta_i e^{cs_i
- cs_j} k^_i . k^_j`` (j < i), ``u = (I + A)^-1 (beta v)`` and ``w = (I +
A)^-1 (beta e^{cs} k^)``; a chunk that starts from the state ``S`` writes ``U
= u - w S``, reads out ``o = e^{cs} q^ S + (e^{cs_i - cs_j} q^_i . k^_j)_{j
<= i} U`` and hands on ``e^{cs_last} S + (e^{cs_last - cs} k^)^T U``. The
inverse is made with no loop (:func:`unit_lower_inverse`: diagonal blocks of
8 by a finite product, doubled three times by block substitution; its
derivative two products with the inverse it made), in f32 from three
bfloat16 pieces an operand; the decays, their sums and the carried states are
f32; the
products' operands are in ``cfg.dtype`` with f32 accumulation (the inverse
too, once made). Nothing of (T, T) and no state a token exists, forward,
replay or backward. Where the local shapes are lane tiles (whole chunks of a
power of two of 8 to 128 tokens, ``dk`` and ``dv`` whole tiles, value heads
a whole multiple of key heads: ``delta_rule_kernels.fits``) it runs as a
forward and a backward Mosaic kernel (ops/pallas/delta_rule_kernels.py: a
chunk's tables and its inverse are made, used and dropped in VMEM, two
heads' (64 x 64) tables as one block-diagonal (128 x 128) operand, and the
states are carried there; the forward call of a gradient keeps the state
each grid step starts from and every chunk's inverse, and the backward
kernel walks a grid step's chunks forward again from that state, then in
reverse; a rematerialised layer keeps by name, ``KEPT_OF_A_LAYER``, ``o``,
those states and those inverses, and the normalised ``q`` and ``k`` and the
``g`` and ``beta`` rows that only this scope makes: 258 MiB a sample and
layer at ``qwen3next80b``'s sizes, so its replay runs neither the forward
kernel nor the scope's XLA code a second time); anywhere else (no Mosaic
backend, a ragged tail, the tests' tiny widths) as XLA code,
:func:`chunked_delta_rule`: a ``lax.scan``
that carries the state, in blocks of ``RULE_BLOCK`` chunks whose in-chunk
tables are made at once and which are replayed a block under
``jax.checkpoint`` (the states kept are one a block, a block's tables alive
at a time; a layer's rematerialisation keeps nothing of it and replays it
whole). The taps take the
Mamba-2 mixer's pass (``ssm_pass_kernels.taps_silu`` with a bias of
noughts, ``q``, ``k`` and ``v`` written apart) where its predicate takes
the shapes, the heads' norm :func:`head_pass`'s kernel; the L2 norms, the
gate and ``beta`` / ``g`` are XLA code. Scopes ``gdn/in_proj``,
``gdn/conv`` (``taps[mosaic]``, or XLA code), ``gdn/rule`` (the L2 norms,
``beta``, ``g`` and the rows' transposes, XLA code either way; then the
chunked form: ``rule[mosaic]``, or XLA code), ``gdn/gate_norm``
(``qk_norm[mosaic]``: the head pass's name; then the gate) and
``gdn/out_proj`` (never under ``attn``, ``conv`` or ``ssm``).

What ``OuroLMConfig`` describes (its defaults: Ouro-2.6B, ByteDance,
``model_type`` ``ouro``; the objective from the family's description,
arXiv:2510.25741): ``AfmoeLMConfig``'s four-norm layer with the dense
gated-SiLU block in EVERY layer (``has_expert_layers`` false: no router, no
counters, no ``moe_*`` entry anywhere) around multi-head ``full_rope``
attention (16 / 16 heads of 128, all lanes rotated), and **the one stack run
``total_ut_steps`` = R times on one set of leaves**:

    x_0 = E[ids]
    z_t = rmsnorm_f(stack(x_{t-1}));  x_t = z_t                  t = 1..R
    lam_t = sigmoid(z_t . w_g + b_g)            a row's exit gate, f32
    p_t = lam_t prod_{j<t} (1 - lam_j)  (t < R);  p_R = prod_{j<R} (1 - lam_j)
    nll_t = next-token cross-entropy of z_t . W_head, a row
    loss = mean over the T - 1 predicted rows of
           [ sum_t p_t nll_t - beta H(p) ],  H(p) = - sum_t p_t ln p_t

(``beta``: ``exit_entropy_weight``; leaves ``passes/layer_<i>/...``,
``passes/final_norm``, ``exit_gate`` (hidden,), ``exit_gate_bias`` (1,),
``lm_head``). **How it runs** (:class:`LoopedStack`, :func:`run_passes`,
:func:`_looped_loss`; ``loop_layout`` on the ``setup/warmup`` row says which
form a trace took): the passes are ONE traced body, a ``lax.scan`` whose
constants are the leaves (the matrices cast to the activations' dtype once,
outside the loop, so the loop's sum of a matrix's gradient over the passes is
carried in that dtype), so set-up traces, lowers and compiles one pass's
layers, not R of them; each layer is rematerialised as any stack's, so the
loop stores a layer's input, its attention's output and ONE lane of its
statistics once a layer AND pass (a head a key-value head leaves 127 of a
tile's 128 lanes empty: ``causal_attention_kernels._vjp_fwd`` keeps the
lane), the final norm's input and the exit's state; a layer reads its leaves
through :func:`_leaves_with_state`, a barrier in the backward pass without
which the compiler moves every layer's weight gradients to the end of the
loop's body and keeps what they read alive until then. The R exits' rows go
through the streamed head as ONE call under their exit weights (``p_t``
depends on the gates, not on the logits, so a chunk's ``dx`` and ``dW`` are
still made in the scan that makes the loss and the head's leaf gets the
exits' sum from one carried array); the weights are differentiated and every
row's loss comes back as a value (``_streamed_nll(..., rows=True)``: the
gate's gradient is ``nll . dp``). ``loss_text`` / ``loss_img`` are the last
pass's; the step's aux also carries ``loss_main`` (the expectation),
``loss_entropy`` (``- beta H``), ``loss_exit_1`` .. ``loss_exit_R`` (each
pass's own mean: what tells the passes apart, since one traced body has one
name in a trace), ``exit_expected_pass`` and ``exit_entropy``. Scopes
``passes`` (the loop and the one cast; the layers' ``layer_<i>/.../attn``,
``ff/dense``, ``rms_norm`` under it), ``exit_gate`` (the gate's sum over the
lanes, the sigmoids in logarithms, the survival sums, the entropy and the
expected pass, forward and backward), ``head`` and ``ce`` as any stack's.

**The expert layer is told which experts it holds** (``experts_held``
consecutive ones from ``expert_offset``): it routes over all
``num_experts``, computes the part of the result its own experts give for
the tokens routed to them, and passes that partial sum on. With every
expert held that is the whole layer; with a share of them it is what one
expert-parallel rank computes before the exchange, and nothing here stands
in for the other ranks or their traffic. No token is dropped and there is
no capacity factor: the assignments that fall on held experts are sorted
by expert into a buffer of ``dispatch_rows`` rows (twice what a uniform
router sends here, plus a tile's rounding an expert) and multiplied group
by group (ops/pallas/grouped_matmul_kernels.py); a step whose router sends
more than that takes the dense lowering of the same sum (every held expert
on every token, times its routing weight) instead, chosen on the device by
the count (:func:`held_experts`). Where the grouped kernels cannot run (no
Mosaic backend, sizes that are not lane tiles) the dense lowering is the
layer.

**Between dispatch and combine the unit of work is a row tile that holds
rows, and what is done to such a tile is done while it is in VMEM.** The
buffer's tail holds none (about half its tiles under a router that favours
no expert): the grouped kernels move nothing for them. Where an expert's
two weight blocks and the tiles fit VMEM (``grouped.block_why_not``, asked
at the site "expert products" of the local ``(dim, width, dtype)``; every
published width so far) gate, up and the activation are one kernel, the
backward's ``dys . W_down^T`` kernel makes the two cotangents and ``act(g) *
u`` on the tile, and one kernel sums ``dg . W_gate^T + du . W_up^T`` into
one ``dxs`` buffer, in f32, rounded once; elsewhere the three products a
direction run with XLA code between them (``moe_layout`` says which, and
why not). Every other rounding is where the three products put it.

**The sorted lowering moves rows four times, all four by runs**: tokens
to rows and the output's cotangent to rows (:func:`_to_rows`), and two
token-major sums, combine's forward ``y[n] = sum_j p[n, j] ys[row[n, j]]``
and dispatch's backward ``dm[n] = sum_j dxs[row[n, j]]``
(:func:`_sum_to_tokens`). The plan sorts the flattened (token, slot)
assignments by held expert with a *stable* sort and ``top_k`` names an
expert once a token, so **a group's rows ascend by token and a tile of
consecutive tokens owns one contiguous run of rows in every group**: a sum
is, a token tile, one copy of a window of rows a held expert and a
placement on the MXU, and the way there is its transpose, a token tile read
once and placed into a window of every group that is written out whole
(ops/pallas/token_sum_kernels.py, ``token_major_sum`` and ``rows_of``: only
rows that hold an assignment are read, each once, nothing is rounded).
**The cotangent moves in the dtype the caller rounds the result to**
(``held_experts(..., rounded_to=)``, a fact ``ExpertLayer`` states of its
own last line: ``dy`` then holds that dtype's numbers widened, and is
widened again on the rows). Each movement has a cost rule read off its
shapes and no flag. The sums: where the kernel's cost, which grows with the
experts held, passes the cost of one gather of all N rows a slot, or its
windows do not fit VMEM, they are those gathers (a slot with no row here
reads a row of its own and is masked): :func:`runs_why_not`. Tokens to
rows: the kernel costs a window a held expert and token tile whatever the
rows, one XLA gather (:func:`_rows_of`) the bytes it writes, so a buffer of
few rows keeps the gather: :func:`rows_why_not`.

The model takes the trainer's batches as they are: ``text`` and ``image``
are the two halves of one token sequence, image ids offset by
``vocab_text``. The loss is the mean next-token cross-entropy over the
T - 1 predicted positions; ``loss_text`` / ``loss_img`` are its means over
the targets of the two fields. Router product and softmax, attention
softmax and cross-entropy are f32; the rest runs in ``cfg.dtype`` from f32
parameters. The head is streamed (:func:`_streamed_nll`: ``cfg.head_chunk``
rows of the logits alive at a time) and, differentiated, makes ``dx`` and
``dW`` in the scan that makes the loss, so a micro-step multiplies the
logits once; nothing of the head is computed again in the backward pass,
and ``head_layout`` on the ``setup/warmup`` row says which calls traced
that rule.

Device scopes (``jax.named_scope`` and module names; the benchmark's
``*_share_pct`` metrics read them): ``embed``, ``attn`` (projections,
kernel), ``rms_norm``, ``ff/router``, ``ff/dispatch``,
``ff/experts``, ``ff/combine``, ``head``, ``ce``; where the configuration
has them ``ff/shared`` (the shared expert), ``ff/dense`` (a dense layer's
block), ``attn/gate`` (the ``W_g`` product, the sigmoid and the multiply),
``attn/qk_norm``, ``attn/rotary``; latent attention's ``attn/q_a``,
``attn/q_b``, ``attn/kv_a``, ``attn/kv_b``, ``attn/latent_norm``,
``attn/rotary`` (``rotary[mosaic]``: the 64-wide parts' interleaved
pairs in one pass on the lanes, below), ``attn/out`` and its kernels
``attn[mosaic]``; the prediction module's under a root ``mtp``
(``mtp/embed``, ``mtp/norms``, ``mtp/proj``, ``mtp/block/attn...``,
``mtp/block/ff...``, ``mtp/head``, ``mtp/ce``); the short convolution's
``conv/in_proj``, ``conv/mix`` (the two gates and the taps, forward and
backward: XLA code, :func:`short_conv_mix`, which reads B, C and u as column
blocks of ``in_proj``'s output and shifts along the tokens; ``conv_layout``
on the ``setup/warmup`` row says so) and ``conv/out_proj`` (never under
``attn``: the attention shares keep meaning attention). The token-major kernel
(``token_major_sum[mosaic]`` in a trace) runs under the scope of its sum,
``ff/combine`` or ``ff/dispatch``, and its transpose (``rows_of[mosaic]``)
under ``ff/dispatch`` (tokens) or ``ff/combine`` (the cotangent); the
grouped products under ``ff/experts`` (``experts[mosaic]``; with the expert
block on the tile nothing else runs there but the weights' casts, and the
sum of the two ``dxs`` counts there and no longer under ``ff/dispatch``).
What is done to each head of queries and keys between
their projections and the attention (the head norm where the
configuration has one, the rotary where the layer has positions) is one
pass on the lanes (ops/pallas/head_norm_kernels.py: no (B, T, H, d) array
exists where it runs, and the rotary reads one head's (T, d) tables;
``head_norm_kernels.fits`` is the rule, and where it refuses the layer
runs ``rms_norm`` on the reshape and ``attention.apply_rotary_lanes``:
:func:`head_pass`):
``qk_norm[mosaic]`` under ``attn/qk_norm`` where there is a norm, the
rotary of a window layer in it; ``rotary[mosaic]`` under ``attn/rotary``
where there is none. Latent attention's rotary of interleaved pairs is
such a pass of its own (``head_norm_kernels.pair_rotary``, the same name
in a trace): the queries' rotary columns read where ``q_b`` wrote them,
the one key where ``kv_a`` wrote it in a second small call, one lane
tile's (T, 128) tables for two heads side by side;
``head_norm_kernels.pairs_fit`` is the rule, and where it refuses
:func:`rotary_interleaved_lanes` runs with tables as wide as the array
(:func:`pair_rotary`). Heads of 256 lanes (``full_rope`` of
``Qwen3NextLMConfig``) take the blockwise kernels' third form, one head
over two lane tiles (the 128-wide kernels with ``width`` 256: the scores'
product 256 deep, the context 256 wide, the statistics a lane a head as
before; ``attn_layout`` says "one head of 256 over 2 lane tiles"), and the
head pass with one lane tile's tables for the 64 lanes it turns. Heads of
64 lanes (``full_rope`` of
``Lfm2MoeLMConfig``) take the blockwise kernels' second form, two heads a
lane tile (``causal_attention_kernels._halves_*_kernel``: ``attn_layout``
says "2 heads of 64 a lane tile"), while their head norm and rotary stay on
:func:`head_pass`'s XLA lowering, for ``head_norm_kernels.fits``'s reason,
which the record keeps. The 128-wide kernels multiply an edge tile of the
band by its sub-tiles that hold an allowed pair (``attn_band`` on the
``setup/warmup`` row has each layer kind's tiles and visited over allowed
pairs, whole tiles' and the sub-tiles'). The latent kernels likewise read ``q_nope``, ``k_nope`` and
``v`` as column blocks of ``q_b``'s and ``kv_b``'s outputs and make their
backward's ``delta`` themselves (``attn_operands`` on the ``setup/warmup``
row says so, or "sliced: <why>": :class:`LatentAttention`).

Every one of those choices between a kernel and its XLA lowering is made,
remembered and said through ops/pallas/lowering.py: a site here is a
predicate, a kernel, a lowering and their specs, and
:func:`engagement_records` asks that module's record what the traced calls
took, by the keys the sites' own ``_*_key`` functions make.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.custom_derivatives import SymbolicZero
from jax.sharding import PartitionSpec as P

from dalle_tpu.config import (LAYER_EXPERTS, LAYER_FULL_ROPE,
                              LAYER_GATED_DELTA, LAYER_MAMBA2,
                              LAYER_SELECTED_ROPE, LAYER_SHORT_CONV,
                              LAYER_WINDOW_ROPE, SparseLMConfig)
from dalle_tpu.models import attention as attn_mod
from dalle_tpu.ops.pallas import causal_attention_kernels as kernels
from dalle_tpu.ops.pallas import delta_rule_kernels
from dalle_tpu.ops.pallas import grouped_matmul_kernels as grouped
from dalle_tpu.ops.pallas import head_norm_kernels as head_norm
from dalle_tpu.ops.pallas import indexer_kernels as index_kernels
from dalle_tpu.ops.pallas import lowering
from dalle_tpu.ops.pallas import ssm_pass_kernels
from dalle_tpu.ops.pallas import ssm_scan_kernels
from dalle_tpu.ops.pallas import token_sum_kernels as token_sum
from dalle_tpu.parallel.mesh import LANES_SPEC, sum_over_manual_data_axes

# rows of the dispatch buffer over what a uniform router sends to the
# held experts (tests shrink it to reach the dense lowering)
ROWS_OVER_EXPECTED = 2.0


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    with jax.named_scope("rms_norm"):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
        return (y * scale).astype(x.dtype)


def _head_pass_site(norm: bool, rotary: bool) -> str:
    return " + ".join(["head norm"] * norm + ["rotary"] * rotary)


def _head_pass_key(tokens: int, lanes: int, head_dim: int, tp: int = 1):
    """What the record knows a per-head pass by: a sample's tokens and a
    ``tp`` shard's lanes of the array's, in heads of ``head_dim``."""
    return tokens, lanes // tp, head_dim


def position_tables(rows: jax.Array, sections: Tuple[int, ...],
                    head_dim: int, theta: float, heads: int = 1):
    """cos/sin (T, heads * head_dim) of a rotate-half rotary whose frequency
    pair i reads the position row its section names: of ``rows`` (R, T)
    the first ``sections[0]`` pairs read row 0, the next ``sections[1]``
    row 1, and so on (both lanes of a pair, in every head). With equal rows
    these are ``attention.rotary_cos_sin``'s tables of that row, bit for
    bit: the same f32 product a lane."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    row_of_lane = np.tile(np.repeat(np.arange(len(sections)), sections),
                          2 * heads)
    rows = rows.astype(jnp.float32)
    pos = rows[0][:, None]
    for r in range(1, len(sections)):
        pos = jnp.where(row_of_lane == r, rows[r][:, None], pos)
    angles = pos * jnp.tile(freqs, 2 * heads)
    return jnp.cos(angles), jnp.sin(angles)


def partial_rotary_lanes(x: jax.Array, cos: jax.Array, sin: jax.Array,
                         head_dim: int) -> jax.Array:
    """The rotary (rotate-half) of the first R lanes of every head of x
    (B, T, H * head_dim), the head's other lanes as they are; cos, sin: (T,
    R) of one head (``attention.rotary_cos_sin`` of R). The XLA lowering of
    the head pass's ``turned`` form: on a reshape to heads, in f32."""
    b, t, _ = x.shape
    turned = cos.shape[-1]
    heads = x.reshape(b, t, -1, head_dim)
    first = heads[..., :turned].astype(jnp.float32)
    x1, x2 = first[..., :turned // 2], first[..., turned // 2:]
    first = first * cos[:, None, :] \
        + jnp.concatenate([-x2, x1], axis=-1) * sin[:, None, :]
    return jnp.concatenate([first.astype(x.dtype), heads[..., turned:]],
                           axis=-1).reshape(x.shape)


def head_pass(x, scale=None, *, mesh, eps: float, head_dim: int,
              theta: Optional[float], positions=None,
              sections: Tuple[int, ...] = (), turned: int = 0):
    """The work on each head of x (B, T, H*d) between a projection and the
    attention: the RMS norm over the head's d lanes where there is a
    ``scale`` (one vector for all heads), then the rotary of positions
    0..T-1 where there is a ``theta`` (with ``positions`` (R, T) and
    ``sections``, of the rows its frequency pairs read:
    :func:`position_tables`). A shard's is one pass of the kernel
    that reads one head's tables where it fits
    (ops/pallas/head_norm_kernels.py), else the two expressions it
    replaces: the reshape to heads and :func:`rms_norm`, and
    ``attention.apply_rotary_lanes`` with tables as wide as the array.
    ``turned``: the lanes of a head the rotary turns, its first, where
    they are not all of them (``partial_rotary_factor``): frequencies of a
    head of ``turned`` lanes, the rest of the head as it is; the same pass,
    with one lane tile's tables (:func:`partial_rotary_lanes` is its XLA
    lowering)."""
    norm, rotary = scale is not None, theta is not None
    name = _head_pass_site(norm, rotary)
    turned = turned if rotary and turned != head_dim else 0

    def cos_sin(tokens: int, heads: int = 1):
        if positions is None:
            return attn_mod.rotary_cos_sin(jnp.arange(tokens),
                                           turned or head_dim, theta, heads)
        return position_tables(positions, sections, head_dim, theta, heads)

    def fits(x, scale=None) -> bool:
        _, t, width = x.shape
        why_not = head_norm.fits(t, width, head_dim)
        return lowering.chose(
            name, _head_pass_key(t, width, head_dim), why_not,
            why_not or f"local {tuple(x.shape)}: heads of {head_dim} lanes, "
            + f"the first {turned} of each rotated, " * bool(turned)
            + f"{head_norm.rows_tile(t, width)} rows a tile")

    def kernel(x, scale=None):
        tables = None
        if rotary:
            tables = (head_norm.partial_rotary_tables if turned
                      else head_norm.rotary_tables)(*cos_sin(x.shape[1]))
        return head_norm.per_head(x, scale, tables, eps, head_dim,
                                  lowering.interpret(), turned)

    def xla(x, scale=None):
        b, t, width = x.shape
        if norm:
            x = rms_norm(x.reshape(b, t, -1, head_dim), scale,
                         eps).reshape(x.shape)
        if turned:
            x = partial_rotary_lanes(x, *cos_sin(t), head_dim)
        elif rotary:
            x = attn_mod.apply_rotary_lanes(
                x, *cos_sin(t, width // head_dim), head_dim)
        return x

    return lowering.site(
        name, fits, kernel, xla, mesh, (LANES_SPEC, P())[:1 + norm],
        LANES_SPEC, head_norm.scope(norm))(x, *[scale] * norm)


def out_proj_init(cfg: SparseLMConfig, **axes) -> Dict[str, Any]:
    """``kernel_init`` of a residual branch's output projection where the
    configuration states the source's ``rescale_prenorm_residual``
    (``cfg.residual_rescale_layers``, the model's PUBLISHED depth): U(+-1 /
    sqrt(fan_in)) over the root of that depth, so that no branch's output
    (a mixer's slowly varying state, the constant part of an expert's
    ``relu(u)^2``) outweighs the token in the residual stream an untrained
    router reads (PERF.md section 6, PR 57). Nothing where it states none:
    the module's default."""
    depth = cfg.residual_rescale_layers
    if not depth:
        return {}
    return {"kernel_init": nn.initializers.variance_scaling(
        1.0 / (3 * depth), "fan_in", "uniform", **axes)}


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def dense_causal_attention(q, k, v, window: Optional[int],
                           head_dim: int) -> jax.Array:
    """The XLA lowering (no Mosaic backend, or a head size that is not a
    lane tile: CPU runs at test sizes): dense masked scores, query head
    ``h`` reading key-value head ``h // group``. q: (B, T, H*d); k, v:
    (B, T, G*d)."""
    b, t, _ = q.shape
    g = k.shape[2] // head_dim
    qh = q.reshape(b, t, g, -1, head_dim)
    kh, vh = k.reshape(b, t, g, head_dim), v.reshape(b, t, g, head_dim)
    s = jnp.einsum("bqgnd,bkgd->bgnqk", qh, kh,
                   preferred_element_type=jnp.float32) * head_dim ** -0.5
    i = np.arange(t)
    allowed = i[None, :] <= i[:, None]
    if window is not None:
        allowed &= i[:, None] - i[None, :] < window
    w = jax.nn.softmax(jnp.where(allowed, s, attn_mod.NEG_INF), axis=-1)
    out = jnp.einsum("bgnqk,bkgd->bqgnd", w.astype(v.dtype), vh,
                     preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(q.dtype)


def _backward_words(split_why: Optional[str]) -> str:
    return (f"dq + dk/dv kernels ({split_why})" if split_why
            else "one kernel a tile")


def _band_words(band: Dict[str, int]) -> str:
    """A call's ``causal_attention_kernels.band_of`` in words: the
    tiles it visits, how the edge tiles among them are multiplied, and
    visited over allowed pairs, whole tiles' first where sub-tiles cut it."""
    over = lambda pairs: f"{pairs / band['allowed']:.4f}"
    cut = band["visited"] < band["whole"]
    return (f"{band['tiles']} tile{'s' * (band['tiles'] != 1)}, "
            f"{band['edge_tiles']} at an edge "
            + (f"by sub-tiles of {band['sub']}" if cut else "whole")
            + ", visited over allowed pairs "
            + (over(band["whole"]) + " -> ") * cut + over(band["visited"]))


def _heads_a_tile(head_dim: int) -> str:
    """The kernels' second and third forms, in words; nothing for a head
    of one lane tile."""
    if head_dim == kernels.HALF:
        return f"2 heads of {head_dim} a lane tile, "
    if head_dim > kernels.LANES:
        return (f"one head of {head_dim} over {head_dim // kernels.LANES} "
                "lane tiles, ")
    return ""


def _blockwise_site(kind: str) -> str:
    return f"{kind} attention"


def _blockwise_key(tokens: int, q_lanes: int, kv_lanes: int, tp: int = 1):
    """What the record knows a blockwise attention by: a sample's tokens
    and a ``tp`` shard's lanes of the queries and of the keys."""
    return tokens, q_lanes // tp, kv_lanes // tp


def attend(q, k, v, *, mesh, kind: str, window: Optional[int],
           head_dim: int, scope: Optional[str] = None):
    """Causal attention of q (B, T, H*d) over k, v (B, T, G*d): a shard's
    is the blockwise kernel where it fits, else
    :func:`dense_causal_attention`."""
    name = _blockwise_site(kind)

    def fits(q, k, v) -> bool:
        why_not = kernels.blockwise_fits(q.shape[2], k.shape[2], head_dim)
        group = q.shape[2] // k.shape[2]
        split_why = None if why_not else kernels.fused_backward_fits(
            q.shape[1], group, q.dtype.itemsize,
            lanes=max(kernels.LANES, head_dim))
        band = None if why_not else kernels.band_of(q.shape[1], window,
                                                    head_dim)
        return lowering.chose(
            name, _blockwise_key(q.shape[1], q.shape[2], k.shape[2]),
            why_not,
            why_not or f"local q{tuple(q.shape)} over k{tuple(k.shape)}: "
            f"blocks of {kernels.BLOCK}, {_band_words(band)}, {group} query "
            "heads a key-value tile, " + _heads_a_tile(head_dim)
            + _backward_words(split_why),
            split_backward=split_why, band=band)

    return lowering.site(
        name, fits,
        lambda q, k, v: kernels.causal_attention(
            q, k, v, window, kernels.BLOCK, lowering.interpret(), head_dim),
        lambda q, k, v: dense_causal_attention(q, k, v, window, head_dim),
        mesh, (LANES_SPEC,) * 3, LANES_SPEC, scope)(q, k, v)


# the kinds whose queries and keys are rotated (``full_rope`` here: grouped
# key-value heads; a class with ``kv_lora_rank`` runs LatentAttention;
# ``selected_rope``: over the keys an indexer chose)
ROPE_KINDS = (LAYER_WINDOW_ROPE, LAYER_FULL_ROPE, LAYER_SELECTED_ROPE)


# ---------------------------------------------------------------------------
# Attention over the keys an indexer chose (layers of kind ``selected_rope``)
# ---------------------------------------------------------------------------

# what ``sel`` holds off a query's set (the kernels' own mask value)
OFF = kernels.MASK_VALUE


def dense_index_scores(qi, ki, w, scale: float) -> jax.Array:
    """(B, T, T) f32: ``scale * sum_j w[t, j] relu(qi[t, j] . ki[s])``, every
    pair. qi: (B, T, J*d); ki: (B, T, d); w: (B, T, J) f32. The XLA lowering
    of ``indexer_kernels.index_scores``, and differentiable."""
    b, t, d = ki.shape
    z = jnp.einsum("bqjd,bkd->bqjk", qi.reshape(b, t, -1, d), ki,
                   preferred_element_type=jnp.float32)
    return scale * jnp.einsum("bqjk,bqj->bqk", jax.nn.relu(z),
                              w.astype(jnp.float32))


def _sortable(x: jax.Array) -> jax.Array:
    """f32 -> uint32 whose order is the numbers' (-0.0 counted as 0.0)."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.int32)
    flipped = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(flipped, jnp.uint32) \
        ^ jnp.uint32(0x80000000)


# bits of a key settled a pass of the threshold's search: 2^4 - 1 = 15
# candidates counted in one reading of the scores, 8 readings in all
SEARCH_BITS = 4


def _kth_largest(keys: jax.Array, k: jax.Array) -> jax.Array:
    """(..., 1) uint32: each row's ``k``-th largest of ``keys`` (..., N),
    the largest u with ``count(keys >= u) >= k``: found from the top bits
    down, ``SEARCH_BITS`` a pass, by counting. No sort."""
    digits = jnp.arange(1, 2 ** SEARCH_BITS, dtype=jnp.uint32)

    def settle(i, found):
        shift = (32 - SEARCH_BITS * (i + 1)).astype(jnp.uint32)
        candidates = found | (digits << shift)              # (..., 15)
        counts = jnp.sum(keys[..., None, :] >= candidates[..., None],
                         axis=-1, dtype=jnp.int32)
        # counts fall as the digit grows: the largest digit that keeps k
        digit = jnp.sum(counts >= k, axis=-1, keepdims=True,
                        dtype=jnp.int32).astype(jnp.uint32)
        return found | (digit << shift)

    return jax.lax.fori_loop(0, 32 // SEARCH_BITS, settle,
                             jnp.zeros(keys.shape[:-1] + (1,), jnp.uint32))


# Row chunks whose keys end at different columns are grouped, and a group's
# chunks run as one loop over the group's widest: the compiled step holds a
# copy of the loop's body a group, a layer and a direction (unrolled, one a
# chunk, the seven layers' selections were 57 of the step's 167 MB of
# program, which no compile cache of the chip's kept: PERF.md section 6, PR
# 52), for about a sixth more keys counted than each chunk's own width.
SELECT_GROUPS = 3


def _chunk_groups(chunks: int, first: int, groups: int):
    """``[(first chunk, chunk after the last)]``: the chunks ``first`` ..
    ``chunks`` - 1 in at most ``groups`` runs of near-equal length."""
    count = chunks - first
    groups = max(1, min(groups, count))
    edges = [first + count * g // groups for g in range(groups + 1)]
    return [(a, b) for a, b in zip(edges, edges[1:]) if b > a]


def _by_chunks(body, operands, first_row: int, chunk: int):
    """``body(row indices (chunk,), *chunk's rows of each operand)`` over
    the ``chunk``-row slices of ``operands`` (B, R, W), R a whole number of
    chunks that starts at row ``first_row``, as one loop; each result
    (B, chunk, ...) comes back as (B, R, ...)."""
    b, r = operands[0].shape[:2]
    split = lambda x: x.reshape(b, r // chunk, chunk, *x.shape[2:]).swapaxes(
        0, 1)
    starts = first_row + chunk * jnp.arange(r // chunk)
    out = jax.lax.map(
        lambda xs: body(xs[0] + jnp.arange(chunk), *xs[1:]),
        (starts, *(split(x) for x in operands)))
    join = lambda y: y.swapaxes(0, 1).reshape(b, r, *y.shape[3:])
    return jax.tree.map(join, out)


def _choose(rows, x, topk: int):
    """(B, chunk, W) bool: of the keys 0 .. W - 1, those of each query's
    set, for queries ``rows`` (chunk,) with scores ``x``."""
    rows = rows[:, None]
    causal = jnp.arange(x.shape[-1])[None, :] <= rows
    keys = jnp.where(causal, _sortable(x), jnp.uint32(0))
    k = jnp.minimum(rows + 1, topk)
    kth = _kth_largest(keys, k)
    above, equal = keys > kth, keys == kth
    want = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    tied = jnp.sum(equal, axis=-1, keepdims=True, dtype=jnp.int32)

    def lower_first(equal):
        before = jnp.cumsum(equal, axis=-1, dtype=jnp.int32) - equal
        return equal & (before < want)

    return causal & (above | jax.lax.cond(
        jnp.any(tied > want), lower_first, lambda e: e, equal))


# jitted so that the layers' calls, forward and backward, share one trace
# and one lowering
@functools.partial(jax.jit, static_argnums=(1, 2))
def select_keys(scores: jax.Array, topk: int, chunk: int) -> jax.Array:
    """(B, T, T) f32 ``sel``: ``scores[t, s]`` where s is one of the
    ``topk`` largest of row t over s <= t (every s <= t where t < ``topk``;
    ties to the lower s, as ``lax.top_k``), :data:`OFF` elsewhere. Reads
    nothing above the diagonal's ``chunk``-row tiles (the kernel leaves
    them unwritten). ``chunk`` rows at a time, each over the keys up to the
    last row of its group of chunks (:func:`_chunk_groups`); a row's
    threshold is found by counting (:func:`_kth_largest`), and the tie rule
    costs a running count along the keys only in a chunk where a tie
    straddles some row's threshold. The rows before ``topk`` choose every
    key before them: no search."""
    t = scores.shape[1]
    pad = -t % chunk
    if pad:
        scores = jnp.pad(scores, ((0, 0), (0, pad), (0, pad)))
    chunks, plain = (t + pad) // chunk, min(topk, t) // chunk
    out = []
    if plain:
        last = plain * chunk
        i = jnp.arange(last)
        out.append((jnp.where(i[None, :] <= i[:, None],
                              scores[:, :last, :last], OFF), last))
    for first, after in _chunk_groups(chunks, plain, SELECT_GROUPS):
        r0, last = first * chunk, after * chunk
        x = scores[:, r0:last, :last]
        chosen = _by_chunks(lambda rows, x: _choose(rows, x, topk), (x,),
                            r0, chunk)
        out.append((jnp.where(chosen, x, OFF), last))
    whole = jnp.concatenate([
        jnp.pad(part, ((0, 0), (0, 0), (0, t + pad - last)),
                constant_values=OFF) for part, last in out], axis=1)
    return whole[:, :t, :t]


# the indexer's three kernels inside the selected attention's rules: no
# choice of their own (``selected_attend`` made it), a row of the record
# each for what their tracing costs, at every call of a rule
SCORES_SITE, SELECTION_SITE, ALIGNMENT_SITE = (
    "indexer scores", "indexer selection", "indexer alignment")


def _chosen_keys(qi, ki, w, topk: int, scale: float):
    """``sel`` (B, T, T) f32 of the indexer's three operands: the scores'
    kernel and the selection's (:func:`select_keys`' array, bit for bit)."""
    t = qi.shape[1]
    with jax.named_scope("indexer"):
        with jax.named_scope("scores"), lowering.traced(
                SCORES_SITE, (t, qi.shape[2], ki.shape[2])):
            scores = index_kernels.index_scores(
                qi, ki, w, scale, kernels.BLOCK, lowering.interpret())
        with jax.named_scope("select"), lowering.traced(
                SELECTION_SITE, (t, topk)):
            return index_kernels.index_select(
                scores, topk, kernels.BLOCK, lowering.interpret())[:, :t, :t]


def _aligned(form, q, k, stats, sel):
    """One form of the heads' mean's kernel (``selected_loss_rows``,
    ``selected_mean_probs``) under the scope ``indexer/align``: the
    kernel and nothing else (the caller cuts its rows to T). One key of the
    record for both: one site."""
    with jax.named_scope("indexer"), jax.named_scope("align"), \
            lowering.traced(ALIGNMENT_SITE,
                            (q.shape[1], q.shape[2], k.shape[2])):
        return form(q, k, stats, sel, kernels.BLOCK, lowering.interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _selected_kernels(q, k, v, qi, ki, w, topk: int, chunk: int,
                      scale: float):
    """Attention over the chosen keys and the indexer's loss, on Mosaic:
    returns the context, each sample's KL summed over its rows, and its
    chosen pairs. ``chunk`` is the dense lowering's (its selection's row
    chunks), taken so that the two lowerings share their statics.

    One derivative rule for all of it, so that what the backward pass needs
    of the three (T, T) arrays (the selection, the heads' mean probability;
    the scores on the way) is made *in* the backward pass, behind a barrier
    with the output's cotangent on one side and all six cotangents on the
    other. Left to a rematerialised layer's replay and to plain
    differentiation, the compiler's schedule holds every layer's arrays at
    once: 0.75 GiB a layer at 8 192 tokens (PERF.md section 6, PR 52). The
    rule keeps the six operands, the context and the statistics, as
    ``causal_attention`` does, and each row's log-sum-exp over its set
    ((B, T) f32), which the loss's gradient reads."""
    return _selected_kernels_fwd(q, k, v, qi, ki, w, topk, chunk, scale)[0]


def _selected_kernels_fwd(q, k, v, qi, ki, w, topk, chunk, scale):
    t = q.shape[1]
    sel = _chosen_keys(qi, ki, w, topk, scale)
    ctx, stats = kernels.selected_forward(q, k, v, sel, kernels.BLOCK,
                                          lowering.interpret())
    ctx = checkpoint_name(ctx, "attn_out")
    stats = checkpoint_name(stats, "attn_stats")
    # the loss's rows, summed where the mean's tiles are made: no (T, T)
    # array of the mean in the forward pass
    rows = _aligned(kernels.selected_loss_rows, q, k, stats, sel)[:, :t]
    # kept with the statistics: a rematerialised layer's replay does not
    # run the rows' kernel again, nor the backward rule for its sums
    lse = checkpoint_name(rows[..., kernels.LSE_LANE], "attn_stats")
    # ... and the context is not out before the loss's sums are (see the
    # backward rule): the selection is held for one layer at a time
    out = jax.lax.optimization_barrier(
        (ctx, jnp.sum(rows[..., kernels.KL_LANE], axis=1),
         jnp.sum(rows[..., kernels.COUNT_LANE], axis=1)))
    return out, (q, k, v, qi, ki, w, ctx, stats, lse)


def _selected_kernels_bwd(topk, chunk, scale, res, cotangents):
    dctx, dkl, _ = cotangents                # a count carries no gradient
    # nothing below starts before the context's cotangent is there
    dctx, res = jax.lax.optimization_barrier((dctx, res))
    q, k, v, qi, ki, w, ctx, stats, lse = res
    sel = _chosen_keys(qi, ki, w, topk, scale)
    dq, dk, dv = kernels.selected_backward(
        q, k, v, sel, ctx, stats, dctx, kernels.BLOCK, lowering.interpret())
    # the mean alone: ``index_grads`` reads it a tile at a time, and the
    # rows' log-sum-exp is the forward's
    t = q.shape[1]
    pbar = _aligned(kernels.selected_mean_probs, q, k, stats, sel)[:, :t, :t]
    # the scores' backward, with the KL's cotangent made on each tile
    with jax.named_scope("indexer"), jax.named_scope("scores"):
        dqi, dki, dw = index_kernels.index_grads(
            qi, ki, w, lse, jnp.broadcast_to(dkl[:, None], lse.shape), sel,
            pbar, scale, kernels.BLOCK, lowering.interpret())
    # ... and no cotangent leaves before all have: the indexer's feed
    # nothing but its own leaves' gradients, and a compiler free to put
    # them off does, to the end of the backward pass, with every layer's
    # (T, T) arrays held until then
    return jax.lax.optimization_barrier((dq, dk, dv, dqi, dki, dw))


_selected_kernels.defvjp(_selected_kernels_fwd, _selected_kernels_bwd)


def dense_selected_attention(q, k, v, qi, ki, w, *, topk: int, chunk: int,
                             scale: float, head_dim: int):
    """The XLA lowering of :func:`_selected_kernels` (no Mosaic backend, or
    widths the kernels refuse): dense scores and masks, the same selection,
    plain differentiation."""
    b, t, _ = q.shape
    with jax.named_scope("indexer"):
        with jax.named_scope("scores"):
            scores = dense_index_scores(qi, ki, w, scale)
        with jax.named_scope("select"):
            sel = select_keys(jax.lax.stop_gradient(scores), topk, chunk)
    on = sel > OFF
    g = k.shape[2] // head_dim
    qh = q.reshape(b, t, g, -1, head_dim)
    kh, vh = k.reshape(b, t, g, head_dim), v.reshape(b, t, g, head_dim)
    s = jnp.einsum("bqgnd,bkgd->bgnqk", qh, kh,
                   preferred_element_type=jnp.float32) * head_dim ** -0.5
    prob = jax.nn.softmax(jnp.where(on[:, None, None], s, attn_mod.NEG_INF),
                          axis=-1)
    ctx = jnp.einsum("bgnqk,bkgd->bqgnd", prob.astype(v.dtype), vh,
                     preferred_element_type=jnp.float32)
    with jax.named_scope("indexer"), jax.named_scope("align"):
        pbar = jax.lax.stop_gradient(jnp.mean(prob, axis=(1, 2)))
        log_sigma = jax.nn.log_softmax(jnp.where(on, scores, OFF), axis=-1)
        kl = jnp.sum(jnp.where(on, jax.scipy.special.xlogy(pbar, pbar)
                               - pbar * log_sigma, 0.0), axis=(1, 2))
        chosen = jnp.sum(on, axis=(1, 2), dtype=jnp.float32)
    return ctx.reshape(q.shape).astype(q.dtype), kl, chosen


SELECTED_SITE = "selected attention"
SAMPLES_SPEC = P(LANES_SPEC[0])


def _selected_key(tokens: int, q_lanes: int, kv_lanes: int, heads: int,
                  topk: int):
    """What the record knows an attention over chosen keys by: a sample's
    tokens, the queries' and keys' lanes, the indexer's heads and the size
    of a set."""
    return tokens, q_lanes, kv_lanes, heads, topk


def selected_attend(q, k, v, qi, ki, w, *, mesh, cfg: SparseLMConfig,
                    scope: Optional[str] = None):
    """Attention of q (B, T, H*d) over the keys of k, v (B, T, G*d) that
    the indexer's qi (B, T, J*e), ki (B, T, e) and w (B, T, J) choose for
    each query, and the indexer's loss: (context, (B,) KL summed over a
    sample's rows, (B,) chosen pairs). A shard's is the kernels where they
    fit, else :func:`dense_selected_attention`. No mesh axis may split the
    heads: the loss's target is their mean."""
    if _splits(mesh, LANES_SPEC[2]):
        raise NotImplementedError(
            "attention over chosen keys averages the heads' probabilities "
            "for the indexer's loss: a mesh axis that splits the heads "
            "(tp) would need that mean summed over it")
    topk, chunk = cfg.index_topk, cfg.index_chunk
    scale = (cfg.index_heads * cfg.index_head_dim) ** -0.5

    def fits(q, k, v, qi, ki, w) -> bool:
        t, item = q.shape[1], q.dtype.itemsize
        why_not = kernels.selected_fits(t, q.shape[2], k.shape[2],
                                        cfg.head_dim, item) \
            or index_kernels.fits(t, cfg.index_heads, cfg.index_head_dim,
                                  item)
        return lowering.chose(
            SELECTED_SITE,
            _selected_key(t, q.shape[2], k.shape[2], cfg.index_heads, topk),
            why_not,
            why_not or f"local q{tuple(q.shape)} over k{tuple(k.shape)}, "
            f"{topk} keys a query chosen by {cfg.index_heads} heads of "
            f"{cfg.index_head_dim}: " + sparse_words())

    lanes, samples = P(*LANES_SPEC[:2], None), SAMPLES_SPEC
    return lowering.site(
        SELECTED_SITE, fits,
        lambda *operands: _selected_kernels(*operands, topk, chunk, scale),
        functools.partial(dense_selected_attention, topk=topk, chunk=chunk,
                          scale=scale, head_dim=cfg.head_dim),
        mesh, (lanes,) * 6, (lanes, samples, samples), scope)(
            q, k, v, qi, ki, w)


def sparse_words() -> str:
    """What the Mosaic lowering of a ``selected_rope`` layer is handed, in
    words (the ``setup/warmup`` row's ``sparse_layout``)."""
    return (
        f"scores (T, T) f32 a sequence by a kernel over the causal band's "
        f"tiles of {kernels.BLOCK}; a query's threshold by counting, a bit "
        f"a pass over the bit planes of {index_kernels.SELECT_ROWS} queries' "
        "scores held in VMEM, one kernel (no sort), ties to the lower key; "
        "the kernels are handed one (T, T) "
        "f32 array that holds a chosen pair's score and the mask value "
        "elsewhere, and visit every tile of the causal band (none is "
        "skipped: the walk does not depend on the data); the heads' mean "
        "probability by a kernel from the forward's statistics: the loss's "
        "row sums in the mean's kernel, forward; the mean alone (T, T) f32, "
        "backward; the rows' log-sum-exp kept a layer; the loss's gradient "
        "to the indexer one kernel with no (T, T) cotangent")


class Indexer(nn.Module):
    """The indexer's three operands of a layer (module docstring): queries
    of ``index_heads`` heads, ONE normed key head, a weight a query and
    head; queries and key rotated (rotate-half over ``index_head_dim``,
    position row 0). It reads the layer's normed input with the gradient
    stopped. Leaves ``q/kernel``, ``k/kernel``, ``k_norm/{scale,bias}``,
    ``weights/kernel``."""
    cfg: SparseLMConfig

    @nn.compact
    def __call__(self, a: jax.Array, positions: jax.Array):
        cfg = self.cfg
        dt, pdt = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        dense = functools.partial(nn.Dense, use_bias=False, dtype=dt,
                                  param_dtype=pdt)
        heads, dim = cfg.index_heads, cfg.index_head_dim
        a = jax.lax.stop_gradient(a)
        with jax.named_scope("proj"):
            qi = dense(heads * dim, name="q")(a)
            ki = nn.LayerNorm(epsilon=cfg.rms_eps, dtype=dt, param_dtype=pdt,
                              name="k_norm")(dense(dim, name="k")(a))
            w = nn.Dense(heads, use_bias=False, dtype=dt, param_dtype=pdt,
                         name="weights")(a).astype(jnp.float32)
            rotate = lambda x: attn_mod.apply_rotary_lanes(
                x, *attn_mod.rotary_cos_sin(positions[0], dim,
                                            cfg.rope_theta,
                                            x.shape[-1] // dim), dim)
            return rotate(qi), rotate(ki), w


class Attention(nn.Module):
    cfg: SparseLMConfig
    kind: str
    mesh: Any = None

    @nn.compact
    def __call__(self, a: jax.Array, positions=None):
        """``positions`` (3, T): the rows a configuration with
        ``mrope_section`` rotates by. A ``selected_rope`` layer returns the
        indexer's counters beside its output."""
        cfg = self.cfg
        dt, pdt = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        dense = functools.partial(nn.Dense, use_bias=False, dtype=dt,
                                  param_dtype=pdt)
        q = dense(cfg.num_heads * cfg.head_dim, name="q")(a)
        k = dense(cfg.num_kv_heads * cfg.head_dim, name="k")(a)
        v = dense(cfg.num_kv_heads * cfg.head_dim, name="v")(a)
        rope = self.kind in ROPE_KINDS
        if cfg.qk_norm or rope:
            rows = dict(positions=positions, sections=cfg.mrope_section) \
                if cfg.mrope_section else {}

            def per_head(x, name):
                scale = self.param(name, nn.initializers.ones,
                                   (cfg.head_dim,), pdt) \
                    if cfg.qk_norm else None
                return head_pass(x, scale, mesh=self.mesh, eps=cfg.rms_eps,
                                 head_dim=cfg.head_dim,
                                 theta=cfg.rope_theta if rope else None,
                                 turned=cfg.rotary_dim, **rows)

            with jax.named_scope(head_norm.scope(cfg.qk_norm)):
                q, k = per_head(q, "q_norm"), per_head(k, "k_norm")
        if self.kind == LAYER_SELECTED_ROPE:
            ctx, kl, chosen = selected_attend(
                q, k, v, *Indexer(cfg, name="indexer")(a, positions),
                mesh=self.mesh, cfg=cfg, scope=self.name)
            return dense(cfg.hidden_size, name="out")(ctx), {
                "index_kl": kl, "index_chosen": chosen}
        ctx = attend(q, k, v, mesh=self.mesh, kind=self.kind,
                     window=cfg.window if self.kind == LAYER_WINDOW_ROPE
                     else None,
                     head_dim=cfg.head_dim, scope=self.name)
        if cfg.attention_gate:
            g = dense(cfg.num_heads * cfg.head_dim, name="gate")(a)
            with jax.named_scope("gate"):
                ctx = ctx * jax.nn.sigmoid(g.astype(jnp.float32)).astype(dt)
        return dense(cfg.hidden_size, name="out", **out_proj_init(cfg))(ctx)


def _pair_angles(tokens: int, lanes: int, head_dim: int,
                 theta: float) -> jax.Array:
    """(tokens, lanes) f32: ``pos * theta^(-2i / head_dim)`` on lanes 2i
    and 2i + 1 of every head of ``head_dim`` lanes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (lanes,), 0)
    freqs = theta ** (-(lane % head_dim // 2 * 2).astype(jnp.float32)
                      / head_dim)
    return jnp.arange(tokens, dtype=jnp.float32)[:, None] * freqs


def rotary_interleaved_lanes(x: jax.Array, head_dim: int,
                             theta: float) -> jax.Array:
    """Rotary of positions 0..T-1 on x (B, T, n * head_dim), each head's
    lanes as interleaved pairs: ``(x_2i, x_2i+1)`` turned by ``pos *
    theta^(-2i / head_dim)``, in f32. The pair's other member is a shift by
    one lane, up for the even lanes and down for the odd ones, so no array
    with a minor dimension of 2 exists. The XLA lowering, with tables as
    wide as the array: what :func:`pair_rotary` runs where the one pass on
    the lanes refuses."""
    angles = _pair_angles(x.shape[1], x.shape[2], head_dim, theta)
    return turn_pairs_lanes(x, jnp.cos(angles), jnp.sin(angles))


def turn_pairs_lanes(x: jax.Array, cos: jax.Array,
                     sin: jax.Array) -> jax.Array:
    """x (B, T, W)'s interleaved pairs turned by (T, W) f32 tables in which
    a pair's two lanes hold the same angle; in f32, cast to ``x.dtype``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (x.shape[2],), 0)
    up = jnp.pad(x[..., 1:], ((0, 0), (0, 0), (0, 1)))        # x[lane + 1]
    down = jnp.pad(x[..., :-1], ((0, 0), (0, 0), (1, 0)))     # x[lane - 1]
    rot = jnp.where(lane % 2 == 0, -up, down).astype(jnp.float32)
    return (x.astype(jnp.float32) * cos + rot * sin).astype(x.dtype)


# the log's word for it, as for a head pass with no norm
PAIR_ROTARY_SITE = "rotary"


def _pair_key(tokens: int, lanes: int, head_dim: int, tp: int = 1):
    """What the record knows a rotary of interleaved pairs by: a sample's
    tokens and a ``tp`` shard's lanes of the rotated ones."""
    return tokens, lanes // tp, head_dim, "pairs"


def _splits(mesh, axis: Optional[str]) -> bool:
    """Whether ``mesh`` splits what a spec puts on ``axis``."""
    return bool(axis) and mesh is not None and mesh.shape.get(axis, 1) > 1


def pair_rotary(x, start: int, *, mesh, spec, head_dim: int, theta: float):
    """The rotary of interleaved pairs on ``x[..., start:]`` (B, T, n *
    head_dim), ``x`` split by ``spec``: a shard's is one pass of the
    kernel that reads one lane tile's tables
    (``head_norm_kernels.pair_rotary``), and ``x`` where a projection
    wrote it (where the lanes before ``start`` are whole blocks of the
    pass), else :func:`rotary_interleaved_lanes` on the slice. But a mesh
    axis that splits the lanes (``tp``) splits the heads of the rotary
    part, not the lanes of ``x``: the part is sliced first."""
    if _splits(mesh, spec[2]):
        x, start = x[..., start:], 0

    def fits(x) -> bool:
        t, width = x.shape[1], x.shape[2] - start
        why_not = head_norm.pairs_fit(t, width, head_dim)
        return lowering.chose(
            PAIR_ROTARY_SITE, _pair_key(t, width, head_dim), why_not,
            why_not or f"local {tuple(x.shape)} from lane {start}: pairs in "
            f"heads of {head_dim} lanes, "
            f"{head_norm.pair_rows_tile(t, width)} rows a tile")

    def kernel(x):
        first = start
        if start % head_norm.pair_block(x.shape[2] - start):
            x, first = x[..., start:], 0
        angles = _pair_angles(x.shape[1], head_norm.LANES, head_dim, theta)
        return head_norm.pair_rotary(
            x, head_norm.pair_tables(jnp.cos(angles), jnp.sin(angles)),
            first, lowering.interpret())

    return lowering.site(
        PAIR_ROTARY_SITE, fits, kernel,
        lambda x: rotary_interleaved_lanes(x[..., start:], head_dim, theta),
        mesh, (spec,), spec, head_norm.ROTARY_SCOPE)(x)


def dense_latent_attention(q_nope, q_rope, k_nope, k_rope, v) -> jax.Array:
    """The XLA lowering of latent attention (no Mosaic backend, or widths
    the kernels refuse): dense masked scores from the two products.
    q_nope, k_nope: (B, T, H*n); q_rope: (B, T, H*r); k_rope: (B, T, r);
    v: (B, T, H*d). Returns (B, T, H*d)."""
    b, t, _ = q_nope.shape
    rope = k_rope.shape[2]
    heads = q_rope.shape[2] // rope
    split = lambda x: x.reshape(b, t, heads, -1)
    qn, kn = split(q_nope), split(k_nope)
    s = jnp.einsum("bqhd,bkhd->bhqk", qn, kn,
                   preferred_element_type=jnp.float32) \
        + jnp.einsum("bqhd,bkd->bhqk", split(q_rope), k_rope,
                     preferred_element_type=jnp.float32)
    s = s * (qn.shape[-1] + rope) ** -0.5
    i = np.arange(t)
    w = jax.nn.softmax(jnp.where(i[None, :] <= i[:, None], s,
                                 attn_mod.NEG_INF), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), split(v),
                     preferred_element_type=jnp.float32)
    return out.reshape(v.shape).astype(v.dtype)


# the one rotary key: a sample's on the shard that has the sample, whole
ROPE_KEY_SPEC = P(LANES_SPEC[0], None, None)

IN_PLACE = ("q_nope, k_nope, v read where q_b and kv_b wrote them, delta in "
            "the backward kernel")
LATENT_SITE = "latent attention"


def _latent_key(tokens: int, heads: int, nope: int, rope: int, value: int,
                tp: int = 1):
    """What the record knows a latent attention by: a sample's tokens, a
    ``tp`` shard's heads and a head's three widths."""
    return tokens, heads // tp, nope, rope, value


def latent_attend(q, q_rope, kv, k_rope, *, mesh, nope: int, rope: int,
                  value: int, scope: Optional[str] = None):
    """Latent attention: a shard's is the blockwise kernels where they
    fit, else :func:`dense_latent_attention`. ``q`` and ``kv`` as
    ``kernels.latent_attention`` takes them: the projections' whole
    outputs, or ``q_nope`` and the pair ``(k_nope, v)`` where a mesh axis
    splits the heads (``tp``; the one rotary key is whole on each shard):
    the projections' whole outputs come in, and are sliced first there.
    The record's ``sliced``: why the call's q_nope, k_nope and v were
    slices and not q_b's and kv_b's outputs read where they lie, None where
    they were read there."""
    if _splits(mesh, LANES_SPEC[2]):
        q, kv = _latent_parts(q, kv, q_rope.shape[2] // rope * nope)

    def fits(q, q_rope, kv, k_rope) -> bool:
        t, heads = q.shape[1], q_rope.shape[2] // rope
        why_not = kernels.latent_fits(t, heads, nope, rope, value,
                                      q.dtype.itemsize)
        sliced = why_not
        if isinstance(kv, tuple):
            sliced = sliced or ("a mesh axis splits the heads of each part, "
                                "not the lanes of q_b's and kv_b's outputs")
        return lowering.chose(
            LATENT_SITE, _latent_key(t, heads, nope, rope, value), why_not,
            why_not or f"local {heads} heads of {nope} + {rope} | {value} "
            f"over one rotary key, {t} tokens: blocks of {kernels.BLOCK}, "
            f"{kernels.LATENT_HEADS} heads a step, " + _backward_words(None)
            + ", " + (f"operands sliced: {sliced}" if sliced else IN_PLACE),
            sliced=sliced)

    def xla(q, q_rope, kv, k_rope):
        q_nope, (k_nope, v) = _latent_parts(
            q, kv, q_rope.shape[2] // rope * nope)
        return dense_latent_attention(q_nope, q_rope, k_nope, k_rope, v)

    return lowering.site(
        LATENT_SITE, fits,
        lambda *operands: kernels.latent_attention(
            *operands, kernels.BLOCK, lowering.interpret()),
        xla, mesh,
        (LANES_SPEC, LANES_SPEC, jax.tree.map(lambda _: LANES_SPEC, kv),
         ROPE_KEY_SPEC), LANES_SPEC, scope)(q, q_rope, kv, k_rope)


def _latent_parts(q, kv, lanes: int):
    """``q_nope`` and the pair ``(k_nope, v)`` as arrays of their own: the
    first ``lanes`` lanes of ``q_b``'s output, the first ``lanes`` lanes of
    ``kv_b``'s and the rest; a pair is handed back as it is."""
    if not isinstance(kv, tuple):
        kv = kv[..., :lanes], kv[..., lanes:]
    return q[..., :lanes], kv


class LatentAttention(nn.Module):
    """Queries through a normed latent, keys and values from one, a head's
    scores the sum of its own 128-wide product and a 64-wide one with the
    one rotary key (module docstring). The projections' columns are
    head-major part by part: ``q_b`` is every head's ``nope`` lanes, then
    every head's ``rope`` lanes; ``kv_a`` the latent, then the rotary key;
    ``kv_b`` every head's ``k_nope``, then every head's ``v``: each part
    starts at a lane tile's edge and no (B, T, H, d) array exists. The
    rotary pass and the latent kernels read their parts of ``q_b``'s,
    ``kv_a``'s and ``kv_b``'s outputs where they lie, as column blocks (no
    slice is traced); where a mesh axis splits the lanes (``tp`` > 1) it
    splits the heads of each part, so the parts are sliced first and the
    same entries get them at offset 0, as the dense lowering does (no Mosaic
    backend, or ``latent_fits`` refuses): ``attn_operands`` says which."""
    cfg: SparseLMConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, a: jax.Array) -> jax.Array:
        cfg = self.cfg
        dt, pdt = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        dense = functools.partial(nn.Dense, use_bias=False, dtype=dt,
                                  param_dtype=pdt)
        heads, nope = cfg.num_heads, cfg.qk_nope_head_dim
        rope, value = cfg.qk_rope_head_dim, cfg.v_head_dim

        def latent_norm(name, x):
            with jax.named_scope("latent_norm"):
                return rms_norm(x, self.param(
                    name, nn.initializers.ones, (x.shape[-1],), pdt),
                    cfg.rms_eps)

        q = dense(heads * (nope + rope), name="q_b")(latent_norm(
            "q_a_norm", dense(cfg.q_lora_rank, name="q_a")(a)))
        kv_a = dense(cfg.kv_lora_rank + rope, name="kv_a")(a)
        kv = dense(heads * (nope + value), name="kv_b")(latent_norm(
            "kv_a_norm", kv_a[..., :cfg.kv_lora_rank]))

        rotary = functools.partial(pair_rotary, mesh=self.mesh, head_dim=rope,
                                   theta=cfg.rope_theta)
        with jax.named_scope(head_norm.ROTARY_SCOPE):
            q_rope = rotary(q, heads * nope, spec=LANES_SPEC)
            k_rope = rotary(kv_a, cfg.kv_lora_rank, spec=ROPE_KEY_SPEC)
        ctx = latent_attend(q, q_rope, kv, k_rope, mesh=self.mesh, nope=nope,
                            rope=rope, value=value, scope=self.name)
        return dense(cfg.hidden_size, name="out")(ctx)


# ---------------------------------------------------------------------------
# The gated short convolution (layers of kind ``short_conv``)
# ---------------------------------------------------------------------------

def short_conv_mix(bcu: jax.Array, taps: jax.Array) -> jax.Array:
    """``C * conv(B * u)`` of ``in_proj``'s output bcu (B, T, 3 D) = ``[B ;
    C ; u]``, read as three column blocks where the projection wrote them:
    the depthwise causal convolution ``z_t = sum_j taps[j] (B u)_{t - (K -
    1) + j}`` (noughts before t = 0) as K shifts along the tokens, each an
    f32 product with an f32 tap, and no (B, T, K, D) array; the two gates
    in ``bcu``'s dtype. taps: (K, D) f32."""
    k, d = taps.shape
    gate_in, gate_out, u = (bcu[..., i * d:(i + 1) * d] for i in range(3))
    bu = gate_in * u
    taps = taps.astype(jnp.float32)
    z = taps[k - 1] * bu.astype(jnp.float32)
    for back in range(1, min(k, bu.shape[1])):
        earlier = jnp.pad(bu[:, :-back], ((0, 0), (back, 0), (0, 0)))
        z = z + taps[k - 1 - back] * earlier.astype(jnp.float32)
    return gate_out * z.astype(bcu.dtype)


class ShortConv(nn.Module):
    """``(C * conv(B * u)) . W_out`` with ``[B ; C ; u] = a . W_in`` (module
    docstring): the operator of a ``short_conv`` layer, under the name
    ``conv``. Scopes ``conv/in_proj``, ``conv/mix`` (the two gates and the
    taps, XLA code) and ``conv/out_proj``. Leaves ``in_proj/kernel`` (D, 3
    D), ``taps`` (K, D) (the source's depthwise weight (D, 1, K), a tap a
    row: tap K - 1 weighs the token itself) and ``out_proj/kernel``."""
    cfg: SparseLMConfig

    @nn.compact
    def __call__(self, a: jax.Array) -> jax.Array:
        cfg = self.cfg
        pdt = jnp.dtype(cfg.param_dtype)
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=jnp.dtype(cfg.dtype), param_dtype=pdt)
        bcu = dense(3 * cfg.hidden_size, name="in_proj")(a)
        taps = self.param(
            "taps", nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal", in_axis=0, out_axis=1),
            (cfg.conv_kernel, cfg.hidden_size), pdt)
        with jax.named_scope("mix"):
            y = short_conv_mix(bcu, taps)
        return dense(cfg.hidden_size, name="out_proj")(y)


def conv_layout(cfg: SparseLMConfig) -> str:
    """The ``setup/warmup`` row's ``conv_layout``: a function of the
    configuration (the mix is XLA code on every backend and mesh)."""
    kinds = [cfg.kind_of_layer(i) for i in range(cfg.num_hidden_layers)]
    convs = kinds.count(LAYER_SHORT_CONV)
    return (f"gated short convolution: {convs} of {len(kinds)} layers, "
            f"{cfg.conv_kernel} taps, causal, depthwise over "
            f"{cfg.hidden_size} lanes; conv/mix is XLA code: B, C and u read "
            f"as column blocks of in_proj's (B, T, {3 * cfg.hidden_size}) "
            f"output in place, the taps as {cfg.conv_kernel - 1} shifts "
            "along the tokens in f32 (no Mosaic kernel)")


# ---------------------------------------------------------------------------
# The Mamba-2 state-space mixer (layers of kind ``mamba2``)
# ---------------------------------------------------------------------------

def causal_taps_silu(xbc: jax.Array, taps: jax.Array,
                     bias: jax.Array) -> jax.Array:
    """``silu(sum_j taps[j] * xbc_{t - (K - 1) + j} + bias)``: the depthwise
    causal convolution over all of xbc's (B, T, W) lanes (noughts before
    t = 0) as K shifts along the tokens, each an f32 product with an f32
    tap (:func:`short_conv_mix`'s way), the bias and the SiLU in f32. taps:
    (K, W); bias: (W,)."""
    k = taps.shape[0]
    taps = taps.astype(jnp.float32)
    z = taps[k - 1] * xbc.astype(jnp.float32) + bias.astype(jnp.float32)
    for back in range(1, min(k, xbc.shape[1])):
        earlier = jnp.pad(xbc[:, :-back], ((0, 0), (back, 0), (0, 0)))
        z = z + taps[k - 1 - back] * earlier.astype(jnp.float32)
    return jax.nn.silu(z).astype(xbc.dtype)


def chunked_scan(x, bm, cm, dt, a, d, *, heads: int, groups: int,
                 chunk: int) -> jax.Array:
    """``y_t = S_t C_t + d x_t`` of ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t
    B_t^T`` (``S_{-1}`` = 0), in chunks of ``chunk`` tokens (module
    docstring). x: (B, T, H*P); bm, cm: (B, T, G*N), head h reading group h
    // (H / G); dt: (B, T, H) f32, after the softplus; a (negative), d:
    (H,) f32. Returns (B, T, H*P) in x's dtype. A sequence that is no whole
    number of chunks is padded behind with tokens whose ``dt`` is 0: they
    change no state and nothing reads them."""
    b, t, _ = x.shape
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, bm, cm, dt = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                         for v in (x, bm, cm, dt))
    c, r = (t + pad) // q, heads // groups
    f32 = jnp.float32
    xc = x.reshape(b, c, q, groups, r, -1)            # (b, c, q, g, r, P)
    bc = bm.reshape(b, c, q, groups, -1)              # (b, c, q, g, N)
    cc = cm.reshape(b, c, q, groups, -1)
    dtc = dt.reshape(b, c, q, heads).transpose(0, 1, 3, 2)   # (b, c, h, q)
    cs = jnp.cumsum(dtc * a[:, None], axis=-1)        # running log decay
    # inside a chunk: the masked (q x q) form a head
    i = np.arange(q)
    seg = jnp.where(i[None, :] <= i[:, None],
                    cs[..., :, None] - cs[..., None, :], -jnp.inf)
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc, preferred_element_type=f32)
    m = (jnp.exp(seg) * dtc[..., None, :]).reshape(b, c, groups, r, q, q) \
        * cb[:, :, :, None]
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", m.astype(x.dtype), xc,
                   preferred_element_type=f32)
    # a chunk's own state, and the state every chunk starts from
    to_end = (jnp.exp(cs[..., -1:] - cs) * dtc).transpose(0, 1, 3, 2)
    xw = (xc.astype(f32) * to_end.reshape(b, c, q, groups, r, 1)).astype(
        x.dtype)
    local = jnp.einsum("bcjgrp,bcjgn->cbgrpn", xw, bc,
                       preferred_element_type=f32)
    total = jnp.exp(cs[..., -1]).reshape(b, c, groups, r).transpose(
        1, 0, 2, 3)                                   # (c, b, g, r)

    def carry(state, chunk_of):
        decay, own = chunk_of
        return decay[..., None, None] * state + own, state

    _, before = jax.lax.scan(carry, jnp.zeros(local.shape[1:], f32),
                             (total, local))          # (c, b, g, r, P, N)
    y = y + jnp.einsum("bcign,cbgrpn->bcigrp", cc, before.astype(x.dtype),
                       preferred_element_type=f32) \
        * jnp.exp(cs).transpose(0, 1, 3, 2).reshape(b, c, q, groups, r, 1)
    y = y + xc.astype(f32) * d.reshape(groups, r, 1)
    return y.reshape(b, t + pad, -1)[:, :t].astype(x.dtype)


def gated_group_norm(y, z, scale, groups: int, eps: float) -> jax.Array:
    """RMS norm over each of ``groups`` runs of lanes of ``y * silu(z)``
    (gate first, norm after), one scale vector for all lanes; in f32."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = gated.reshape(*gated.shape[:-1], groups, -1)
    normed = parts * jax.lax.rsqrt(
        jnp.mean(parts * parts, -1, keepdims=True) + eps)
    return (normed.reshape(gated.shape) * scale).astype(y.dtype)


SCAN_SITE = "ssm scan"
# the backward's kind, a fact of the site's record
SCAN_BACKWARD = ("one kernel, the chunks in reverse from the states the "
                 "forward kept")


def _scan_key(tokens: int, cfg: SparseLMConfig):
    """What the record knows a chunked scan by: a sample's tokens and the
    mixer's sizes."""
    return (tokens, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_groups,
            cfg.ssm_state_size, cfg.ssm_chunk)


def ssm_scan(x, bm, cm, dt, a, d, *, mesh, cfg: SparseLMConfig,
             scope: Optional[str] = None):
    """The chunked scan as a call site: a shard's samples, every head (no
    mesh axis splits the mixer's lanes). The kernels of
    ops/pallas/ssm_scan_kernels.py where their predicate takes the local
    shapes, else :func:`chunked_scan`."""
    sizes = dict(heads=cfg.mamba_num_heads, groups=cfg.ssm_groups,
                 chunk=cfg.ssm_chunk)

    def fits(x, bm, cm, dt, a, d) -> bool:
        tokens = x.shape[1]
        key = _scan_key(tokens, cfg)
        why_not = ssm_scan_kernels.fits(*key, x.dtype.itemsize)
        if why_not is not None:
            return lowering.chose(SCAN_SITE, key, why_not, why_not)
        r = cfg.mamba_num_heads // cfg.ssm_groups
        k = ssm_scan_kernels.chunks_a_step(
            tokens // cfg.ssm_chunk, cfg.ssm_chunk, r, cfg.mamba_head_dim,
            cfg.ssm_state_size, x.dtype.itemsize)
        return lowering.chose(
            SCAN_SITE, key, None,
            f"local x{x.shape} in chunks of {cfg.ssm_chunk}, {k} a grid "
            f"step, {r} heads a group",
            chunks_a_step=k, backward=SCAN_BACKWARD)

    kernel = functools.partial(ssm_scan_kernels.scan, **sizes,
                               interpret=lowering.interpret())
    xla = functools.partial(chunked_scan, **sizes)
    lanes = P(*LANES_SPEC[:2], None)
    return lowering.site(SCAN_SITE, fits, kernel, xla, mesh,
                         (lanes,) * 4 + (P(), P()), lanes, scope)(
                             x, bm, cm, dt, a, d)


TAPS_SITE = "ssm taps"
GATE_NORM_SITE = "ssm gate norm"


def _taps_key(tokens: int, cfg: SparseLMConfig):
    """What the record knows a taps pass by: a sample's tokens, where
    ``xBC`` lies in ``in_proj``'s output, its parts' widths, the taps and
    the operands' bytes a number."""
    state = cfg.ssm_groups * cfg.ssm_state_size
    return (tokens, cfg.mamba_inner, (cfg.mamba_inner, state, state),
            cfg.conv_kernel, jnp.dtype(cfg.dtype).itemsize)


def _gate_norm_key(tokens: int, cfg: SparseLMConfig):
    return (tokens, cfg.mamba_inner, cfg.ssm_groups,
            jnp.dtype(cfg.dtype).itemsize)


def _taps_site(site: str, key, words, operand, taps, bias, *, mesh,
               scope: Optional[str]):
    """The taps, a bias and the SiLU as a call site of either mixer: the
    parts of ``operand`` (B, T, .) that ``key`` names (tokens, the lane they
    start at, their widths, the taps, bytes a number), each written as an
    array of its own. The pass of ops/pallas/ssm_pass_kernels.py, which
    reads the parts' columns where they lie, where its predicate takes the
    local shapes; else :func:`causal_taps_silu` on a slice, and slices of its
    result. ``words(operand, key)``: what the log says the kernel was
    given."""
    _, before, widths, _, _ = key
    ends = np.cumsum(widths)

    def fits(operand, taps, bias) -> bool:
        local = (operand.shape[1], *key[1:])
        why_not = ssm_pass_kernels.taps_fit(*local)
        return lowering.chose(site, local, why_not,
                              why_not or words(operand, local))

    def kernel(operand, taps, bias):
        f32 = jnp.float32
        return ssm_pass_kernels.taps_silu(
            operand, taps.astype(f32), bias.astype(f32), before, widths,
            lowering.interpret())

    def xla(operand, taps, bias):
        out = causal_taps_silu(operand[..., before:before + ends[-1]], taps,
                               bias)
        return tuple(out[..., end - width:end]
                     for end, width in zip(ends, widths))

    lanes = P(*LANES_SPEC[:2], None)
    return lowering.site(site, fits, kernel, xla, mesh, (lanes, P(), P()),
                         (lanes,) * len(widths), scope)(operand, taps, bias)


def ssm_taps(zxbcdt, taps, bias, *, mesh, cfg: SparseLMConfig,
             scope: Optional[str] = None):
    """The Mamba-2 mixer's taps, bias and SiLU (:func:`_taps_site`): ``x``,
    ``B`` and ``C`` of ``in_proj``'s whole output (B, T, .)."""
    def words(zxbcdt, key):
        return (f"local xBC{zxbcdt.shape[:2] + (cfg.mamba_conv_lanes,)} "
                f"read at lane {cfg.mamba_inner} of {zxbcdt.shape[2]}, "
                f"{ssm_pass_kernels.rows_tile(key[0], key[-1])} tokens a "
                "grid step")

    return _taps_site(TAPS_SITE, _taps_key(zxbcdt.shape[1], cfg), words,
                      zxbcdt, taps, bias, mesh=mesh, scope=scope)


def ssm_gate_norm(y, zxbcdt, scale, *, mesh, cfg: SparseLMConfig,
                  scope: Optional[str] = None):
    """The gate and the group norm as a call site: of the scan's ``y`` and
    ``in_proj``'s whole output, whose first lanes are ``z``. The pass of
    ops/pallas/ssm_pass_kernels.py where its predicate takes the local
    shapes, else :func:`gated_group_norm` on a slice."""
    def fits(y, zxbcdt, scale) -> bool:
        key = _gate_norm_key(y.shape[1], cfg)
        why_not = ssm_pass_kernels.gate_norm_fit(*key)
        return lowering.chose(
            GATE_NORM_SITE, key, why_not, why_not or (
                f"local y{y.shape} and z read at lane 0 of "
                f"{zxbcdt.shape[2]}, {cfg.ssm_groups} groups of "
                f"{y.shape[2] // cfg.ssm_groups} lanes, "
                f"{ssm_pass_kernels.rows_tile(key[0], key[-1])} tokens a "
                "grid step"))

    def kernel(y, zxbcdt, scale):
        return ssm_pass_kernels.gate_norm(
            y, zxbcdt, scale.astype(jnp.float32), cfg.ssm_groups,
            cfg.rms_eps, lowering.interpret())

    def xla(y, zxbcdt, scale):
        return gated_group_norm(y, zxbcdt[..., :y.shape[2]], scale,
                                cfg.ssm_groups, cfg.rms_eps)

    lanes = P(*LANES_SPEC[:2], None)
    return lowering.site(GATE_NORM_SITE, fits, kernel, xla, mesh,
                         (lanes, lanes, P()), lanes, scope)(y, zxbcdt, scale)


# what the source's keys time_step_min, time_step_max and time_step_floor
# are for: ``dt_bias`` at init is the inverse softplus of a step drawn
# log-uniformly between the first two and held above the third. The
# decays' ``A`` is drawn from U(1, 16), the family's default range (no key)
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
A_RANGE = (1.0, 16.0)


def _dt_bias_init(key, shape, dtype):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (np.log(DT_MAX) - np.log(DT_MIN)) + np.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE)
                   ).astype(dtype)


class Mamba2Mixer(nn.Module):
    """The state-space mixer of a ``mamba2`` layer (module docstring),
    under the name ``ssm``. Leaves ``in_proj/kernel`` (D, 2 H P + 2 G N +
    H), ``taps`` (K, H P + 2 G N) (the source's depthwise weight, a tap a
    row: tap K - 1 weighs the token itself), ``conv_bias``, ``dt_bias``,
    ``A_log``, ``D`` (H each), ``norm`` (H P) and ``out_proj/kernel``."""
    cfg: SparseLMConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, a: jax.Array) -> jax.Array:
        cfg = self.cfg
        pdt = jnp.dtype(cfg.param_dtype)
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=jnp.dtype(cfg.dtype), param_dtype=pdt)
        heads, inner = cfg.mamba_num_heads, cfg.mamba_inner
        lanes = cfg.mamba_conv_lanes
        zxbcdt = dense(inner + lanes + heads, name="in_proj")(a)
        taps = self.param(
            "taps", nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal", in_axis=0, out_axis=1),
            (cfg.conv_kernel, lanes), pdt)
        bias = self.param("conv_bias", nn.initializers.zeros, (lanes,), pdt)
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), pdt)
        a_log = self.param("A_log", _a_log_init, (heads,), pdt)
        skip = self.param("D", nn.initializers.ones, (heads,), pdt)
        scale = self.param("norm", nn.initializers.ones, (inner,), pdt)
        with jax.named_scope("conv"):
            x, bm, cm = ssm_taps(zxbcdt, taps, bias, mesh=self.mesh, cfg=cfg,
                                 scope="conv")
        with jax.named_scope("scan"):
            f32 = jnp.float32
            dt = jax.nn.softplus(zxbcdt[..., inner + lanes:].astype(f32)
                                 + dt_bias.astype(f32))
            y = ssm_scan(x, bm, cm, dt, -jnp.exp(a_log.astype(f32)),
                         skip.astype(f32), mesh=self.mesh, cfg=cfg,
                         scope="scan")
        with jax.named_scope("gate_norm"):
            y = ssm_gate_norm(y, zxbcdt, scale, mesh=self.mesh, cfg=cfg,
                              scope="gate_norm")
        return dense(cfg.hidden_size, name="out_proj", **out_proj_init(cfg))(y)


def ssm_layout(cfg: SparseLMConfig) -> str:
    """The ``setup/warmup`` row's ``ssm_layout``: the configuration's
    sizes, and of the mixer's three sites what their traced calls said."""
    kinds = [cfg.kind_of_layer(i) for i in range(cfg.num_hidden_layers)]
    tokens, chunk = cfg.total_seq_len, cfg.ssm_chunk
    scan_key = _scan_key(tokens, cfg)
    why = lowering.why_not(SCAN_SITE, scan_key)
    if why is not None:
        scan = (f"ssm/scan is XLA code ({why}), its backward plain "
                "differentiation of the chunked form")
    else:
        said = lowering.recorded(SCAN_SITE, scan_key)
        scan = (f"ssm/scan is a pair of Pallas kernels "
                f"({said['chunks_a_step']} chunks a grid step, a chunk's "
                "form and the carried states in VMEM; backward: "
                f"{said['backward']}), the decays' running sums in the "
                "kernels, the step sizes around them XLA code")

    def stage(site, key, one_pass):
        why = lowering.why_not(site, key)
        return one_pass if why is None else f"XLA code ({why})"

    taps = stage(TAPS_SITE, _taps_key(tokens, cfg),
                 "one pass a direction, x, B and C written apart")
    gate_norm = stage(GATE_NORM_SITE, _gate_norm_key(tokens, cfg),
                      "one pass a direction")
    return (
        f"Mamba-2 mixer: {kinds.count(LAYER_MAMBA2)} of {len(kinds)} layers, "
        f"{cfg.mamba_num_heads} heads x {cfg.mamba_head_dim}, "
        f"{cfg.ssm_groups} groups of B and C, state {cfg.ssm_state_size}, "
        f"{cfg.conv_kernel} taps with a bias over {cfg.mamba_conv_lanes} "
        f"lanes; chunked scan: chunks of {chunk}, {-(-tokens // chunk)} a "
        f"sequence of {tokens}: inside a chunk the masked ({chunk} x "
        f"{chunk}) form a head, across chunks the carried "
        f"({cfg.mamba_head_dim} x {cfg.ssm_state_size}) state, the decays, "
        "their sums and the states in f32; no (T, T) array and no state a "
        f"token; {scan}; taps, bias and SiLU: {taps}; gate and group norm: "
        f"{gate_norm}; the replay keeps nothing of the mixer but the "
        "layer's input")


# ---------------------------------------------------------------------------
# The gated-delta-rule mixer (layers of kind ``gated_delta``)
# ---------------------------------------------------------------------------

# the diagonal blocks of a chunk's unit lower-triangular matrix that are
# inverted by products; the blocks between them by block substitution
INVERSE_BASE = 8
# the inverse's products: three bfloat16 pieces an f32 operand (about 2^-17
# a product, amplified by at most a few tens inside a block of 8 and not at
# all by the substitution), under the one rounding to ``cfg.dtype`` that its
# result gets; the six pieces of HIGHEST cost the step 0.08 s more on the v5e
# (1.760 -> 1.678 s) and the reference check's worst and median leaf read
# inside their ranges either way (PERF.md section 6, PR 64)
_INVERSE_PRECISION = jax.lax.Precision.HIGH


@jax.custom_vjp
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` of a strictly lower-triangular ``a`` (..., C, C) f32,
    C a power of two, with no loop and no division. The diagonal blocks of
    ``INVERSE_BASE`` = 8 by the finite product ``(I + a)^-1 = prod_{i<3} (I
    + (-a)^(2^i))`` (a block's 8th power is nought), then block sizes
    doubled: ``[[L11, 0], [L21, L22]]^-1 = [[L11^-1, 0], [-L22^-1 L21
    L11^-1, L22^-1]]``, which is substitution by blocks. The whole chunk by
    the product alone sums powers up to the 63rd, whose entries reach
    binomial sizes before they cancel: in f32 that loses the answer where
    keys resemble each other (tests/test_qwen3next_model.py holds both to
    the recurrence). Every product in f32 at ``_INVERSE_PRECISION``.
    Differentiated, it is two products with the inverse it made, ``da = -
    T^T dT T^T`` (the blocks' products are not walked back: at 8 192 tokens
    and 32 heads they were a tenth of the step, PERF.md section 6, PR 64);
    the cotangent is ``a``'s as a whole matrix, which the caller's mask
    cuts to the strictly lower part."""
    c = a.shape[-1]
    base = min(INVERSE_BASE, c)
    mm = functools.partial(jnp.matmul, precision=_INVERSE_PRECISION)

    def diagonal(size):
        """(..., C / size, size, size): ``a``'s diagonal blocks of ``size``,
        as static slices (a reshape and ``jnp.diagonal`` here trips a check
        of XLA's CPU compiler)."""
        return jnp.stack([a[..., lo:lo + size, lo:lo + size]
                          for lo in range(0, c, size)], axis=-3)

    eye = jnp.eye(base, dtype=a.dtype)
    power = -diagonal(base)
    inv = eye + power
    for _ in range(max(base.bit_length() - 2, 0)):
        power = mm(power, power)
        inv = mm(inv, eye + power)
    size = base
    while size < c:
        # the pairs of neighbouring diagonal blocks, and the block under
        # the first of each pair
        first, second = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        under = diagonal(2 * size)[..., size:, :size]
        low = -mm(second, mm(under, first))
        inv = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
            jnp.concatenate([low, second], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def _unit_lower_inverse_fwd(a):
    inverse = unit_lower_inverse(a)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, cotangent):
    mm = functools.partial(jnp.matmul, precision=_INVERSE_PRECISION)
    turned = jnp.swapaxes(inverse, -1, -2)
    return (-mm(turned, mm(cotangent, turned)),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


# chunks whose in-chunk work (the inverse, the products with it, the
# decays' tables) is made at once and held together: a block of the
# sequence. A block is replayed whole in the backward pass, so the tables of
# one block are alive at a time, not the sequence's (at 8 192 tokens and 32
# heads those are 5 GiB a layer: PERF.md section 6, PR 64)
RULE_BLOCK = 16


def _rule_block(state, operands, *, dtype):
    """One block of chunks from the carried ``state`` (B, G, R, dk, dv)
    f32: ``(state after, o)``. operands: q, k (B, N, C, G, dk), v (B, N, C,
    G, R, dv) and g, beta (B, N, G, R, C) f32 of the block's N chunks of C
    tokens."""
    qc, kc, vc, gc, bc = operands
    c = qc.shape[2]
    f32, dt = jnp.float32, dtype
    cs = jnp.cumsum(gc, axis=-1)                          # running log decay
    since_start = jnp.exp(cs)       # what is left of the chunk's first state
    i = np.arange(c)
    decay = jnp.exp(jnp.where(i[None, :] <= i[:, None],
                              cs[..., :, None] - cs[..., None, :], -jnp.inf))
    kk = jnp.einsum("bnigd,bnjgd->bngij", kc, kc, preferred_element_type=f32)
    qk = jnp.einsum("bnigd,bnjgd->bngij", qc, kc, preferred_element_type=f32)
    # (I + A) u = beta (v - exp(cs) k S): A strictly lower
    a = jnp.where(i[None, :] < i[:, None],
                  bc[..., :, None] * decay * kk[:, :, :, None], 0.0)
    inverse = unit_lower_inverse(a).astype(dt)            # (b, n, G, R, c, c)
    per_row = lambda x: x.transpose(0, 1, 4, 2, 3)[..., None]  # (b,n,c,G,R,1)
    kr = kc[:, :, :, :, None, :].astype(f32)              # (b, n, c, G, 1, dk)
    qr = qc[:, :, :, :, None, :].astype(f32)
    v_in = (vc.astype(f32) * per_row(bc)).astype(dt)
    k_in = (kr * per_row(bc * since_start)).astype(dt)
    # what the rule writes with an empty state, and what a state takes off
    u_own = jnp.einsum("bngrij,bnjgrp->nbgrip", inverse, v_in,
                       preferred_element_type=f32)
    w = jnp.einsum("bngrij,bnjgrd->nbgrid", inverse, k_in,
                   preferred_element_type=f32).astype(dt)
    attn = (decay * qk[:, :, :, None]).astype(dt).transpose(1, 0, 2, 3, 4, 5)
    q_in = (qr * per_row(since_start)).astype(dt).transpose(1, 0, 3, 4, 2, 5)
    k_out = (kr * per_row(jnp.exp(cs[..., -1:] - cs))).astype(dt).transpose(
        1, 0, 3, 4, 2, 5)                                 # (n, b, G, R, c, dk)
    total = since_start[..., -1].transpose(1, 0, 2, 3)    # (n, b, G, R)

    def carry(state, chunk_of):
        u_own, w, attn, q_in, k_out, total = chunk_of
        s = state.astype(dt)
        u = (u_own - jnp.einsum("bgrid,bgrdp->bgrip", w, s,
                                preferred_element_type=f32)).astype(dt)
        o = jnp.einsum("bgrid,bgrdp->bgrip", q_in, s,
                       preferred_element_type=f32) \
            + jnp.einsum("bgrij,bgrjp->bgrip", attn, u,
                         preferred_element_type=f32)
        state = total[..., None, None] * state + jnp.einsum(
            "bgrid,bgrip->bgrdp", k_out, u, preferred_element_type=f32)
        return state, o.astype(dt)

    return jax.lax.scan(carry, state, (u_own, w, attn, q_in, k_out, total))


def chunked_delta_rule(q, k, v, g, beta, *, key_heads: int,
                       chunk: int) -> jax.Array:
    """``o_t = S_t^T q_t`` of ``S_t = exp(g_t) S_{t-1} + k_t (beta_t (v_t -
    (exp(g_t) S_{t-1})^T k_t))^T`` (``S_{-1}`` = 0), in chunks of ``chunk``
    tokens (module docstring), the chunks in blocks of ``RULE_BLOCK`` that
    carry the state from one to the next and are replayed whole in the
    backward pass. q, k: (B, T, G*dk), L2-normalised, value head h reading
    query/key head h // (H / G); v: (B, T, H*dv); g (the log of the decay,
    <= 0), beta: (B, T, H) f32. Returns (B, T, H*dv) in v's dtype. A
    sequence that is no whole number of chunks is padded behind with tokens
    whose ``k``, ``beta`` and ``g`` are 0: they change no state and nothing
    reads them."""
    b, t, _ = v.shape
    heads = g.shape[-1]
    c = min(chunk, 1 << max(t - 1, 0).bit_length())
    pad = -t % c
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                            for x in (q, k, v, g, beta))
    n, r = (t + pad) // c, heads // key_heads
    per = max(m for m in range(1, min(n, RULE_BLOCK) + 1) if n % m == 0)
    # (blocks, b, chunks a block, ...)
    blocks = lambda x: jnp.moveaxis(
        x.reshape(b, n // per, per, *x.shape[2:]), 1, 0)
    by_head = lambda x: x.reshape(b, n, c, key_heads, r).transpose(
        0, 1, 3, 4, 2)                                    # (b, n, G, R, c)
    operands = tuple(map(blocks, (
        q.reshape(b, n, c, key_heads, -1), k.reshape(b, n, c, key_heads, -1),
        v.reshape(b, n, c, key_heads, r, -1), by_head(g), by_head(beta))))
    start = jnp.zeros((b, key_heads, r, q.shape[-1] // key_heads,
                       v.shape[-1] // heads), jnp.float32)
    _, o = jax.lax.scan(
        jax.checkpoint(functools.partial(_rule_block, dtype=v.dtype)),
        start, operands)
    # (blocks, chunks a block, b, G, R, c, dv) -> (b, T, H * dv)
    return o.transpose(2, 0, 1, 5, 3, 4, 6).reshape(b, t + pad, -1)[:, :t]


DELTA_SITE = "delta rule"


def _delta_key(tokens: int, cfg: SparseLMConfig):
    """What the record knows a delta rule by: a sample's tokens and the
    mixer's sizes."""
    return (tokens, cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.delta_chunk)


def delta_rule(q, k, v, g, beta, *, mesh, cfg: SparseLMConfig,
               scope: Optional[str] = None):
    """The rule as a call site: a shard's samples, every head (no mesh axis
    splits the mixer's lanes). The kernels of
    ops/pallas/delta_rule_kernels.py where their predicate takes the local
    shapes, else :func:`chunked_delta_rule`."""
    sizes = dict(key_heads=cfg.linear_num_key_heads, chunk=cfg.delta_chunk)

    def fits(q, k, v, g, beta) -> bool:
        tokens = q.shape[1]
        key = _delta_key(tokens, cfg)
        why_not = delta_rule_kernels.fits(*key, v.dtype.itemsize)
        if why_not is not None:
            return lowering.chose(DELTA_SITE, key, why_not, why_not)
        r = cfg.linear_num_value_heads // cfg.linear_num_key_heads
        keys = delta_rule_kernels.keys_a_step(cfg.linear_num_key_heads, r,
                                              cfg.delta_chunk)
        n = delta_rule_kernels.chunks_a_step(
            tokens // cfg.delta_chunk, cfg.delta_chunk, r,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            v.dtype.itemsize, keys)
        return lowering.chose(
            DELTA_SITE, key, None,
            f"local v{v.shape} in chunks of {cfg.delta_chunk}, {n} chunks "
            f"of {keys} key heads a grid step, {r} value heads a key head",
            chunks_a_step=n, keys_a_step=keys,
            backward=delta_rule_kernels.BACKWARD)

    kernel = functools.partial(delta_rule_kernels.rule, **sizes,
                               interpret=lowering.interpret())
    xla = functools.partial(chunked_delta_rule, **sizes)
    lanes = P(*LANES_SPEC[:2], None)
    return lowering.site(DELTA_SITE, fits, kernel, xla, mesh, (lanes,) * 5,
                         lanes, scope)(q, k, v, g, beta)


GDN_TAPS_SITE = "gdn taps"


def _gdn_taps_key(tokens: int, cfg: SparseLMConfig):
    """As :func:`_taps_key`: ``[q ; k ; v]`` lie first in ``in_proj``'s
    output."""
    return (tokens, 0, (cfg.linear_key_lanes, cfg.linear_key_lanes,
                        cfg.linear_value_lanes),
            cfg.linear_conv_kernel_dim, jnp.dtype(cfg.dtype).itemsize)


def gdn_taps(qkv, taps, *, mesh, cfg: SparseLMConfig,
             scope: Optional[str] = None):
    """The gated-delta mixer's taps and SiLU (:func:`_taps_site`, with a
    bias of noughts: the source has none): ``q``, ``k`` and ``v`` of
    ``in_proj``'s ``[q ; k ; v]`` (B, T, .), written apart."""
    def words(qkv, key):
        return (f"local qkv{tuple(qkv.shape)} read at lane 0 of "
                f"{qkv.shape[2]}, "
                f"{ssm_pass_kernels.rows_tile(key[0], key[-1])} tokens a "
                "grid step")

    return _taps_site(GDN_TAPS_SITE, _gdn_taps_key(qkv.shape[1], cfg), words,
                      qkv, taps, jnp.zeros(taps.shape[1:], jnp.float32),
                      mesh=mesh, scope=scope)


def l2_normed(x: jax.Array, head_dim: int, scale: float = 1.0) -> jax.Array:
    """Each head of x (B, T, H * head_dim) over the root of its squares' sum
    + 1e-6, times ``scale``; in f32, rounded to x's dtype."""
    heads = x.astype(jnp.float32).reshape(*x.shape[:2], -1, head_dim)
    heads = heads * (scale * jax.lax.rsqrt(
        jnp.sum(heads * heads, -1, keepdims=True) + 1e-6))
    return heads.reshape(x.shape).astype(x.dtype)


# ``A_log`` at init: the log of U(0, 16), as the source's modeling code
# draws it; ``dt_bias`` ones
GDN_A_RANGE = (0.0, 16.0)


def _gdn_a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(
        key, shape, jnp.float32, *GDN_A_RANGE,
    ).clip(min=jnp.finfo(jnp.float32).tiny)).astype(dtype)


class GatedDeltaInProj(nn.Module):
    """The mixer's way in, under the name ``in_proj``: ``[q ; k ; v]``, ``z``
    and ``[b ; a]`` of the layer's normed input as three products, leaves
    ``qkv/kernel`` (D, 2 G dk + H dv), ``z/kernel`` (D, H dv) and
    ``ba/kernel`` (D, 2 H). The source's ``in_proj_qkvz`` and
    ``in_proj_ba`` hold the same columns, interleaved by key head (a
    layout). Three arrays and not one with column blocks: each reader's
    cotangent is then its own product's operand, where one array's is the
    readers' three padded to its width and summed in f32, 4.8 ms a layer
    and micro-step at the cell's size (PERF.md section 6, PR 64)."""
    cfg: SparseLMConfig

    @nn.compact
    def __call__(self, a: jax.Array):
        cfg = self.cfg
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=jnp.dtype(cfg.dtype),
            param_dtype=jnp.dtype(cfg.param_dtype))
        return (dense(cfg.linear_conv_lanes, name="qkv")(a),
                dense(cfg.linear_value_lanes, name="z")(a),
                dense(2 * cfg.linear_num_value_heads, name="ba")(a))


class GatedDeltaMixer(nn.Module):
    """The gated-delta-rule mixer of a ``gated_delta`` layer (module
    docstring), under the name ``gdn``. Leaves ``in_proj/{qkv,z,ba}/kernel``
    (:class:`GatedDeltaInProj`), ``taps`` (K, 2 G dk + H dv) (a tap a row: tap K - 1
    weighs the token itself), ``dt_bias``, ``A_log`` (H each), ``norm`` (dv:
    one scale vector for every head) and ``out_proj/kernel``."""
    cfg: SparseLMConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, a: jax.Array) -> jax.Array:
        cfg = self.cfg
        dt, pdt = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        dense = functools.partial(nn.Dense, use_bias=False, dtype=dt,
                                  param_dtype=pdt)
        heads, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                         cfg.linear_value_head_dim)
        lanes = cfg.linear_conv_lanes
        qkv, z, ba = GatedDeltaInProj(cfg, name="in_proj")(a)
        taps = self.param(
            "taps", nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal", in_axis=0, out_axis=1),
            (cfg.linear_conv_kernel_dim, lanes), pdt)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (heads,), pdt)
        a_log = self.param("A_log", _gdn_a_log_init, (heads,), pdt)
        scale = self.param("norm", nn.initializers.ones, (dv,), pdt)
        with jax.named_scope("conv"):
            q, k, v = gdn_taps(qkv, taps, mesh=self.mesh, cfg=cfg,
                               scope="conv")
        with jax.named_scope("rule"):
            f32 = jnp.float32
            ba = ba.astype(f32)
            beta = jax.nn.sigmoid(ba[..., :heads])
            g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
                ba[..., heads:] + dt_bias.astype(f32))
            o = delta_rule(l2_normed(q, dk, dk ** -0.5), l2_normed(k, dk), v,
                           g, beta, mesh=self.mesh, cfg=cfg, scope="rule")
        with jax.named_scope("gate_norm"):
            # the norm BEFORE the gate, over each head's lanes
            o = head_pass(o, scale, mesh=self.mesh, eps=cfg.rms_eps,
                          head_dim=dv, theta=None)
            o = (o.astype(f32) * jax.nn.silu(z.astype(f32))).astype(dt)
        return dense(cfg.hidden_size, name="out_proj")(o)


def gdn_layout(cfg: SparseLMConfig, tp: int = 1) -> str:
    """The ``setup/warmup`` row's ``gdn_layout``: the configuration's sizes,
    and of the mixer's sites what their traced calls said."""
    kinds = [cfg.kind_of_layer(i) for i in range(cfg.num_hidden_layers)]
    tokens, chunk = cfg.total_seq_len, cfg.delta_chunk
    delta_key = _delta_key(tokens, cfg)
    why = lowering.why_not(DELTA_SITE, delta_key)
    if why is not None:
        rule = (f"XLA chunks ({why}), replayed a block of {RULE_BLOCK} "
                "chunks in the backward pass")
    else:
        said = lowering.recorded(DELTA_SITE, delta_key)
        kept = delta_rule_kernels.kept_bytes(
            *delta_key, jnp.dtype(cfg.dtype).itemsize) / 2 ** 20
        rule = (f"a Pallas kernel a direction ({said['chunks_a_step']} "
                f"chunks of {said['keys_a_step']} key heads a grid step, a "
                "chunk's tables, its inverse and the "
                f"carried states in VMEM; backward: {said['backward']}; a "
                "rematerialised layer keeps o, the state a grid step, the "
                "inverses, the normalised q and k and the g and beta rows, "
                f"{kept:g} MiB a sample and layer: its replay runs neither "
                "the forward kernel nor the norms and rows again)")
    why = lowering.why_not(GDN_TAPS_SITE, _gdn_taps_key(tokens, cfg))
    taps = ("the Mamba-2 mixer's pass, one a direction, q, k and v written "
            "apart, a bias of noughts" if why is None
            else f"XLA code ({why})")
    why = lowering.first_refusal([(
        _head_pass_site(True, False), _head_pass_key(
            tokens, cfg.linear_value_lanes, cfg.linear_value_head_dim, tp))])
    norm = "one pass on the lanes" if why is None else f"XLA code ({why})"
    return (
        f"gated-delta-rule mixer: {kinds.count(LAYER_GATED_DELTA)} of "
        f"{len(kinds)} layers, {cfg.linear_num_key_heads} query/key heads x "
        f"{cfg.linear_key_head_dim} serving {cfg.linear_num_value_heads} "
        f"value heads x {cfg.linear_value_head_dim}, "
        f"{cfg.linear_conv_kernel_dim} taps with no bias over "
        f"{cfg.linear_conv_lanes} lanes; the rule in chunks of {chunk}, "
        f"{-(-tokens // chunk)} a sequence of {tokens}: inside a chunk the "
        f"inverse of a unit lower-triangular ({chunk} x {chunk}) matrix a "
        f"head (blocks of {min(INVERSE_BASE, chunk)} by products, then "
        "doubled by substitution), across chunks the carried "
        f"({cfg.linear_key_head_dim} x {cfg.linear_value_head_dim}) state, "
        "the decays, their sums, the inverse and the states in f32; no (T, "
        f"T) array and no state a token; gdn/rule: {rule}; taps and SiLU: "
        f"{taps}; the heads' norm before the gate: {norm}, the gate XLA "
        "code")


# ---------------------------------------------------------------------------
# The expert layer
# ---------------------------------------------------------------------------

def dispatch_rows(tokens: int, cfg: SparseLMConfig) -> int:
    """Rows of the dispatch buffer for ``tokens`` tokens: what a uniform
    router sends to the held experts times ``ROWS_OVER_EXPECTED``, in
    whole row tiles, and never more than every assignment a token can make
    to held experts."""
    worst = tokens * min(cfg.experts_per_token, cfg.experts_held)
    expected = tokens * cfg.experts_per_token * cfg.experts_held \
        / cfg.num_experts
    tile = grouped.TILE
    return min(worst, -(-int(ROWS_OVER_EXPECTED * expected) // tile) * tile)


# What the two lowerings of a token-major sum cost on the v5e (my chip runs,
# PR 32: 16 384 tokens of 2 560 in bf16, 6 slots, 8 and 16 of 64 experts
# held): a gathered slot 49 ns a token, whatever holds a row there (0.8 ms
# a slot; six and their select-adds 4.8 ms a sum); a window of
# token_sum.WINDOW rows copied and placed 1.2 us (0.39 ms a sum without
# weights, 0.82 with, for 64 tiles x 8 experts).
SLOT_NS_A_TOKEN = 49.0
WINDOW_NS = 1200.0

SUM_SITE = "token-major sum"


def _sum_key(slots: int, held: int, width: int, dtype):
    return slots, held, width, jnp.dtype(dtype).name


def runs_why_not(tokens: int, slots: int, held: int, dim: int,
                 dtype) -> Optional[str]:
    """Why a token-major sum of these shapes takes one gather a slot and
    not the kernel over runs (token_sum_kernels.py); None where it takes
    the kernel. The kernel reads a window for every held expert and token
    tile, the gathers a row for every slot and token, held here or not:
    the kernel wins while ``held`` stays under about ten experts a slot
    (``TOKENS * SLOT_NS_A_TOKEN / WINDOW_NS`` = 10.4), if its windows fit
    VMEM. With 8 of 64 experts held and 6 slots it costs an eighth; the
    crossing, 62 experts for 6 slots, lies past the 16 whose windows fit
    at a width of 2 560."""
    why_not = token_sum.fits(held, dim, dtype)
    if why_not is None:
        windows = -(-tokens // token_sum.tokens_tile(tokens)) * held
        if windows * WINDOW_NS > tokens * slots * SLOT_NS_A_TOKEN:
            why_not = (f"{windows} windows cost more than {slots} gathers "
                       f"of {tokens} rows")
    return why_not


# What the two lowerings of tokens -> rows cost on the v5e (my chip runs,
# PR 49: scripts/rows_of_probe.py, seed 490001, 8 held experts, the four
# sparse cells' shapes; beside them the traced steps of
# lfm2moe-train-solo, seed 2147490011, and smallthinker21b-train-solo, seed
# 2147490022). One XLA gather with its mask, a KiB it writes: 4.68 ns at a
# width of 2 560 in bf16 and 4.68-4.70 in f32 alone, 4.7-4.8 in the step for
# the f32 cotangent (alone, a narrow bf16 one reads 3.0; in the step the
# forward's mask is a pass of its own and the two read 6.5 together: the
# rule takes the cheaper reading). The kernel, a KiB of the windows it
# writes (token tiles x held experts x token_sum.WINDOW rows, whatever the
# buffer's rows): 2.83-2.97 ns in bf16 (760 ns a window of 64 x 2 048, 907
# of 64 x 2 560; 566-758 and 904 in the step), 5.08 in f32 (the product at
# HIGHEST).
GATHER_NS_A_KIB = 4.7
ROWS_NS_A_KIB = {2: 2.9, 4: 5.1}

ROWS_SITE = "row gather"


def _rows_key(held: int, width: int, dtype, what: str):
    """``what``: "tokens" (the forward's and the replay's ``xs``) or
    "cotangent" (the backward's ``dy``)."""
    return held, width, jnp.dtype(dtype).name, what


def rows_why_not(tokens: int, rows: int, held: int, dim: int,
                 dtype) -> Optional[str]:
    """Why tokens of these shapes go to the buffer's rows by one XLA gather
    and not by the kernel over runs (``token_sum_kernels.rows_of``); None
    where they take the kernel. The kernel places and writes a window for
    every held expert and token tile, whatever the rows; the gather pays
    for every row it writes: the kernel wins where the buffer has more
    than about 0.6 rows (1.1 in f32) a row of windows, if the windows fit
    VMEM. With 8 experts held the 18 432 and 26 624 rows of 8 192 and
    16 384 tokens take it, 10 240 rows of 8 192 tokens by a hair, 6 144
    keep the gather."""
    dtype = jnp.dtype(dtype)
    why_not = token_sum.fits(held, dim, dtype)
    if why_not is None:
        windows = -(-tokens // token_sum.tokens_tile(tokens)) * held
        if windows * token_sum.WINDOW * ROWS_NS_A_KIB[dtype.itemsize] \
                > rows * GATHER_NS_A_KIB:
            why_not = (f"{windows} windows cost more than a gather of "
                       f"{rows} rows")
    return why_not


def held_key(idx: jax.Array, offset: int, held: int) -> jax.Array:
    """(tokens * k,) the held expert's local index of every assignment,
    ``held`` for an assignment to an expert that lives elsewhere."""
    local = idx.reshape(-1) - offset
    return jnp.where((local >= 0) & (local < held), local, held)


def group_sizes(key: jax.Array, held: int) -> jax.Array:
    return jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                   dtype=jnp.int32)


class _Plan(NamedTuple):
    """Where every assignment goes, as integer arrays (no gradient). The
    buffer's rows are laid out by ``grouped_matmul_kernels.tile_plan``:
    a held expert's assignments are contiguous and start at a multiple of
    the row tile. Slots are a token's k assignments."""
    token: jax.Array      # (rows,) the token a row computes
    slot: jax.Array       # (rows,) which of the token's k assignments
    valid: jax.Array      # (rows,) the row holds an assignment
    row: jax.Array        # (N, k) the row of a token's assignment
    here: jax.Array       # (N, k) that row exists (a held expert's)
    key: jax.Array        # (N, k) the assignment's held expert, or held
    sizes: jax.Array      # (held,) assignments to each held expert
    tiles: grouped.Tiles  # the row tiles' experts
    # what the token-major kernel reads (token_sum_kernels.py)
    row_of: jax.Array     # (N, held) a token's row in an expert's group, -1
    start: jax.Array      # (token tiles + 1, held) the tiles' runs of rows
    written: jax.Array    # () rows from here on are in no active tile


def _buffer_tiles(rows: int, held: int) -> int:
    """Row tiles of a buffer that holds ``rows`` assignments: a tile more
    an expert for the rounding."""
    return -(-rows // grouped.TILE) + held


def dispatch_plan(idx: jax.Array, offset: int, held: int,
                  rows: int) -> _Plan:
    """``rows``: assignments the buffer has to hold."""
    n, k = idx.shape
    tile = grouped.TILE
    n_tiles = _buffer_tiles(rows, held)
    key = held_key(idx, offset, held)
    sizes = group_sizes(key, held)
    order = jnp.argsort(key, stable=True)     # held experts' first, by expert
    rank = jnp.argsort(order)                 # the inverse permutation
    first_sorted = jnp.cumsum(sizes) - sizes
    first_row, tiles = grouped.tile_plan(sizes, n_tiles, tile)
    # assignment -> row. A table of ``held`` entries is read by a select a
    # held expert, and a row tile's expert is the same for its ``tile``
    # rows: an XLA gather of scalars costs the v5e 10-20 ns an element
    e = jnp.minimum(key, held - 1)
    shift = jnp.sum(jnp.where(e[:, None] == jnp.arange(held),
                              (first_row - first_sorted)[None, :], 0), axis=1)
    row = (shift + rank).reshape(n, k)
    here = (key.reshape(n, k) < held) & (row < n_tiles * tile)
    # row -> assignment
    of_tile = lambda table: jnp.repeat(table[tiles.expert], tile)
    within = jnp.arange(n_tiles * tile) - of_tile(first_row)
    valid = (jnp.repeat(tiles.active, tile) == 1) & (within < of_tile(sizes))
    a = order[jnp.clip(of_tile(first_sorted) + within, 0, n * k - 1)]
    # a slot with no row here is gathered all the same and masked after:
    # it reads a row of its own (one row for all of them is a hot spot:
    # 0.73 against 0.59 ms a gather on the v5e)
    elsewhere = (jnp.arange(n) % (n_tiles * tile))[:, None]
    # token -> its row in every held expert's group, and the token tiles'
    # runs: a group's rows ascend by token (the sort is stable, a token
    # names an expert once), so a tile of tokens owns a contiguous run
    key = key.reshape(n, k)
    mine = (key[:, :, None] == jnp.arange(held)) & here[:, :, None]
    row_of = jnp.max(jnp.where(mine, row[:, :, None], -1), axis=1)
    return _Plan(a // k, a % k, valid, jnp.where(here, row, elsewhere), here,
                 key, sizes, tiles, row_of, _run_starts(key, first_row),
                 jnp.sum(tiles.active) * tile)


def _run_starts(key: jax.Array, first_row: jax.Array) -> jax.Array:
    """(token tiles + 1, held): where every token tile's run of rows
    starts in every held expert's group. key: (N, k) from ``held_key``."""
    held = first_row.shape[0]
    return token_sum.run_starts(
        jnp.any(key[:, :, None] == jnp.arange(held), axis=1), first_row)


def _sum_over_slots(rows_of, plan: _Plan, weight=None):
    """(N, D) f32: every token's sum over its assignments computed here of
    [weight x] the row that computed it, one gather of N rows a slot (a
    slot with no row here reads a row of its own and is masked). Rows that
    hold no assignment are never read (the grouped products leave rows
    outside every group unwritten)."""
    total = 0.0
    for j in range(plan.row.shape[1]):
        picked = jnp.where(plan.here[:, j, None], rows_of[plan.row[:, j]],
                           0).astype(jnp.float32)
        total = total + (picked if weight is None
                         else picked * weight[:, j, None])
    return total


def _sum_to_tokens(rows_of, plan: _Plan, weight=None, dtype=jnp.float32):
    """(N, D) ``dtype``: the same sum by the lowering its shapes choose
    (:func:`runs_why_not`): where the kernel over runs, a tile of tokens
    reads its run of rows in every held expert's group, each row once."""
    (n, k), held = plan.row.shape, plan.row_of.shape[1]
    why_not = runs_why_not(n, k, held, rows_of.shape[1], rows_of.dtype)
    # a cost rule and no gate: the sum is traced only under the grouped
    # products, which have one. Remembered, not said
    key = _sum_key(k, held, rows_of.shape[1], rows_of.dtype)
    lowering.record(SUM_SITE, key, why_not, tile=token_sum.tokens_tile(n))
    with lowering.traced(SUM_SITE, key):
        if why_not is not None:
            return _sum_over_slots(rows_of, plan, weight).astype(dtype)
        if weight is not None:
            weight = jnp.sum(jnp.where(
                plan.key[:, :, None] == jnp.arange(held),
                weight[:, :, None], 0.0), axis=1)
        return token_sum.token_major_sum(
            rows_of, plan.row_of, plan.start, plan.written, weight,
            out_dtype=dtype, interpret=lowering.interpret())


def _rows_of(source, plan: _Plan):
    """(rows, D): ``source[plan.token]`` where the row holds an assignment,
    zero elsewhere."""
    return jnp.where(plan.valid[:, None], source[plan.token], 0)


def _to_rows(source, plan: _Plan, what: str, **facts):
    """(rows, D) in ``source``'s dtype: :func:`_rows_of` by the lowering its
    shapes choose (:func:`rows_why_not`): where the kernel over runs, a
    tile of tokens is read once and writes its run of rows in every held
    expert's group. The kernel leaves the rows of inactive row tiles
    (``plan.written`` on) unwritten: the grouped products skip them, the
    token-major kernel zeroes them and ``score`` masks them."""
    (n, held), rows = plan.row_of.shape, plan.token.shape[0]
    why_not = rows_why_not(n, rows, held, source.shape[1], source.dtype)
    # a cost rule and no gate, as the sums'
    key = _rows_key(held, source.shape[1], source.dtype, what)
    lowering.record(ROWS_SITE, key, why_not, **facts)
    with lowering.traced(ROWS_SITE, key):
        if why_not is not None:
            return _rows_of(source, plan)
        return token_sum.rows_of(
            source, plan.row_of, plan.start, plan.written, rows=rows,
            interpret=lowering.interpret())


def grouped_kernels_why_not(dim: int, width: int) -> Optional[str]:
    """Why the grouped Pallas products cannot take experts of ``dim`` x
    ``width``; None where they can (interpreted, any size)."""
    # a width may end in half a lane tile (1 856 = 14.5 x 128): every block
    # of the grouped kernels spans its array's whole minor dimension, and
    # Mosaic masks the last tile's upper lanes. No leaf is padded
    if (dim % 128 or width % 128 not in (0, 64)) \
            and not lowering.interpret():
        return f"{dim} x {width} are not lane tiles"
    return None


def _block_key(dim: int, width: int, dtype, gated: bool = True):
    """What the record knows the expert block's form by: what
    ``grouped.block_why_not`` chose it from."""
    return (dim, width, jnp.dtype(dtype).name) + ("ungated",) * (not gated)


def _three_products(first: jax.Array, gated: bool = True) -> Optional[str]:
    """Why these (held, D, F) experts' block runs as its products (three a
    direction, two where not ``gated``) with XLA code between them; None
    where its tile work is in the kernels."""
    return grouped.block_why_not(*first.shape[1:], first.dtype, gated)


BLOCK_ON_THE_TILE = ("gate, up and activation one kernel; cotangents on the "
                     "tile; one dxs; inactive tiles unmoved")
UNGATED_ON_THE_TILE = ("two products an expert, not gated: up and the "
                       "activation one kernel; the cotangent on the tile; "
                       "inactive tiles unmoved")


def _block_words(why_not: Optional[str], gated: bool = True) -> str:
    if not gated:
        return (f"two products a direction, not gated ({why_not})"
                if why_not else UNGATED_ON_THE_TILE)
    return (f"three products a direction ({why_not})" if why_not
            else BLOCK_ON_THE_TILE)


def _grouped(plan: _Plan):
    """How every grouped kernel of a call is run: the plan's tiles."""
    return dict(tiles=plan.tiles, tile=grouped.TILE,
                interpret=lowering.interpret())


class _Kept(NamedTuple):
    """What the sorted lowering keeps for its backward pass."""
    plan: _Plan
    xs: jax.Array         # (rows, D) the rows' tokens
    gate: Any             # (rows, F) xs . W_gate; () of ungated experts
    up: jax.Array         # (rows, F) xs . W_up
    ys: jax.Array         # (rows, D) the experts' outputs


def _sorted_forward(m, idx, p, *weights, offset: int, rows: int, act: str):
    """The held experts' part of the layer for assignments sorted by
    expert. m: (N, D); idx, p: (N, k); weights: gate, up (E_h, D, F) and
    down (E_h, F, D), or up and down alone of experts that are not gated.
    Returns (((N, D) f32, assignments computed), _Kept)."""
    *gate, up, down = weights
    with jax.named_scope("dispatch"):
        plan = dispatch_plan(idx, offset, up.shape[0], rows)
        xs = _to_rows(m, plan, "tokens")
    with jax.named_scope("experts"):
        how = _grouped(plan)
        if not gate:
            g = ()
            if _three_products(up, gated=False):
                u = grouped.grouped_matmul(xs, up, **how)
                hidden = grouped.act(act, u)
            else:
                u, hidden = grouped.hidden(xs, up, name=act, **how)
        elif _three_products(gate := gate[0]):
            g = grouped.grouped_matmul(xs, gate, **how)
            u = grouped.grouped_matmul(xs, up, **how)
            hidden = grouped.act(act, g) * u
        else:
            g, u, hidden = grouped.gated_hidden(xs, gate, up, name=act, **how)
        ys = grouped.grouped_matmul(hidden, down, **how)
    with jax.named_scope("combine"):
        y = _sum_to_tokens(ys, plan, p)
    return ((y, jnp.sum(plan.valid, dtype=jnp.float32)),
            _Kept(plan, xs, g, u, ys))


def _sorted_backward(kept: _Kept, p, weights, dy, act: str, stated: bool):
    """Cotangents of (m, p, *weights) from what the forward kept:
    no product and no gather of the forward pass is run again. ``dy`` moves
    to the rows in the dtype it comes in and is widened there (``stated``:
    the caller said its numbers are that dtype's, for the record)."""
    plan, xs, g, u, ys = kept
    *gate, up, down = weights
    with jax.named_scope("combine"):
        weight = jnp.where(plan.valid, p[plan.token, plan.slot], 0.0)
        dy_rows = _to_rows(dy, plan, "cotangent",
                           stated=stated).astype(jnp.float32)
        dys = (dy_rows * weight[:, None]).astype(ys.dtype)
        # a routing weight's cotangent is its row's <dy, ys>: taken on the
        # rows, then one scalar a slot (not one row a slot)
        score = jnp.sum(jnp.where(plan.valid[:, None],
                                  dy_rows * ys.astype(jnp.float32), 0.0),
                        axis=-1)
        dp = jnp.where(plan.here, score[plan.row], 0.0)
    how = _grouped(plan)
    if not gate:
        with jax.named_scope("experts"):
            if _three_products(up, gated=False):
                hidden = grouped.act(act, u)
                dhidden, ddown = grouped.grouped_matmul_grads(
                    hidden, down, dys, **how)
                du = grouped.act_cotangent(act, u, dhidden)
            else:
                du, hidden = grouped.hidden_grads(dys, down, u, name=act,
                                                  **how)
                ddown = grouped.weights_grad(hidden, dys, like=down, **how)
            dup = grouped.weights_grad(xs, du, like=up, **how)
            dxs = grouped.grouped_matmul(du, up, transpose_w=True, **how)
        with jax.named_scope("dispatch"):
            return _sum_to_tokens(dxs, plan, dtype=xs.dtype), dp, dup, ddown
    if _three_products(gate := gate[0]):
        with jax.named_scope("experts"):
            grads = functools.partial(grouped.grouped_matmul_grads, **how)
            hidden = grouped.act(act, g)
            dhidden, ddown = grads(hidden * u, down, dys)
            dxs_gate, dgate = grads(
                xs, gate, grouped.gate_cotangent(act, g, u, dhidden))
            dxs_up, dup = grads(xs, up, dhidden * hidden)
        with jax.named_scope("dispatch"):
            dxs = dxs_gate + dxs_up
    else:
        with jax.named_scope("experts"):
            dg, du, hidden = grouped.gated_hidden_grads(dys, down, g, u,
                                                        name=act, **how)
            dgate, dup, ddown = (
                grouped.weights_grad(x, dy, like=w, **how)
                for x, dy, w in ((xs, dg, gate), (xs, du, up),
                                 (hidden, dys, down)))
            dxs = grouped.rows_grad(dg, du, gate, up, **how)
    with jax.named_scope("dispatch"):
        dm = _sum_to_tokens(dxs, plan, dtype=xs.dtype)
    return dm, dp, dgate, dup, ddown


def _every_expert(m, idx, p, *weights, offset: int, act: str):
    """The same sum with no dispatch: every held expert on every token,
    times its routing weight (0 for a token not routed to it)."""
    held = weights[0].shape[0]

    def one(y, xs):
        e, *w_gate, w_up, w_down = xs
        with jax.named_scope("combine"):
            weight = jnp.sum(jnp.where(idx == e + offset, p, 0.0), axis=1)
        with jax.named_scope("experts"):
            if w_gate:
                hidden = grouped.act(act, jnp.dot(m, w_gate[0])) \
                    * jnp.dot(m, w_up)
            else:
                hidden = grouped.act(act, jnp.dot(m, w_up))
            out = jnp.dot(hidden, w_down)
        with jax.named_scope("combine"):
            return y + out.astype(jnp.float32) * weight[:, None], None

    y, _ = jax.lax.scan(one, jnp.zeros(m.shape, jnp.float32),
                        (jnp.arange(held), *weights))
    here = jnp.sum(held_key(idx, offset, held) < held, dtype=jnp.float32)
    return y, here


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _held_experts(offset, rows, act, rounded_to, m, idx, p, weights):
    return _held_experts_fwd(offset, rows, act, rounded_to, m, idx, p,
                             weights)[0]


def _held_experts_fwd(offset, rows, act, rounded_to, m, idx, p, weights):
    # One conditional a direction, written out: lax.cond's own derivative
    # would return both lowerings' residuals from the forward conditional
    # and run neither backward without them. Here the sorted lowering
    # returns what its backward reads (_Kept); the dense one, taken when a
    # step's assignments do not fit, hands back zeros of those shapes and
    # is computed again in the backward pass.
    operands = (m, idx, p, *weights)
    held = weights[0].shape[0]
    sorted_ = functools.partial(_sorted_forward, offset=offset, rows=rows,
                                act=act)
    like = jax.eval_shape(sorted_, *operands)[1]

    def dense(*operands):
        return (_every_expert(*operands, offset=offset, act=act),
                jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), like))

    fits = jnp.sum(held_key(idx, offset, held) < held) <= rows
    out, kept = jax.lax.cond(fits, sorted_, dense, *operands)
    return out, (fits, kept, operands)


def _held_experts_bwd(offset, rows, act, rounded_to, res, cotangent):
    fits, kept, (m, idx, p, *weights) = res
    dy = cotangent[0]
    if rounded_to is not None:
        # the caller rounds the result to this dtype before anything reads
        # it, so what comes back are its numbers, widened: exact
        dy = dy.astype(rounded_to)

    def dense(kept, m, p, *rest):
        *w, dy = rest
        _, vjp = jax.vjp(lambda m, p, *w: _every_expert(
            m, idx, p, *w, offset=offset, act=act)[0], m, p, *w)
        return vjp(dy.astype(jnp.float32))

    def sorted_(kept, m, p, *rest):
        *w, dy = rest
        return _sorted_backward(kept, p, w, dy, act,
                                stated=rounded_to is not None)

    dm, dp, *dw = jax.lax.cond(fits, sorted_, dense, kept, m, p, *weights,
                               dy)
    return (dm, np.zeros(idx.shape, jax.dtypes.float0), dp, tuple(dw))


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


PRODUCTS_SITE = "expert products"


def held_experts(m, idx, p, *weights, offset: int, rows: int,
                 act: str = "relu", rounded_to=None):
    """The held experts' part of the layer: the sorted lowering where the
    step's assignments to held experts fit ``rows``, the dense one where
    they do not, chosen on the device by the count; the dense one alone
    where the grouped kernels cannot run. Returns ((N, D) f32, the
    assignments computed). ``weights``: gate, up and down, or up and down
    alone of experts that are not gated (``act`` then stands between the
    two products).

    ``rounded_to``: a fact the caller states, not a request: the dtype it
    rounds the (N, D) result to (after any sums in f32) before anything
    else reads it. The result's cotangent then holds numbers of that dtype,
    widened, and the sorted lowering moves it to the rows in that dtype
    (half the bytes for a 16-bit one) and widens it there: the same
    cotangents, bit for bit. A caller that states nothing (None) keeps the
    f32 movement. The forward's arithmetic is the same either way."""
    gated = len(weights) == 3

    def fits(m, idx, p, first, *others) -> bool:
        why_not = grouped_kernels_why_not(m.shape[1], first.shape[2])
        words = why_not
        if why_not is None:
            # the block's form, which the sorted lowering asks again of the
            # same shapes: remembered for ``moe_layout``
            three = _three_products(first, gated)
            lowering.record(
                PRODUCTS_SITE,
                _block_key(*first.shape[1:], first.dtype, gated), three)
            words = (f"{rows} rows in tiles of {grouped.TILE}, "
                     f"{first.shape[0]} experts of {m.shape[1]} x "
                     f"{first.shape[2]}, " + _block_words(three, gated))
        return lowering.chose(PRODUCTS_SITE, (rows, *first.shape), why_not,
                              words)

    kernels_ = functools.partial(
        _held_experts, offset, rows, act,
        None if rounded_to is None else jnp.dtype(rounded_to))
    return lowering.site(
        PRODUCTS_SITE, fits,
        lambda m, idx, p, *weights: kernels_(m, idx, p, weights),
        functools.partial(_every_expert, offset=offset, act=act))(
            m, idx, p, *weights)


class ExpertWeights(nn.Module):
    """The held experts' weights, stacked on a leading axis: leaves
    ``.../experts/{gate,up,down}`` (LAMB: one trust ratio an expert), ``up``
    and ``down`` alone where the experts are not gated."""
    cfg: SparseLMConfig

    @nn.compact
    def __call__(self):
        cfg = self.cfg
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,))
        pdt, dt = jnp.dtype(cfg.param_dtype), jnp.dtype(cfg.dtype)
        d, f, e = cfg.hidden_size, cfg.expert_width, cfg.experts_held
        leaves = (("gate", (e, d, f)), ("up", (e, d, f)),
                  ("down", (e, f, d)))[not cfg.expert_gated:]
        out = out_proj_init(cfg, in_axis=-2, out_axis=-1, batch_axis=(0,))
        return tuple(
            self.param(name, out.get("kernel_init", init)
                       if name == "down" else init, shape, pdt).astype(dt)
            for name, shape in leaves)


class GatedBlock(nn.Module):
    """``W_down(act(W_gate m) * W_up m)`` of one width on every token: a
    dense layer's feed-forward and an expert layer's shared expert.
    Ordinary leaves ``.../{gate,up,down}/kernel``."""
    cfg: SparseLMConfig
    width: int

    @nn.compact
    def __call__(self, m: jax.Array) -> jax.Array:
        cfg = self.cfg
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=jnp.dtype(cfg.dtype),
            param_dtype=jnp.dtype(cfg.param_dtype))
        hidden = grouped.act(cfg.hidden_act,
                             dense(self.width, name="gate")(m)) \
            * dense(self.width, name="up")(m)
        return dense(cfg.hidden_size, name="down")(hidden)


class UngatedBlock(nn.Module):
    """``W_down act(W_up m)`` of one width on every token (``act``: the
    square of ReLU): the shared expert of experts that are not gated.
    Ordinary leaves ``.../{up,down}/kernel``."""
    cfg: SparseLMConfig
    width: int

    @nn.compact
    def __call__(self, m: jax.Array) -> jax.Array:
        cfg = self.cfg
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=jnp.dtype(cfg.dtype),
            param_dtype=jnp.dtype(cfg.param_dtype))
        hidden = grouped.act(cfg.hidden_act, dense(self.width, name="up")(m))
        return dense(cfg.hidden_size, name="down", **out_proj_init(cfg))(
            hidden)


class DenseFF(nn.Module):
    """A dense layer's feed-forward, under the scope ``ff/dense``."""
    cfg: SparseLMConfig

    @nn.compact
    def __call__(self, m: jax.Array) -> jax.Array:
        return GatedBlock(self.cfg, self.cfg.dense_width, name="dense")(m)


class ExpertLayer(nn.Module):
    cfg: SparseLMConfig

    def setup(self):
        cfg = self.cfg
        pdt = jnp.dtype(cfg.param_dtype)
        self.router = self.param(
            "router", nn.initializers.lecun_normal(),
            (cfg.hidden_size, cfg.num_experts), pdt)
        if cfg.selection_bias:
            # moves which experts are chosen, not their weights; no
            # gradient reaches it (the source steps it by the sign of each
            # expert's token count: not in this program yet)
            self.router_bias = self.param(
                "router_bias", nn.initializers.zeros, (cfg.num_experts,),
                pdt)
        self.experts = ExpertWeights(cfg)
        if cfg.num_shared_experts:
            block = GatedBlock if cfg.expert_gated else UngatedBlock
            self.shared = block(cfg, cfg.shared_width)
        if cfg.shared_expert_gate:
            self.shared_gate = self.param(
                "shared_gate", nn.initializers.normal(
                    stddev=cfg.hidden_size ** -0.5), (cfg.hidden_size,), pdt)

    def route(self, a: jax.Array):
        """Top-k of the router's f32 scores of its normed input (the
        layer's, or the post-attention one: ``cfg.router_input``): (B, T,
        k) expert ids and weights. ``softmax``: the largest scores and
        their softmax; ``sigmoid``: the largest of sigmoid(score) + bias,
        weighted by their sigmoids alone, normalised (``route_norm``) and
        times ``route_scale``."""
        # flax names this call's scope ``ff.route``: the router's
        # operations are put under ``ff/router`` by hand, beside the rest
        # of the layer's
        with jax.named_scope("ff"), jax.named_scope("router"):
            scores = jnp.einsum(
                "btd,de->bte", a.astype(jnp.float32),
                self.router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            cfg = self.cfg
            if cfg.score_func == "softmax":
                # the weights are top_k's own values, so a replay takes it
                # again whatever is kept: these sets are not kept (kept
                # beside the replay's top_k the step was 0.7% slower on the
                # v5e, read back through a gather 4.3%:
                # smallthinker21b-train-solo, PR 44)
                top, idx = jax.lax.top_k(scores, cfg.experts_per_token)
                return idx, jax.nn.softmax(top, axis=-1)
            scores = jax.nn.sigmoid(scores)
            select = scores
            if cfg.selection_bias:
                select = scores + jax.lax.stop_gradient(
                    self.router_bias.astype(jnp.float32))
            _, idx = jax.lax.top_k(select, cfg.experts_per_token)
            idx = checkpoint_name(idx, "chosen")
            top = jnp.take_along_axis(scores, idx, axis=-1)
            if cfg.route_norm:
                top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
            return idx, top * cfg.route_scale

    def __call__(self, m: jax.Array, idx: jax.Array, p: jax.Array):
        cfg = self.cfg
        b, t, d = m.shape
        rows = dispatch_rows(b * t, cfg)
        y, computed = held_experts(
            m.reshape(b * t, d), idx.reshape(b * t, -1),
            p.reshape(b * t, -1), *self.experts(),
            offset=cfg.expert_offset, rows=rows, act=cfg.hidden_act,
            rounded_to=m.dtype)     # this call's last line
        with jax.named_scope("router"):
            key = held_key(idx.reshape(b * t, -1), cfg.expert_offset,
                           cfg.experts_held)
            counts = group_sizes(key, cfg.experts_held)
            sizes = counts.astype(jnp.float32)
            here = jnp.sum(sizes)
            # what held_experts just chose for this call's rows and
            # its (held, D, F) experts
            sorted_ = lowering.why_not(PRODUCTS_SITE, (
                rows, cfg.experts_held, d, cfg.expert_width)) is None
            dense = (here > rows) | (not sorted_)
            # of the sorted lowering: the share of its grid's row tiles
            # that hold rows (the grouped kernels move nothing for the
            # rest), and the runs of its token-major kernel that pass
            # their first window (none where the sums are gathered); a
            # dense call has neither
            active, spills = 0.0, 0.0
            if sorted_:
                first_row, tiles = grouped.tile_plan(
                    counts, _buffer_tiles(rows, cfg.experts_held))
                active = jnp.where(dense, 0, jnp.mean(
                    tiles.active.astype(jnp.float32)))
                if runs_why_not(b * t, cfg.experts_per_token,
                                cfg.experts_held, d, m.dtype) is None:
                    spills = jnp.where(dense, 0, token_sum.spills(
                        _run_starts(key.reshape(b * t, -1), first_row))
                    ).astype(jnp.float32)
            counters = {
                "here": here / (b * t * cfg.experts_per_token),
                "load": jnp.max(sizes) / jnp.maximum(jnp.mean(sizes), 1e-9),
                "dense": dense.astype(jnp.float32),
                "dropped": here - computed,
                "spills": spills,
                "tiles_active": active}
        if cfg.num_shared_experts:
            shared = self.shared(m).reshape(b * t, d)
            if cfg.shared_expert_gate:
                # sigmoid(w_g . m) a token, on the shared expert alone
                with jax.named_scope("shared"):
                    gate = jax.nn.sigmoid(jnp.dot(
                        m.reshape(b * t, d), self.shared_gate.astype(m.dtype),
                        preferred_element_type=jnp.float32))
                    shared = gate[:, None] * shared
            y = y + shared
        return y.reshape(b, t, d).astype(m.dtype), counters


class Layer(nn.Module):
    """One layer: the operator its ``kind`` names (a softmax attention
    under ``attn``, or the gated short convolution under ``conv``) and a
    feed-forward; ``dense``: that is the dense gated block (no router, no
    counters). With ``cfg.sandwich_norms`` the attention's
    and the feed-forward's results are normed before they join the
    residual: four norms a layer."""
    cfg: SparseLMConfig
    kind: str
    mesh: Any = None
    dense: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, positions=None):
        cfg = self.cfg
        norm = lambda name, v: rms_norm(
            v, self.param(name, nn.initializers.ones, (cfg.hidden_size,),
                          jnp.dtype(cfg.param_dtype)), cfg.rms_eps)
        ff = (DenseFF if self.dense else ExpertLayer)(cfg, name="ff")
        early = not self.dense and cfg.router_input == "input_norm"
        a = norm("attn_norm", x)
        if early:
            idx, p = ff.route(a)
        if self.kind == LAYER_SHORT_CONV:
            y = ShortConv(cfg, name="conv")(a)
        elif self.kind == LAYER_GATED_DELTA:
            y = GatedDeltaMixer(cfg, self.mesh, name="gdn")(a)
        elif self.kind == LAYER_FULL_ROPE and cfg.kv_lora_rank:
            y = LatentAttention(cfg, self.mesh, name="attn")(a)
        elif self.kind == LAYER_SELECTED_ROPE:
            y, indexer = Attention(cfg, self.kind, self.mesh, name="attn")(
                a, positions)
        else:
            y = Attention(cfg, self.kind, self.mesh, name="attn")(
                a, *[positions] * bool(cfg.mrope_section))
        if cfg.sandwich_norms:
            y = norm("post_attn_norm", y)
        h = x + y
        m = norm("ff_norm", h)
        if self.dense:
            y, counters = ff(m), None
        else:
            if not early:
                idx, p = ff.route(m)
            # for whoever asks (apply(..., mutable=["intermediates"])):
            # the experts every token chose; nothing is kept otherwise
            self.sow("intermediates", "chosen", idx)
            y, counters = ff(m, idx, p)
            if self.kind == LAYER_SELECTED_ROPE:
                counters = {**counters, **indexer}
        if cfg.sandwich_norms:
            y = norm("post_ff_norm", y)
        return h + y, counters


class OnePartLayer(nn.Module):
    """One layer of a configuration with ``one_part_layers``: ``x +
    part(rmsnorm(x))``, the part its ``kind`` names and nothing else: a
    state-space mixer under ``ssm``, a softmax attention under ``attn``, or
    the expert feed-forward under ``ff`` (``experts``: the only kind with a
    router and counters). One norm, ``norm``."""
    cfg: SparseLMConfig
    kind: str
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.cfg
        a = rms_norm(x, self.param("norm", nn.initializers.ones,
                                   (cfg.hidden_size,),
                                   jnp.dtype(cfg.param_dtype)), cfg.rms_eps)
        if self.kind == LAYER_EXPERTS:
            ff = ExpertLayer(cfg, name="ff")
            idx, p = ff.route(a)
            # as ``Layer``: the experts every token chose, for whoever asks
            self.sow("intermediates", "chosen", idx)
            y, counters = ff(a, idx, p)
            return x + y, counters
        if self.kind == LAYER_MAMBA2:
            return x + Mamba2Mixer(cfg, self.mesh, name="ssm")(a), None
        return x + Attention(cfg, self.kind, self.mesh, name="attn")(a), None


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

# What a rematerialised layer keeps besides its input: its attention's
# output and row statistics (the backward pass replays the projections and
# the experts, not the attention kernel), of a gated-delta mixer what its
# rule's kernel made and read (``o``, the state a grid step, every chunk's
# inverse, the normalised ``q`` and ``k`` and the two rows:
# ``delta_rule_kernels.kept_bytes``, 258 MiB a sample and layer at
# ``qwen3next80b``'s sizes, where the replay of the forward kernel was 2.07
# ms a layer and micro-step and of the XLA code around it 0.6; PERF.md
# section 6, PR 66) and, of a sigmoid router, the experts its tokens chose,
# (B, T, k) int32. A compiler may fuse and round
# the replay's scores otherwise than the forward pass's, and a top-k taken
# again then flips near-ties, so that the backward pass differentiates
# other experts than the forward pass ran: XLA's CPU backend does (the
# routed leaves a few times further from the reference at the program's
# own sets), the v5e's did not at the cell's size (PERF.md section 6, PR
# 44). Kept, the sets a forward-and-backward program returns are the ones
# it differentiates, whatever the compiler.
KEPT_OF_A_LAYER = ("attn_out", "attn_stats", "chosen",
                   *delta_rule_kernels.KEPT)

HEAD_SITE = "streamed head"


def _head_key(hidden: int, vocab: int, n_sums: int, tied: bool, dtype):
    """What the record knows a call of the streamed head by: the widths, its
    columns of weights (two of the main loss, one of the prediction
    module's) and the operands' form; the rows are a fact of the call."""
    return hidden, vocab, n_sums, tied, jnp.dtype(dtype).name


def head_layout(cfg: SparseLMConfig) -> str:
    """The ``setup/warmup`` row's ``head_layout``: which of the
    configuration's calls of the streamed head (the main loss's, a
    prediction module's) traced the rule that makes the gradients with the
    loss, in what chunks, and what carried ``dW``'s sum."""
    calls = {"main": 2, "mtp": 1} if cfg.num_nextn_predict_layers else {
        "main": 2}
    exits = cfg.total_ut_steps
    if exits > 1:       # one column of weights: the exit distribution
        calls = {f"main: the {exits} exits' rows in one call under their "
                 "exit weights, which are differentiated": 1}
    made = {name: said for name, n_sums in calls.items()
            if (said := lowering.recorded(HEAD_SITE, _head_key(
                cfg.hidden_size, cfg.vocab_size, n_sums,
                cfg.tied_embeddings, cfg.dtype)))}
    words = f"gradients made with the loss: {len(made)} of {len(calls)} calls"
    if not made:
        return words
    # the calls share their rows and the chunk: one says it
    said = next(iter(made.values()))
    return words + (f" ({', '.join(made)}), {said['chunks']} chunks of "
                    f"{said['rows']} rows, dW added in float32 and carried "
                    f"in {said['carried']}")


def _streamed_nll(h, kernel, targets, weights, chunk: int,
                  tied: bool = False, rows: bool = False):
    """``(total, sums)``: the sums of ``weights`` x next-token negative
    log-likelihood over the rows of ``h`` (N, D), a sum a column of
    ``weights`` (N, n_sums), and their total, ``chunk`` rows of the (N, V)
    logits alive at a time and none kept. kernel: the head (D, V), or with
    ``tied`` the embedding's table (V, D), contracted over its second axis
    where it lies: no transposed copy stands beside it over the scan.

    ``total`` is what a caller differentiates; ``sums`` are reported
    (``loss_text`` / ``loss_img``), and a derivative that reaches them is
    refused. Differentiated, the one scan makes the gradients with the
    loss: a chunk's ``dlogits`` of a unit cotangent from the logits it has
    in hand, its rows of ``dx`` and its term of ``dW``, so the logits are
    multiplied once and the backward pass scales two arrays by the
    cotangent that arrives and multiplies nothing. ``dW``'s sum over the
    chunks is carried in the head's dtype, as the transposed scan carried
    it, and a chunk's term is added to it in f32 (an f32 carry's bytes are
    not hidden behind the product: 0.5 ms a chunk at 2 560 x 18 992). The
    products are autodiff's: f32 ``dlogits`` against the operands as they
    lie, which a TPU's default precision rounds to their dtype in the unit,
    as it rounded the transposed scan's.

    ``rows``: ``(total, sums, nll)`` with every row's negative
    log-likelihood (N,) as a value, and ``weights`` differentiated: a
    caller whose weights depend on parameters (a looped stack's exit
    distribution, known before the logits are) gets ``d total / d weights =
    nll`` from the rows the scan wrote, and still nothing of (rows,
    vocabulary) outlives its chunk. The rows themselves are reported, as
    ``sums`` are."""
    n, dims = h.shape[0], (((1,), (1 if tied else 0,)), ((), ()))
    split = lambda x: jnp.pad(
        x, ((0, -n % chunk),) + ((0, 0),) * (x.ndim - 1)).reshape(
            -1, chunk, *x.shape[1:])

    def nll_of(hc, kernel, tc):
        with jax.named_scope("head"):
            logits = jax.lax.dot_general(
                hc, kernel, dims, preferred_element_type=jnp.float32)
        with jax.named_scope("ce"):
            lse = jax.nn.logsumexp(logits, axis=-1)
            return logits, lse, lse - jnp.take_along_axis(
                logits, tc[:, None], axis=-1)[:, 0]

    def add(sums, nll, wc):
        with jax.named_scope("ce"):
            return sums + jnp.sum(nll[:, None] * wc, axis=0)

    zeros = lambda: jnp.zeros(weights.shape[1:], jnp.float32)

    @jax.custom_vjp
    def scan(h, kernel, targets, weights):
        def body(sums, xs):
            hc, tc, wc = xs
            nll = nll_of(hc, kernel, tc)[2]
            return add(sums, nll, wc), (nll if rows else None)

        sums, nll = jax.lax.scan(body, zeros(),
                                 (split(h), split(targets), split(weights)))
        out = jnp.sum(sums), sums
        return (*out, nll.reshape(-1)[:n]) if rows else out

    def forward(h, kernel, targets, weights):
        h, kernel, targets, weights = (
            x.value for x in (h, kernel, targets, weights))

        def body(carry, xs):
            (sums, dw), (hc, tc, wc) = carry, xs
            logits, lse, nll = nll_of(hc, kernel, tc)
            with jax.named_scope("ce"):
                hot = tc[:, None] == jax.lax.broadcasted_iota(
                    tc.dtype, logits.shape, 1)
                dlogits = (jnp.exp(logits - lse[:, None]) - hot) * jnp.sum(
                    wc, axis=1, keepdims=True)
                # written once for both products: left to the compiler,
                # each product's fusion makes its own from the logits
                # (0.2 ms a product more at 2 048 x 18 992)
                dlogits = jax.lax.optimization_barrier(dlogits)
            with jax.named_scope("head"):
                dx = jax.lax.dot_general(
                    dlogits, kernel, (((1,), (0 if tied else 1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dw = (dw + jax.lax.dot_general(
                    *((dlogits, hc) if tied else (hc, dlogits)),
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)).astype(dw.dtype)
            return (add(sums, nll, wc), dw), ((dx, nll) if rows else dx)

        (sums, dw), made = jax.lax.scan(
            body, (zeros(), jnp.zeros_like(kernel)),
            (split(h), split(targets), split(weights)))
        dx, nll = (made[0], (made[1].reshape(-1)[:n],)) if rows else (
            made, ())
        lowering.record(
            HEAD_SITE, _head_key(h.shape[1], kernel.shape[0 if tied else 1],
                                 weights.shape[1], tied, h.dtype),
            None, chunks=dx.shape[0], rows=chunk, carried=dw.dtype.name)
        return (jnp.sum(sums), sums, *nll), (
            dx.reshape(-1, h.shape[1])[:n], dw, *nll)

    def backward(made, cotangents):
        c, *reported = cotangents
        if not all(isinstance(of, SymbolicZero) for of in reported):
            raise TypeError(
                "the streamed head differentiates its total, one cotangent "
                "for every column of weights; a derivative reached its "
                "sums, which are reported and not differentiated")
        if isinstance(c, SymbolicZero):
            return None, None, None, None
        dx, dw, *nll = made
        # the scalar before the one rounding to the operands' dtype
        return ((dx * c).astype(h.dtype), (dw * c).astype(kernel.dtype),
                None, jnp.broadcast_to((nll[0] * c)[:, None], weights.shape)
                if rows else None)

    scan.defvjp(forward, backward, symbolic_zeros=True)
    return scan(h, kernel, targets, weights)


class PredictionModule(nn.Module):
    """One more expert layer that reads the main model's normed last state
    ``z`` and the NEXT token's embedding (module docstring); returns its own
    normed state, which the caller puts through the shared head, and the
    block's counters."""
    cfg: SparseLMConfig
    layer_cls: Any
    mesh: Any = None

    @nn.compact
    def __call__(self, z: jax.Array, next_emb: jax.Array):
        cfg = self.cfg
        dt, pdt = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        scale = lambda name: self.param(name, nn.initializers.ones,
                                        (cfg.hidden_size,), pdt)
        with jax.named_scope("norms"):
            both = jnp.concatenate(
                [rms_norm(next_emb, scale("enorm"), cfg.rms_eps),
                 rms_norm(z, scale("hnorm"), cfg.rms_eps)], axis=-1)
        # W_eh makes the module's input of an embedding and a state: its
        # product counts with the embedding's (``embed_share_pct``)
        with jax.named_scope("embed"):
            x = nn.Dense(cfg.hidden_size, use_bias=False, dtype=dt,
                         param_dtype=pdt, name="proj")(both)
        x, counters = self.layer_cls(cfg, LAYER_FULL_ROPE, self.mesh,
                                     name="block")(x)
        return rms_norm(x, scale("final_norm"), cfg.rms_eps), counters


def remat_layer(cfg: SparseLMConfig):
    """The configuration's layer class, rematerialised but for
    ``KEPT_OF_A_LAYER``."""
    return nn.remat(
        OnePartLayer if cfg.one_part_layers else Layer,
        policy=jax.checkpoint_policies.save_only_these_names(
            *KEPT_OF_A_LAYER))


@jax.custom_vjp
def _leaves_with_state(x, leaves):
    """``(x, leaves)`` as they are. Backward, the state's cotangent and the
    leaves' leave together or not at all."""
    return x, leaves


_leaves_with_state.defvjp(
    lambda x, leaves: ((x, leaves), None),
    lambda _, cotangents: jax.lax.optimization_barrier(cotangents))


class LoopedStack:
    """The stack of a configuration with ``total_ut_steps`` > 1 as plain
    functions of its leaves (``layer_<i>/...``, ``final_norm``: the tree
    :class:`SparseLM` holds under ``passes``): :meth:`init` draws them,
    :meth:`one_pass` is the body every pass runs. Every pass runs THESE
    leaves, so a leaf's gradient is the sum over the passes."""

    def __init__(self, cfg: SparseLMConfig, mesh=None):
        self.cfg = cfg
        layer_cls = remat_layer(cfg)
        self.layers = {f"layer_{i}": layer_cls(
            cfg, cfg.kind_of_layer(i), mesh, cfg.layer_is_dense(i))
            for i in range(cfg.num_hidden_layers)}

    def init(self, rng: jax.Array, x: jax.Array):
        keys = jax.random.split(rng, len(self.layers))
        leaves = {name: layer.init(key, x)["params"] for key, (name, layer)
                  in zip(keys, self.layers.items())}
        leaves["final_norm"] = jnp.ones((self.cfg.hidden_size,),
                                        jnp.dtype(self.cfg.param_dtype))
        return leaves

    def as_run(self, leaves):
        """The leaves as the passes read them: the matrices in the
        activations' dtype, cast ONCE, outside the loop (a layer's products
        cast them to it anyway), the vectors as they are. The loop's sum of
        a matrix's gradient over the passes is then carried in that dtype,
        half the bytes of the leaf's own, and widened once (PERF.md section
        6, PR 67: the carried sums and a body's terms beside them are what
        the chip has no room for in float32)."""
        dt = jnp.dtype(self.cfg.dtype)
        return jax.tree.map(lambda a: a.astype(dt) if a.ndim > 1 else a,
                            leaves)

    def one_pass(self, leaves, x: jax.Array):
        """``(the normed state, the same again as this pass's exit)``. A
        layer reads its leaves through :func:`_leaves_with_state`: backward,
        the state's cotangent goes on to the layer before only with this
        layer's weight gradients made. Nothing else orders them inside a
        loop's body: the compiler moved every layer's to the body's end and
        kept what they read (a layer's replayed activations) alive until
        then, 6.7 GiB at ``ouro2b6``'s sizes where one layer's are 0.8."""
        for name, layer in self.layers.items():
            x, mine = _leaves_with_state(x, leaves[name])
            with jax.named_scope(name):
                x = layer.apply({"params": mine}, x)[0]
        # the final norm closes the pass; its input is kept, not an f32 copy
        z = jax.checkpoint(rms_norm, static_argnums=(2,))(
            x, leaves["final_norm"], self.cfg.rms_eps)
        return z, z


LOOP_SITE = "pass loop"


def _loop_key(cfg: SparseLMConfig):
    return cfg.num_hidden_layers, cfg.total_ut_steps, cfg.hidden_size


def run_passes(stack: LoopedStack, x: jax.Array, leaves):
    """``stack.one_pass`` ``cfg.total_ut_steps`` times from the state ``x``:
    the passes' exits stacked, (R, B, T, D). ONE traced body: a
    ``lax.scan`` over the passes, so that tracing, lowering and compiling
    cost what one pass's layers cost. The loop stores, a pass, what a
    rematerialised layer keeps (its input and ``KEPT_OF_A_LAYER``), the
    final norm's input and the exit's state. (The tests compare it with a
    Python loop over the same body, tests/ouro_unrolled.py; the program has
    this form alone.)"""
    cfg = stack.cfg
    lowering.record(LOOP_SITE, _loop_key(cfg), None, form="one traced pass")
    leaves = stack.as_run(leaves)
    _, exits = jax.lax.scan(lambda x, _: stack.one_pass(leaves, x), x, None,
                            length=cfg.total_ut_steps)
    return exits


def loop_layout(cfg: SparseLMConfig) -> str:
    """The ``setup/warmup`` row's ``loop_layout``: the looped stack's sizes
    and the form the traced call gave the loop over the passes."""
    layers, passes = cfg.num_hidden_layers, cfg.total_ut_steps
    said = lowering.recorded(LOOP_SITE, _loop_key(cfg))
    return (f"{layers} layers x {passes} passes: {layers * passes} "
            f"applications of {layers} parameter sets, "
            + (said["form"] if said else "none traced"))


def exit_log_probs(logit: jax.Array) -> jax.Array:
    """``ln p_t`` (R, ...) of the exit gates' logits (R, ...), f32: ``p_t =
    lam_t prod_{j<t} (1 - lam_j)`` with ``lam = sigmoid(logit)``, the last
    pass taking what is left, ``p_R = prod_{j<R} (1 - lam_j)`` (its own gate
    is read by nothing). In logarithms: no product of small numbers, and
    ``p ln p`` is finite wherever a gate saturates."""
    stop = jax.nn.log_sigmoid(logit)
    go = jax.nn.log_sigmoid(-logit)
    before = jnp.cumsum(go, axis=0) - go        # sum_{j<t} ln (1 - lam_j)
    return jnp.concatenate([(before + stop)[:-1], before[-1:]], axis=0)


def _looped_loss(module, x, ids, table, text_len: int, loss_mask):
    """What :class:`SparseLM` does after the embedding where the stack is
    looped (module docstring, ``OuroLMConfig``): the passes, the exit
    distribution, ONE call of the streamed head over the R exits' rows with
    the exit weights, and the loss with its parts. ``module``: the
    ``SparseLM`` whose compact call this is (the leaves are its)."""
    cfg = module.cfg
    dt, pdt = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
    passes = cfg.total_ut_steps
    stack = LoopedStack(cfg, module.mesh)
    leaves = module.param("passes", stack.init, x)
    with jax.named_scope("passes"):
        exits = run_passes(stack, x, leaves)
    gate = module.param(
        "exit_gate", nn.initializers.normal(stddev=cfg.exit_gate_init_std),
        (cfg.hidden_size,), pdt)
    bias = module.param("exit_gate_bias", nn.initializers.zeros, (1,), pdt)
    tied = cfg.tied_embeddings
    head = table if tied else module.param(
        "lm_head", nn.initializers.normal(stddev=0.02),
        (cfg.hidden_size, cfg.vocab_size), pdt)

    # position t predicts token t + 1; the last has nothing to predict
    b, t = ids.shape
    targets = jnp.concatenate(
        [ids[:, 1:], jnp.zeros((b, 1), ids.dtype)], axis=1)
    scored = jnp.broadcast_to(
        (jnp.arange(t) < t - 1).astype(jnp.float32), (b, t))
    if loss_mask is not None:
        scored = scored * jnp.concatenate(
            [loss_mask[:, 1:], jnp.zeros((b, 1), loss_mask.dtype)],
            1).astype(jnp.float32)
    in_text = (jnp.arange(t) + 1 < text_len).astype(jnp.float32)
    with jax.named_scope("exit_gate"):
        # a number a row and pass, in f32: a sum over the lanes, no product
        # on the unit
        logit = jnp.sum(exits.astype(jnp.float32) * gate.astype(jnp.float32),
                        axis=-1) + bias.astype(jnp.float32)
        log_p = exit_log_probs(logit)                        # (R, B, T)
        p = jnp.exp(log_p)
    # the exits' rows through the head as one call: the weights are known
    # before the logits, so a chunk's dx and dW are made with its loss, and
    # the head's leaf gets the four exits' sum from one carried array
    rows = passes * b * t
    total, _, nll = _streamed_nll(
        exits.reshape(rows, -1), head.astype(dt),
        jnp.tile(targets.reshape(-1), passes),
        (p * scored).reshape(rows, 1), min(cfg.head_chunk, b * t), tied,
        rows=True)
    nll = nll.reshape(passes, b, t)
    # normalised over the WHOLE (micro)batch, as the unlooped loss is
    denom = sum_over_manual_data_axes(jnp.sum(scored))
    fields = sum_over_manual_data_axes(jnp.stack(
        [jnp.sum(scored * in_text), jnp.sum(scored * (1.0 - in_text))]))
    if loss_mask is not None:
        denom = jnp.maximum(denom, 1.0)
    with jax.named_scope("exit_gate"):
        entropy = jnp.sum(-jnp.sum(p * log_p, axis=0) * scored) / denom
        order = jnp.arange(1, passes + 1, dtype=jnp.float32)
        expected = jnp.sum(jnp.einsum("r,rbt->bt", order, p) * scored) / denom
    loss_main = total / denom
    loss_entropy = -cfg.exit_entropy_weight * entropy
    aux = {
        "loss": loss_main + loss_entropy,
        # the expectation over the exits, and the entropy's term
        "loss_main": loss_main, "loss_entropy": loss_entropy,
        # the last pass's, as a model run once reports them
        "loss_text": jnp.sum(nll[-1] * scored * in_text)
        / jnp.maximum(fields[0], 1.0),
        "loss_img": jnp.sum(nll[-1] * scored * (1.0 - in_text))
        / jnp.maximum(fields[1], 1.0),
        # each pass's own mean loss: what tells the passes apart
        **{f"loss_exit_{i + 1}": jnp.sum(nll[i] * scored) / denom
           for i in range(passes)},
        "exit_expected_pass": expected, "exit_entropy": entropy}
    return aux["loss"], aux


class SparseLM(nn.Module):
    cfg: SparseLMConfig
    # as DALLE.mesh: the Mosaic kernels run per shard of it
    mesh: Any = None

    @nn.compact
    def __call__(self, text_tokens: jax.Array, image_tokens: jax.Array,
                 loss_mask: Optional[jax.Array] = None):
        cfg = self.cfg
        dt, pdt = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        # cfg.embed_init_std: an assumption of the configuration (no
        # source pins an embedding's scale). At unit variance a token's
        # own embedding outweighs the running mean of its prefix that
        # attention adds to the residual stream, and an untrained router
        # reads tokens; at 0.02 (the head's scale) it reads sequences
        # and sends a whole sequence's assignments to this chip's experts
        # or to none (PERF.md section 6, PR 31).
        table = self.param(
            "token_emb", nn.initializers.normal(stddev=cfg.embed_init_std),
            (cfg.vocab_size, cfg.hidden_size), pdt)
        ids = jnp.concatenate(
            [text_tokens, image_tokens + cfg.vocab_text], axis=1)
        with jax.named_scope("embed"):
            x = jnp.take(table, ids, axis=0)
            if cfg.mup_enabled:
                x = x * cfg.hidden_size ** 0.5
            x = x.astype(dt)
        if cfg.total_ut_steps > 1:
            return _looped_loss(self, x, ids, table, text_tokens.shape[1],
                                loss_mask)

        layer_cls = remat_layer(cfg)
        # the rows a configuration with ``mrope_section`` rotates by: an
        # input of the layer stack, made here from the two fields' lengths
        rows = ()
        if cfg.mrope_section:
            rows = (field_positions(text_tokens.shape[1],
                                    image_tokens.shape[1], cfg.image_grid),)
        counters = []
        for i in range(cfg.num_hidden_layers):
            # a layer of two parts is told whether its second is dense
            two = () if cfg.one_part_layers else (cfg.layer_is_dense(i),)
            x, c = layer_cls(cfg, cfg.kind_of_layer(i), self.mesh, *two,
                             name=f"layer_{i}")(x, *rows)
            if c is not None:
                counters.append(c)
        x = rms_norm(x, self.param("final_norm", nn.initializers.ones,
                                   (cfg.hidden_size,), pdt), cfg.rms_eps)
        # the head: a leaf of its own, or the embedding's table, which then
        # gets the sum of both uses' gradients and is one leaf to LAMB
        tied = cfg.tied_embeddings
        head = table if tied else self.param(
            "lm_head", nn.initializers.normal(stddev=0.02),
            (cfg.hidden_size, cfg.vocab_size), pdt)

        # position t predicts token t + 1; the last has nothing to predict
        b, t = ids.shape
        targets = jnp.concatenate(
            [ids[:, 1:], jnp.zeros((b, 1), ids.dtype)], axis=1)
        scored = (jnp.arange(t) < t - 1).astype(jnp.float32)
        in_text = (jnp.arange(t) + 1 < text_tokens.shape[1]).astype(
            jnp.float32)
        weights = jnp.broadcast_to(
            jnp.stack([scored * in_text, scored * (1.0 - in_text)], -1),
            (b, t, 2))
        if loss_mask is not None:
            shifted = jnp.concatenate(
                [loss_mask[:, 1:], jnp.zeros((b, 1), loss_mask.dtype)], 1)
            weights = weights * shifted[..., None].astype(jnp.float32)
        total, sums = _streamed_nll(
            x.reshape(b * t, -1), head.astype(dt), targets.reshape(-1),
            weights.reshape(b * t, 2), min(cfg.head_chunk, b * t), tied)
        # normalised over the WHOLE (micro)batch: under the accumulation's
        # shard_map over dp the denominators are summed over the shards
        # and this shard returns its share (as DALLE)
        denoms = sum_over_manual_data_axes(jnp.sum(weights, axis=(0, 1)))
        shards = sum_over_manual_data_axes(1)
        if loss_mask is not None:
            denoms = jnp.maximum(denoms, 1.0)
        loss = total / jnp.sum(denoms)
        losses = {}
        if cfg.num_nextn_predict_layers:
            # position i reads z_i and token i + 1 and predicts token i + 2:
            # the last two positions have nothing to predict (the rows run
            # all the same, on a token 0: causality keeps them to
            # themselves)
            shift = lambda a, n: jnp.concatenate(
                [a[:, n:], jnp.zeros((b, n), a.dtype)], axis=1)
            with jax.named_scope("mtp"), jax.named_scope("embed"):
                next_emb = jnp.take(table, shift(ids, 1), axis=0).astype(dt)
            z, c = PredictionModule(cfg, layer_cls, self.mesh, name="mtp")(
                x, next_emb)
            counters.append(c)
            weights = (jnp.arange(t) < t - 2).astype(jnp.float32)
            weights = jnp.broadcast_to(weights[None, :, None], (b, t, 1))
            if loss_mask is not None:
                weights = weights * shift(loss_mask, 2)[..., None].astype(
                    jnp.float32)
            with jax.named_scope("mtp"):
                mtp_total, _ = _streamed_nll(
                    z.reshape(b * t, -1), head.astype(dt),
                    shift(ids, 2).reshape(-1), weights.reshape(b * t, 1),
                    min(cfg.head_chunk, b * t), tied)
            mtp_denom = sum_over_manual_data_axes(jnp.sum(weights))
            if loss_mask is not None:
                mtp_denom = jnp.maximum(mtp_denom, 1.0)
            losses = {"loss_main": loss, "loss_mtp": mtp_total / mtp_denom}
            loss = loss + cfg.mtp_loss_weight * losses["loss_mtp"]
        stack = lambda key: jnp.stack([c[key] for c in counters])
        if cfg.index_topk:
            # the indexer's loss: the layers' sum of the mean over the
            # (micro)batch's tokens of a row's KL; and the chosen pairs over
            # the causal pairs, the layers' median
            rows_all = sum_over_manual_data_axes(float(b * t))
            causal = sum_over_manual_data_axes(b * t * (t + 1) / 2.0)
            losses = {"loss_main": loss,
                      "loss_indexer": jnp.sum(stack("index_kl")) / rows_all,
                      "sparse_selected_pct": 100.0 * jnp.median(jnp.sum(
                          stack("index_chosen"), axis=1)) / causal}
            loss = loss + cfg.indexer_loss_weight * losses["loss_indexer"]
        aux = {
            "loss": loss, **losses,
            "loss_text": sums[0] / jnp.maximum(denoms[0], 1.0),
            "loss_img": sums[1] / jnp.maximum(denoms[1], 1.0),
            # the expert layers' counters: share of the tokens x k
            # assignments that fell on held experts (mean over layers),
            # the busiest held expert's tokens over the mean (worst
            # layer), assignments to held experts left uncomputed
            "moe_assignments_here_pct":
                100.0 * jnp.mean(stack("here")) / shards,
            "moe_load_max_over_mean": jnp.max(stack("load")) / shards,
            "moe_dropped": jnp.sum(stack("dropped")),
            # expert layers of this micro-batch whose assignments did not
            # fit the dispatch buffer and took the dense lowering
            "moe_dense_calls": jnp.sum(stack("dense")),
            # runs of rows (a token tile's in a held expert's group) that
            # passed the token-major kernel's first window and cost their
            # tile a further round, both sums of a layer alike
            "moe_sum_spills": jnp.sum(stack("spills")),
            # row tiles of the grouped kernels' grid that hold rows (the
            # layers' median): what the kernels move nothing for is the rest
            "moe_tiles_active_pct":
                100.0 * jnp.median(stack("tiles_active")) / shards,
        }
        return aux["loss"], aux


# ---------------------------------------------------------------------------
# What task.py and training/loop.py ask of a model's module
# ---------------------------------------------------------------------------

def field_positions(text_len: int, image_len: int, grid: int) -> jax.Array:
    """(3, T) int32, the three position rows of a ``text`` field of
    ``text_len`` tokens followed by an ``image`` field of ``image_len``
    tokens, rows of ``grid``: text token i has (i, i, i); the image token
    at (r, c) has (text_len, text_len + r, text_len + c). The one place
    that knows the rule: the layers take the rows."""
    i = np.arange(text_len)
    r, c = np.divmod(np.arange(image_len), grid)
    start = np.full(image_len, text_len)
    return jnp.asarray(np.stack([
        np.concatenate([i, start]), np.concatenate([i, start + r]),
        np.concatenate([i, start + c])]).astype(np.int32))


def build(cfg: SparseLMConfig, mesh=None) -> SparseLM:
    return SparseLM(cfg, mesh=mesh)


def init_params(model: SparseLM, rng: jax.Array, batch: int = 2):
    """Parameter shapes depend on no length: initialised on a one-block
    sequence, as one jitted program."""
    if model.mesh is not None:
        shards = model.mesh.shape["dp"] * model.mesh.shape["fsdp"]
        batch = -(-batch // shards) * shards
    tokens = jnp.zeros((batch, 8), jnp.int32)
    return jax.jit(model.init)(rng, tokens, tokens)


def _latent_records(cfg: SparseLMConfig, tp: int) -> Dict[str, str]:
    """``attn_layout`` and ``attn_operands`` of a configuration whose every
    layer is latent attention, the prediction module's block among them:
    the widths, which lowering the traced calls took, and whether their
    kernels read ``q_nope``, ``k_nope`` and ``v`` where ``q_b`` and
    ``kv_b`` wrote them. Every layer has the one shape, and its rotary the
    same two (the queries' rotary parts and the one key): all took the
    kernel, or the first refusal says why none did."""
    layers = cfg.num_hidden_layers + cfg.num_nextn_predict_layers
    tokens, rope = cfg.total_seq_len, cfg.qk_rope_head_dim
    call = LATENT_SITE, _latent_key(
        tokens, cfg.num_heads, cfg.qk_nope_head_dim, rope, cfg.v_head_dim,
        tp)
    why_not = lowering.why_not(*call)
    not_the_pass = lowering.first_refusal(
        (PAIR_ROTARY_SITE, key) for key in (
            _pair_key(tokens, cfg.num_heads * rope, rope, tp),
            _pair_key(tokens, rope, rope)))
    sliced = why_not or lowering.recorded(*call)["sliced"]
    took = (f"dense XLA lowering ({why_not})" if why_not else
            f"blockwise {kernels.BLOCK}: {layers} of {layers} layers, "
            f"{kernels.LATENT_HEADS} heads a step, backward: "
            + _backward_words(None))
    rotary = (f"XLA: {not_the_pass}" if not_the_pass else
              f"one pass on the lanes: {layers} of {layers} layers")
    return {
        "attn_layout": (
            f"latent {cfg.q_lora_rank} / {cfg.kv_lora_rank} + one rotary "
            f"key of {rope}, heads {cfg.num_heads} x "
            f"({cfg.qk_nope_head_dim} + {rope} | "
            f"{cfg.v_head_dim}), {took}, rotary ({rotary})"),
        "attn_operands": (
            f"sliced: {sliced}" if sliced else
            f"latent: {IN_PLACE}: {layers} of {layers} layers")}


def engagement_records(cfg: SparseLMConfig, mesh=None) -> Dict[str, str]:
    """The ``setup/warmup`` row's attributes: which layers' traced calls
    took the blockwise kernel and which backward those took (looked up in
    what the dispatcher did), how the layers run, and what the expert layer
    holds."""
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    tokens = cfg.total_seq_len
    layers = [cfg.kind_of_layer(i) for i in range(cfg.num_hidden_layers)]
    # the attention layers (a short convolution has ``conv_layout``, a
    # state-space mixer ``ssm_layout``, a gated-delta-rule mixer
    # ``gdn_layout``, a layer of experts alone none)
    kinds = [k for k in layers
             if k not in (LAYER_SHORT_CONV, LAYER_MAMBA2, LAYER_EXPERTS,
                          LAYER_GATED_DELTA)]
    calls = [(_blockwise_site(k), _blockwise_key(
        tokens, cfg.num_heads * cfg.head_dim,
        cfg.num_kv_heads * cfg.head_dim, tp)) for k in kinds]
    took = [c for c in calls if lowering.why_not(*c) is None]
    on = len(took)
    # the backward of the layers that took the kernel: one length, group and
    # dtype, so one answer
    split_why = next(filter(None, (
        lowering.recorded(*c)["split_backward"] for c in took)), None)
    backward = ""
    if on:
        backward = ", backward: " + _backward_words(split_why)
        if not split_why:
            backward += f" ({on} of {len(kinds)} layers)"
    windows = sum(k == LAYER_WINDOW_ROPE for k in kinds)
    whole = sum(k == LAYER_FULL_ROPE for k in kinds)
    ropes = sum(k in ROPE_KINDS for k in kinds)

    def passes(norm: bool, rotary: bool):
        """Queries' and keys' per-head work of one kind: the same two
        shapes in every layer that has it."""
        return [(_head_pass_site(norm, rotary), _head_pass_key(
            tokens, h * cfg.head_dim, cfg.head_dim, tp))
            for h in (cfg.num_heads, cfg.num_kv_heads)]

    def lowering_of(asked, took: str) -> str:
        why_not = lowering.first_refusal(asked)
        return f"(XLA: {why_not})" if why_not else f"({took})"

    words = ""
    if cfg.qk_norm:
        words += ", normed queries and keys " + lowering_of(
            [c for rotary in {k in ROPE_KINDS for k in kinds}
             for c in passes(True, rotary)],
            f"one pass on the lanes: {len(kinds)} of {len(kinds)} layers")
    if ropes:
        words += ", rotary " + (
            f"of a head's first {cfg.rotary_dim} lanes "
            * (cfg.rotary_dim != cfg.head_dim)) + lowering_of(
            passes(cfg.qk_norm, True),
            ("in the head pass" if cfg.qk_norm else "one pass on the lanes")
            + f": {ropes} of {ropes} rope layers")
    first, last = cfg.expert_offset, cfg.expert_offset + cfg.experts_held - 1
    devices = mesh.size if mesh is not None else 1
    summed = lowering.recorded(SUM_SITE, _sum_key(
        cfg.experts_per_token, cfg.experts_held, cfg.hidden_size, cfg.dtype))
    sums = ("none traced (the dense lowering)" if summed is None else
            f"one gather a slot ({summed['why_not']})" if summed["why_not"]
            else f"runs of rows, {summed['tile']} tokens a tile, windows "
            f"of {token_sum.WINDOW} rows")
    # tokens -> rows: the forward's and the replay's one record, and the
    # cotangent's in whichever dtype it moved
    key = functools.partial(_rows_key, cfg.experts_held, cfg.hidden_size)
    how = lambda said: ("kernel over runs" if said["why_not"] is None
                        else f"one gather ({said['why_not']})")
    to_rows = lowering.recorded(ROWS_SITE, key(cfg.dtype, "tokens"))
    if to_rows is not None:
        sums += f"; rows from tokens: {how(to_rows)}"
    for dtype in dict.fromkeys((cfg.dtype, "float32")):
        moved = lowering.recorded(ROWS_SITE, key(dtype, "cotangent"))
        if moved is not None:
            sums += (f"; cotangent gathered as {jnp.dtype(dtype).name} ("
                     + ("the caller rounds the result to it" if moved["stated"]
                        else "the caller states no rounding")
                     + f"), {how(moved)}")
            break
    # the products between them: a traced call's form of the expert block
    block = lowering.recorded(PRODUCTS_SITE, _block_key(
        cfg.hidden_size, cfg.expert_width, cfg.dtype, cfg.expert_gated))
    if block is not None:
        sums += "; expert block: " + _block_words(block["why_not"],
                                                  cfg.expert_gated)
    # the router's kind, and what stands beside the routed experts
    router = "softmax over the chosen"
    if cfg.score_func == "sigmoid":
        router = ("sigmoid" + ", bias" * cfg.selection_bias
                  + ", norm" * cfg.route_norm + f", x{cfg.route_scale:g}")
    beside = ""
    if cfg.num_shared_experts:
        beside += f", a shared expert of {cfg.shared_width}" \
            + " under a sigmoid gate a token" * cfg.shared_expert_gate
    if cfg.num_dense_layers:
        beside += (f", layers 0-{cfg.num_dense_layers - 1} dense "
                   f"{cfg.dense_width}")
    of_kind = [(len(kinds) - ropes, "full no-rope"),
               (windows, f"window {cfg.window} rope"), (whole, "full rope")]
    # the first two always (the accepted cells' words); with a ``full_rope``
    # layer only the kinds the configuration has
    of_kind = " + ".join(f"{n} {what}" for n, what in of_kind
                         if n or not (whole or what == "full rope"))
    attn_layout = (
        f"blockwise {kernels.BLOCK}: {on} of {len(kinds)} "
        + "attention " * (len(kinds) < len(layers)) + f"layers, {of_kind}, "
        + _heads_a_tile(cfg.head_dim)
        + f"{cfg.num_heads // cfg.num_kv_heads} query "
        f"heads a key-value head{backward}"
        + words
        + ", gated output" * cfg.attention_gate)
    said = {"attn_layout": attn_layout}
    # the band of each kind of layer that took the kernel, as its call read
    # it off the function the kernels cut their edge tiles by
    bands = {kind: lowering.recorded(*call)["band"]
             for kind, call in zip(kinds, calls) if call in took}
    if bands:
        said["attn_band"] = "; ".join(
            f"{kinds.count(kind)} {kind}: {_band_words(band)}"
            for kind, band in bands.items())
    if cfg.kv_lora_rank:
        said = _latent_records(cfg, tp)
    if cfg.index_topk:
        # every layer is of the one kind and shape: one call says it
        why_not = lowering.why_not(SELECTED_SITE, _selected_key(
            tokens, cfg.num_heads * cfg.head_dim,
            cfg.num_kv_heads * cfg.head_dim, cfg.index_heads,
            cfg.index_topk))
        took = (f"dense XLA lowering ({why_not})" if why_not else
                f"blockwise {kernels.BLOCK}: {len(kinds)} of {len(kinds)} "
                f"layers, {cfg.num_heads // cfg.num_kv_heads} query heads a "
                "key-value head, backward: " + _backward_words(None))
        said = {
            "attn_layout": (
                f"over {cfg.index_topk} keys a query, chosen by an indexer "
                f"of {cfg.index_heads} heads of {cfg.index_head_dim} over "
                f"one key head, {took}{words}, positions: three rows by "
                f"sections {list(cfg.mrope_section)}"),
            "sparse_layout": (f"dense masks in XLA code ({why_not})"
                              if why_not else sparse_words())}
    if LAYER_SHORT_CONV in layers:
        said["conv_layout"] = conv_layout(cfg)
    if LAYER_MAMBA2 in layers:
        said["ssm_layout"] = ssm_layout(cfg)
    if LAYER_GATED_DELTA in layers:
        said["gdn_layout"] = gdn_layout(cfg, tp)
    said["head_layout"] = (
        f"tied: the head is the embedding's table ({cfg.vocab_size} x "
        f"{cfg.hidden_size}), contracted where it lies in the streamed "
        "cross-entropy; one leaf, the sum of both uses' gradients, one "
        "LAMB trust ratio; ") * cfg.tied_embeddings + head_layout(cfg)
    if cfg.num_nextn_predict_layers:
        said["mtp_layout"] = (
            "one prediction module after the final norm: [norm(next "
            "token's embedding) ; norm(last state)] . W_eh, one expert "
            "layer, a final norm of its own; shares the embedding and the "
            f"head; loss_mtp over T - 2 positions, weight "
            f"{cfg.mtp_loss_weight:g}")
    said["layer_loop"] = (
        ("a pass " if cfg.total_ut_steps > 1 else "")
        + f"unrolled: {len(layers)} layers, each rematerialised but its "
        "attention" + (", one part a layer behind one norm: "
                       + " ".join(layers)) * cfg.one_part_layers)
    if cfg.total_ut_steps > 1:
        said["loop_layout"] = loop_layout(cfg)
    if not cfg.has_expert_layers:
        return said
    return {
        **said,
        "moe_layout": (
            f"{cfg.experts_held} of {cfg.num_experts} experts held "
            f"({first}-{last}), top {cfg.experts_per_token} of "
            f"{cfg.num_experts}, {router}{beside}, no exchange: "
            f"{'one device' if devices == 1 else f'{devices} devices, data parallel'}"
            f"; token-major sums: {sums}"),
    }


def step_attributes(cfg: SparseLMConfig) -> Tuple[str, ...]:
    """Entries of the step's aux that go onto every ``loop/step`` row: the
    expert layers' counters and, of a configuration with a prediction
    module or an indexer, the two losses its loss is made of (and an
    indexer's chosen pairs over the causal pairs)."""
    losses = ("loss_main", "loss_mtp") if cfg.num_nextn_predict_layers else ()
    if cfg.index_topk:
        losses = ("loss_main", "loss_indexer", "sparse_selected_pct")
    if cfg.total_ut_steps > 1:
        # a looped stack's: the loss's two terms, each pass's own loss (what
        # tells the passes apart: a trace cannot), and the exit distribution
        losses = ("loss_main", "loss_entropy", *(
            f"loss_exit_{i + 1}" for i in range(cfg.total_ut_steps)),
            "exit_expected_pass", "exit_entropy")
    if not cfg.has_expert_layers:
        return losses
    return ("moe_assignments_here_pct", "moe_load_max_over_mean",
            "moe_dropped", "moe_dense_calls", "moe_sum_spills",
            "moe_tiles_active_pct") + losses


# those of them that count a slower lowering, so that a step above its
# median in one is late because of the model (obs/late.py's cause "model");
# a spill costs its tile microseconds and is reported without being blamed
SLOW_STEP_ATTRIBUTES = ("moe_dense_calls",)
