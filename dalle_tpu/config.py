"""Configuration dataclasses for the TPU-native collaborative DALL-E trainer.

Mirrors the reference's three-axis config split (model/trainer || swarm ||
peer-role) from ``arguments.py:8-165`` of learning-at-home/dalle, redesigned
for a JAX/XLA stack: model shape lives in :class:`ModelConfig` (reference
hard-codes it in ``task.py:62-83``), optimizer hyperparameters in
:class:`OptimizerConfig` (reference ``arguments.py:18-27``), collaboration
behavior in :class:`CollabConfig` (reference ``arguments.py:60-78``) and peer
identity/networking in :class:`PeerConfig` (reference ``arguments.py:81-137``).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, ClassVar, Dict, Optional, Sequence, Tuple

# Attention layer kinds supported by the attention zoo (reference
# ``task.py:63-64`` selects from dalle-pytorch's attn_types).
ATTN_FULL = "full"
ATTN_AXIAL_ROW = "axial_row"
ATTN_AXIAL_COL = "axial_col"
ATTN_CONV_LIKE = "conv_like"

VALID_ATTN_TYPES = (ATTN_FULL, ATTN_AXIAL_ROW, ATTN_AXIAL_COL, ATTN_CONV_LIKE)

# Sequence/context parallelism modes over the mesh's ``sp`` axis (the
# reference has none — SURVEY.md §5; long-context is first-class here).
SP_NONE = "none"
SP_ULYSSES = "ulysses"   # all-to-all seq<->head resharding; any attn type
SP_RING = "ring"         # ppermute ring flash attention; full-causal layers

VALID_SP_MODES = (SP_NONE, SP_ULYSSES, SP_RING)


@dataclass(frozen=True)
class ModelConfig:
    """DALL-E transformer shape.

    Defaults reproduce the reference's flagship configuration
    (``task.py:62-83``): dim 1024, depth 64 with 4 weight-shared unique
    blocks cycling ``axial_row, axial_col, axial_row, axial_row`` plus a
    final distinct ``conv_like`` block, 16 heads x 64 head dim, rotary
    embeddings, tied input/output embeddings, text 256 + image 32x32 tokens.
    """

    vocab_text: int = 32100          # T5 tokenizer vocab (task.py:58, 32100)
    vocab_image: int = 8192          # VQGAN f8 Gumbel codebook (task.py:26-32)
    text_seq_len: int = 256          # arguments.py:15
    image_grid: int = 32             # 256px / f8 VQGAN -> 32x32 codes
    dim: int = 1024
    depth: int = 64
    heads: int = 16
    head_dim: int = 64
    ff_mult: int = 4
    # Attention types cycled over the unique shared blocks (task.py:63-64).
    attn_types: Tuple[str, ...] = (
        ATTN_AXIAL_ROW, ATTN_AXIAL_COL, ATTN_AXIAL_ROW, ATTN_AXIAL_ROW)
    # Number of unique weight-shared blocks the depth cycles through
    # (task.py:65,78-79: shared_attn_ids/shared_ff_ids cycle(0,1,2,3)).
    # 0 disables sharing (every layer owns parameters).
    shared_block_cycle: int = 4
    # Dense (cycle=0) stacks as a scan with STACKED per-iteration params
    # instead of unrolling depth blocks: the compiled body is one
    # attn-type cycle, each iteration reads its own parameter slice
    # (leading axis = repetitions). A 64-independent-block flagship
    # unrolls to a ~16x larger XLA program whose compile never finished
    # (>70 min); the scanned dense body compiles like the weight-shared
    # model. Train-path only (decode reads per-block trees).
    dense_scan: bool = False
    # Whether the final layer is a distinct conv_like block with its own
    # parameters ('w_conv' shared id in task.py:65).
    final_conv_block: bool = True
    conv_kernel: int = 11            # local window size for conv_like attn
    rotary: bool = True              # task.py:80
    tied_embeddings: bool = True     # share_input_output_emb, task.py:82
    dropout: float = 0.0             # ff_dropout/attn_dropout = 0 (task.py:76-77)
    loss_img_weight: float = 7.0     # dalle-pytorch default weighting
    # Memory saving: jax.checkpoint (remat) replaces the reference's
    # reversible layers (task.py:81) with the XLA-idiomatic equivalent.
    remat: bool = True
    # The knobs from here to ln_fusion are set by the presets below.
    # What a preset costs as a whole is the ledger's (PERF_LEDGER.jsonl;
    # PERF.md §5); what each knob is worth alone is not measured on
    # today's stack (ROADMAP Design 5).
    # None = blanket remat (save only block boundaries); "save_ctx" saves
    # the attention kernel's outputs (context + softmax row stats) so
    # backward never re-runs the forward attention kernel; "save_attn"
    # additionally saves rotated q/k/v so backward also skips the
    # projections (most memory, least compute).
    remat_policy: Optional[str] = None
    # Partial remat: leave this many of the unique weight-shared blocks
    # un-rematerialized (their activations are saved instead of recomputed
    # in backward). Trades HBM for the remat recompute — each skipped
    # block removes 1/cycle of the extra forward pass.
    remat_skip_blocks: int = 0
    # Streaming cross-entropy: compute the image-segment head loss as a
    # chunked logsumexp over the vocabulary (chunks of this many ids)
    # instead of materializing the full (B, T, vocab) logits in HBM.
    # 0 = off (dense head). Identical losses either way. Head +
    # cross-entropy at 2048 are 1.19% of the flagship's busy time
    # (`head_ce_share_pct`, ledger, PR 28).
    head_chunk: int = 0
    # Cycle passes unrolled inside ONE scan iteration of the weight-shared
    # body. Backward accumulates the shared weights' f32 gradients into
    # the scan carry once per iteration; unroll N divides that
    # read-modify-write of every unique weight by N at the cost of an
    # N-times-larger compiled body. At unroll 2 the layer scan's own
    # traffic is 6.68% of the flagship's busy time
    # (`layer_scan_share_pct`, ledger, PR 28).
    scan_unroll: int = 1
    # Hoist the f32->bf16 parameter casts OUT of the weight-shared scan
    # (and its remat region): the scan body then reads pre-cast bf16
    # weights — the per-iteration casts and their remat replays disappear
    # and the shared-grad scan carry accumulates in BF16, halving the
    # carry read-modify-write bytes. The cost is bf16 round-nearest
    # gradient accumulation across the cycle repetitions (master
    # params/LAMB stay f32); the flagship's worst gradient leaf then
    # reads 0.040–0.046 relative L2 against the f32 reference (PERF.md
    # §4, chip runs of PRs 23–28).
    param_cast_hoist: bool = False
    # Fused Pallas GEGLU feed-forward (ops/pallas/geglu_kernels.py): the
    # (B*T, ff_mult*dim) intermediates stay in VMEM tiles and backward
    # saves only the FF input. "plain" fuses the non-rematted blocks
    # (remat_skip_blocks), whose FF autodiff residual it replaces at
    # fewer FLOPs than remat would; "all" also fuses rematted blocks
    # (their replay already avoids the residual, so this mostly trades
    # FLOPs for HBM traffic); "none" keeps the unfused XLA lowering
    # everywhere. On the flagship `ff[mosaic]` is 8.0% of busy time
    # beside 48.3% of feed-forward left to XLA (PERF.md §5, PR 28).
    ff_fusion: str = "plain"
    # Single-pass Pallas LayerNorm with fused backward
    # (ops/pallas/ln_kernels.py): forward reads/writes each row once with
    # both statistics formed in-register; backward produces dx and the
    # dscale/dbias partials in ONE pass instead of XLA's separate
    # reduction fusions. flax-parity numerics; unsupported shapes (tiny
    # test models, single-token decode) fall back to the plain lowering.
    # On for the flagship (`ff_norm[mosaic]` 0.024 s a step), off for
    # xl, whose LayerNorm runs as two XLA reductions of 0.260 + 0.246 s
    # a step (PERF.md §5, PR 28).
    ln_fusion: bool = False
    dtype: str = "bfloat16"          # activation dtype on TPU (MXU-native)
    param_dtype: str = "float32"
    # Sequence parallelism over the mesh's ``sp`` axis: "none", "ulysses"
    # (all-to-all, any attention type) or "ring" (ring attention; requires
    # every layer be 'full'). Active only when the model is built with a
    # mesh whose sp axis is > 1 (parallel/sequence.py).
    sequence_parallel: str = SP_NONE

    # Where this architecture lives: the module that builds the model, its
    # parameters and its engagement records from a configuration of this
    # class (``models/__init__.py:family``). Not a field.
    model_module: ClassVar[str] = "dalle_tpu.models.dalle"
    # what the decode path lacks for this architecture (None: it decodes)
    decode_missing: ClassVar[Optional[str]] = None

    @property
    def image_seq_len(self) -> int:
        return self.image_grid * self.image_grid

    @property
    def total_seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len

    @property
    def vocab_total(self) -> int:
        return self.vocab_text + self.vocab_image

    def optimizer_stacking(self) -> Dict[str, int]:
        """The leading axes of this model's leaves that hold independent
        weights, as ``OptimizerConfig`` fields: LAMB takes one trust ratio
        a slice there."""
        return {"stacked_reps": self.dense_scan_reps(), "stacked_experts": 0}

    def fuse_ff(self, is_plain: bool) -> bool:
        """Whether a block routes its FF through the fused Pallas GEGLU
        kernel: "all" fuses every block; "plain" fuses blocks whose
        residuals are actually saved — the remat_skip (plain) blocks, or
        everything when remat is off. ONE definition for both the scanned
        and unrolled transformer paths."""
        return (self.ff_fusion == "all"
                or (self.ff_fusion == "plain"
                    and (is_plain or not self.remat)))

    def layer_schedule(self) -> Tuple[Tuple[int, str], ...]:
        """(unique_block_id, attn_type) per layer.

        Layers cycle through ``shared_block_cycle`` unique blocks; if
        ``final_conv_block`` the last layer is a standalone conv block with
        block id -1 (reference 'w_conv', task.py:65).
        """
        sched = []
        body = self.depth - (1 if self.final_conv_block else 0)
        cycle = self.shared_block_cycle or body
        for i in range(body):
            uid = i % cycle
            sched.append((uid, self.attn_types[uid % len(self.attn_types)]))
        if self.final_conv_block:
            sched.append((-1, ATTN_CONV_LIKE))
        return tuple(sched)

    def dense_scan_reps(self) -> int:
        """Scan repetitions of the dense_scan (stacked-params) path — the
        ONE source of truth for "is the dense tree stacked?", shared by
        the transformer build and decode's parameter slicing. 0 when the
        dense stack unrolls instead (weight sharing on, dense_scan off,
        or body too shallow to scan)."""
        if self.shared_block_cycle or not self.dense_scan:
            return 0
        body = self.depth - (1 if self.final_conv_block else 0)
        reps = -(-body // len(self.attn_types))
        return reps if reps > 1 else 0

    def validate(self) -> None:
        for t in self.attn_types:
            if t not in VALID_ATTN_TYPES:
                raise ValueError(f"unknown attention type {t!r}")
        if self.dim != self.heads * self.head_dim:
            raise ValueError("dim must equal heads * head_dim")
        if self.remat_policy not in (None, "save_ctx", "save_attn"):
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r}; "
                "expected None, 'save_ctx' or 'save_attn'")
        if not (0 <= self.remat_skip_blocks
                <= max(self.shared_block_cycle, 0)):
            raise ValueError(
                f"remat_skip_blocks {self.remat_skip_blocks} outside "
                f"[0, shared_block_cycle={self.shared_block_cycle}]")
        if self.ff_fusion not in ("none", "plain", "all"):
            raise ValueError(
                f"unknown ff_fusion {self.ff_fusion!r}; "
                "expected 'none', 'plain' or 'all'")
        if self.sequence_parallel not in VALID_SP_MODES:
            raise ValueError(
                f"unknown sequence_parallel {self.sequence_parallel!r}; "
                f"expected one of {VALID_SP_MODES}")
        if self.sequence_parallel == SP_RING:
            types = set(self.attn_types) | (
                {ATTN_CONV_LIKE} if self.final_conv_block else set())
            if types != {ATTN_FULL}:
                raise ValueError(
                    "sequence_parallel='ring' requires every layer be "
                    f"'full' attention (got {sorted(types)}); axial/conv "
                    "masks need mode 'ulysses'")


@dataclass(frozen=True)
class OptimizerConfig:
    """LAMB hyperparameters (reference ``arguments.py:18-27``)."""

    learning_rate: float = 2.5e-3
    warmup_steps: int = 3125
    total_steps: int = 31250
    beta1: float = 0.9
    beta2: float = 0.96
    eps: float = 1e-6
    weight_decay: float = 0.045
    max_grad_norm: float = 4.0        # global clip inside LAMB (lamb_8bit.py:84-88)
    clamp_value: float = 10000.0      # weight-norm clamp in trust ratio (lamb_8bit.py:149-158)
    # 8-bit block-quantized moments (lamb_8bit.py); "fp32" uses dense state.
    state_bits: int = 8
    block_size: int = 4096            # quantization block (lamb_8bit.py:49)
    min_8bit_size: int = 65536        # fp32 fallback below this (lamb_8bit.py:49,103)
    # Reference offloads optimizer state to host (offload.py, task.py:130);
    # on TPU the idiomatic default is sharded on-device state.
    offload: bool = False
    # dense_scan stacked-leaf leading-axis size (ModelConfig
    # .dense_scan_reps()), threaded in by the task wiring so LAMB's
    # per-slice trust ratios are CONFIG-derived, not inferred from
    # parameter names (ADVICE r4). 0 = the model has no stacked leaves;
    # None = infer by path heuristic (standalone optimizer construction).
    stacked_reps: "int | None" = None
    # The same for an expert layer's leaves (``.../experts/<name>``, the
    # experts held on the leading axis): that many experts a leaf, one
    # trust ratio each. 0 = none; None = infer from the path.
    stacked_experts: "int | None" = None


@dataclass(frozen=True)
class TrainerConfig:
    """Local training-loop knobs (reference ``arguments.py:8-56``)."""

    per_device_batch: int = 2         # arguments.py:12-14
    grad_accum_steps: int = 1
    seed: int = 0
    # Mesh axis sizes; -1 means "use all remaining devices" on the dp axis.
    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1                       # sequence parallelism (ring attention)

    @property
    def local_batch_per_step(self) -> int:
        return self.per_device_batch * self.grad_accum_steps


@dataclass(frozen=True)
class CollabConfig:
    """Swarm-wide optimizer semantics (reference ``arguments.py:60-78``)."""

    run_id: str = "dalle-tpu"
    target_batch_size: int = 4096     # arguments.py:62-65
    matchmaking_time: float = 15.0    # arguments.py:66-68
    allreduce_timeout: float = 60.0   # arguments.py:69-71
    averaging_timeout: float = 180.0  # arguments.py:72-74
    # Average params+opt state with peers every N epochs to bound drift.
    # Rounds are byte-identical across surviving members (part owners apply
    # the same lossy wire bytes they broadcast), so state averaging is
    # repair for peers that missed chunks, not a per-epoch necessity —
    # and keeping it off the common path halves the per-epoch matchmaking
    # cost, which keeps peers' matchmaking windows aligned.
    average_state_every: int = 10
    # Compression: tensors with <= threshold elems -> fp16, else uniform 8-bit
    # (SizeAdaptiveCompression(threshold=2**16+1, ...), task.py:125-126).
    # "power_sgd" instead exchanges rank-r low-rank factors with error
    # feedback (swarm/powersgd.py; hivemind carries PowerSGD upstream,
    # SURVEY.md §2 component 15).
    size_adaptive_threshold: int = 2 ** 16 + 1
    # NOTE: the benchmark's cells run size_adaptive. power_sgd keeps
    # device-resident f32 error-feedback + in-flight M caches at
    # gradient size (~500 MB persistent + ~2x transient for the
    # flagship's 125.6M unique params); its footprint beside the
    # flagship's micro 4 x accum 8 step (9.44 GB peak; PERF.md §5,
    # PR 28) is not measured.
    grad_compression: str = "size_adaptive"
    state_compression: str = "size_adaptive"
    # Where the u8/u4/f16 wire codec EXECUTES (never what it emits —
    # wire bytes are backend-identical, mixed groups interoperate):
    # "device" runs quantize/dequantize as jitted programs on the
    # accelerator (swarm/device_codec.py — VERDICT r5 weak #1: 20.1 s +
    # 13.8 s of host numpy codec per N=4 flagship epoch while the TPU
    # idled) and hands gradients to the wire without the host f32 pull;
    # "host" is the numpy path; "auto" picks device on TPU peers, host
    # elsewhere.
    wire_codec_backend: str = "auto"
    # --- In-collective quantization (r15; EQuARX arxiv 2506.17615,
    # DynamiQ arxiv 2602.08923). wire_bits_reduce / wire_bits_gather PIN
    # the wire codec of the butterfly's two legs for the whole run —
    # 8 -> blockwise u8, 4 -> blockwise u4 (half the sync bytes again)
    # — instead of the per-part SizeAdaptive dispatch. A pinned leg
    # also REJECTS frames naming any other codec (codec flapping is
    # authenticated garbage: error-feedback residual scales are only
    # meaningful against one stable quantizer). None keeps the legacy
    # grad_compression dispatch for that leg, byte-identical to r14.
    wire_bits_reduce: "int | None" = None
    wire_bits_gather: "int | None" = None
    # Error-feedback residuals through the collective: each sender
    # carries the previous round's quantization error into this round's
    # scatter encode (device-resident, donated under the device codec
    # backend), and each part owner carries its own residual into the
    # gather re-quantize (the DynamiQ second aggregation-hop stage; the
    # carry-in is suspended on audit-challenged parts so the r14 replay
    # stays bit-exact — swarm/error_feedback.py). Requires BOTH
    # wire_bits knobs pinned; False + 8-bit leaves every round
    # byte-identical to the r14 protocol.
    ef_residuals: bool = False
    # --- In-collective hop pipelining (DynamiQ arXiv 2602.08923,
    # EQuARX arXiv 2506.17615: the win is overlapping compressed hops
    # INSIDE the collective against compute, not just overlapping the
    # round as a whole). With pipeline_hops the butterfly's legs stop
    # being strictly sequential: gather-leg frames drain/decode/apply
    # on a background thread from round start, the owner's averaged
    # part is served as soon as the reduce finishes (before the scatter
    # barrier + EF store), and scatter parts are encoded/sent with at
    # most pipeline_depth parts in flight so encode(part i+1) overlaps
    # send(part i). OFF leaves every round byte-identical to the
    # sequential protocol; ON changes only wall-clock placement — the
    # averaged bytes, EF residuals, and audit transcripts are bit-exact
    # either way (pinned by tests/test_pipeline.py).
    pipeline_hops: bool = False
    # Max scatter parts concurrently in the encode/send window (>=1).
    pipeline_depth: int = 2
    powersgd_rank: int = 4
    # Run PowerSGD's Gram-Schmidt on the host (bit-stable IEEE f32 loop
    # order) instead of on device. Cross-peer basis agreement needs every
    # group member to orthogonalize identical averaged bytes identically;
    # device MGS guarantees that only on a homogeneous XLA backend, and a
    # volunteer swarm is exactly where jax/XLA builds differ — divergent
    # bases silently corrupt reconstructed gradients on every peer. Host
    # MGS is bit-stable across peers and costs O(m*r^2) on a rank-4
    # (m x 4) factor — noise next to the wire round-trip — so it is the
    # DEFAULT; flip off only for a fleet known to run one backend build.
    powersgd_host_orthogonalize: bool = True
    # AEAD-encrypt the all-reduce data plane under a per-round group key
    # distributed through the signed matchmaking confirmation
    # (swarm/crypto.py). The reference gets transport encryption from
    # libp2p's security handshake; ours is framing-level.
    encrypt_data_plane: bool = True
    delay_optimizer_step: bool = True  # task.py:129
    metrics_expiration: float = 600.0  # statistics_expiration, arguments.py:129-131
    # --- Byzantine defense (swarm/screening.py + swarm/health.py;
    # CHAOS.md "Defense in depth"). Signatures and strict parsing stop
    # forged/malformed traffic; the CONTENT layer — screening of
    # valid-but-wrong gradients, the sender-weight clamp, gossiped
    # signed strike receipts, verified aggregation, round repair and
    # evidence by reference — is armed on every swarm-speaking peer
    # (CollaborativeOptimizer.__init__); what follows are its
    # thresholds, not switches.
    # Norm/cosine outlier screening of scatter contributions at each
    # part owner (drop/keep, never reweight — surviving rounds stay
    # bit-identical to an honest-only round). Auto-skipped below
    # screen_min_senders weighted contributors (small swarms keep the
    # pre-screening semantics byte-for-byte).
    screen_min_senders: int = 4
    # never drop a majority (see screening.ScreenPolicy for the
    # calibration rationale on every threshold)
    screen_max_drop_frac: float = 0.49
    screen_norm_tolerance: float = 8.0
    screen_cosine_floor: float = -0.5
    # Clamp on sender-supplied frame weights (a single signed frame
    # claiming weight=1e9 otherwise drowns the swarm with no value
    # screen tripping): claims outside [0, max_peer_weight] are dropped
    # with an attributable strike. None -> target_batch_size (no single
    # peer can legitimately carry more than the whole swarm's target);
    # 0 disables the clamp.
    max_peer_weight: "float | None" = None
    # Attributable strikes gossip as Ed25519-signed receipts under
    # {run_id}_strikes, and verified remote receipts fold into the local
    # ledger (bounded influence: no issuer veto, and remote evidence
    # alone can never convict — health.py), this often (seconds).
    strike_gossip_period: float = 5.0
    # Verified aggregation (swarm/audit.py; CHAOS.md "Defense in
    # depth" row 7): each round a deterministic challenge derived from
    # the shared round id selects parts whose owner must serve a
    # signed audit transcript (the sender-signed inputs it averaged,
    # its drop-set, the accumulation order); any member replays the
    # weighted mean + the screen decisions and bit-compares against
    # the part it gathered. A mismatch is an owner-audit-fail strike
    # that gossips via the signed-receipt plane. audit_frac is the
    # per-part challenge probability per round: a challenged part
    # costs its owner the transcript (≈ the part's scatter traffic
    # re-served from its mailbox) and each auditor a fetch + full
    # re-verify/replay, so the default SAMPLES — every owner is
    # audited in expectation every ~1/frac rounds, which convicts a
    # persistent cheat within a few epochs at a quarter of the
    # bandwidth/CPU tax (the soaks and gates run frac=1.0 for
    # deterministic conviction-latency oracles). audit_ttl bounds how
    # long a transcript stays fetchable in the owner's mailbox. The
    # PowerSGD factor rounds ({run}_grads_p/_q) and periodic state
    # averaging ({run}_state) ride the same butterfly and the same
    # challenge/transcript/replay machinery, each under its own prefix.
    # A replayed-bytes-mismatch conviction in any phase has recomputed
    # the honest part bit-exactly, so the optimizer applies the
    # correction honest - served at that phase's application site
    # (swarm/repair.py; CHAOS.md "Round repair").
    audit_frac: float = 0.25
    audit_ttl: float = 120.0
    # BYTE bound on the audit worker's retained-round ring (the
    # pending RoundAudits hold signed frames + gathered part copies
    # that late repair/proofs need): oldest-first eviction with a
    # counted eviction, so flagship-size parts cannot balloon host
    # RAM under a slow audit. The round-count bound (8) still applies.
    audit_ring_bytes: int = 256 << 20
    # Evidence by reference (swarm/audit.EvidencePlane): evidence
    # bundles too large to embed inline in a proof receipt
    # (PROOF_MAX_BYTES) are parked chunked in the issuer's mailbox and
    # the receipt carries a sha256 digest + mailbox descriptor;
    # verifiers fetch under the hard byte/time budgets below
    # (hash-check before any sized allocation), replay, and re-serve
    # verified bundles for failover.
    # hard per-bundle byte budget a verifier will fetch (an oversize
    # descriptor claim is rejected before any allocation or I/O); the
    # flagship 502 MB part's bundle (~2x part bytes: transcript
    # frames + gather frames) sizes the default
    proof_fetch_max_bytes: int = 2 << 30
    # hard wall-clock budget for one bundle fetch, covering every
    # retry and failover server — the gossip fold blocks at most this
    # long per by-reference receipt
    proof_fetch_budget_s: float = 30.0
    # per-chunk mailbox-read attempts (exponential backoff between)
    # before a server is abandoned for the next one
    proof_fetch_retries: int = 3
    # Plausible-lead bound on progress-record EPOCH claims (the epoch
    # twin of the sample cap): a peer's claimed epoch may lead this
    # node's local epoch by at most this margin in the aggregate —
    # clamped always, struck (progress-overclaim) only beyond 100x
    # the bound, because honest peers legitimately run several epochs
    # ahead of a slow or partitioned node. 0 disables.
    progress_max_epoch_lead: int = 2
    # Absolute per-sender L2 norm ceiling in the gradient screen,
    # active at ANY sender count — it narrows the <4-sender gap where
    # leave-one-out screening must skip. Below the screen quorum the
    # drop is unstruck (2-peer unattributability preserved). 0
    # disables; size it well above the honest gradient envelope (the
    # bound is model- and scale-specific, hence no finite default).
    screen_abs_norm_ceiling: float = 0.0
    # Deterministic fault injection (swarm/chaos.py, CHAOS.md): a
    # FaultPlan as inline JSON ('{...}') or a path to a JSON file. The
    # plan wraps this peer's DHT transport with seeded message
    # drop/delay/duplication, payload corruption/truncation, bandwidth
    # throttles, timed blackouts (partitions) and crash-at-epoch — the
    # churn-soak harness (scripts/churn_soak.py) drives it. None (the
    # default) leaves the transport untouched; every swarm entry point
    # exposes it as --chaos-plan.
    chaos_plan: Optional[str] = None
    # Flight recorder (dalle_tpu/obs, OBSERVABILITY.md): append this
    # peer's round-lifecycle spans (matchmaking → allreduce phases →
    # apply → state averaging, plus state-transfer streams) as JSONL
    # rows whose trace ids are protocol ids — merge files from
    # several peers with scripts/trace_report.py for the cross-peer
    # round timeline. None (the default) records nothing and the
    # round paths stay byte-identical to the uninstrumented protocol.
    trace_file: Optional[str] = None
    # Byte cap on the in-memory flight ring behind the tracer (the
    # last-N-rounds dump a failure artifact wants). A trainer's ring also
    # keeps its set-up: the spans, a compile event a program (800 on a
    # host of four with an eager init) and a row a traced call of a Mosaic
    # call site are read after the run (the benchmark's per-layer set-up
    # metrics), 225 KB of rows on four chips, so the cap is twice that.
    trace_ring_kb: int = 512


@dataclass(frozen=True)
class PeerConfig:
    """Peer identity and networking (reference ``arguments.py:81-137``)."""

    host: str = "127.0.0.1"
    port: int = 0                      # 0 = ephemeral, like /ip4/0.0.0.0/tcp/0
    initial_peers: Tuple[str, ...] = ()
    client_mode: bool = False          # outbound-only peers (arguments.py:89-92)
    identity_path: Optional[str] = None  # persisted keypair (arguments.py:118-124)
    experiment_prefix: str = "dalle-tpu"
    statistics_expiration: float = 600.0
    # Access-token authorization (swarm/auth.py; reference
    # huggingface_auth.py:46-193): hex Ed25519 public key of the experiment
    # authority (None = open swarm) and the path to this peer's token file
    # issued by ``python -m dalle_tpu.cli.issue_token``.
    auth_authority: Optional[str] = None
    auth_token_path: Optional[str] = None
    # Rendezvous bootstrap (swarm/rendezvous.py) — the offline-exercisable
    # analogue of the reference's IPFS-assisted bootstrap (use_ipfs,
    # arguments.py:100-106): a shared file (NFS / mounted bucket) where
    # routable peers advertise and joiners with an empty initial_peers
    # list find their first contact; peers also advertise in the DHT
    # under {prefix}_rendezvous for list-repair after first contact.
    rendezvous_path: Optional[str] = None


@dataclass(frozen=True)
class ServingConfig:
    """Continuous-batching decode engine knobs (``dalle_tpu/serving/``).

    The reference has no serving path at all (its inference tool is a
    one-shot CLI); these knobs size the slot-recycled KV-cache engine
    that replaces whole-batch lockstep decode for online traffic.
    """

    # KV-cache slots = max concurrently decoding requests. The cache is
    # allocated once at this batch size; a finished slot is recycled
    # immediately from the request queue (image generation is fixed-
    # length, so staggered admission gives staggered completion).
    n_slots: int = 4
    # Decode positions advanced per jitted call. Admission, completion
    # harvest and metrics sampling happen at call boundaries, so this is
    # the scheduling granularity: smaller = finer admission latency,
    # larger = less host-loop overhead per token.
    steps_per_call: int = 8
    # Cap on KV-cache bytes the engine may OCCUPY concurrently; caps
    # admitted slots at floor(budget / bytes-per-slot) when set. The
    # cache itself is statically allocated at n_slots (XLA needs static
    # shapes) — the budget models co-tenancy pressure (HBM shared with a
    # trainer or a second engine) by bounding live occupancy.
    kv_budget_mb: Optional[int] = None
    # Prefix-bucket count for the statically-truncated cache reads
    # (models/decode.py resolve_buckets); None = the measured adaptive
    # choice for n_slots. Each bucket compiles one step variant.
    decode_buckets: Optional[int] = None
    # Cap on requests admitted per chunk boundary (None = all eligible).
    # The pipelined loop scatters each admission batch as ONE jitted
    # dispatch; bounding the burst keeps a cold start against a deep
    # queue from wedging one outsized scatter between chunks.
    admit_burst: Optional[int] = None
    # Fall back to the r8 host-synchronous loop: block on a device→host
    # position pull after every chunk instead of scheduling from the
    # deterministic host mirror. Exists as the A/B baseline for
    # scripts/engine_loop_bench.py and as a debug escape hatch — the
    # pulled values always equal the mirror, so this buys nothing but
    # the stall it measures.
    host_sync_loop: bool = False
    # Queued (not yet admitted) requests beyond this are rejected at
    # submit — backpressure instead of unbounded growth. The capacity
    # is shared across priority lanes.
    queue_capacity: int = 256
    # Starvation bound for the low priority lane: after this many
    # consecutive boundaries where the low lane had queued work but
    # every grant went high, one admission is reserved for it. None =
    # strict priority (the low lane may starve under sustained load).
    low_lane_bypass: Optional[int] = 8
    # Default per-request completion deadline (seconds from submit)
    # when the request carries none; None = no deadline (never shed).
    # A request whose predicted completion (queue depth x measured
    # decode cadence) misses its deadline is SHED at submit, before
    # any decode is spent.
    default_deadline_s: Optional[float] = None
    # Brownout hysteresis: degraded serving (front-end trims n_images
    # to brownout_max_images, pixel stage skips CLIP rerank) engages
    # once the queue sits at/above high_frac x queue_capacity for
    # hold_s seconds, and disengages at low_frac x queue_capacity.
    brownout_high_frac: float = 0.75
    brownout_low_frac: float = 0.25
    brownout_hold_s: float = 1.0
    brownout_max_images: int = 1
    # Prompt-prefix KV cache (serving/prefix_cache.py): pool the
    # teacher-forced text-segment KV per distinct prompt on device and
    # admit repeated prompts at pos = text_seq_len, skipping their
    # whole text prefill (bit-exact to the cold path — the text KV is
    # a pure function of the prompt; pinned by test). The value is the
    # pool's byte budget in MB (fixed-size entries, LRU eviction); when
    # kv_budget_mb is also set the pool is RESERVED out of it, so the
    # engine's total KV footprint stays under the one existing budget.
    # None (the default) disables the pool — admission byte-identical
    # to the r12 path.
    prefix_cache_mb: Optional[float] = None
    # Serving fault plan (serving/chaos.py ServeFaultPlan: inline JSON
    # or a file path). None = the bit-transparent clean path.
    chaos_plan: Optional[str] = None
    # How long a front-end waits on a request future before 504 (the
    # timeout also CANCELS the request mid-decode — slots are
    # reclaimed, not left decoding for a client that gave up).
    request_timeout_s: float = 300.0
    # stop(drain=True) bound: finish queued + in-flight work within this
    # window, then the engine thread is joined regardless.
    drain_timeout_s: float = 60.0
    # Serving front-end bind address (stdlib HTTP server).
    http_host: str = "127.0.0.1"
    http_port: int = 8080
    # Seconds between metrics JSONL snapshot rows (0 disables).
    metrics_interval_s: float = 5.0
    # Flight recorder (dalle_tpu/obs, OBSERVABILITY.md): append the
    # engine's request-lifecycle spans (submit → admit → first_code →
    # harvest → pixels → complete, trace id = the request id) plus
    # chunk-cadence spans as JSONL. None (the default) records
    # nothing; the engine loop pays one `is None` test.
    trace_file: Optional[str] = None
    trace_ring_kb: int = 256

    def validate(self) -> None:
        if self.n_slots < 1:
            raise ValueError(f"n_slots must be >= 1 (got {self.n_slots})")
        if self.steps_per_call < 1:
            raise ValueError(
                f"steps_per_call must be >= 1 (got {self.steps_per_call})")
        if self.queue_capacity < 1:
            raise ValueError(
                f"queue_capacity must be >= 1 (got {self.queue_capacity})")
        if self.admit_burst is not None and self.admit_burst < 1:
            raise ValueError(
                f"admit_burst must be >= 1 or None (got {self.admit_burst})")
        if self.low_lane_bypass is not None and self.low_lane_bypass < 1:
            raise ValueError(
                f"low_lane_bypass must be >= 1 or None "
                f"(got {self.low_lane_bypass})")
        if self.default_deadline_s is not None \
                and not self.default_deadline_s > 0:
            raise ValueError(
                f"default_deadline_s must be > 0 or None "
                f"(got {self.default_deadline_s})")
        if not 0.0 < self.brownout_high_frac <= 1.0:
            raise ValueError(
                f"brownout_high_frac must be in (0, 1] "
                f"(got {self.brownout_high_frac})")
        if not 0.0 <= self.brownout_low_frac < self.brownout_high_frac:
            raise ValueError(
                "brownout_low_frac must satisfy 0 <= low < high_frac "
                f"(got {self.brownout_low_frac})")
        if self.brownout_hold_s < 0:
            raise ValueError(
                f"brownout_hold_s must be >= 0 "
                f"(got {self.brownout_hold_s})")
        if self.brownout_max_images < 1:
            raise ValueError(
                f"brownout_max_images must be >= 1 "
                f"(got {self.brownout_max_images})")
        if self.prefix_cache_mb is not None \
                and not self.prefix_cache_mb > 0:
            raise ValueError(
                f"prefix_cache_mb must be > 0 or None "
                f"(got {self.prefix_cache_mb})")


@dataclass(frozen=True)
class AuxConfig:
    """Aux (monitor/checkpoint) peer knobs (reference ``arguments.py:140-165``)."""

    refresh_period: float = 10.0       # arguments.py:146
    checkpoint_dir: Optional[str] = None
    store_checkpoints: bool = True
    # Beyond-the-stub: the reference DECLARES this mode but its
    # implementation raises NotImplementedError (run_aux_peer.py:99-104).
    # Here it is real (swarm/assist.py): the aux peer joins every
    # gradient round as a weight-0 part owner — pure reduce/gather
    # bandwidth for the trainers, contributing no data. Unsupported (and
    # refused loudly) with grad_compression="power_sgd", whose wire
    # shapes an aux peer without a model cannot reproduce.
    assist_in_averaging: bool = False


LAYER_FULL_NOPE = "full_nope"      # causal over the whole sequence, no positions
LAYER_WINDOW_ROPE = "window_rope"  # causal inside ``window``, rotary
# causal over the whole sequence, rotary: which attention runs it is read
# from the configuration's fields (``kv_lora_rank``: latent attention; else
# grouped key-value heads, as the two kinds above)
LAYER_FULL_ROPE = "full_rope"
# no attention: a gated short convolution (``models/sparse_lm.ShortConv``)
LAYER_SHORT_CONV = "short_conv"
# grouped key-value heads with rotary over the whole sequence, each query
# over the keys its layer's indexer chose (``index_topk`` of those before
# it): a class that states an indexer has it (``KeyeLMConfig``)
LAYER_SELECTED_ROPE = "selected_rope"
# no attention: a Mamba-2 state-space mixer (``models/sparse_lm.Mamba2Mixer``:
# a scalar decay a head over a fixed-size state, trained by a chunked scan);
# a class that states its sizes has it (``NemotronHLMConfig``)
LAYER_MAMBA2 = "mamba2"
# no operator at all: the layer is its expert feed-forward alone. Only where
# a class's layers are one part each (``one_part_layers``)
LAYER_EXPERTS = "experts"
# no attention: a gated-delta-rule mixer (``models/sparse_lm.GatedDeltaMixer``:
# a linear attention whose (key x value) state a head is read back before it
# is written, ``v_t - S^T k_t``, under a scalar decay a head and token,
# trained by a chunked form); a class that states its sizes has it
# (``Qwen3NextLMConfig``)
LAYER_GATED_DELTA = "gated_delta"

VALID_LAYER_KINDS = (LAYER_FULL_NOPE, LAYER_WINDOW_ROPE, LAYER_FULL_ROPE,
                     LAYER_SHORT_CONV, LAYER_SELECTED_ROPE, LAYER_MAMBA2,
                     LAYER_EXPERTS, LAYER_GATED_DELTA)


@dataclass(frozen=True)
class SparseLMConfig:
    """A decoder-only language model whose every layer is a mixture of
    experts: RMSNorm, grouped key-value heads, per-layer operator kind
    (``layer_kinds``, cycled over the depth: three kinds of softmax
    attention and, for a class that states its length, a gated short
    convolution), a router that reads the
    normed layer input, gated-ReLU experts, an untied head
    (``models/sparse_lm.py``). Defaults are SmallThinker-21BA3B-Instruct
    (PowerInfer, config.json) cut to the share one of the 8 chips of a
    layer holds: one period of the layer pattern, experts 0-7 of 64, an
    eighth of the vocabulary; every width as published.

    The trainer's batches are ``text`` and ``image`` id fields: here the
    two halves of one token sequence, image ids offset by ``vocab_text``
    into the one vocabulary (``vocab_text + vocab_image == vocab_size``).
    """

    hidden_size: int = 2560
    num_hidden_layers: int = 4       # published 52: 13 periods of layer_kinds
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    expert_width: int = 768
    num_experts: int = 64            # what the router scores
    experts_per_token: int = 6
    # The experts this peer holds: ``experts_held`` consecutive ones from
    # ``expert_offset``. The layer routes over all ``num_experts`` and
    # computes the held experts' part of the result (published: all 64).
    experts_held: int = 8
    expert_offset: int = 0
    vocab_size: int = 18992          # published 151936: the slice's rows
    window: int = 4096
    layer_kinds: Tuple[str, ...] = (
        LAYER_FULL_NOPE, LAYER_WINDOW_ROPE, LAYER_WINDOW_ROPE,
        LAYER_WINDOW_ROPE)
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6
    router_softmax_over_chosen: bool = True
    tied_embeddings: bool = False
    router_input: str = "input_norm"
    attention_bias: bool = False
    # rows of the (tokens, vocab_size) logits alive at once in the head's
    # streaming cross-entropy
    head_chunk: int = 2048
    # the embedding's scale at init: no source pins it (models/sparse_lm.py
    # says what it decides: whether an untrained router reads tokens)
    embed_init_std: float = 1.0
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    text_seq_len: int = 4096
    image_grid: int = 64
    vocab_text: int = 9496
    vocab_image: int = 9496

    model_module: ClassVar[str] = "dalle_tpu.models.sparse_lm"
    # What models/sparse_lm.py reads besides, fixed for this class and
    # fields of ``AfmoeLMConfig``: class attributes here, so that
    # ``asdict`` of this class (benchmark/configs/smallthinker21b.json
    # holds it) keeps its keys.
    num_dense_layers: ClassVar[int] = 0
    dense_width: ClassVar[int] = 0
    num_shared_experts: ClassVar[int] = 0
    hidden_act: ClassVar[str] = "relu"
    score_func: ClassVar[str] = "softmax"
    selection_bias: ClassVar[bool] = False
    route_norm: ClassVar[bool] = False
    route_scale: ClassVar[float] = 1.0
    attention_gate: ClassVar[bool] = False
    qk_norm: ClassVar[bool] = False
    sandwich_norms: ClassVar[bool] = False
    mup_enabled: ClassVar[bool] = False
    # ... and fields of ``JoyAILMConfig``: latent attention (0: the three
    # projections of one width above) and prediction modules after the
    # last layer (0: one loss)
    q_lora_rank: ClassVar[int] = 0
    kv_lora_rank: ClassVar[int] = 0
    qk_nope_head_dim: ClassVar[int] = 0
    qk_rope_head_dim: ClassVar[int] = 0
    v_head_dim: ClassVar[int] = 0
    rope_interleave: ClassVar[bool] = False
    num_nextn_predict_layers: ClassVar[int] = 0
    mtp_loss_weight: ClassVar[float] = 0.0
    # ... and of ``Lfm2MoeLMConfig``: the taps of the layers of kind
    # ``short_conv`` (0: no such layer)
    conv_kernel: ClassVar[int] = 0
    conv_bias: ClassVar[bool] = False
    # ... and of ``KeyeLMConfig``: the indexer of the layers of kind
    # ``selected_rope`` (0 keys a query: no such layer), its loss's weight,
    # and the frequency pairs that read each of three position rows (none:
    # one row, position = index)
    index_topk: ClassVar[int] = 0
    index_heads: ClassVar[int] = 0
    index_head_dim: ClassVar[int] = 0
    index_chunk: ClassVar[int] = 0
    indexer_rotary: ClassVar[bool] = False
    indexer_loss_weight: ClassVar[float] = 0.0
    mrope_section: ClassVar[Tuple[int, ...]] = ()
    # ... and of ``NemotronHLMConfig``: layers that are ONE part behind one
    # norm (``layer_kinds`` then names a layer's part: a mixer or
    # ``experts``), experts of two products (no gate), a shared expert's
    # width of its own (0: ``num_shared_experts`` x ``expert_width``), and
    # the sizes of the layers of kind ``mamba2`` (0 heads: no such layer)
    one_part_layers: ClassVar[bool] = False
    expert_gated: ClassVar[bool] = True
    shared_expert_width: ClassVar[int] = 0
    mamba_num_heads: ClassVar[int] = 0
    mamba_head_dim: ClassVar[int] = 0
    ssm_groups: ClassVar[int] = 0
    ssm_state_size: ClassVar[int] = 0
    ssm_chunk: ClassVar[int] = 0
    residual_rescale_layers: ClassVar[int] = 0
    # ... and of ``Qwen3NextLMConfig``: the share of a head's lanes that
    # the rotary turns (1: all of them), a sigmoid gate on the shared
    # expert's output, and the sizes of the layers of kind ``gated_delta``
    # (0 heads: no such layer)
    partial_rotary_factor: ClassVar[float] = 1.0
    shared_expert_gate: ClassVar[bool] = False
    linear_num_key_heads: ClassVar[int] = 0
    linear_num_value_heads: ClassVar[int] = 0
    linear_key_head_dim: ClassVar[int] = 0
    linear_value_head_dim: ClassVar[int] = 0
    linear_conv_kernel_dim: ClassVar[int] = 0
    delta_chunk: ClassVar[int] = 0
    # ... and of ``OuroLMConfig``: the times the one stack of layers is run
    # on the one set of parameters (1: once, one exit), and the weight of
    # the exit distribution's entropy in the loss
    total_ut_steps: ClassVar[int] = 1
    exit_entropy_weight: ClassVar[float] = 0.0
    pass_input: ClassVar[str] = ""
    exit_gate_input: ClassVar[str] = ""
    exit_gate_bias: ClassVar[bool] = False
    exit_gate_init_std: ClassVar[float] = 0.0
    exit_loss: ClassVar[str] = ""
    # fields a configuration's file states and no entry point's flag sets:
    # what the source fixes and models/sparse_lm.py is written for
    # (``validate`` holds each to its one value), and the one assumption
    # about initialisation
    no_flag: ClassVar[Tuple[str, ...]] = (
        "router_softmax_over_chosen", "tied_embeddings", "router_input",
        "attention_bias", "embed_init_std")
    decode_missing: ClassVar[Optional[str]] = (
        "models/decode.py has no grouped key-value heads, no cache per "
        "layer kind (full / window) and no expert layer")

    @property
    def image_seq_len(self) -> int:
        return self.image_grid * self.image_grid

    @property
    def total_seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len

    def kind_of_layer(self, layer: int) -> str:
        return self.layer_kinds[layer % len(self.layer_kinds)]

    def optimizer_stacking(self) -> Dict[str, int]:
        return {"stacked_reps": 0, "stacked_experts": self.experts_held}

    @property
    def has_expert_layers(self) -> bool:
        """Whether any layer routes: a class that states no experts
        (``num_experts`` 0, every layer dense: ``OuroLMConfig``) has no
        router, no counters and no ``moe_*`` entry anywhere."""
        return self.num_experts > 0

    def layer_is_dense(self, layer: int) -> bool:
        """Whether ``layer``'s feed-forward is the dense gated block and
        not the expert layer (the leading ``num_dense_layers``)."""
        return layer < self.num_dense_layers

    @property
    def rotary_dim(self) -> int:
        """The lanes of a head that the rotary turns: its first
        ``partial_rotary_factor`` of ``head_dim``."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def shared_width(self) -> int:
        """The shared expert's width (0: none)."""
        return self.shared_expert_width \
            or self.num_shared_experts * self.expert_width

    def validate_shapes(self) -> None:
        # ``full_rope`` is any class's: grouped key-value heads with rotary
        # over the whole sequence, or latent attention where the class
        # states ``kv_lora_rank``
        for kind in self.layer_kinds:
            if kind not in VALID_LAYER_KINDS:
                raise ValueError(f"unknown layer kind {kind!r}")
            if kind == LAYER_SHORT_CONV and self.conv_kernel < 1:
                raise ValueError(
                    f"layer kind {kind!r} is a short convolution: a class "
                    "that states its length has it (Lfm2MoeLMConfig)")
            if kind == LAYER_SELECTED_ROPE and self.index_topk < 1:
                raise ValueError(
                    f"layer kind {kind!r} attends over the keys an indexer "
                    "chose: a class that states one has it (KeyeLMConfig)")
            if kind == LAYER_MAMBA2 and self.mamba_num_heads < 1:
                raise ValueError(
                    f"layer kind {kind!r} is a state-space mixer: a class "
                    "that states its sizes has it (NemotronHLMConfig)")
            if kind == LAYER_GATED_DELTA and self.linear_num_value_heads < 1:
                raise ValueError(
                    f"layer kind {kind!r} is a gated-delta-rule mixer: a "
                    "class that states its sizes has it (Qwen3NextLMConfig)")
            if kind == LAYER_EXPERTS and not self.one_part_layers:
                raise ValueError(
                    f"layer kind {kind!r} is a feed-forward with no "
                    "operator: a class whose layers are one part each has "
                    "it (NemotronHLMConfig)")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if self.vocab_text + self.vocab_image != self.vocab_size:
            raise ValueError("vocab_text + vocab_image must equal vocab_size")
        if not (0 <= self.expert_offset
                and self.expert_offset + self.experts_held
                <= self.num_experts):
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are "
                f"not among the router's {self.num_experts}")
        if self.experts_per_token > self.num_experts:
            raise ValueError("experts_per_token exceeds num_experts")

    def validate(self) -> None:
        self.validate_shapes()
        if self.router_input != "input_norm":
            raise ValueError(
                f"router_input {self.router_input!r}: the router reads the "
                "normed layer input ('input_norm')")
        if (self.tied_embeddings or self.attention_bias
                or not self.router_softmax_over_chosen):
            raise ValueError(
                "this class has an untied head, no attention bias and a "
                "softmax over the chosen experts only")


def smallthinker21b_model_config(**overrides: Any) -> SparseLMConfig:
    """Preset ``smallthinker21b``: the cell ``smallthinker21b-train-solo``
    (benchmark/configs/smallthinker21b.json holds ``asdict`` of it)."""
    return dataclasses.replace(SparseLMConfig(), **overrides)


@dataclass(frozen=True)
class AfmoeLMConfig(SparseLMConfig):
    """``SparseLMConfig`` with the mechanisms of ``model_type`` ``afmoe``
    as fields (the same ``models/sparse_lm.py`` reads each): leading dense
    gated layers before the expert layers, a shared expert every token
    takes beside the routed ones, gated-SiLU blocks, a router that reads
    the post-attention norm, scores by sigmoid, selects on score + a bias
    that is no trained parameter and weighs by the unbiased scores of the
    chosen (normalised, scaled), attention's output gated by
    ``sigmoid(W_g a)`` over RMS-normed queries and keys, four norms a
    layer, the embedding's output times sqrt(hidden_size). Defaults are
    Trinity-Mini (arcee-ai, config.json) cut to the share one of the 16
    chips of a layer holds: the first dense layer and one period of expert
    layers (``layer_kinds`` names all five: published layers 0 and 4-7),
    experts 0-7 of 128, an eighth of the vocabulary; every width as
    published."""

    hidden_size: int = 2048
    num_hidden_layers: int = 5       # published 32: 2 dense + 30 expert
    num_heads: int = 32
    num_kv_heads: int = 4
    expert_width: int = 1024
    num_experts: int = 128
    experts_per_token: int = 8
    vocab_size: int = 25024          # published 200192
    window: int = 2048
    layer_kinds: Tuple[str, ...] = (
        LAYER_WINDOW_ROPE, LAYER_WINDOW_ROPE, LAYER_WINDOW_ROPE,
        LAYER_WINDOW_ROPE, LAYER_FULL_NOPE)
    rope_theta: float = 1e4
    rms_eps: float = 1e-5
    router_softmax_over_chosen: bool = False
    router_input: str = "post_attention_norm"
    # assumed, as the parent class's and for its reason: at 0.02 (the
    # family's initializer range; 0.9 after ``mup_enabled``) the normed
    # attention output, a prefix mean alike for a sequence's tokens,
    # weighs as much as the token and an untrained router sends a whole
    # sequence to few experts (PERF.md section 6, PR 33)
    embed_init_std: float = 1.0
    vocab_text: int = 12512
    vocab_image: int = 12512
    num_dense_layers: int = 1        # published 2
    dense_width: int = 6144
    num_shared_experts: int = 1      # of expert_width each
    hidden_act: str = "silu"
    score_func: str = "sigmoid"
    selection_bias: bool = True
    route_norm: bool = True
    route_scale: float = 2.826
    attention_gate: bool = True
    qk_norm: bool = True
    sandwich_norms: bool = True
    mup_enabled: bool = True

    no_flag: ClassVar[Tuple[str, ...]] = SparseLMConfig.no_flag + (
        "num_shared_experts", "hidden_act", "score_func", "selection_bias",
        "route_norm", "route_scale", "attention_gate", "qk_norm",
        "sandwich_norms", "mup_enabled")
    decode_missing: ClassVar[Optional[str]] = (
        "models/decode.py has no grouped key-value heads, no cache per "
        "layer kind (full / window), no gated attention over normed "
        "queries and keys, no dense gated block and no expert layer with "
        "a shared expert")

    def validate(self) -> None:
        self.validate_shapes()
        if self.tied_embeddings or self.attention_bias:
            raise ValueError(
                "this class has an untied head and no attention bias")
        self.validate_blocks_and_router()

    def validate_blocks_and_router(self) -> None:
        if not 0 <= self.num_dense_layers < self.num_hidden_layers:
            raise ValueError(
                "num_dense_layers must leave an expert layer (the step's "
                "counters are the expert layers')")
        if self.num_dense_layers and self.dense_width <= 0:
            raise ValueError("dense layers need a dense_width")
        # a gated block's activation, or the square of ReLU between an
        # ungated one's two products
        acts = ("relu", "silu") if self.expert_gated else ("relu2",)
        if self.hidden_act not in acts:
            raise ValueError(f"unknown hidden_act {self.hidden_act!r}")
        if self.router_input != "post_attention_norm":
            raise ValueError(
                f"router_input {self.router_input!r}: the router reads the "
                "norm the experts read ('post_attention_norm')")
        softmax = self.score_func == "softmax"
        if self.score_func not in ("softmax", "sigmoid") \
                or softmax != self.router_softmax_over_chosen:
            raise ValueError(
                "score_func is 'softmax' (over the chosen: "
                "router_softmax_over_chosen) or 'sigmoid' (not)")
        if softmax and (self.selection_bias or self.route_norm
                        or self.route_scale != 1.0):
            raise ValueError(
                "a selection bias, route_norm and route_scale belong to "
                "the sigmoid router")


def trinitymini_model_config(**overrides: Any) -> AfmoeLMConfig:
    """Preset ``trinitymini``: the cell ``trinitymini-train-solo``
    (benchmark/configs/trinitymini.json holds ``asdict`` of it)."""
    return dataclasses.replace(AfmoeLMConfig(), **overrides)


@dataclass(frozen=True)
class JoyAILMConfig(AfmoeLMConfig):
    """``AfmoeLMConfig``'s router, shared expert and leading dense layer
    (no output gate, no head norms, two norms a layer, no embedding scale)
    with the two mechanisms of ``model_type`` ``joyai_llm_flash`` as
    fields: **latent attention** in every layer (queries through a normed
    latent of ``q_lora_rank``; keys and values from a normed latent of
    ``kv_lora_rank``; a head's query-key width is ``qk_nope_head_dim`` +
    ``qk_rope_head_dim``, the rotary part of the key is ONE head that every
    query head reads; values are ``v_head_dim`` wide; rotary on interleaved
    pairs, causal over the whole sequence: ``full_rope``), and
    ``num_nextn_predict_layers`` **prediction modules** after the last
    layer (one more expert layer on ``[norm(E[t+1]) ; norm(z)] . W_eh``
    through the same embedding and head, its loss weighted
    ``mtp_loss_weight``). Defaults are JoyAI-LLM-Flash (jdopensource,
    config.json) cut to the share one of the 32 chips of a layer holds:
    the dense layer and four expert layers (published 0-4), experts 0-7 of
    256, an eighth of the vocabulary, the prediction module; every width
    as published. ``head_dim`` and ``num_kv_heads`` are the source's keys
    (64, 32) and nothing here reads them; ``window`` is no layer's."""

    num_hidden_layers: int = 5       # published 40: 1 dense + 39 expert
    num_kv_heads: int = 32
    head_dim: int = 64
    expert_width: int = 768
    num_experts: int = 256
    vocab_size: int = 16160          # published 129280
    window: int = 0
    layer_kinds: Tuple[str, ...] = (LAYER_FULL_ROPE,)
    rope_theta: float = 3.2e7
    rms_eps: float = 1e-6
    vocab_text: int = 8080
    vocab_image: int = 8080
    dense_width: int = 7168
    route_scale: float = 2.5
    attention_gate: bool = False
    qk_norm: bool = False
    sandwich_norms: bool = False
    mup_enabled: bool = False
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_interleave: bool = True
    num_nextn_predict_layers: int = 1
    # assumed: config.json gives no weight; 0.3 is the DeepSeek-V3
    # report's for most of its run (section 4.2)
    mtp_loss_weight: float = 0.3

    # the head's three widths are the source's and the only ones the
    # blockwise kernels take
    no_flag: ClassVar[Tuple[str, ...]] = AfmoeLMConfig.no_flag + (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rope_interleave", "mtp_loss_weight")
    decode_missing: ClassVar[Optional[str]] = (
        "models/decode.py has no latent attention (no cache of the "
        "compressed keys and values and the one rotary key, no absorbed "
        "form), no dense gated block, no expert layer with a shared expert "
        "and no use for a prediction module")

    def validate(self) -> None:
        super().validate()
        if min(self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
               self.qk_rope_head_dim, self.v_head_dim) <= 0 \
                or self.qk_rope_head_dim % 2:
            raise ValueError(
                "latent attention needs q_lora_rank, kv_lora_rank, "
                "qk_nope_head_dim, v_head_dim and an even qk_rope_head_dim")
        if not self.rope_interleave:
            raise ValueError(
                "models/sparse_lm.py rotates latent attention's rotary part "
                "on interleaved pairs (rope_interleave)")
        if self.attention_gate or self.qk_norm:
            raise ValueError("latent attention has no output gate and no "
                             "head norms")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("models/sparse_lm.py has one prediction module "
                             "or none")
        if self.num_nextn_predict_layers and self.total_seq_len < 3:
            raise ValueError("a prediction module needs three tokens")


def joyaiflash_model_config(**overrides: Any) -> JoyAILMConfig:
    """Preset ``joyaiflash``: the cell ``joyaiflash-train-solo``
    (benchmark/configs/joyaiflash.json holds ``asdict`` of it)."""
    return dataclasses.replace(JoyAILMConfig(), **overrides)


@dataclass(frozen=True)
class Lfm2MoeLMConfig(AfmoeLMConfig):
    """``AfmoeLMConfig``'s sigmoid router with a selection bias, leading
    dense layer and head norms (no shared expert, no output gate, two norms
    a layer, no embedding scale) with the mechanisms of ``model_type``
    ``lfm2_moe`` as fields: in the layers of kind ``short_conv`` the
    operator is no attention but a **gated short convolution** (``[B ; C ;
    u] = a . W_in``, a causal depthwise convolution of ``conv_kernel`` taps
    over ``B * u``, ``(C * z) . W_out``; ``conv_bias``: none), the layers of
    kind ``full_rope`` are grouped-query attention with rotary over the
    whole sequence on normed 64-wide heads (two a lane tile), and the head
    is the embedding's table (``tied_embeddings``). Defaults are
    LFM2-8B-A1B (LiquidAI, config.json) cut to the share one of the 4 chips
    of a layer holds: the first dense layer and one period of expert layers
    (``layer_kinds`` names all five: published layers 0 and 2-5), experts
    0-7 of 32, a quarter of the vocabulary; every width as published.
    ``window`` is no layer's."""

    num_kv_heads: int = 8
    head_dim: int = 64
    expert_width: int = 1792
    num_experts: int = 32
    experts_per_token: int = 4
    vocab_size: int = 16384          # published 65536
    window: int = 0
    layer_kinds: Tuple[str, ...] = (
        LAYER_SHORT_CONV, LAYER_FULL_ROPE, LAYER_SHORT_CONV,
        LAYER_SHORT_CONV, LAYER_SHORT_CONV)
    rope_theta: float = 1e6
    tied_embeddings: bool = True
    vocab_text: int = 8192
    vocab_image: int = 8192
    num_dense_layers: int = 1        # published 2
    dense_width: int = 7168
    num_shared_experts: int = 0
    route_scale: float = 1.0
    attention_gate: bool = False
    sandwich_norms: bool = False
    mup_enabled: bool = False
    conv_kernel: int = 3             # the source's conv_L_cache
    conv_bias: bool = False

    no_flag: ClassVar[Tuple[str, ...]] = AfmoeLMConfig.no_flag + (
        "conv_bias",)
    decode_missing: ClassVar[Optional[str]] = (
        "models/decode.py has no short convolution (no state of its last "
        "conv_kernel - 1 tokens beside the cache), no grouped key-value "
        "heads with head norms and rotary, no dense gated block and no "
        "expert layer")

    def validate(self) -> None:
        self.validate_shapes()
        self.validate_blocks_and_router()
        if self.attention_bias or self.conv_bias:
            raise ValueError(
                "models/sparse_lm.py has no attention bias and no bias in "
                "the short convolution or its projections")


def lfm2moe_model_config(**overrides: Any) -> Lfm2MoeLMConfig:
    """Preset ``lfm2moe``: the cell ``lfm2moe-train-solo``
    (benchmark/configs/lfm2moe.json holds ``asdict`` of it)."""
    return dataclasses.replace(Lfm2MoeLMConfig(), **overrides)


@dataclass(frozen=True)
class KeyeLMConfig(AfmoeLMConfig):
    """``AfmoeLMConfig``'s gated-SiLU experts behind a softmax router over
    the chosen that reads the post-attention norm (no dense layer, no
    shared expert, no output gate, two norms a layer, no embedding scale,
    head norms) with the mechanisms of ``model_type`` ``KeyeVL2``'s language
    model as fields: every layer is of kind ``selected_rope``, grouped-query
    attention in which **a query attends to the keys its layer's indexer
    chose** (``index_heads`` heads of ``index_head_dim`` over ONE key head
    score every earlier key, ``relu`` a head, weighted a query and head;
    the ``index_topk`` largest are the query's set; the indexer reads the
    layer's normed input with the gradient stopped and learns from a loss
    of its own, the KL from the heads' mean attention over the set to the
    softmax of its scores there, weight ``indexer_loss_weight``), and the
    rotary reads **three position rows** (``mrope_section``: of a head's
    64 frequency pairs the first 16 read row 0, the next 24 row 1, the
    last 24 row 2; the indexer's own rotary reads row 0). Defaults are
    Keye-VL-2.0-30B-A3B (Kwai-Keye, config.json; the vision tower is not
    this model's) cut to the share one of the 16 chips of a layer holds:
    7 of 48 alike layers, experts 0-7 of 128, an eighth of the vocabulary;
    every width as published. ``window`` is no layer's; ``index_chunk`` is
    the rows of scores the selection and the loss hold at a time (the
    source's ``q_chunk_size``: it changes no number)."""

    num_hidden_layers: int = 7       # published 48, all alike
    expert_width: int = 768
    vocab_size: int = 18992          # published 151936
    window: int = 0
    layer_kinds: Tuple[str, ...] = (LAYER_SELECTED_ROPE,)
    rope_theta: float = 1e7
    rms_eps: float = 1e-6
    router_softmax_over_chosen: bool = True
    vocab_text: int = 9496
    vocab_image: int = 9496
    num_dense_layers: int = 0
    dense_width: int = 0
    num_shared_experts: int = 0
    score_func: str = "softmax"
    selection_bias: bool = False
    route_norm: bool = False
    route_scale: float = 1.0
    attention_gate: bool = False
    sandwich_norms: bool = False
    mup_enabled: bool = False
    index_topk: int = 2048
    index_heads: int = 16
    index_head_dim: int = 64
    index_chunk: int = 512
    # assumed, each with its reason in benchmark/configs/keyevl2.json
    indexer_rotary: bool = True
    indexer_loss_weight: float = 1.0
    mrope_section: Tuple[int, ...] = (16, 24, 24)

    no_flag: ClassVar[Tuple[str, ...]] = AfmoeLMConfig.no_flag + (
        "indexer_rotary", "indexer_loss_weight", "mrope_section")
    decode_missing: ClassVar[Optional[str]] = (
        "models/decode.py has no indexer (no cache of its one key head, no "
        "selection of a query's keys at decode time), no grouped key-value "
        "heads with head norms and three-row rotary and no expert layer")

    def validate(self) -> None:
        super().validate()
        if self.num_dense_layers:
            raise ValueError("every layer of this class is an expert layer")
        if min(self.index_heads, self.index_head_dim, self.index_chunk) < 1:
            raise ValueError("the indexer needs index_heads, index_head_dim "
                             "and index_chunk")
        if self.index_head_dim % 2 or not self.indexer_rotary:
            raise ValueError(
                "models/sparse_lm.py rotates the indexer's queries and key "
                "(rotate-half over an even index_head_dim)")
        if sum(self.mrope_section) * 2 != self.head_dim \
                or len(self.mrope_section) != 3:
            raise ValueError(
                "mrope_section names the frequency pairs of three position "
                "rows: its sum is head_dim / 2")


def keyevl2_model_config(**overrides: Any) -> KeyeLMConfig:
    """Preset ``keyevl2``: the cell ``keyevl2-train-solo``
    (benchmark/configs/keyevl2.json holds ``asdict`` of it)."""
    return dataclasses.replace(KeyeLMConfig(), **overrides)


@dataclass(frozen=True)
class NemotronHLMConfig(AfmoeLMConfig):
    """``AfmoeLMConfig``'s sigmoid router with a selection bias, normalised
    weights, a scale and a shared expert beside the routed ones (no dense
    layer, no output gate, no head norms, no embedding scale, no positions
    anywhere) with the mechanisms of ``model_type`` ``nemotron_h`` as
    fields: **every layer is one part behind one norm**
    (``one_part_layers``: ``h + part(rmsnorm(h))``; ``layer_kinds`` names
    each layer's part, cycled over the depth), the parts being a **Mamba-2
    state-space mixer** (kind ``mamba2``: ``mamba_num_heads`` heads of
    ``mamba_head_dim``, ``ssm_groups`` groups of B and C of
    ``ssm_state_size``, ``conv_kernel`` causal depthwise taps with a bias
    and a SiLU, a scalar decay a head, a gated RMS norm over each group's
    lanes; every part's output projection drawn as the source's
    ``rescale_prenorm_residual`` has it, ``residual_rescale_layers``;
    trained by a chunked scan of ``ssm_chunk`` tokens), softmax
    attention over the whole sequence with no positions (``full_nope``), and
    the expert feed-forward alone (``experts``) whose experts are **two
    products, not gated** (``expert_gated`` false: ``W_down relu(W_up
    m)^2``, ``hidden_act`` ``relu2``), the shared expert of a width of its
    own (``shared_expert_width``). Defaults are the 52-layer stack of
    Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 (nvidia, config.json; the
    denoiser tower its description speaks of has no key there and is not
    this model's) cut to the share one of the 16 chips of a layer holds:
    published layers 0-6 (``MEMEM*E``, one turn of the pattern's 7-layer
    cycle), experts 0-7 of 128, an eighth of the vocabulary; every width as
    published. ``window`` and ``rope_theta`` are no layer's."""

    hidden_size: int = 2688
    num_hidden_layers: int = 7       # published 52; 3 M, 3 E, 1 *
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    expert_width: int = 1856
    num_experts: int = 128
    experts_per_token: int = 6
    vocab_size: int = 16384          # published 131072
    window: int = 0
    layer_kinds: Tuple[str, ...] = (
        LAYER_MAMBA2, LAYER_EXPERTS, LAYER_MAMBA2, LAYER_EXPERTS,
        LAYER_MAMBA2, LAYER_FULL_NOPE, LAYER_EXPERTS)
    rope_theta: float = 1e4          # the source's key; nothing reads it
    rms_eps: float = 1e-5
    vocab_text: int = 8192
    vocab_image: int = 8192
    num_dense_layers: int = 0
    dense_width: int = 0
    num_shared_experts: int = 1
    hidden_act: str = "relu2"
    route_scale: float = 2.5
    attention_gate: bool = False
    qk_norm: bool = False
    sandwich_norms: bool = False
    mup_enabled: bool = False
    conv_kernel: int = 4
    conv_bias: bool = True
    one_part_layers: bool = True
    expert_gated: bool = False
    shared_expert_width: int = 3712
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_groups: int = 8              # the source's n_groups
    ssm_state_size: int = 128
    ssm_chunk: int = 128             # the source's chunk_size
    # the source's rescale_prenorm_residual: at init every layer's one
    # output projection (a mixer's, attention's, the experts' and the shared
    # expert's ``down``) is divided by the root of the PUBLISHED depth,
    # which a cut of the layers does not change (0: no rescale)
    residual_rescale_layers: int = 52

    no_flag: ClassVar[Tuple[str, ...]] = AfmoeLMConfig.no_flag + (
        "conv_bias", "one_part_layers", "expert_gated",
        "residual_rescale_layers")
    decode_missing: ClassVar[Optional[str]] = (
        "models/decode.py has no state-space mixer (kind 'mamba2': no cache "
        "of the recurrence's state and of the convolution's last conv_kernel "
        "- 1 tokens), no layer that is one part alone, no grouped key-value "
        "heads and no expert layer with a shared expert")

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_lanes(self) -> int:
        """x, B and C side by side: what the depthwise taps run over."""
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state_size

    def validate(self) -> None:
        self.validate_shapes()
        self.validate_blocks_and_router()
        if self.attention_bias or self.tied_embeddings:
            raise ValueError(
                "this class has an untied head and no attention bias")
        if not self.one_part_layers or self.expert_gated \
                or self.hidden_act != "relu2":
            raise ValueError(
                "this class's layers are one part each and its experts two "
                "products with relu^2 between (hidden_act 'relu2')")
        if self.num_dense_layers or self.sandwich_norms \
                or self.attention_gate or self.qk_norm or self.mup_enabled:
            raise ValueError(
                "a one-part layer has one norm, and this class no dense "
                "layer, output gate, head norm or embedding scale")
        if LAYER_EXPERTS not in [
                self.kind_of_layer(i) for i in range(self.num_hidden_layers)]:
            raise ValueError("layer_kinds must leave an expert layer (the "
                             "step's counters are the expert layers')")
        if any(k not in (LAYER_MAMBA2, LAYER_FULL_NOPE, LAYER_EXPERTS)
               for k in self.layer_kinds):
            raise ValueError(
                "a one-part layer is 'mamba2', 'full_nope' or 'experts' "
                "(the family's attention has no positions)")
        if min(self.mamba_num_heads, self.mamba_head_dim, self.ssm_groups,
               self.ssm_state_size, self.ssm_chunk, self.conv_kernel) < 1 \
                or self.mamba_num_heads % self.ssm_groups:
            raise ValueError(
                "the state-space mixer needs mamba_num_heads (a multiple of "
                "ssm_groups), mamba_head_dim, ssm_state_size, ssm_chunk and "
                "conv_kernel")


def twotower30b_model_config(**overrides: Any) -> NemotronHLMConfig:
    """Preset ``twotower30b``: the cell ``twotower30b-train-solo``
    (benchmark/configs/twotower30b.json holds ``asdict`` of it)."""
    return dataclasses.replace(NemotronHLMConfig(), **overrides)

@dataclass(frozen=True)
class Qwen3NextLMConfig(AfmoeLMConfig):
    """``AfmoeLMConfig``'s gated-SiLU experts behind a softmax router over
    the chosen that reads the post-attention norm, with a shared expert
    beside the routed ones and a sigmoid gate on the attention's output over
    RMS-normed queries and keys (no dense layer, no selection bias, two
    norms a layer, no embedding scale) with the mechanisms of ``model_type``
    ``qwen3_next`` as fields: in the layers of kind ``gated_delta`` the
    operator is no attention but a **gated-delta-rule mixer**
    (``linear_num_key_heads`` query/key heads of ``linear_key_head_dim``,
    each serving ``linear_num_value_heads / linear_num_key_heads`` value
    heads of ``linear_value_head_dim``; ``[q ; k ; v]`` through a depthwise
    causal convolution of ``linear_conv_kernel_dim`` taps with no bias and a
    SiLU; L2-normalised queries and keys; a (key x value) state a value head
    that a scalar decay ``exp(g_t)`` shrinks and the delta rule ``S +=
    k beta (v - S^T k)^T`` writes; an RMS norm over each head's lanes
    BEFORE the ``silu(z)`` gate; trained in chunks of ``delta_chunk``
    tokens), the layers of kind ``full_rope`` are grouped-query attention on
    heads of 256 lanes, two lane tiles, of which the rotary turns the first
    ``partial_rotary_factor`` (64 lanes, halves of 32) and leaves the rest,
    and the shared expert's output is times ``sigmoid(w_g . m)``
    (``shared_expert_gate``). Defaults are Qwen3-Next-80B-A3B-Instruct
    (Qwen, config.json; the prediction module its description speaks of has
    no key there and is not this model's) cut to the share one of the 32
    chips of a layer holds: published layers 0-3 (three ``gated_delta`` and
    one ``full_rope``: ``full_attention_interval`` 4), experts 0-15 of 512,
    an eighth of the vocabulary; every width as published. ``window`` is no
    layer's."""

    num_hidden_layers: int = 4       # published 48: 12 periods of layer_kinds
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    expert_width: int = 512
    num_experts: int = 512
    experts_per_token: int = 10
    experts_held: int = 16
    vocab_size: int = 18992          # published 151936
    window: int = 0
    layer_kinds: Tuple[str, ...] = (
        LAYER_GATED_DELTA, LAYER_GATED_DELTA, LAYER_GATED_DELTA,
        LAYER_FULL_ROPE)
    rope_theta: float = 1e7
    rms_eps: float = 1e-6
    router_softmax_over_chosen: bool = True
    vocab_text: int = 9496
    vocab_image: int = 9496
    num_dense_layers: int = 0
    dense_width: int = 0
    num_shared_experts: int = 1      # of shared_expert_intermediate_size 512
    score_func: str = "softmax"
    selection_bias: bool = False
    route_norm: bool = False
    route_scale: float = 1.0
    sandwich_norms: bool = False
    mup_enabled: bool = False
    partial_rotary_factor: float = 0.25
    shared_expert_gate: bool = True
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # how the recurrence is computed, not a change of it
    delta_chunk: int = 64

    no_flag: ClassVar[Tuple[str, ...]] = AfmoeLMConfig.no_flag + (
        "shared_expert_gate",)
    decode_missing: ClassVar[Optional[str]] = (
        "models/decode.py has no gated-delta-rule mixer (kind 'gated_delta': "
        "no cache of the (key x value) state a head and of the convolution's "
        "last taps' tokens), no grouped key-value heads of 256 lanes with a "
        "partial rotary, head norms and an output gate, and no expert layer "
        "with a gated shared expert")

    @property
    def linear_key_lanes(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def linear_value_lanes(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def linear_conv_lanes(self) -> int:
        """q, k and v side by side: what the depthwise taps run over."""
        return 2 * self.linear_key_lanes + self.linear_value_lanes

    def validate(self) -> None:
        super().validate()
        if self.num_dense_layers or self.sandwich_norms or self.mup_enabled \
                or not (self.attention_gate and self.qk_norm):
            raise ValueError(
                "this class has no dense layer, two norms a layer, no "
                "embedding scale, and a gated attention over normed queries "
                "and keys")
        if any(k not in (LAYER_GATED_DELTA, LAYER_FULL_ROPE)
               for k in self.layer_kinds):
            raise ValueError(
                "a layer of this class is 'gated_delta' or 'full_rope'")
        if not self.num_shared_experts or not self.shared_expert_gate:
            raise ValueError("this class has a gated shared expert")
        rotary = self.head_dim * self.partial_rotary_factor
        if rotary != int(rotary) or int(rotary) % 2 or not 0 < rotary \
                <= self.head_dim:
            raise ValueError(
                "partial_rotary_factor x head_dim is the even number of a "
                "head's lanes that the rotary turns")
        chunk = self.delta_chunk
        if min(self.linear_num_key_heads, self.linear_num_value_heads,
               self.linear_key_head_dim, self.linear_value_head_dim,
               self.linear_conv_kernel_dim, chunk) < 1 \
                or self.linear_num_value_heads % self.linear_num_key_heads \
                or chunk & (chunk - 1):
            raise ValueError(
                "the gated-delta-rule mixer needs linear_num_value_heads (a "
                "multiple of linear_num_key_heads), linear_key_head_dim, "
                "linear_value_head_dim, linear_conv_kernel_dim and a "
                "delta_chunk that is a power of two")


def qwen3next80b_model_config(**overrides: Any) -> Qwen3NextLMConfig:
    """Preset ``qwen3next80b``: the cell ``qwen3next80b-train-solo``
    (benchmark/configs/qwen3next80b.json holds ``asdict`` of it)."""
    return dataclasses.replace(Qwen3NextLMConfig(), **overrides)


@dataclass(frozen=True)
class OuroLMConfig(AfmoeLMConfig):
    """``AfmoeLMConfig``'s four-norm layer with a dense gated-SiLU
    feed-forward in EVERY layer (``num_dense_layers`` is the depth:
    **no expert layer**, so ``num_experts``, ``experts_held``,
    ``experts_per_token`` and ``expert_width`` are 0 and the router's fields
    are read by nothing; no output gate, no head norms, no embedding scale)
    around multi-head attention of kind ``full_rope`` (16 / 16 heads of 128,
    rotary over all of a head's lanes), with the mechanism of ``model_type``
    ``ouro`` as fields: **the one stack of layers is run ``total_ut_steps``
    times on one set of parameters**. The final norm closes every pass and
    its output is both the next pass's input (``pass_input``) and that
    pass's exit: an exit gate ``lam_t = sigmoid(z_t . w_g + b_g)``
    (``exit_gate_input``, ``exit_gate_bias``) makes a row's exit
    distribution ``p_t = lam_t prod_{j<t} (1 - lam_j)``, the last pass
    taking what is left, the head is read after every pass, and the loss is
    the rows' mean of ``sum_t p_t nll_t - exit_entropy_weight H(p)``
    (``exit_loss``). Defaults are Ouro-2.6B (ByteDance, config.json; the
    objective from the family's description, arXiv:2510.25741) cut for one
    chip: 6 of the 48 layers (the period is one layer; 8 do not fit beside
    the loop's carried state: PERF.md section 6, PR 67), half of the
    vocabulary; every width and the four passes as published. ``window`` is
    no layer's."""

    hidden_size: int = 2048
    num_hidden_layers: int = 6       # published 48, every one alike
    num_heads: int = 16
    num_kv_heads: int = 16
    head_dim: int = 128
    expert_width: int = 0
    num_experts: int = 0
    experts_per_token: int = 0
    experts_held: int = 0
    vocab_size: int = 24576          # published 49152
    window: int = 0
    layer_kinds: Tuple[str, ...] = (LAYER_FULL_ROPE,)
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    vocab_text: int = 12288
    vocab_image: int = 12288
    num_dense_layers: int = 6        # every layer: the source has no experts
    dense_width: int = 5632          # the source's intermediate_size
    num_shared_experts: int = 0
    selection_bias: bool = False
    route_norm: bool = False
    route_scale: float = 1.0
    attention_gate: bool = False
    qk_norm: bool = False
    mup_enabled: bool = False
    total_ut_steps: int = 4
    # assumed, each held to its one value by ``validate`` (config.json has
    # no key for them; the configuration's file gives the reasons)
    pass_input: str = "final_norm"
    exit_gate_input: str = "final_norm"
    exit_gate_bias: bool = True
    exit_gate_init_std: float = 0.02
    exit_loss: str = "expected_nll_minus_entropy"
    exit_entropy_weight: float = 0.1

    no_flag: ClassVar[Tuple[str, ...]] = AfmoeLMConfig.no_flag + (
        "pass_input", "exit_gate_input", "exit_gate_bias",
        "exit_gate_init_std", "exit_loss")
    decode_missing: ClassVar[Optional[str]] = (
        "models/decode.py has no looped stack (its cache is one a layer, "
        "where layers run total_ut_steps times on one set of parameters "
        "need one a pass and layer), no exit gate, no four-norm layer and "
        "no rotary on 128-wide heads")

    def validate(self) -> None:
        self.validate_shapes()
        if self.tied_embeddings or self.attention_bias:
            raise ValueError(
                "this class has an untied head and no attention bias")
        if self.num_experts or self.experts_held or self.experts_per_token \
                or self.expert_width or self.num_shared_experts \
                or self.num_dense_layers != self.num_hidden_layers \
                or self.dense_width <= 0:
            raise ValueError(
                "this class has no expert layer: num_dense_layers is the "
                "depth, dense_width the feed-forward's width, and the "
                "experts' counts and width are 0")
        if self.hidden_act != "silu" or not self.sandwich_norms \
                or self.attention_gate or self.qk_norm or self.mup_enabled \
                or self.layer_kinds != (LAYER_FULL_ROPE,):
            raise ValueError(
                "a layer of this class is 'full_rope' attention and a "
                "gated-SiLU block behind four norms, with no output gate, "
                "no head norms and no embedding scale")
        if self.total_ut_steps < 2:
            raise ValueError(
                "total_ut_steps counts the passes of the looped stack: 2 "
                "or more (a stack run once is the parent class's)")
        if (self.pass_input, self.exit_gate_input, self.exit_gate_bias,
                self.exit_loss) != ("final_norm", "final_norm", True,
                                    "expected_nll_minus_entropy"):
            raise ValueError(
                "models/sparse_lm.py runs the next pass on the final "
                "norm's output, reads the exit gate (with its bias) from "
                "it, and trains on the expected loss over the exits less "
                "the exit distribution's weighted entropy")
        if self.exit_entropy_weight < 0 or self.exit_gate_init_std <= 0:
            raise ValueError("exit_entropy_weight >= 0 and a gate drawn "
                             "with exit_gate_init_std > 0")


def ouro2b6_model_config(**overrides: Any) -> OuroLMConfig:
    """Preset ``ouro2b6``: the cell ``ouro2b6-train-solo``
    (benchmark/configs/ouro2b6.json holds ``asdict`` of it)."""
    return dataclasses.replace(OuroLMConfig(), **overrides)


def tiny_model_config(**overrides: Any) -> ModelConfig:
    """CPU-smoke configuration (preset ``tiny``): 4 full-attention layers
    of width 64."""
    base = dict(
        vocab_text=128, vocab_image=64, text_seq_len=16, image_grid=4,
        dim=64, depth=4, heads=4, head_dim=16, shared_block_cycle=0,
        final_conv_block=False, attn_types=(ATTN_FULL,), rotary=True,
        dtype="float32", remat=False,
    )
    base.update(overrides)
    return ModelConfig(**base)


# The flagship's v5e training knobs: partial remat leaves 1 of the 4
# weight-shared blocks un-rematerialized; streaming cross-entropy chunks
# the image head's logsumexp at 2048 vocabulary ids; two cycle passes
# per scan iteration halve the shared-weight grad-carry traffic;
# save_attn remat (backward replays neither projections nor attention);
# the fused LayerNorm; the hoisted bf16 parameter cast. `--preset
# flagship` trains exactly what the benchmark's `flagship` configuration
# holds (benchmark/configs/flagship.json equals asdict of the preset,
# held by tests/benchmark_tests): 16 817 tokens/s/chip in
# `flagship-train-solo`, 16 718 in `flagship-train-dp4` (ledger, PR 28;
# PERF.md §4–§5). Each knob's own worth: not measured on today's stack
# (ROADMAP Design 5).
FLAGSHIP_TUNED = dict(remat_skip_blocks=1, head_chunk=2048, scan_unroll=2,
                      ln_fusion=True, remat_policy="save_attn",
                      param_cast_hoist=True)


def flagship_model_config(**overrides: Any) -> ModelConfig:
    """The 1.3B flagship (reference task.py:62-83 shape) with its v5e
    training knobs (``FLAGSHIP_TUNED``) applied."""
    base = dict(FLAGSHIP_TUNED)
    base.update(overrides)
    return dataclasses.replace(ModelConfig(), **base)


def xl_model_config(**overrides: Any) -> ModelConfig:
    """DALL-E-XL ~3B (preset ``xl``; this repo's own widening of the
    flagship, not a published architecture): dim 1792, depth 64 with the
    same 4-block weight sharing, 28 heads x 64, VQGAN-f16 tokens (16384-code
    codebook; 512px images -> 32x32 codes). Sized for pod-slice peers
    (v5p-64 in the north star); on one v5e chip it runs at micro 2 x
    accum 8, 10.91 GB peak and 5 778 tokens/s/chip (`xl-train-solo`;
    PERF.md §5 and ledger, PR 28).
    """
    # Blanket remat and the XLA LayerNorm (ln_fusion off): a pre-round
    # reading had the fused kernel slower at this width, where XLA fuses
    # the norm into its neighbours; not measured on today's stack
    # (ROADMAP Design 5).
    base = dict(dim=1792, heads=28, head_dim=64,
                vocab_image=16384, image_grid=32,
                remat_skip_blocks=0, head_chunk=2048, scan_unroll=2)
    base.update(overrides)
    return dataclasses.replace(ModelConfig(), **base)


def long_context_model_config(**overrides: Any) -> ModelConfig:
    """Long-sequence variant: a 64x64 code grid (4096 image tokens, e.g.
    512px images under an f8 VQGAN) with full-causal layers sharded over the
    ``sp`` mesh axis via ring attention. The reference caps its sequence at
    1280 tokens and has no sequence parallelism (SURVEY.md §5); this preset
    is the long-context extension the sp axis exists for.
    """
    base = dict(image_grid=64, attn_types=(ATTN_FULL,),
                final_conv_block=False, sequence_parallel=SP_RING)
    base.update(overrides)
    return dataclasses.replace(ModelConfig(), **base)
