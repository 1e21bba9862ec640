"""The ``dalle`` yardstick's FLOP and byte counters against counts made by
hand at a tiny shape, and the chip's table of peaks."""
import pytest

from benchmark import harness
from benchmark.manifest import Manifest

counts = Manifest().yardstick("dalle")

TINY = {"vocab_text": 128, "vocab_image": 64, "text_seq_len": 4,
        "image_grid": 2, "dim": 8, "depth": 3, "heads": 2, "head_dim": 4,
        "ff_mult": 4, "attn_types": ["axial_row", "axial_col"],
        "shared_block_cycle": 2, "final_conv_block": True, "conv_kernel": 3}


def test_attention_pairs_by_hand():
    # text: causal 4 tokens -> 1+2+3+4 = 10; each of 4 image tokens sees
    # the 4 text tokens -> 16
    # axial_row on a 2x2 grid: (0,0):1 (0,1):2 (1,0):1 (1,1):2 -> 6
    assert counts.attention_pairs(TINY, "axial_row") == 10 + 16 + 6
    # axial_col: (0,0):1 (0,1):1 (1,0):2 (1,1):2 -> 6
    assert counts.attention_pairs(TINY, "axial_col") == 32
    # full: 1+2+3+4 = 10 ; conv 3x3 covers the whole 2x2 grid -> causal
    assert counts.attention_pairs(TINY, "full") == 36
    assert counts.attention_pairs(TINY, "conv_like") == 36


def test_effective_params_and_flops_by_hand():
    block = 4 * 8 * 8 + 3 * 8 * 32           # q k v out + wi gate wo
    assert counts.block_matmul_params(TINY) == block == 1024
    head = (4 * 128 + 4 * 64) / 8 * 8        # rows seen per token x dim
    assert counts.head_params_per_token(TINY) == head == 768
    assert counts.effective_params(TINY) == 3 * 1024 + 768
    # layers: row, col, conv -> pairs 32, 32, 36; 4 flops x d x heads
    attn = (32 + 32 + 36) * 4 * 4 * 2
    fwd = 2 * (3 * 1024 + 768) * 8 + attn
    assert counts.train_flops_per_sample(TINY) == 3 * fwd


def test_attention_roofline_time_by_hand():
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    got = counts.attention_min_seconds_per_sample(TINY, peaks)
    tensor = 8 * 8 * 2                        # tokens x dim x bf16
    want = by_bytes = 0.0
    for pairs in (32, 32, 36):
        f = pairs * 4 * 4 * 2
        for n, mult in ((4, 1), (8, 2)):
            tf, tb = mult * f / 1e3, n * tensor / 1e3
            want += max(tf, tb)
            by_bytes += tb if tb >= tf else 0.0
    assert got["seconds"] == pytest.approx(want)
    assert got["bandwidth_bound_share"] == pytest.approx(by_bytes / want)


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        harness.peaks_for("_source")
