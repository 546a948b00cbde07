"""Operations and bytes from the shapes, against counts by hand at a
small size, and the peaks table."""
import pytest

import smoke
import harness

W = harness.module("work", "qwen1.5-moe-a2.7b")


def test_counts_by_hand_at_a_small_size():
    s = smoke.qwen_smoke()  # d 64, 4 heads of 16, 2 layers, E 8 top-2
    d, L, V = 64, 2, 512
    attn = 64 * (4 + 2 * 4) * 16 + 4 * 16 * 64          # 16384
    routed = 2 * 3 * 64 * 32                              # 12288
    shared = 3 * 64 * 64                                  # 12288
    router = 64 * 8
    lin = 2 * (L * (attn + routed + shared + router) + d * V)
    assert W.linear_flops_per_token(s) == lin == 231424
    assert W.attn_flops(s, 10) == 2 * 2 * L * 4 * 16 * 10
    # prefill of 3 tokens after 5 shared: contexts 6, 7, 8
    assert W.prefill_flops(s, 5, 3) == 3 * lin + W.attn_flops(s, 21)
    # one decode row: experts read = top-2 of 8
    flops, nbytes = W.decode_step(s, [10])
    wb = 4  # float32 smoke weights
    weights = L * ((attn + shared) * wb + 64 * 8 * 4 + 2 * 3 * 64 * 32 * wb) \
        + V * d * wb
    kv = L * 2 * 4 * 16 * wb
    assert flops == W.token_flops(s, 10)
    assert nbytes == weights + kv * 11
    # 4 rows x top-2 = 8 >= 8 experts: every expert is read once
    _, nb4 = W.decode_step(s, [3, 4, 5, 6])
    assert nb4 == W.weight_bytes(s, 8) + kv * (18 + 4)


def test_full_size_decode_reads_the_held_weights():
    s = harness.data_file("configs", "qwen1.5-moe-a2.7b")
    _, nbytes = W.decode_step(s, [1] * 32)
    assert 9.7e9 < W.weight_bytes(s, 60) < 9.8e9
    assert nbytes - W.weight_bytes(s, 60) == 64 * 1024 * 64
    # about 2 GFLOP a token, of which the head is 0.62
    assert 1.9e9 < W.linear_flops_per_token(s) < 2.1e9


def test_peaks_by_device_kind_and_unknown_kind_is_an_error():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.SetupError, match="no peaks"):
        harness.peaks("TPU v99")
