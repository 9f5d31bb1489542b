"""FLOPs and bytes against hand-computed figures for both configurations,
and the peaks table."""

import os

import pytest

from perfbench import byname, peaks, roofline

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def config(name):
    return byname.load_json([BENCH], "configs", name)


def family(name):
    return byname.load_family([BENCH], config(name))


def test_parameter_counts():
    # wte 50304*E + wpe 1024*E + L*(12 E^2 + 13 E) + 2 E
    assert family("gpt2-medium").param_count(config("gpt2-medium")) \
        == 50304 * 1024 + 1024 * 1024 + 24 * (12 * 1024**2 + 13 * 1024) \
        + 2 * 1024 == 354_871_296
    assert family("gpt2-xl").param_count(config("gpt2-xl")) \
        == 50304 * 1600 + 1024 * 1600 + 48 * (12 * 1600**2 + 13 * 1600) \
        + 2 * 1600 == 1_557_686_400


def test_train_flops_per_token():
    # 6 N + 12 L E T at T = 1024
    assert roofline.train_flops_per_token(1000, 2, 8, 16) \
        == 6 * 1000 + 12 * 2 * 8 * 16
    assert family("gpt2-medium").train_flops_per_token(
        config("gpt2-medium"), 1024) \
        == 6 * 354_871_296 + 12 * 24 * 1024 * 1024 == 2_431_217_664
    assert family("gpt2-xl").train_flops_per_token(
        config("gpt2-xl"), 1024) \
        == 6 * 1_557_686_400 + 12 * 48 * 1600 * 1024 == 10_289_836_800
    # 30,718 tokens/s (ledger, PR 22) is then 37.9 % of 197 TFLOP/s.
    assert 30718 * 2_431_217_664 / 197e12 == pytest.approx(0.379, abs=1e-3)


def test_flash_kernel_flops():
    # medium, 16 sequences: batch*heads 256, T 1024, d 64, causal:
    # T(T+1)/2 = 524,800 score entries, 2*d FLOPs per product and entry.
    entries = 1024 * 1025 // 2
    assert roofline.flash_flops("fwd", 256, 1024, 64) \
        == 2 * 2 * 256 * entries * 64 == 34_393_292_800
    assert roofline.flash_flops("dq", 256, 1024, 64) \
        == 3 * 2 * 256 * entries * 64
    assert roofline.flash_flops("dkv", 256, 1024, 64) \
        == 4 * 2 * 256 * entries * 64
    # XL on one of four chips: 4 sequences * 25 heads.
    assert roofline.flash_flops("fwd", 100, 1024, 64, causal=False) \
        == 2 * 2 * 100 * 1024 * 1024 * 64


def test_paged_bytes_and_roofline_share():
    assert family("gpt2-xl").kv_shape(config("gpt2-xl")) == (48, 25, 64, 2)
    # XL, one layer, 8 sequences of 40 live pages: K and V rows of
    # 320 pages * 16 tokens * 25 heads * 64 * 2 bytes.
    assert roofline.paged_attn_bytes(320, 16, 25, 64, 2) \
        == 2 * 320 * 16 * 25 * 64 * 2 == 32_768_000
    v5e = peaks.peaks_for("TPU v5 lite")
    # 32.768 MB at 819 GB/s is 40.0 us; taking 200 us is 20 %.
    assert roofline.roofline_share_pct(0.0, 32_768_000, 200e-6, v5e) \
        == pytest.approx(20.0, rel=1e-3)
    # FLOP-bound: 34.39 GFLOP at 197 TFLOP/s is 174.6 us.
    assert roofline.roofline_share_pct(34_393_292_800, 1e6, 349.2e-6, v5e) \
        == pytest.approx(50.0, rel=1e-3)


def test_peaks_table_knows_the_v5e_and_nothing_by_default():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e.bf16_flops_per_s, v5e.hbm_bytes_per_s, v5e.hbm_bytes) \
        == (197e12, 819e9, 16e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
