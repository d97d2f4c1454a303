"""FLOP and byte counts against numbers worked by hand."""

import dataclasses
import json
import os

import pytest

from benchmarks.lib import counts as C
from benchmarks.lib.peaks import PEAKS, peaks_for

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_pythia_1_4b_at_depth_4_by_hand():
    m = C.dims_of(config("pythia-1.4b"))
    assert (m.d, m.heads, m.head_dim, m.ff, m.vocab) == (
        2048, 16, 128, 8192, 50304)
    assert 4 <= m.layers < 24  # the file's cut; the hand-worked depth is 4
    m = dataclasses.replace(m, layers=4)
    # a layer: 4 * 2048^2 + 2 * 2048 * 8192 = 16,777,216 + 33,554,432
    assert C.layer_matmul_params(m) == 50_331_648
    # head: 50304 * 2048 = 103,022,592
    assert C.param_count(m) == 4 * (50_331_648 + 4096) + 103_022_592 + 2048
    # per token: 6 * (4 * 50,331,648 + 103,022,592) + 4 * 6 * 2048 * 2048
    assert C.train_flops_per_token(m, 2048) == pytest.approx(
        6 * 304_349_184 + 100_663_296)
    # at 62,000 tokens/s that is 119.5 TFLOP/s, 60.6% of one v5e
    mfu = C.train_flops_per_token(m, 2048) * 62_000 / 197e12
    assert mfu == pytest.approx(0.6064, abs=1e-3)


def test_flash_counts_at_the_train_shape_by_hand():
    shape = (4, 16, 2048, 128)  # B, H, T, D
    unit = 4 * 16 * 2048 * 2048 * 128  # one causal T x T x D matmul
    assert unit == 34_359_738_368
    assert C.flash_fwd_flops(*shape) == 2 * unit
    assert C.flash_bwd_flops(*shape) == 5 * unit
    one = 4 * 16 * 2048 * 128 * 2  # q (or k, v, o) in bf16: 32 MiB
    assert one == 33_554_432
    assert C.flash_fwd_bytes(*shape) == 4 * one + 4 * 16 * 2048 * 4
    assert C.flash_bwd_bytes(*shape) == 8 * one + 2 * 4 * 16 * 2048 * 4
    # compute binds on a v5e: 0.35 ms of FLOPs against 0.16 ms of bytes
    p = peaks_for("TPU v5 lite")
    assert C.flash_fwd_flops(*shape) / p.bf16_flops > \
        C.flash_fwd_bytes(*shape) / p.hbm_bytes_per_s


def test_pythia_6_9b_depth_8_decode_bytes_by_hand():
    m = C.dims_of(config("pythia-6.9b"))
    assert (m.d, m.heads, m.head_dim, m.ff, m.vocab, m.layers) == (
        4096, 32, 128, 16384, 50432, 8)
    # a layer: 4 * 4096^2 + 2 * 4096 * 16384 = 67,108,864 + 134,217,728
    assert C.layer_matmul_params(m) == 201_326_592
    # K and V of one position: 2 * 8 layers * 4096 * 2 bytes = 128 KiB
    assert C.kv_bytes_per_token(m) == 131_072
    weights = 8 * 201_326_592 + 50432 * 4096  # 1,817,182,208
    assert weights == 1_817_182_208
    # f32 weights, 32 sequences of 300 live positions
    assert C.decode_round_bytes(m, 9600) == weights * 4 + 9600 * 131_072
    # 8.53 GB over 819 GB/s: 10.4 ms is the least a round can take
    assert C.decode_round_bytes(m, 9600) / 819e9 == pytest.approx(0.010411, abs=1e-5)
    # held in bf16 the same round would need half the weight bytes
    assert C.decode_round_bytes(m, 0, weight_itemsize=2) == weights * 2


def test_peaks_table_has_its_source_and_refuses_an_unknown_kind():
    p = peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s, p.hbm_bytes) == (197e12, 819e9, 16e9)
    assert "Google Cloud" in p.source
    assert all(v.source for v in PEAKS.values())
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
