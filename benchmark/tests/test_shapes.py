"""Operations and bytes from shapes, the peaks table, and which
roofline bounds each program at the benchmark's shapes."""

import pytest

from benchmark.harness import shapes


def test_als_half_step_counts():
    R, nnz, n, m = 4, 10, 3, 5
    h = shapes.als_half_step(nnz, n, m, R, factor_bytes=4)
    assert h["assembly_flops"] == 2 * nnz * R * R
    assert h["solve_flops"] == pytest.approx(n * R ** 3 / 3)
    assert h["gather_bytes"] == nnz * R * 4
    assert h["flops"] == pytest.approx(
        2 * m * R * R + nnz * (2 * R * R + 2 * R)
        + n * (R ** 3 / 3 + 2 * R * R))
    assert h["bytes"] == nnz * (R * 4 + 8) + m * R * 4 + n * R * 4


def test_als_iteration_is_both_sides():
    it = shapes.als_iteration(100, 7, 9, 8)
    u = shapes.als_half_step(100, 7, 9, 8)
    i = shapes.als_half_step(100, 9, 7, 8)
    assert it["flops"] == pytest.approx(u["flops"] + i["flops"])
    assert it["bytes"] == pytest.approx(u["bytes"] + i["bytes"])


def test_ml20m_iteration_is_memory_bound_on_v5e():
    peak = shapes.peaks("TPU v5 lite")
    need = shapes.als_iteration(17_505_091, 138_000, 27_000, 64)
    t = shapes.least_time(need["flops"], need["bytes"], peak,
                          mxu_passes=peak["fp32_highest_passes"])
    # 2 x 17.5M pairs x (256 + 8) bytes ~ 9.3 GB -> ~11 ms at 819 GB/s;
    # 2 x 17.5M x 2 x 64^2 flops x 6 passes / 197 TF ~ 9 ms
    assert t["bound"] == "memory"
    assert 0.010 < t["seconds"] < 0.013
    assert 0.007 < t["compute_s"] < 0.011


def test_topk_dispatch_counts_and_bound():
    d = shapes.topk_dispatch(256, 41_140, 64, 16, store_bytes=2)
    assert d["flops"] == 2 * 256 * 41_140 * 64
    assert d["bytes"] == pytest.approx(
        41_140 * 64 * 2 + 256 * 64 * 2 + 256 * 41_140 / 8 + 256 * 16 * 8)
    peak = shapes.peaks("TPU v5 lite")
    t = shapes.least_time(d["flops"], d["bytes"], peak)
    assert t["bound"] == "memory" and 5e-6 < t["seconds"] < 12e-6
    one = shapes.topk_dispatch(8, 41_140, 64, 16, store_bytes=2)
    assert shapes.least_time(one["flops"], one["bytes"],
                             peak)["bound"] == "memory"
    two = shapes.topk_dispatch(256, 41_140, 64, 16, store_bytes=2,
                               stage2_width=64, candidates=128)
    assert two["flops"] == d["flops"] + 2 * 256 * 128 * 64
    assert two["bytes"] == d["bytes"] + 256 * 129 * 64 * 2


def test_padded_slots():
    assert shapes.padded_slots([(8, 32), (16, 64)]) == 8 * 32 + 16 * 64


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        shapes.peaks("TPU v9 imaginary")
    v5e = shapes.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "source" in v5e
