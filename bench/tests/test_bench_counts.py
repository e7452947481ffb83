"""Operation and byte counts of the kernels, and the peak table, against
shapes worked out by hand."""
import pytest

from bench import peaks
from bench.harness import kernel_work


def test_paged_attention_counts_live_rows_only():
    work = kernel_work("paged_attention")
    # two live rows at positions 9 and 99: 10 + 100 context rows;
    # qwen2.5-3b heads (16 q, 2 kv, 128 wide), bf16
    flops, nbytes = work([10, 100], n_q=16, n_kv=2, head_dim=128)
    assert flops == 4 * 16 * 128 * 110             # 901,120
    kv = 2 * 110 * 2 * 128 * 2                     # K and V rows: 112,640
    q_o = 2 * 2 * 16 * 128 * 2                     # q in, o out: 16,384
    assert nbytes == kv + q_o == 129_024


def test_decode_tail_reads_the_head_once_per_call():
    work = kernel_work("decode_tail")
    flops, nbytes = work(16, d_model=2048, vocab=151936)
    assert flops == 2 * 16 * 2048 * 151936
    head = 2048 * 151936 * 2                       # 622,329,856
    assert nbytes == head + 16 * (2048 * 2 + 4) + 2048 * 2


def test_peaks_of_v5e_and_roofline_bound():
    p = peaks.peak("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    t, bound = peaks.roofline_seconds(197e12, 819e9 / 2, "TPU v5 lite")
    assert (t, bound) == (1.0, "compute")
    t, bound = peaks.roofline_seconds(1.0, 819e9 * 3, "TPU v5 lite")
    assert (t, bound) == (3.0, "memory")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.roofline_seconds(1.0, 1.0, "cpu")
