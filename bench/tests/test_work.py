"""The least-work functions, on shapes counted by hand."""

import numpy as np
import pytest

from bench import work


def test_exact_scan_counts_one_read_of_the_codes_per_batch():
    ops, nbytes = work.exact_scan(q_rows=64, n_docs=2_100_000, dim=128,
                                  code_bytes=128, in_dim=768, k=10)
    assert ops == 2 * 64 * 2_100_000 * 128
    assert nbytes == 2_100_000 * 128 + 64 * 768 * 4 + 64 * 10 * 8


def test_ivf_scan_reads_each_distinct_list_once_at_its_true_length():
    lens = np.array([5, 7, 11, 13])
    probes = np.array([[0, 1], [1, 2], [1, 0]])      # lists 0, 1, 2 touched
    ops, nbytes = work.ivf_scan(probes, lens, dim=4, code_bytes=4, in_dim=6,
                                k=2)
    pairs = (5 + 7) + (7 + 11) + (7 + 5)              # (row, list) pairs
    assert ops == 2 * 4 * pairs
    assert nbytes == (5 + 7 + 11) * 4 + 3 * 6 * 4 + 3 * 2 * 8


def test_least_time_takes_the_larger_bound_and_names_it():
    assert work.least_time(ops=1e12, nbytes=1e9, peak_ops=1e12,
                           bytes_per_s=1e12) == (1.0, "ops")
    assert work.least_time(ops=1.0, nbytes=2e12, peak_ops=1e12,
                           bytes_per_s=1e12) == (2.0, "bytes")


def test_the_v5e_peaks_and_an_unknown_kind():
    pk = work.peaks("TPU v5 lite")
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["int8_ops_per_s"] == 393e12
    assert pk["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_a_full_exact_batch_on_a_v5e_is_bound_by_bytes():
    pk = work.peaks("TPU v5 lite")
    t, bound = work.least_time(
        *work.exact_scan(64, 2_100_000, 128, 128, 768, 10),
        pk["int8_ops_per_s"], pk["hbm_bytes_per_s"])
    assert bound == "bytes"
    assert t == pytest.approx(268_800_000 / 819e9, rel=1e-3)
