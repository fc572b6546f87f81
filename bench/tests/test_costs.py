"""The least-work count of the monitor and the table of peaks."""
import os

import pytest

import costs
import harness


def test_monitor_least_work_by_hand():
    # per sample: 1 (entering less leaving) + 4 bins x (1 add + 6 for the
    # complex rotation + 3 for the squared magnitude) + 3 maxima over 4
    # bins + 2 (root, scale) = 46 operations; 4 bytes read, 4 + 1 written
    ops, nbytes = costs.monitor_least_work([1000], bins=4)
    assert ops == 46_000
    assert nbytes == 9_000


def test_monitor_least_work_counts_each_rows_real_samples():
    # rows of 6,000, 9,000 and 12,000 samples, padded by the program to
    # 12,000 each: the algorithm needs 27,000 samples' work, not 36,000
    ops, nbytes = costs.monitor_least_work([6000, 9000, 12000], bins=4)
    assert ops == 46 * 27_000
    assert nbytes == 9 * 27_000


def test_sweep_counts_dispatched_rows_by_real_length():
    sweep = harness.load_module(
        os.path.join(harness.BENCH, "drivers", "sweep.py"), "sweep_driver")
    lengths = [6000] * 3 + [9000] * 3 + [12000] * 3
    # one whole Study, then one stopped after its first 4 rows
    assert sweep.rows_by_length(lengths, [9, 4]) == {
        "6000": 6, "9000": 4, "12000": 3}


def test_roofline_names_the_binding_bound():
    peak = costs.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    ops, nbytes = costs.monitor_least_work([1000], bins=4)
    share, bound = costs.roofline(ops, nbytes, 1e-6, peak)
    # 9,000 B / 819 GB/s = 10.989 ns > 46,000 / 197 TFLOP/s = 0.234 ns
    assert bound == "hbm"
    assert share == pytest.approx(100 * 9_000 / 819e9 / 1e-6)
    share, bound = costs.roofline(1e9, 1.0, 1.0, peak)
    assert bound == "flops"
    assert share == pytest.approx(100 * 1e9 / 197e12)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        costs.peaks("TPU v9 imaginary")
