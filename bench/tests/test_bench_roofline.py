"""Bytes and operations of the kernels on known shapes, and the share."""
import pytest

from bench import roofline
from bench.peaks import PEAKS, peaks


def test_planner_kernels_on_the_plan_cell_shapes():
    # P = 32 chains, K = 16 proposals, n = 4 moved tasks, B = 100, V = 35
    assert roofline.population_reduce(32, 100, 35) == (
        4 * (32 * 100 + 100 * 35 + 100 + 4 * 32 * 35), 4 * 32 * 100)
    nbytes, ops = roofline.delta_population_fitness(32, 16, 4, 100, 35)
    assert nbytes == 4 * (32 * 100 + 100 * 35 + 100 + 32 * 16 * 5
                          + 4 * 32 * 35 + 4 * 35 + 3 * 32 * 16)
    assert ops == 4 * 32 * 16 * 5 * 100 + 10 * 32 * 16 * 35


def test_share_and_its_bound():
    p = {"flops_per_s": 1e12, "bytes_per_s": 1e9}
    # 1 MB at 1 GB/s is 1 ms; two calls of 4 ms each read 25 %
    pct, bound = roofline.share([0.004, 0.004], 1e6, 1e6, p)
    assert pct == pytest.approx(25.0) and bound == "bytes"
    pct, bound = roofline.share([0.002], 1e3, 1e9, p)
    assert pct == pytest.approx(50.0) and bound == "ops"
    assert roofline.share([], 1.0, 1.0, p) is None


def test_kernel_calls_are_found_by_their_trace_name():
    reduced = {"op_durations": {"%population_reduce.8": [1e-3, 2e-3],
                                "%population_reduce": [1e-3],
                                "%population_reduce_x.1": [5e-4],
                                "%delta_population_fitness.8": [2e-4]}}
    assert roofline.durations(reduced, "population_reduce") == [1e-3, 2e-3,
                                                                1e-3]
    assert roofline.durations(reduced, "delta_population_fitness") == [2e-4]


def test_peaks_table():
    assert peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    assert PEAKS["TPU v5 lite"]["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
