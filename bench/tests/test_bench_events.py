"""The eval cell's inputs: the ED200 bag law is the program's, the copy of
the Table V sampler keeps the event-tensor contract, and the engine
kernels' bytes and operations are the stated formulas."""
import json
import os

import numpy as np
import pytest

from bench import events, generator, roofline_engine

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "bench", "configs", "ed200-sc5.json")) as f:
    CONF = json.load(f)


@pytest.mark.parametrize("seed", [0, 1, 7])
def test_uniform_law_matches_the_programs_ed200_sampler(seed):
    from repro.sim.workloads import _ed_tasks
    law = CONF["bag"]
    mem, base = generator.bag(law, np.random.default_rng(seed))
    want = _ed_tasks(law["n_tasks"], np.random.default_rng(seed))
    np.testing.assert_array_equal(mem, [t.memory_mb for t in want])
    np.testing.assert_array_equal(base, [t.base_time for t in want])


def test_catalog_is_the_programs_default():
    from repro.core.types import CloudConfig
    from bench import deploy
    assert deploy.program_cloud(CONF) == CloudConfig()


def test_sc5_draw_keeps_the_tensor_contract():
    s, v, dt, d = 512, 5, 30.0, CONF["deadline_s"]
    n = events.n_slots(d, dt, 3.0)
    t = events.draw(2 ** 40 + 3, CONF["market"], s=s, n=n, v=v, dt=dt,
                    deadline_s=d)
    assert n == 270
    assert t["hib_k"].shape == t["res_k"].shape == (s, n)
    assert t["hib_k"].dtype == np.int32
    assert t["hib_u"].shape == t["res_u"].shape == (s, n, v)
    u = np.asarray(t["hib_u"])
    assert u.dtype == np.float32 and 0.0 <= u.min() and u.max() < 1.0
    # events only in slots that start before the deadline
    assert not t["hib_k"][:, int(d / dt):].any()
    assert set(np.unique(t["hib_k"])) <= {0, 1}
    # Table V: k_h hibernations and k_r resumes expected over D
    for key, k in (("hib_k", CONF["market"]["k_h"]),
                   ("res_k", CONF["market"]["k_r"])):
        mean = t[key].sum(axis=1).mean()
        assert abs(mean - k) < 4 * np.sqrt(k / s), (key, mean)
    again = events.draw(2 ** 40 + 3, CONF["market"], s=s, n=n, v=v, dt=dt,
                        deadline_s=d)
    np.testing.assert_array_equal(t["hib_k"], again["hib_k"])
    np.testing.assert_array_equal(np.asarray(t["res_u"]),
                                  np.asarray(again["res_u"]))


@pytest.mark.parametrize("s,b,v", [(1024, 200, 33), (8, 12, 15)])
def test_engine_kernel_bytes_and_operations(s, b, v):
    assert roofline_engine.mc_span_reduce(s, b, v) == (
        16 * s * b + 4 * s + 12 * s * v, 6 * s * b)
    assert roofline_engine.mc_vm_reduce(s, b, v) == (
        8 * s * b + 12 * s * v, 3 * s * b)


def test_engine_kernel_roofline_weights_each_call_by_its_plan():
    """Two plans of different widths: the least time is each call's own
    bytes over the peak, summed over the calls the counters report."""
    from types import SimpleNamespace
    widths = {"a": 15, "b": 33}
    records = [{"results": {
        "a": {"n_steps": 10, "branch_entries": {"hibernate": 2, "ac": 5}},
        "b": {"n_steps": 30, "branch_entries": {"defer": 1, "retry": 1}}}}]
    cell = SimpleNamespace(records=records, shapes=lambda: {"s": 64, "b": 8},
                           n_vms=widths.__getitem__)
    peaks = {"flops_per_s": 1e12, "bytes_per_s": 1e9}
    trace = {"op_durations": {"%mc_span_reduce.4": [1e-3] * 40,
                              "%mc_vm_reduce.1": [2e-3] * 4,
                              "%fusion.2": [5.0]}}
    run = SimpleNamespace(cell=cell, trace=trace, peaks=peaks)
    least = sum(k * roofline_engine.mc_span_reduce(64, 8, widths[p])[0]
                for p, k in (("a", 10), ("b", 30))) / 1e9
    assert roofline_engine.kernel_roofline(run, "mc_span_reduce") == \
        pytest.approx(100 * least / 40e-3)
    least = sum(k * roofline_engine.mc_vm_reduce(64, 8, widths[p])[0]
                for p, k in (("a", 2), ("b", 2))) / 1e9
    assert roofline_engine.kernel_roofline(run, "mc_vm_reduce") == \
        pytest.approx(100 * least / 8e-3)
    assert roofline_engine.kernel_roofline(
        SimpleNamespace(cell=cell, trace=None, peaks=peaks),
        "mc_span_reduce") is None
