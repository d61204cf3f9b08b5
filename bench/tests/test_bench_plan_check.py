"""``j100-sc5.plan``'s comparison with the reference, at a small size:
sound runs pass, the bfloat16 and truncated-search controls fail, and
each fault planted under the timed path turns ``correct`` false."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench.tests.benchutil import run_small, small_cell

CELL = "j100-sc5.plan"


def test_sound_run_is_correct(monkeypatch):
    res = run_small(monkeypatch, CELL)
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"plans_per_s", "setup_s"}
    assert set(res["checks"]) == {"search_gap_mean", "fitness_error_share",
                                  "final_plan_mismatch"}
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("control,number", [
    ("bfloat16", "fitness_error_share"), ("truncated", "search_gap_mean")])
def test_control_fails_where_the_program_passes(control, number):
    kind, cell = small_cell(CELL, seed=4242)
    for i in range(4):
        cell.request(i)
    sound = cell.check()[number]
    ctl = cell.check(control=control)[number]
    assert sound["value"] <= sound["limit"] < ctl["value"]


def test_traced_run_reads_the_planner_layer(monkeypatch):
    import bench.peaks
    monkeypatch.setitem(bench.peaks.PEAKS, "cpu",
                        {"flops_per_s": 1e12, "bytes_per_s": 1e11})
    res = run_small(monkeypatch, CELL, seconds=0.5, trace=1)
    assert res["correct"]
    assert {"ils_device_ms.plan", "device_idle_pct.plan"} <= \
        set(res["metrics"])
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"]


def _fault(kind, monkeypatch):
    import repro.core.dynamic as dynamic
    import repro.core.ils_jax as ils_jax
    scan = ils_jax._ils_scan
    if kind == "state_unchanged":
        # the scan hands back its starting carry
        def unchanged(donate):
            def run(alloc, best_fit, keys, *args, **kw):
                return alloc, best_fit, jnp.zeros(keys.shape[0])
            return run
        monkeypatch.setattr(ils_jax, "_ils_scan", unchanged)
    elif kind == "half_the_proposals":
        # each iteration scores half of every chain's K candidates
        delta = ils_jax.delta_fitness

        def half(*args, **kw):
            fit, cost, mkp = delta(*args, **kw)
            k = fit.shape[1]
            return (fit.at[:, k // 2:].set(jnp.inf), cost, mkp)
        monkeypatch.setattr(ils_jax, "delta_fitness", half)

        def retraced(donate):
            # a function of its own, so that no trace of the sound scan
            # is reused
            def impl(*args, **kw):
                return ils_jax._ils_scan_impl(*args, **kw)
            return jax.jit(impl, static_argnames=("k", "n"))
        monkeypatch.setattr(ils_jax, "_ils_scan", retraced)
    elif kind == "quarter_of_the_iterations":
        def quarter(donate):
            def run(alloc, best_fit, keys, *args, **kw):
                return scan(False)(alloc, best_fit,
                                   keys[:keys.shape[0] // 4], *args, **kw)
            return run
        monkeypatch.setattr(ils_jax, "_ils_scan", quarter)
    elif kind == "answer_altered":
        run = getattr(ils_jax.run_batched_ils, "__wrapped__",
                      ils_jax.run_batched_ils)

        def altered(*args, **kw):
            res = run(*args, **kw)
            return dataclasses.replace(
                res, fitness_bound=res.fitness_bound * 1.01)
        monkeypatch.setattr(ils_jax, "run_batched_ils", altered)
    elif kind == "burst_mode_altered":
        burst = dynamic.burst_allocation

        def altered(*args, **kw):
            out = burst(*args, **kw)
            out.solution.modes[0] = 1 - out.solution.modes[0]
            return out
        monkeypatch.setattr(dynamic, "burst_allocation", altered)


@pytest.mark.parametrize("fault", [
    "state_unchanged", "half_the_proposals", "quarter_of_the_iterations",
    "answer_altered", "burst_mode_altered"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    _fault(fault, monkeypatch)
    res = run_small(monkeypatch, CELL)
    assert res["correct"] is False, res["checks"]


def test_moved_program_symbol_fails_the_request_by_name(monkeypatch):
    """A planner that stops calling the ILS through the module attribute
    the check wraps fails every request, naming that symbol."""
    import repro.core.dynamic as dynamic
    kind, cell = small_cell(CELL, warm=False)
    # a planner with the ILS inlined: the wrapped symbol is never called
    monkeypatch.setattr(dynamic, "build_primary_map", lambda *a, **kw: None)
    with pytest.raises(RuntimeError, match="run_batched_ils"):
        cell.request(0)
