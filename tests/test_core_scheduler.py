"""Scheduler invariants: greedy, packer, ILS, burst allocation, D_spot."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (CloudConfig, ILSParams, burst_allocation,
                        compute_dspot, evaluate, initial_solution, run_ils)
from repro.core.dspot import worst_case_migration_s
from repro.core.formulation import solve_exact
from repro.core.types import Market, TaskSpec
from repro.sim.workloads import make_job

CFG = CloudConfig()


def tasks_strategy(max_tasks=12):
    task = st.builds(
        lambda m, t: (m, t),
        st.floats(2.0, 200.0),
        st.floats(60.0, 330.0))
    return st.lists(task, min_size=1, max_size=max_tasks).map(
        lambda raw: [TaskSpec(tid=i, memory_mb=m, base_time=t)
                     for i, (m, t) in enumerate(raw)])


def _validate_packing(sol, tasks, cfg, dspot, deadline):
    res = evaluate(sol, tasks, cfg, dspot, deadline)
    assert res.feasible, res.violation
    for uid, vs in res.per_vm.items():
        vm = vs.vm
        events = []
        for a in vs.assignments:
            assert a.start >= cfg.boot_overhead_s - 1e-9
            events.append((a.start, 1, a.task.memory_mb))
            events.append((a.end, -1, -a.task.memory_mb))
        events.sort()
        conc = mem = 0.0
        for _, d, m in events:
            conc += d
            mem += m
            assert conc <= vm.vcpus + 1e-9          # Eq. 3
            assert mem <= vm.memory_mb + 1e-6       # Eq. 2
    return res


@settings(max_examples=25, deadline=None)
@given(tasks=tasks_strategy())
def test_greedy_solution_is_feasible(tasks):
    dspot = compute_dspot(2700.0, tasks, CFG)
    sol = initial_solution(tasks, CFG.instance_pool(), CFG, dspot)
    assert (sol.alloc >= 0).all()                    # Eq. 4
    _validate_packing(sol, tasks, CFG, dspot, 2700.0)


@settings(max_examples=10, deadline=None)
@given(tasks=tasks_strategy(max_tasks=8), seed=st.integers(0, 100))
def test_ils_never_worse_than_greedy(tasks, seed):
    dspot = compute_dspot(2700.0, tasks, CFG)
    pool = CFG.instance_pool()
    greedy = initial_solution(tasks, pool, CFG, dspot)
    g = evaluate(greedy, tasks, CFG, dspot, 2700.0)
    params = ILSParams(max_iteration=10, max_attempt=10, seed=seed)
    res = run_ils(tasks, pool, CFG, dspot, 2700.0, params)
    r = _validate_packing(res.solution, tasks, CFG, res.rd_spot, 2700.0)
    assert r.fitness <= g.fitness + 1e-9


def test_ils_matches_exact_optimum_tiny():
    """On enumerable instances the ILS must reach the Eq. 1 optimum."""
    tasks = [TaskSpec(0, 10.0, 300.0), TaskSpec(1, 10.0, 200.0),
             TaskSpec(2, 10.0, 120.0)]
    small = CloudConfig(max_per_type_market=1)
    pool = small.instance_pool()
    dspot = compute_dspot(2700.0, tasks, small)
    exact = solve_exact(tasks, pool, small, dspot, 2700.0)
    assert exact.result is not None and exact.result.feasible
    res = run_ils(tasks, pool, small, dspot, 2700.0,
                  ILSParams(max_iteration=40, max_attempt=20, seed=0))
    fit = evaluate(res.solution, tasks, small, dspot, 2700.0).fitness
    assert fit <= exact.result.fitness * 1.0 + 1e-6
    assert fit >= exact.result.fitness - 1e-6       # exact is the optimum


def test_dspot_bounds():
    job = make_job("J60")
    dspot = compute_dspot(job.deadline_s, job.tasks, CFG)
    assert 0 < dspot < job.deadline_s
    assert worst_case_migration_s(job.tasks, CFG) == \
        pytest.approx(job.deadline_s - dspot)


def test_dspot_too_tight_raises():
    tasks = [TaskSpec(0, 10.0, 3000.0)]
    with pytest.raises(ValueError):
        compute_dspot(100.0, tasks, CFG)


def test_burst_allocation_adds_burstables_and_respects_deadline():
    job = make_job("J60")
    pool = CFG.instance_pool()
    dspot = compute_dspot(job.deadline_s, job.tasks, CFG)
    res = run_ils(job.tasks, pool, CFG, dspot, job.deadline_s,
                  ILSParams(max_iteration=20, max_attempt=10, seed=1))
    ba = burst_allocation(res.solution, job.tasks, CFG, dspot,
                          job.deadline_s, burst_rate=0.2)
    assert len(ba.burstable_uids) >= 1
    # every burstable hosts at most one task, in baseline mode
    for uid in ba.burstable_uids:
        idx = ba.solution.tasks_on(uid)
        assert len(idx) <= 1
        assert all(ba.solution.modes[i] == 1 for i in idx)
    out = evaluate(ba.solution, job.tasks, CFG, res.rd_spot, job.deadline_s)
    assert out.feasible


def test_greedy_uses_wrr_type_mix():
    """WRR should spread selected spot VMs across heterogeneous types."""
    job = make_job("J100")
    dspot = compute_dspot(job.deadline_s, job.tasks, CFG)
    sol = initial_solution(job.tasks, CFG.instance_pool(), CFG, dspot)
    types = {sol.pool[u].vm_type.name for u in sol.used_uids()}
    assert len(types) >= 2


def test_ondemand_market_greedy():
    job = make_job("J60")
    sol = initial_solution(job.tasks, CFG.instance_pool(), CFG,
                           job.deadline_s, market=Market.ONDEMAND)
    assert all(sol.pool[u].market == Market.ONDEMAND
               for u in sol.used_uids())


@pytest.mark.parametrize("name", ["J60", "J80", "J100", "ED200"])
def test_batched_ils_improves_over_seed(name):
    from repro.core.evaluator import CachedEvaluator
    from repro.core.ils_jax import BatchedILSParams, run_batched_ils

    job = make_job(name)
    pool = CFG.instance_pool()
    dspot = compute_dspot(job.deadline_s, job.tasks, CFG)
    res = run_batched_ils(job.tasks, pool, CFG, dspot, job.deadline_s,
                          BatchedILSParams(population=8, iterations=10,
                                           proposals=8, seed=0))
    assert np.isfinite(res.fitness_bound)
    assert res.history[-1] <= res.history[0] + 1e-9
    # the winner re-validates with the exact packer
    ev = CachedEvaluator(job.tasks, CFG, job.deadline_s)
    assert np.isfinite(ev.fitness(res.solution, dspot * 1.3))
