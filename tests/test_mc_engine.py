"""Batched Monte-Carlo engine: DES parity contract + property tests.

The parity tolerances pin the contract documented in DESIGN.md §2.3: on an
event-free (SC_NONE) scenario the S=1 MC run must match the discrete-event
simulator's cost and makespan within the slot-quantization bound — each
task's completion rounds up to a slot edge, so per-VM drift is bounded by
(queue depth per core) * dt.
"""
import numpy as np
import pytest

from repro.core.dynamic import (BURST_HADS, HADS, ILS_ONDEMAND,
                                build_primary_map)
from repro.core.ils import ILSParams
from repro.core.types import CloudConfig, Job, Market, Solution, TaskSpec
from repro.sim.events import SCENARIOS, SC_NONE, Scenario
from repro import api
from repro.sim.mc_engine import MCParams, run_mc
from repro.sim.simulator import Simulator

CFG = CloudConfig()
FAST = ILSParams(max_iteration=25, max_attempt=15, seed=3)

#: DESIGN.md §2.3 parity contract (S=1, SC_NONE, dt=15): measured drift is
#: ~5% cost / ~2% makespan; the pinned bound leaves 2x headroom.
PARITY_DT = 15.0
COST_RTOL = 0.10
MKP_RTOL = 0.05


@pytest.fixture(scope="module")
def j60():
    from repro.sim.workloads import make_job
    return make_job("J60")


@pytest.fixture(scope="module")
def plan_bh(j60):
    return build_primary_map(j60, CFG, BURST_HADS, FAST)


@pytest.fixture(scope="module")
def plan_hads(j60):
    return build_primary_map(j60, CFG, HADS, FAST)


@pytest.mark.parametrize("which", ["burst-hads", "hads"])
def test_sc_none_parity_vs_des(j60, plan_bh, plan_hads, which):
    plan = plan_bh if which == "burst-hads" else plan_hads
    des = Simulator(j60, plan, CFG, SC_NONE, seed=0).run()
    mc = run_mc(j60, plan, CFG, SC_NONE,
                MCParams(n_scenarios=1, dt=PARITY_DT, seed=0))
    assert mc.unfinished[0] == 0
    assert bool(mc.deadline_met[0]) == des.deadline_met
    assert abs(mc.cost[0] - des.cost) <= COST_RTOL * des.cost, \
        (mc.cost[0], des.cost)
    assert abs(mc.makespan[0] - des.makespan) <= MKP_RTOL * des.makespan, \
        (mc.makespan[0], des.makespan)


def test_deterministic_per_seed(j60, plan_bh):
    p = MCParams(n_scenarios=16, dt=30.0, seed=7)
    a = run_mc(j60, plan_bh, CFG, SCENARIOS["sc5"], p)
    b = run_mc(j60, plan_bh, CFG, SCENARIOS["sc5"], p)
    np.testing.assert_array_equal(a.cost, b.cost)
    np.testing.assert_array_equal(a.makespan, b.makespan)
    np.testing.assert_array_equal(a.n_hibernations, b.n_hibernations)
    c = run_mc(j60, plan_bh, CFG, SCENARIOS["sc5"],
               MCParams(n_scenarios=16, dt=30.0, seed=8))
    assert not np.array_equal(a.cost, c.cost)


def test_hibernated_vms_accrue_no_billing():
    """A spot VM hibernated before its boot completes and never resumed
    must bill zero seconds, while the job still finishes on the dynamic
    on-demand capacity the deferred migration launches."""
    from repro.core.dynamic import PrimaryPlan
    cfg = CloudConfig(max_per_type_market=1)
    pool = cfg.instance_pool()
    tasks = (TaskSpec(tid=0, memory_mb=100.0, base_time=400.0),
             TaskSpec(tid=1, memory_mb=100.0, base_time=400.0))
    job = Job(name="TINY", tasks=tasks, deadline_s=6000.0)
    sol = Solution(alloc=np.zeros(2, np.int32), modes=np.zeros(2, np.int8),
                   pool=pool, selected_uids={0})
    plan = PrimaryPlan(solution=sol, dspot=5000.0, policy=HADS)
    # hibernation probability 1 per slot -> the only spot VM freezes at t=0
    always = Scenario("always", k_h=job.deadline_s / 30.0, k_r=0.0)
    res = run_mc(job, plan, cfg, always,
                 MCParams(n_scenarios=4, dt=30.0, seed=0, horizon_mult=1.2))
    spot_col = res.vm_uids.index(0)
    np.testing.assert_allclose(res.billed_s[:, spot_col], 0.0)
    assert np.all(res.n_hibernations >= 1)
    assert np.all(res.unfinished == 0)
    assert np.all(res.makespan > 1000.0)   # finished late, on migrated VMs
    # cost comes only from the dynamically launched on-demand capacity
    od_cols = [c for c, u in enumerate(res.vm_uids)
               if pool[u].market == Market.ONDEMAND]
    od_billed = res.billed_s[:, od_cols].sum(axis=1)
    assert np.all(od_billed > 0.0)


def test_all_complete_or_violation_flag(j60, plan_bh):
    import dataclasses
    res = run_mc(j60, plan_bh, CFG, SCENARIOS["sc4"],
                 MCParams(n_scenarios=32, dt=30.0, seed=1))
    # every scenario actually finishes all tasks within the horizon and
    # records a real completion instant
    assert np.all(res.unfinished == 0)
    assert np.all((res.makespan > 0) &
                  (res.makespan <= 3.0 * j60.deadline_s))
    # paper headline: Burst-HADS keeps meeting the deadline under sc4
    assert res.deadline_met.mean() >= 0.9
    # an impossibly tight deadline must flip the violation flag even
    # though the work itself still completes within the horizon
    tight = dataclasses.replace(j60, deadline_s=300.0)
    late = run_mc(tight, plan_bh, CFG, SC_NONE,
                  MCParams(n_scenarios=2, dt=30.0, seed=1,
                           horizon_mult=9.0))
    assert np.all(late.unfinished == 0)
    assert not np.any(late.deadline_met)


@pytest.fixture(scope="module")
def paper_plan(j60, plan_bh):
    """(job, plan) of a Table III job under a paper policy, each built
    once per module."""
    from repro.sim.workloads import make_job
    policies = {"burst-hads": BURST_HADS, "hads": HADS,
                "ils-ondemand": ILS_ONDEMAND}
    cache = {("J60", "burst-hads"): (j60, plan_bh)}

    def get(name, policy):
        if (name, policy) not in cache:
            job = make_job(name)
            cache[name, policy] = (job, build_primary_map(
                job, CFG, policies[policy], FAST))
        return cache[name, policy]
    return get


@pytest.mark.parametrize("sc_name", ["none", "sc5"])
@pytest.mark.parametrize("name,policy", [
    ("J60", "burst-hads"),
    # the eval cell's job and its three policies
    ("ED200", "burst-hads"), ("ED200", "hads"), ("ED200", "ils-ondemand")])
def test_kernel_engine_matches_jnp_engine(paper_plan, name, policy, sc_name):
    """Pallas-kernel stats path == jnp stats path, including a scenario
    where hibernation events drive migration decisions off the kernel's
    load reduction (both paths score post-progress remaining work)."""
    job, plan = paper_plan(name, policy)
    base = dict(n_scenarios=8, dt=60.0, seed=0)
    a = run_mc(job, plan, CFG, SCENARIOS.get(sc_name, SC_NONE),
               MCParams(**base, use_kernel=False))
    b = run_mc(job, plan, CFG, SCENARIOS.get(sc_name, SC_NONE),
               MCParams(**base, use_kernel=True))
    np.testing.assert_allclose(a.cost, b.cost, rtol=1e-6)
    np.testing.assert_allclose(a.makespan, b.makespan, rtol=1e-6)
    np.testing.assert_array_equal(a.n_hibernations, b.n_hibernations)


@pytest.mark.parametrize("name", ["J60", "J80", "J100", "ED200"])
def test_burst_hads_meets_deadline_under_sc5(paper_plan, name):
    """The paper's headline claim on the engine, for every Table III job:
    under sc5 each scenario finishes all tasks and Burst-HADS meets the
    deadline in at least 90% of them."""
    job, plan = paper_plan(name, "burst-hads")
    res = run_mc(job, plan, CFG, SCENARIOS["sc5"],
                 MCParams(n_scenarios=8, dt=30.0, seed=1))
    assert np.all(res.unfinished == 0)
    assert res.deadline_met.mean() >= 0.9, res.makespan


def test_scenario_trends(j60, plan_bh, plan_hads):
    """Table VI trends at distribution level: Burst-HADS meets the deadline
    at least as often as HADS, and stays cheaper than the on-demand map."""
    p = MCParams(n_scenarios=48, dt=30.0, seed=5)
    bh = run_mc(j60, plan_bh, CFG, SCENARIOS["sc5"], p)
    hd = run_mc(j60, plan_hads, CFG, SCENARIOS["sc5"], p)
    assert bh.deadline_met.mean() >= hd.deadline_met.mean()
    od = api.run(job=j60, policy=ILS_ONDEMAND, process=SC_NONE,
                 backend="mc-adaptive", cfg=CFG,
                 mc=MCParams(n_scenarios=1, dt=30.0, seed=5),
                 ils=FAST).raw
    assert bh.cost.mean() < od.cost[0]
    # hibernation events actually fire under sc5
    assert bh.n_hibernations.mean() > 0.2


def test_dt_validation(j60, plan_bh):
    """The fixed-slot engine needs dt on the ω/AC grid; the adaptive
    engine treats boundaries as jump targets and accepts any dt
    (DESIGN.md §2.5 — exercised end-to-end in tests/test_stepping.py)."""
    with pytest.raises(ValueError):
        run_mc(j60, plan_bh, CFG, SC_NONE,
               MCParams(n_scenarios=1, dt=37.0, stepping="slot"))
    res = run_mc(j60, plan_bh, CFG, SC_NONE,
                 MCParams(n_scenarios=1, dt=37.0))
    assert res.unfinished[0] == 0
