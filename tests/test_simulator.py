"""Simulator + dynamic-module behaviour under the Table V scenarios,
driven through the ``repro.api`` facade (the legacy ``simulate`` wrapper
is a deprecated shim)."""
import pytest

from repro import api
from repro.core.dynamic import BURST_HADS, HADS, ILS_ONDEMAND
from repro.core.ils import ILSParams
from repro.core.types import CloudConfig
from repro.sim.events import SCENARIOS, SC_NONE
from repro.sim.workloads import make_job

CFG = CloudConfig()
FAST = ILSParams(max_iteration=25, max_attempt=15, seed=3)


def simulate(job, cfg, pol, scenario, seed=0, params=None):
    """One DES trace via the facade, returning the raw ``SimResult``."""
    return api.run(job=job, policy=pol, process=scenario, backend="des",
                   cfg=cfg, seed=seed, ils=params).raw


@pytest.fixture(scope="module")
def j60():
    return make_job("J60")


def test_no_hibernation_completes(j60):
    r = simulate(j60, CFG, BURST_HADS, SC_NONE, seed=0, params=FAST)
    assert r.deadline_met and r.unfinished == 0
    assert r.cost > 0 and 0 < r.makespan <= j60.deadline_s
    assert r.n_hibernations == 0


@pytest.fixture(scope="module", params=["J60", "J80", "J100", "ED200"])
def paper_job(request):
    """Each Table III job, built once per module."""
    return make_job(request.param)


@pytest.mark.parametrize("sc", ["sc1", "sc2", "sc3", "sc4", "sc5"])
def test_burst_hads_meets_deadline_all_scenarios(paper_job, sc):
    """The paper's headline claim: deadline met even under hibernations,
    on every Table III job."""
    for seed in (0, 1):
        r = simulate(paper_job, CFG, BURST_HADS, SCENARIOS[sc], seed=seed,
                     params=FAST)
        assert r.unfinished == 0
        assert r.deadline_met, (paper_job.name, sc, seed, r.makespan)


def test_hads_slower_than_burst_hads(j60):
    rb = simulate(j60, CFG, BURST_HADS, SCENARIOS["sc2"], seed=11,
                  params=FAST)
    rh = simulate(j60, CFG, HADS, SCENARIOS["sc2"], seed=11, params=FAST)
    assert rb.makespan < rh.makespan      # Table VI trend


def test_burst_hads_cheaper_than_ondemand(j60):
    rb = simulate(j60, CFG, BURST_HADS, SC_NONE, seed=0, params=FAST)
    ro = simulate(j60, CFG, ILS_ONDEMAND, SC_NONE, seed=0, params=FAST)
    assert rb.cost < ro.cost              # Table IV trend


def test_migration_happens_on_hibernation(j60):
    r = simulate(j60, CFG, BURST_HADS, SCENARIOS["sc2"], seed=11,
                 params=FAST)
    assert r.n_hibernations >= 1
    assert any(k.startswith("migrations") for k in r.counters)


def test_determinism(j60):
    a = simulate(j60, CFG, BURST_HADS, SCENARIOS["sc5"], seed=7, params=FAST)
    b = simulate(j60, CFG, BURST_HADS, SCENARIOS["sc5"], seed=7, params=FAST)
    assert a.cost == b.cost and a.makespan == b.makespan
    assert a.counters == b.counters


def test_cost_bounds(j60):
    """Billing sanity: cost is bounded below by work at the cheapest spot
    core-rate and above by the whole pool running the full horizon."""
    r = simulate(j60, CFG, BURST_HADS, SC_NONE, seed=0, params=FAST)
    work = sum(t.base_time for t in j60.tasks)
    cheapest = min(t.price_spot / 3600 / t.vcpus for t in CFG.spot_types)
    assert r.cost >= work * cheapest * 0.5
    pool = CFG.instance_pool()
    worst = sum(vm.price_per_sec for vm in pool) * j60.deadline_s * 3
    assert r.cost <= worst


def test_trace_records_consistent(j60):
    from repro.core.dynamic import build_primary_map
    from repro.sim.simulator import Simulator
    plan = build_primary_map(j60, CFG, BURST_HADS, FAST)
    sim = Simulator(j60, plan, CFG, SCENARIOS["sc2"], seed=11)
    res = sim.run()
    assert res.unfinished == 0
    completes = [r for r in sim.records if r["ev"] == "complete"]
    assert len(completes) == j60.n_tasks
    # every complete is preceded by a dispatch of the same task
    by_tid = {}
    for r in sim.records:
        by_tid.setdefault(r["tid"], []).append(r["ev"])
    for tid, evs in by_tid.items():
        assert evs[0] == "dispatch"
        assert evs[-1] == "complete"


def test_burstable_credit_invariants(j60):
    from repro.core.dynamic import build_primary_map
    from repro.sim.simulator import Simulator
    plan = build_primary_map(j60, CFG, BURST_HADS, FAST)
    sim = Simulator(j60, plan, CFG, SCENARIOS["sc4"], seed=3)
    sim.run()
    for v in sim.cluster.vms.values():
        if v.vm.is_burstable:
            cap = v.vm.vm_type.credit_rate_per_hour * 24.0
            assert -1e-6 <= v.credits <= cap + 1e-6
            assert v.reserved_credits >= -1e-6
