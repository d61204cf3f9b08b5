"""Event-horizon stepping: adaptive-vs-slot parity + jump-safety properties.

Pins the DESIGN.md §2.5 contract:

* **parity** — on dt-aligned tensors the adaptive engine reproduces the
  fixed-slot engine exactly: identical hibernation/resume/unfinished
  counts in every scenario, cost/makespan equal to rounding (the only
  admissible difference is float re-association of the closed-form span
  advance vs per-slot subtraction);
* **golden** — the legacy slot path itself stays pinned to the PR 2
  engine via tests/data/mc_golden.json (the default adaptive path is
  pinned against the same goldens by tests/test_market.py);
* **jump safety** — the adaptive engine never lands past an unprocessed
  event slot or an AC boundary: every requested-event slot and every
  AC-handling slot below a scenario's exit is full-stepped (checked
  against the engine's per-scenario ``visited`` mask);
* **off-grid dt** — adaptive stepping lifts the dt-divides-ω/AC
  restriction that the slot engine still enforces.
"""
import json
import os

import jax
import numpy as np
import pytest

from repro.core.dynamic import BURST_HADS, HADS, build_primary_map
from repro.core.ils import ILSParams
from repro.core.types import CloudConfig
from repro.sim.market import (MarkovModulatedProcess, TraceReplayProcess,
                              WeibullProcess, as_process)
from repro.sim.mc_engine import (MCParams, n_slots_for, plan_column_uids,
                                 run_mc, run_mc_events)
from repro.sim.workloads import make_job

CFG = CloudConfig()
FAST = ILSParams(max_iteration=25, max_attempt=15, seed=3)
GOLDEN = os.path.join(os.path.dirname(__file__), "data", "mc_golden.json")


@pytest.fixture(scope="module")
def j60():
    return make_job("J60")


@pytest.fixture(scope="module")
def plans(j60):
    return {"burst-hads": build_primary_map(j60, CFG, BURST_HADS, FAST),
            "hads": build_primary_map(j60, CFG, HADS, FAST)}


def _pair(job, plan, spec, **kw):
    a = run_mc(job, plan, CFG, spec, MCParams(stepping="slot", **kw))
    b = run_mc(job, plan, CFG, spec, MCParams(stepping="adaptive", **kw))
    return a, b


def _assert_parity(slot, adaptive):
    # event resolution must be *identical*, not just statistically close
    np.testing.assert_array_equal(adaptive.n_hibernations,
                                  slot.n_hibernations)
    np.testing.assert_array_equal(adaptive.n_resumes, slot.n_resumes)
    np.testing.assert_array_equal(adaptive.unfinished, slot.unfinished)
    np.testing.assert_array_equal(adaptive.deadline_met, slot.deadline_met)
    # closed-form span advance re-associates float ops: to-rounding only
    np.testing.assert_allclose(adaptive.cost, slot.cost, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(adaptive.makespan, slot.makespan,
                               rtol=1e-5, atol=1e-2)
    np.testing.assert_allclose(adaptive.billed_s, slot.billed_s,
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("policy", ["burst-hads", "hads"])
@pytest.mark.parametrize("spec", ["sc1", "sc5"])
def test_parity_poisson(j60, plans, policy, spec):
    slot, adaptive = _pair(j60, plans[policy], spec,
                           n_scenarios=32, dt=30.0, seed=2)
    _assert_parity(slot, adaptive)
    # the adaptive engine actually skipped work (it's not the slot walk)
    assert adaptive.slots_visited < adaptive.slots_total


@pytest.mark.parametrize("policy", ["burst-hads", "hads"])
def test_parity_weibull_and_mmpp(j60, plans, policy):
    d = j60.deadline_s
    for proc in (WeibullProcess(shape_h=0.7, scale_h=d / 3.0, shape_r=1.0,
                                scale_r=d / 2.5, name="wb"),
                 MarkovModulatedProcess(k_h_calm=0.5, k_h_turb=12.0,
                                        k_r=2.5, name="mmpp")):
        slot, adaptive = _pair(j60, plans[policy], proc,
                               n_scenarios=16, dt=30.0, seed=4)
        _assert_parity(slot, adaptive)


def test_parity_trace_replay(j60, plans):
    """dt-aligned empirical trace: explicit-vm and anonymous events must
    resolve to the same victims under both steppings."""
    trace = TraceReplayProcess.from_events(
        [(120.0, "hibernate", -1), (600.0, "hibernate", 0),
         (900.0, "resume", -1), (1500.0, "hibernate", -1),
         (1800.0, "resume", -1)], name="trace")
    for policy in ("burst-hads", "hads"):
        slot, adaptive = _pair(j60, plans[policy], trace,
                               n_scenarios=8, dt=30.0, seed=6)
        _assert_parity(slot, adaptive)


def test_slot_engine_stays_pinned_to_golden(plans):
    """The legacy fixed-slot path must keep reproducing the PR 2 engine
    per seed (tests/test_market.py pins the *adaptive* default against
    the same goldens)."""
    with open(GOLDEN) as f:
        golden = json.load(f)
    job = make_job(golden["job"])
    for case in golden["cases"][:2]:
        res = run_mc(job, plans[case["policy"]], CFG, case["scenario"],
                     MCParams(n_scenarios=case["s"], dt=case["dt"],
                              seed=case["seed"], stepping="slot"))
        np.testing.assert_array_equal(res.n_hibernations,
                                      case["n_hibernations"])
        np.testing.assert_array_equal(res.n_resumes, case["n_resumes"])
        np.testing.assert_allclose(res.cost, case["cost"], rtol=1e-5,
                                   atol=2e-6)
        np.testing.assert_allclose(res.makespan, case["makespan"],
                                   rtol=1e-5, atol=2e-3)


@pytest.mark.parametrize("policy", ["burst-hads", "hads"])
def test_never_lands_past_event_or_ac(j60, plans, policy):
    """Property sweep: per scenario, every slot with a requested event
    and every AC-handling slot below the scenario's exit must appear in
    the engine's full-stepped ``visited`` mask — jumps may stop early,
    never late."""
    plan = plans[policy]
    dt = 30.0
    p = MCParams(n_scenarios=24, dt=dt, seed=9)
    n = n_slots_for(j60.deadline_s, p)
    v = len(plan_column_uids(plan))
    d = j60.deadline_s
    for proc in (as_process("sc5"),
                 WeibullProcess(shape_h=0.6, scale_h=d / 4.0, shape_r=1.0,
                                scale_r=d / 2.0, name="wb"),
                 MarkovModulatedProcess(k_h_calm=0.5, k_h_turb=15.0,
                                        k_r=3.0, name="mmpp")):
        ev = proc.sample(jax.random.PRNGKey(11), s=p.n_scenarios,
                         n_slots=n, v=v, dt=dt, deadline_s=d)
        res = run_mc_events(j60, plan, CFG, ev, p)
        has_ev = np.asarray((ev.hib_k > 0) | (ev.res_k > 0))
        visited = res.visited
        exits = res.exit_slots
        ac = np.arange(n)
        boot_slots = round(CFG.boot_overhead_s / dt)
        ac_slots = round(CFG.allocation_cycle_s / dt)
        is_ac_handler = ((ac + 1 > boot_slots) &
                         ((ac + 1 - boot_slots) % ac_slots == 0))
        for s_ in range(p.n_scenarios):
            # a scenario is live until its last task completes (events
            # and AC blocks are gate-masked no-ops afterwards — the slot
            # engine ignores them too, so jumping them is admissible)
            until = exits[s_] if res.unfinished[s_] > 0 else \
                min(exits[s_], int(np.floor(res.makespan[s_] / dt - 1e-6)))
            live = np.arange(n) < until
            ev_missed = has_ev[s_] & live & ~visited[s_]
            ac_missed = is_ac_handler & live & ~visited[s_]
            assert not ev_missed.any(), \
                (proc.name, s_, np.nonzero(ev_missed))
            assert not ac_missed.any(), \
                (proc.name, s_, np.nonzero(ac_missed))


def test_unfinished_at_horizon_freezes(j60, plans):
    """A scenario that reaches the horizon with pending work must freeze
    (no billing, progress or event accrual) while other scenarios keep
    running — under per-scenario clocks it would otherwise keep
    full-stepping the clamped last slot.  A truncated horizon forces a
    mix of finished and unfinished scenarios; parity with the lockstep
    slot walk (whose global exit freezes everyone) pins the behaviour."""
    kw = dict(n_scenarios=24, dt=30.0, seed=3, horizon_mult=1.0)
    slot, adaptive = _pair(j60, plans["hads"], "sc5", **kw)
    assert (adaptive.unfinished > 0).any(), "want unfinished-at-horizon"
    assert (adaptive.unfinished == 0).any(), "want a mixed batch"
    _assert_parity(slot, adaptive)


def test_off_grid_dt(j60, plans):
    """Adaptive stepping accepts a dt that divides neither ω nor AC —
    boundaries are jump targets, not grid points — while the slot engine
    still refuses it."""
    with pytest.raises(ValueError):
        run_mc(j60, plans["burst-hads"], CFG, "sc5",
               MCParams(n_scenarios=2, dt=37.0, stepping="slot"))
    res = run_mc(j60, plans["burst-hads"], CFG, "sc5",
                 MCParams(n_scenarios=4, dt=37.0, seed=3))
    assert np.all(res.unfinished == 0)
    # coarse cross-check against the aligned run: same distribution scale
    ref = run_mc(j60, plans["burst-hads"], CFG, "sc5",
                 MCParams(n_scenarios=4, dt=30.0, seed=3))
    assert abs(res.cost.mean() - ref.cost.mean()) < 0.25 * ref.cost.mean()


@pytest.mark.parametrize("s,b,v", [(7, 130, 17), (1, 1, 1), (3, 17, 5),
                                   (8, 60, 27), (16, 200, 35),
                                   (9, 200, 64)])
def test_span_kernel_matches_oracle(s, b, v):
    """``mc_span_reduce`` (fused span advance + reductions) against the
    jnp oracle, including per-scenario span lengths and opt-out tasks.
    b > one task tile hits accumulation; (16, 200, 35) is ED200 on the
    35-VM catalog."""
    from repro.kernels.sched_fitness.ops import mc_span_advance
    from repro.kernels.sched_fitness.ref import mc_span_advance_ref
    key = jax.random.PRNGKey(3)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    assign = jax.random.randint(k1, (s, b), -1, v)
    rem = jax.random.uniform(k2, (s, b)) * 50.0
    rem = rem * (jax.random.uniform(k3, (s, b)) > 0.2)
    drem = jax.random.uniform(k4, (s, b)) * 0.5
    m = jax.numpy.asarray(np.resize([0., 1., 3., 10., 40., 2., 7.], s))
    got = mc_span_advance(assign, rem, drem, m, v=v)
    want = mc_span_advance_ref(
        assign, rem, jax.numpy.where(rem > 0, drem, 0.0), m, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


def test_plan_array_cache_hits(j60, plans):
    """Repeated ``run_mc`` calls on one plan reuse the flattened arrays
    (the S=1 hot-path fix): same object, no re-flattening."""
    from repro.sim import mc_engine
    plan = plans["burst-hads"]
    arr1, uids1, ms1 = mc_engine._plan_arrays_cached(j60, plan, CFG, 0.10)
    arr2, uids2, ms2 = mc_engine._plan_arrays_cached(j60, plan, CFG, 0.10)
    assert arr1 is arr2 and uids1 is uids2 and ms1 == ms2
