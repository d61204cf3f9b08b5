"""Spot-termination event direction end-to-end (DESIGN.md §2.8).

Terminations are the third event direction of the tensor contract: unlike
hibernation the column's state is *lost* — billing stops permanently, the
VM never resumes, and unfinished tasks roll back to the checkpoint floor
and always re-enter Alg. 4 migration.  This suite pins:

  * DES-vs-MC S=1 parity — explicit-vm ``TraceReplayProcess`` traces
    replayed through both engines give *exact* terminate/hibernate
    counts and cost/makespan within the engines' parity tolerances,
    across >=3 policies x >=3 termination traces;
  * collision semantics — terminate resolves before hibernate on a
    shared slot (ties toward the lower column index), and a terminated
    column can never be revived by a later resume;
  * adaptive-vs-slot stepping parity — the event-horizon jump lattice
    can never skip a terminate slot;
  * property invariants (hypothesis): a terminated VM never bills past
    its terminate instant; preemption rollback never exceeds the
    checkpoint floor; total work (hence cost at any fixed rate) is
    monotone non-decreasing in the checkpoint overhead budget; and
    under an immediate-migration policy a terminate-only trace is
    *equal* to the same trace hibernating forever (no resumes) — all
    across the full checkpoint axis (periodic | off | random);
  * a trace-hash golden (tests/data/termination_golden.json) freezing
    one terminating Poisson run on both engines.
"""
import dataclasses
import functools
import json
import math
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro import api
from repro.core.dynamic import POLICIES as POLICY_REGISTRY
from repro.core.ils import ILSParams
from repro.core.ils_jax import BatchedILSParams
from repro.core.runtime import (CHECKPOINT_WRITE_S, TaskRun, TaskState,
                                VMState)
from repro.core.types import CloudConfig, TaskSpec
from repro.ft.checkpoint import CHECKPOINT_MODES, checkpoint_schedule
from repro.sim.events import SCENARIOS
from repro.sim.market import (EventTensor, EventTensorError, PoissonProcess,
                              TraceReplayProcess)
from repro.sim.mc_engine import (MCParams, _select, n_slots_for,
                                 plan_column_uids, run_mc, run_mc_events)
from repro.sim.megabatch import evaluate_grid
from repro.sim.simulator import Simulator
from repro.sim.workloads import make_job

CFG = CloudConfig()
FAST = ILSParams(max_iteration=25, max_attempt=15, seed=3)
PARITY_MC = MCParams(n_scenarios=1, dt=10.0, seed=0)
#: DESIGN.md §2.3 pins cost parity for SC_NONE only; *eventful* S=1 runs
#: inherit the engines' migration-heuristic drift (measured here: ~25%
#: cost / ~24% makespan on the immediate-migration family — identical for
#: the hibernate twin of each trace, i.e. nothing terminate-specific).
#: The pinned bound keeps the §2.3 idiom of ~2x headroom over measured.
COST_RTOL, MKP_RTOL = 0.50, 0.50
GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "termination_golden.json")

#: >=3 policies across the steal/freeze axes of the immediate-migration
#: family (the deferred hads family keeps exact *count* parity only —
#: see test_deferred_family_keeps_exact_count_parity); the checkpoint
#: axis is swept separately by the property tests below
POLICIES = ("burst-hads", "burst-hads+nosteal", "burst-hads+freeze")
CKPT_POLICIES = ("burst-hads", "burst-hads+ckpt-off",
                 "burst-hads+ckpt-random")

#: sc5 with half the hibernations Bernoulli-converted into terminations
TERM_SC5 = dataclasses.replace(
    PoissonProcess.from_scenario(SCENARIOS["sc5"]),
    termination_frac=0.5, name="sc5-term")


@functools.lru_cache(maxsize=None)
def _j60():
    return make_job("J60")


@functools.lru_cache(maxsize=None)
def _cached_plan(name: str):
    """Plan via the facade's cross-backend cache (shared with api.run)."""
    return api._plan(_j60(), CFG, api.policy(name), FAST, None)


def _spot_cols(plan) -> list[int]:
    """Engine column indices of the plan's *primary spot* VMs — busy from
    boot until the job drains, so early events on them always find an
    eligible victim in both engines."""
    uids = plan_column_uids(plan)
    pool = {vm.uid: vm for vm in plan.solution.pool}
    primary = set(plan.solution.selected_uids)
    return [i for i, u in enumerate(uids)
            if pool[u].is_spot and u in primary]


def _term_traces(plan) -> list[TraceReplayProcess]:
    """>=3 termination traces targeting the plan's own spot columns, all
    inside the busy window (J60 drains around t~500s)."""
    cols = _spot_cols(plan)
    a, b, c, d = (cols * 4)[:4]
    return [
        TraceReplayProcess.from_events(
            [(240.0, "terminate", a)], name="term-one"),
        TraceReplayProcess.from_events(
            [(180.0, "terminate", a), (300.0, "hibernate", c),
             (390.0, "terminate", b)], name="term-mixed"),
        TraceReplayProcess.from_events(
            [(150.0, "terminate", b), (210.0, "terminate", c),
             (300.0, "terminate", a), (420.0, "terminate", d)],
            name="term-storm"),
    ]


# ---------------------------------------------------------------------------
# DES vs MC S=1 parity: exact counts, pinned cost/makespan tolerance
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pol", POLICIES)
@pytest.mark.parametrize("i_trace", range(3))
def test_des_mc_s1_termination_parity(pol, i_trace):
    """The S=1 parity bridge (§2.8): the same explicit-vm trace through
    the DES and the MC engine terminates the same VMs (exact counts) and
    lands within the engines' eventful-drift tolerance on cost/makespan
    (see COST_RTOL above — §2.3 pins the tight bound for SC_NONE only)."""
    job, plan = _j60(), _cached_plan(pol)
    proc = _term_traces(plan)[i_trace]
    des = Simulator(job, plan, CFG, scenario=proc, seed=0).run()
    mc = run_mc(job, plan, CFG, scenario=proc, params=PARITY_MC)
    assert mc.n_terminations is not None
    assert int(mc.n_terminations[0]) == des.n_terminations >= 1
    assert int(mc.n_hibernations[0]) == des.n_hibernations
    assert des.unfinished == 0 and int(mc.unfinished[0]) == 0
    np.testing.assert_allclose(mc.cost[0], des.cost, rtol=COST_RTOL)
    np.testing.assert_allclose(mc.makespan[0], des.makespan, rtol=MKP_RTOL)


@pytest.mark.parametrize("pol", ("hads", "hads+burst"))
def test_deferred_family_keeps_exact_count_parity(pol):
    """The deferred-migration (hads) family still terminates the exact
    same VMs in both engines.  Cost is deliberately NOT pinned here —
    under the default drain-argmin destination scoring the engines pack
    a failed VM's bag differently; the tightened cost/makespan pins live
    in test_hads_family_gap_stays_within_measured_bound, which runs the
    DES-faithful ``dest_cascade`` parity mode plus the §2.10
    orphan-retry ledger."""
    job, plan = _j60(), _cached_plan(pol)
    proc = _term_traces(plan)[0]
    des = Simulator(job, plan, CFG, scenario=proc, seed=0).run()
    mc = run_mc(job, plan, CFG, scenario=proc, params=PARITY_MC)
    assert int(mc.n_terminations[0]) == des.n_terminations >= 1
    assert int(mc.n_hibernations[0]) == des.n_hibernations
    assert des.unfinished == 0 and int(mc.unfinished[0]) == 0


#: ROADMAP 4(a) measured-bound pin, post-§2.10 fault recovery.  Three
#: mechanisms closed the old count-only gap (cost rel 2.29 / makespan
#: rel 0.76 / one 20-task orphan group dropped for good): released
#: on-demand columns relaunch (AC-idle termination no longer shrinks
#: launchable capacity), the orphan-retry ledger re-attempts every
#: infeasibility-gated migration group at later boundaries, and
#: ``dest_cascade`` scores destinations by the DES's literal Alg. 4
#: attempt order under the check_migration deadline rule.  Measured
#: worst case across the 2 policies x 3 traces below: cost rel 0.18
#: (hads / term-mixed), makespan rel 0.11 (term-one); the bounds keep
#: the §2.3 headroom idiom, the dropped bound is exact — the DES drains
#: every bag and now so does the MC.
HADS_GAP_COST_REL, HADS_GAP_MKP_REL = 1.1, 1.05
HADS_GAP_MAX_DROPPED = 0

#: DES-parity engine mode for the gap pins: the Alg. 4 cascade scoring
#: (the default drain-argmin stays pinned by the goldens)
CASCADE_MC = dataclasses.replace(PARITY_MC, dest_cascade=True)


@pytest.mark.parametrize("pol", ("hads", "hads+burst"))
@pytest.mark.parametrize("i_trace", range(3))
def test_hads_family_gap_stays_within_measured_bound(pol, i_trace):
    """The one-shot-migration vs orphan-retry gap of ROADMAP 4(a),
    pinned: event counts stay *exact* on every trace, BOTH engines drain
    the bag (the §2.10 recovery subsystem strands nothing), and the
    cost/makespan drift stays under the measured bounds (see HADS_GAP_*
    above)."""
    job, plan = _j60(), _cached_plan(pol)
    proc = _term_traces(plan)[i_trace]
    des = Simulator(job, plan, CFG, scenario=proc, seed=0).run()
    mc = run_mc(job, plan, CFG, scenario=proc, params=CASCADE_MC)
    assert int(mc.n_terminations[0]) == des.n_terminations >= 1
    assert int(mc.n_hibernations[0]) == des.n_hibernations
    assert int(mc.n_resumes[0]) == des.n_resumes
    assert des.unfinished == 0
    assert int(mc.unfinished[0]) <= HADS_GAP_MAX_DROPPED
    cost_rel = abs(float(mc.cost[0]) - des.cost) / des.cost
    mkp_rel = abs(float(mc.makespan[0]) - des.makespan) / des.makespan
    assert cost_rel <= HADS_GAP_COST_REL, cost_rel
    assert mkp_rel <= HADS_GAP_MKP_REL, mkp_rel


# ---------------------------------------------------------------------------
# Collision semantics + the jump lattice
# ---------------------------------------------------------------------------
def test_select_ties_to_lower_index():
    """The rank pass resolves score ties toward the lower column index and
    honours the negative-score opt-out regardless of rank."""
    pick = _select(jnp.full((1, 4), 0.5, jnp.float32),
                   jnp.ones((1, 4), bool), jnp.array([2], jnp.int32))
    np.testing.assert_array_equal(np.asarray(pick),
                                  [[True, True, False, False]])
    pick = _select(jnp.array([[0.5, -0.1, 0.9, 0.5]], jnp.float32),
                   jnp.array([[True, True, False, True]]),
                   jnp.array([2], jnp.int32))
    np.testing.assert_array_equal(np.asarray(pick),
                                  [[True, False, False, True]])


def test_terminate_wins_slot_collisions_and_never_revives():
    """On a shared slot the terminate resolves first (tie toward the
    lower index wins it the column) and excludes its victim from the
    hibernate pick; a later resume can never revive the terminated
    column, so neither event fires."""
    job, plan = _j60(), _cached_plan("burst-hads")
    v = len(plan_column_uids(plan))
    a, b = _spot_cols(plan)[:2]
    params = MCParams(n_scenarios=1, dt=30.0, seed=0)
    n = n_slots_for(job.deadline_s, params)
    s0, s1 = int(240 // params.dt), int(600 // params.dt)
    hib_k = np.zeros((1, n), np.int32)
    res_k = np.zeros((1, n), np.int32)
    term_k = np.zeros((1, n), np.int32)
    hib_u, res_u, term_u = (np.full((1, n, v), -2.0, np.float32)
                            for _ in range(3))
    term_k[0, s0] = 1
    term_u[0, s0, [a, b]] = 1.0      # equal scores: tie -> lower index a
    hib_k[0, s0] = 1
    hib_u[0, s0, a] = 1.0            # only the terminated column opts in
    res_k[0, s1] = 1
    res_u[0, s1, [a, b]] = 1.0       # nothing is hibernated at s1
    ev = EventTensor(jnp.asarray(hib_k), jnp.asarray(hib_u),
                     jnp.asarray(res_k), jnp.asarray(res_u), None,
                     jnp.asarray(term_k), jnp.asarray(term_u)
                     ).validate().with_index()
    res = run_mc_events(job, plan, CFG, ev, params)
    assert res.n_terminations.tolist() == [1]
    assert res.n_hibernations.tolist() == [0]
    assert res.n_resumes.tolist() == [0]


def test_adaptive_stepping_cannot_skip_terminations():
    """The event-horizon jump lattice counts terminate slots as events:
    adaptive and fixed-slot stepping agree on every terminating
    scenario (counts exactly, cost/makespan to f32 tolerance)."""
    job, plan = _j60(), _cached_plan("burst-hads")
    a = run_mc(job, plan, CFG, scenario=TERM_SC5,
               params=MCParams(n_scenarios=8, dt=30.0, seed=5))
    s = run_mc(job, plan, CFG, scenario=TERM_SC5,
               params=MCParams(n_scenarios=8, dt=30.0, seed=5,
                               stepping="slot"))
    np.testing.assert_array_equal(a.n_terminations, s.n_terminations)
    np.testing.assert_array_equal(a.n_hibernations, s.n_hibernations)
    np.testing.assert_array_equal(a.n_resumes, s.n_resumes)
    np.testing.assert_allclose(a.cost, s.cost, rtol=1e-6)
    np.testing.assert_allclose(a.makespan, s.makespan, rtol=1e-6)
    assert int(np.sum(a.n_terminations)) >= 1


def test_trace_tensor_has_termination_direction():
    """A terminating trace materializes ``term_k``/``term_u`` (explicit
    target score 2.0, everyone else opted out) and the next-event index
    points at the terminate slot."""
    tr = TraceReplayProcess.from_events(
        [(45.0, "terminate", 1), (45.0, "hibernate", 0)], name="x")
    ev = tr.sample(jax.random.PRNGKey(3), s=2, n_slots=10, v=3, dt=30.0,
                   deadline_s=300.0)
    assert ev.has_terminations
    tk = np.asarray(ev.term_k)
    assert tk[0, 1] == 1 and tk.sum() == 2          # one per scenario
    assert np.asarray(ev.hib_k)[0, 1] == 1          # collision stays put
    tu = np.asarray(ev.term_u)
    assert tu[0, 1, 1] == 2.0
    assert (tu[0, 1, [0, 2]] < 0.0).all()           # explicit slot: opt-out
    assert int(np.asarray(ev.nxt)[0, 0]) == 1       # jump lands on the slot


# ---------------------------------------------------------------------------
# EventTensor.pad + CSV round-trip with the terminate kind
# ---------------------------------------------------------------------------
def test_event_tensor_pad_keeps_terminations_inert():
    proc = dataclasses.replace(PoissonProcess.from_scenario(SCENARIOS["sc5"]),
                               termination_frac=1.0)
    ev = proc.sample(jax.random.PRNGKey(0), s=2, n_slots=10, v=4, dt=30.0,
                     deadline_s=300.0)
    assert ev.has_terminations
    p = ev.pad(n_slots=16, v=6)
    np.testing.assert_array_equal(p.term_k[:, :10], ev.term_k)
    np.testing.assert_array_equal(p.term_u[:, :10, :4], ev.term_u)
    assert not np.asarray(p.term_k)[:, 10:].any()   # pad slots event-free
    assert (np.asarray(p.term_u)[:, :, 4:] == -2.0).all()   # pad cols out
    assert (np.asarray(p.term_u)[:, 10:, :] == -2.0).all()
    # a termination-free tensor stays two-direction through pad
    ev2 = PoissonProcess.from_scenario(SCENARIOS["sc5"]).sample(
        jax.random.PRNGKey(0), s=2, n_slots=10, v=4, dt=30.0,
        deadline_s=300.0)
    p2 = ev2.pad(n_slots=16, v=6)
    assert p2.term_k is None and p2.term_u is None


def test_trace_csv_roundtrip_with_terminations(tmp_path):
    tr = TraceReplayProcess.from_events(
        [(12.5, "terminate", 0), (100.0, "hibernate", -1),
         (200.25, "resume", 2), (250.0, "terminate", -1)], name="rt")
    path = str(tmp_path / "trace.csv")
    tr.to_csv(path)
    assert TraceReplayProcess.from_csv(path, name="rt") == tr
    # unknown kinds are rejected before the tensor build, with the file row
    bad = tmp_path / "bad.csv"
    bad.write_text("time_s,kind,vm\n10.0,hibernate,0\n20.0,explode,1\n")
    with pytest.raises(EventTensorError, match="row 3"):
        TraceReplayProcess.from_csv(str(bad))
    with pytest.raises(EventTensorError, match="explode"):
        TraceReplayProcess.from_csv(str(bad))


# ---------------------------------------------------------------------------
# MC-side terminate == hibernate-forever (lost-work bracketing, exact end)
# ---------------------------------------------------------------------------
def test_mc_terminate_equals_hibernate_forever():
    """Under an immediate-migration policy with no resume events the two
    directions are observationally identical: both stop billing at the
    event and both roll the column's tasks to the checkpoint floor and
    migrate them — the bracket collapses to equality."""
    job, plan = _j60(), _cached_plan("burst-hads")
    a, b = _spot_cols(plan)[:2]
    term = TraceReplayProcess.from_events(
        [(180.0, "terminate", a), (300.0, "terminate", b)], name="t")
    hib = TraceReplayProcess.from_events(
        [(180.0, "hibernate", a), (300.0, "hibernate", b)], name="h")
    p = MCParams(n_scenarios=1, dt=30.0, seed=0)
    rt = run_mc(job, plan, CFG, scenario=term, params=p)
    rh = run_mc(job, plan, CFG, scenario=hib, params=p)
    assert rt.n_terminations.tolist() == [2]
    assert rh.n_hibernations.tolist() == [2]
    assert rt.n_hibernations.tolist() == [0]
    np.testing.assert_allclose(rt.cost, rh.cost, rtol=1e-6)
    np.testing.assert_allclose(rt.makespan, rh.makespan, rtol=1e-6)


# ---------------------------------------------------------------------------
# Trace-hash golden: one terminating Poisson run frozen on both engines
# ---------------------------------------------------------------------------
def _records_crc(records: list[dict]) -> int:
    lines = []
    for r in records:
        lines.append(",".join(
            f"{k}={r[k]:.6f}" if isinstance(r[k], float) else f"{k}={r[k]}"
            for k in sorted(r)))
    return zlib.crc32("\n".join(lines).encode())


def test_termination_trace_golden():
    with open(GOLDEN) as f:
        golden = json.load(f)
    job = _j60()
    g = golden["des"]
    plan = _cached_plan(g["policy"])
    sim = Simulator(job, plan, CFG, scenario=TERM_SC5, seed=g["seed"])
    res = sim.run()
    assert res.n_terminations == g["n_terminations"]
    assert res.n_hibernations == g["n_hibernations"]
    assert res.n_resumes == g["n_resumes"]
    assert res.unfinished == g["unfinished"]
    np.testing.assert_allclose(res.cost, g["cost"], atol=1e-6)
    np.testing.assert_allclose(res.makespan, g["makespan"], atol=1e-3)
    assert _records_crc(sim.records) == g["records_crc32"]

    m = golden["mc"]
    mc = run_mc(job, plan, CFG, scenario=TERM_SC5,
                params=MCParams(**m["params"]))
    np.testing.assert_array_equal(mc.n_terminations, m["n_terminations"])
    np.testing.assert_array_equal(mc.n_hibernations, m["n_hibernations"])
    np.testing.assert_allclose(mc.cost, m["cost"], atol=1e-6)


# ---------------------------------------------------------------------------
# Property sweep (all checkpoint axis points: periodic | off | random)
# ---------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(t=st.floats(120.0, 380.0), which=st.integers(0, 3),
       m=st.integers(0, 2))
def test_terminated_vm_never_bills_after_terminate(t, which, m):
    """Billing of a terminated VM stops at the terminate instant and never
    restarts — its final cost is exactly rate x (terminate - boot_done)
    even though the run continues well past it."""
    job, plan = _j60(), _cached_plan(CKPT_POLICIES[m])
    cols = _spot_cols(plan)
    col = cols[which % len(cols)]
    tr = TraceReplayProcess.from_events([(t, "terminate", col)], name="p1")
    sim = Simulator(job, plan, CFG, scenario=tr, seed=0)
    res = sim.run()
    assert res.n_terminations == 1 and res.unfinished == 0
    vm = sim.cluster.vms[plan_column_uids(plan)[col]]
    assert vm.state == VMState.TERMINATED
    assert vm.terminated_at == t
    assert res.makespan > t          # the run outlived the terminate
    assert math.isclose(vm.cost, vm.vm.price_per_sec * (t - vm.boot_done),
                        rel_tol=1e-9)


@settings(max_examples=40)
@given(base=st.floats(30.0, 600.0), ovh=st.floats(0.01, 0.5),
       frac=st.floats(0.0, 1.2), m=st.integers(0, 2),
       tid=st.integers(0, 10_000))
def test_preempt_rolls_back_to_checkpoint_floor(base, ovh, frac, m, tid):
    """Rollback lands exactly on the checkpoint grid: never above the
    floor of the raw progress, a multiple of the period (or completion),
    and mode 'off' loses everything short of completion."""
    mode = CHECKPOINT_MODES[m]
    tr = TaskRun(spec=TaskSpec(tid=tid, memory_mb=4.0, base_time=base),
                 ovh=ovh, ckpt=mode)
    tr.state = TaskState.RUNNING
    tr.started_at = 0.0
    tr.speed = 1.0
    now = frac * tr.total_base
    tr.preempt(now)
    cp = tr.cp_period_base
    assert 0.0 < cp <= tr.total_base + 1e-6
    raw = min(now, tr.total_base)
    assert tr.done_base <= raw + 1e-9                 # never invents work
    if raw >= tr.total_base - 1e-9:
        assert tr.done_base == tr.total_base          # finished at preempt
    else:
        assert tr.done_base == math.floor(raw / cp) * cp
        if mode == "off":
            assert tr.done_base == 0.0                # total loss
    assert tr.state == TaskState.PENDING and tr.vm_uid == -1


@settings(max_examples=30)
@given(base=st.lists(st.floats(20.0, 500.0), min_size=1, max_size=8),
       o1=st.floats(0.01, 0.4), o2=st.floats(0.01, 0.4),
       m=st.integers(0, 2))
def test_total_work_monotone_in_checkpoint_overhead(base, o1, o2, m):
    """More overhead budget never shrinks the billed work: ``total`` is
    monotone non-decreasing in ovh for every mode, and the checkpoint
    period always fits inside the total."""
    lo, hi = sorted((o1, o2))
    mode = CHECKPOINT_MODES[m]
    tids = list(range(len(base)))
    t_lo, cp_lo = checkpoint_schedule(base, lo, mode,
                                      write_s=CHECKPOINT_WRITE_S, tids=tids)
    t_hi, cp_hi = checkpoint_schedule(base, hi, mode,
                                      write_s=CHECKPOINT_WRITE_S, tids=tids)
    assert (t_hi >= t_lo).all()
    assert (cp_lo > 0).all() and (cp_hi > 0).all()
    assert (cp_lo <= t_lo + 1e-5).all() and (cp_hi <= t_hi + 1e-5).all()
    if mode == "off":
        np.testing.assert_array_equal(t_lo, t_hi)     # no overhead paid


def test_des_cost_monotone_in_overhead():
    """End-to-end form of the same monotonicity: the DES under the
    event-free baseline bills more as ovh grows (more work, same VMs)."""
    job, plan = _j60(), _cached_plan("burst-hads")
    costs = [Simulator(job, plan, CFG, seed=0, ovh=o).run().cost
             for o in (0.0, 0.10, 0.25)]
    assert costs == sorted(costs)


@settings(max_examples=5, deadline=None)
@given(times=st.lists(st.floats(120.0, 380.0), min_size=1, max_size=3),
       m=st.integers(0, 2))
def test_terminate_equals_hibernate_forever_under_migration(times, m):
    """The DES bracketing property on the whole checkpoint axis: with
    immediate migration and no resumes, terminating a VM and hibernating
    it forever are the same trajectory (identical rollback, identical
    final billing instant), so cost and makespan match exactly."""
    job, plan = _j60(), _cached_plan(CKPT_POLICIES[m])
    cols = _spot_cols(plan)
    events = [(t, cols[i % len(cols)]) for i, t in enumerate(sorted(times))]
    term = TraceReplayProcess.from_events(
        [(t, "terminate", c) for t, c in events], name="term")
    hib = TraceReplayProcess.from_events(
        [(t, "hibernate", c) for t, c in events], name="hib")
    rt = Simulator(job, plan, CFG, scenario=term, seed=0).run()
    rh = Simulator(job, plan, CFG, scenario=hib, seed=0).run()
    assert rt.n_terminations == rh.n_hibernations >= 1
    assert rt.n_hibernations == rh.n_terminations == 0
    assert math.isclose(rt.cost, rh.cost, rel_tol=1e-9)
    assert math.isclose(rt.makespan, rh.makespan, rel_tol=1e-9)


# ---------------------------------------------------------------------------
# Table VI-style trend artifact (ROADMAP 4(c)): termination_frac sweep
# ---------------------------------------------------------------------------
#: the three paper aliases swept below (Table V/VI column set)
PAPER_ALIASES = ("burst-hads", "hads", "ils-ondemand")


def test_termination_frac_trend_across_paper_aliases():
    """Teylo-style (arxiv 1810.10279) deadline-met vs termination-rate
    trend on one fused megabatch grid: 3 paper aliases x sc5 with
    ``termination_frac`` in (~0, 0.5, 1.0).  Positive fracs share the
    hibernation schedule (the frac only thresholds the conversion
    draws), so along the axis terminations can only be *added*.

    The trend is asserted where the engine structurally guarantees it:

      * burst-hads (the paper's full framework — immediate Alg. 4
        migration + stealing) stays monotone non-increasing in the
        frac and *dominates* hads at every point — Table VI's
        substantive claim;
      * ``mean_terminations`` is monotone non-decreasing in the frac
        for every alias, and actually fires for the event-exposed ones;
      * ils-ondemand holds no spot VMs, so its whole row is invariant
        in the frac with zero terminations.

    hads itself is deliberately NOT pinned monotone: converting a
    hibernation into a termination *bypasses* its deferred-migration
    wait (terminations always migrate immediately), so its deadline-met
    fraction can recover at high fracs (measured 0.875 -> 0.875 -> 1.0
    on this grid) — the ROADMAP 4(a) family effect, not a bug."""
    fracs = (1e-9, 0.5, 1.0)
    procs = [dataclasses.replace(
        PoissonProcess.from_scenario(SCENARIOS["sc5"]),
        termination_frac=f, name=f"sc5-t{i}") for i, f in enumerate(fracs)]
    grid = evaluate_grid(["J30"], list(PAPER_ALIASES), procs, cfg=CFG,
                         params=MCParams(n_scenarios=16, dt=30.0, seed=5),
                         ils_params=FAST,
                         batched_ils=BatchedILSParams(iterations=8, seed=3))
    rows = {(r["policy"], r["process"]): r for r in grid.rows}
    assert len(rows) == len(PAPER_ALIASES) * len(fracs)
    met = {p: [rows[p, f"sc5-t{i}"]["deadline_met_frac"]
               for i in range(len(fracs))] for p in PAPER_ALIASES}
    terms = {p: [rows[p, f"sc5-t{i}"]["mean_terminations"]
                 for i in range(len(fracs))] for p in PAPER_ALIASES}
    bh = met["burst-hads"]
    assert all(a >= b for a, b in zip(bh, bh[1:])), bh
    assert all(b >= h for b, h in zip(bh, met["hads"])), (bh, met["hads"])
    assert len(set(met["ils-ondemand"])) == 1, met["ils-ondemand"]
    assert terms["ils-ondemand"] == [0.0, 0.0, 0.0]
    for p in PAPER_ALIASES:
        assert terms[p] == sorted(terms[p]), (p, terms[p])
    assert terms["burst-hads"][-1] > 0 and terms["hads"][-1] > 0


#: the 48 distinct lattice points (aliases share objects with canonical
#: entries, so dedup by identity), in a stable order
LATTICE_48 = tuple(sorted({id(p): p for p in POLICY_REGISTRY.values()}
                          .values(), key=lambda p: p.name))


@settings(max_examples=4, deadline=None)
@given(frac=st.floats(0.5, 1.0), k_h=st.floats(2.0, 6.0),
       i_pol=st.integers(0, 47), seed=st.integers(0, 10**6))
def test_work_conservation_across_full_lattice(frac, k_h, i_pol, seed):
    """No task vanishes under terminate-heavy tensors, anywhere on the
    48-point policy lattice, in either engine.  The MC leg fuses all 48
    points through the megabatch grid and reads the engine's completion
    census (``work_conserved``: n_done + unfinished == n_tasks in every
    scenario); the DES leg replays one drawn point and asserts the same
    identity on its counters."""
    assert len(LATTICE_48) == 48
    proc = PoissonProcess(k_h, 1.0, termination_frac=frac, name="t-heavy")
    names = [p.name for p in LATTICE_48]
    grid = evaluate_grid(["J12"], names, [proc], cfg=CFG,
                         params=MCParams(n_scenarios=2, dt=30.0, seed=seed),
                         ils_params=FAST,
                         batched_ils=BatchedILSParams(iterations=8, seed=3))
    assert len(grid.rows) == 48
    for r in grid.rows:
        assert r["work_conserved"], (r["policy"], r)
    pol = LATTICE_48[i_pol]
    job = make_job("J12")
    plan = api._plan(job, CFG, pol, FAST, None)
    des = Simulator(job, plan, CFG, scenario=proc, seed=seed).run()
    assert des.n_completed + des.unfinished == len(job.tasks), \
        (pol.name, des.n_completed, des.unfinished)
