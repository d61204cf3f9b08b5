"""Online service mode end-to-end (DESIGN.md §2.9).

Two contracts are pinned here:

* **Mid-horizon entry** — engine start state is an explicit input
  (``EngineState``), and the extract/inject round trip
  ``run(plan) == run(run(plan, stop=t).state, from=t)`` is *bit-exact*
  on the slot path: chaining a J60/sc5 run through every AC boundary
  reproduces the uninterrupted run's cost / makespan / billing / event
  counts exactly (adaptive stepping: counts exact, cost/makespan within
  the §2.5 span bound).  A two-engine golden
  (``tests/data/service_roundtrip_golden.json``) freezes both steppings
  across sessions; re-entry through a sliced tensor
  (``events.slice_event_tensor`` + ``t0_s``) is part of the pin.

* **Admission invariants** — ``service.Service`` renders one
  deterministic verdict per arrival (DEADLINE_MISSED / CONGESTION /
  SUCCESS): verdicts are a pure function of (stream, seed); an ADMITTED
  task is feasible at its admission instant (projected ETA within its
  deadline); rejects never mutate the incumbent plan (pruning rejected
  arrivals from the stream leaves the admitted tasks' verdicts,
  placements and the final engine outcome bit-identical); and
  warm-started replanning never evicts an already-admitted task past
  its deadline (the ``_eviction_safe`` guard, unit + end-to-end).

Run this file as a script to regenerate the golden:
``PYTHONPATH=src python tests/test_service.py``.
"""
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro import api
from repro.core.dynamic import ArrivalPolicy
from repro.core.ils import ILSParams
from repro.core.types import CloudConfig, TaskSpec
from repro.service import (PROVENANCE_ARRIVAL, PROVENANCE_REQUEUED,
                           VERDICT_CONGESTION, VERDICT_DEADLINE_MISSED,
                           VERDICT_SUCCESS, VM_TERMINATED, Arrival, Service,
                           arrivals_from_csv, arrivals_to_csv,
                           bursty_arrivals, stationary_arrivals)
from repro.sim.events import SCENARIOS, slice_event_tensor
from repro.sim.market import PoissonProcess
from repro.sim.mc_engine import (EngineState, MCParams, n_slots_for,
                                 plan_column_uids, run_mc_events)

CFG = CloudConfig()
FAST = ILSParams(max_iteration=25, max_attempt=15, seed=3)
GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "service_roundtrip_golden.json")

#: the round-trip cell: J60 / sc5 / burst-hads, S=4 scenarios
RT_SEED, RT_S, RT_DT = 7, 4, 30.0


@functools.lru_cache(maxsize=None)
def _j60():
    from repro.sim.workloads import make_job
    return make_job("J60")


@functools.lru_cache(maxsize=None)
def _plan():
    return api._plan(_j60(), CFG, api.policy("burst-hads"), FAST, None)


@functools.lru_cache(maxsize=None)
def _tensor():
    """One pregenerated sc5 tensor shared by every round-trip test."""
    job, plan = _j60(), _plan()
    params = MCParams(n_scenarios=RT_S, dt=RT_DT, seed=RT_SEED)
    return PoissonProcess.from_scenario(SCENARIOS["sc5"]).sample(
        jax.random.PRNGKey(RT_SEED), s=RT_S,
        n_slots=n_slots_for(job.deadline_s, params),
        v=len(plan_column_uids(plan)), dt=RT_DT,
        deadline_s=job.deadline_s)


def _params(stepping: str) -> MCParams:
    return MCParams(n_scenarios=RT_S, dt=RT_DT, seed=RT_SEED,
                    stepping=stepping)


def _ac_boundaries() -> list[float]:
    """Every AC-check instant inside the horizon: omega + k * AC —
    the paper's allocation cycle anchored at the boot edge."""
    job = _j60()
    horizon = job.deadline_s * 3.0
    omega, ac = CFG.boot_overhead_s, CFG.allocation_cycle_s
    out, t = [], omega + ac
    while t < horizon:
        out.append(t)
        t += ac
    return out


def _uninterrupted(stepping: str):
    return run_mc_events(_j60(), _plan(), CFG, _tensor(),
                         _params(stepping), label="sc5")


def _chained(stepping: str, stops):
    """Stop at every boundary, extract the state, re-enter — then run
    out to the horizon."""
    params = _params(stepping)
    state = None
    for t in stops:
        r = run_mc_events(_j60(), _plan(), CFG, _tensor(), params,
                          label="sc5", stop_s=t, state=state,
                          return_state=True)
        assert isinstance(r.state, EngineState)
        state = r.state
    return run_mc_events(_j60(), _plan(), CFG, _tensor(), params,
                         label="sc5", state=state)


def _counts(res) -> dict:
    return {"n_hib": res.n_hibernations.tolist(),
            "n_res": res.n_resumes.tolist(),
            "n_term": res.n_terminations.tolist(),
            "unfinished": res.unfinished.tolist()}


# ---------------------------------------------------------------------------
# Mid-horizon entry: the extract/inject round trip
# ---------------------------------------------------------------------------
def test_roundtrip_bit_exact_on_slot_path():
    """Chaining through every AC boundary == the uninterrupted run,
    bit-for-bit: cost, makespan, per-VM billing and event counts."""
    ref = _uninterrupted("slot")
    chained = _chained("slot", _ac_boundaries())
    assert _counts(chained) == _counts(ref)
    np.testing.assert_array_equal(chained.cost, ref.cost)
    np.testing.assert_array_equal(chained.makespan, ref.makespan)
    np.testing.assert_array_equal(chained.billed_s, ref.billed_s)
    assert int(np.sum(ref.n_hibernations)) >= 1      # an eventful run


def test_roundtrip_adaptive_within_span_bound():
    """Adaptive stepping: AC boundaries are already jump targets, so
    stopping there cuts no span — counts are exact and cost/makespan
    land within the §2.5 closed-form-span tolerance."""
    ref = _uninterrupted("adaptive")
    chained = _chained("adaptive", _ac_boundaries())
    assert _counts(chained) == _counts(ref)
    np.testing.assert_allclose(chained.cost, ref.cost, rtol=1e-6)
    np.testing.assert_allclose(chained.makespan, ref.makespan, rtol=1e-6)


@pytest.mark.parametrize("stepping", ("slot", "adaptive"))
def test_sliced_tensor_reentry(stepping):
    """Re-entry may drop already-consumed slots: slicing the tensor at
    the stop instant and anchoring it with ``t0_s`` continues the same
    absolute timeline."""
    t = _ac_boundaries()[0]
    params = _params(stepping)
    ref = _uninterrupted(stepping)
    r1 = run_mc_events(_j60(), _plan(), CFG, _tensor(), params,
                       label="sc5", stop_s=t, return_state=True)
    tail = slice_event_tensor(_tensor(), t, RT_DT)
    r2 = run_mc_events(_j60(), _plan(), CFG, tail, params, label="sc5",
                       state=r1.state, t0_s=t)
    assert _counts(r2) == _counts(ref)
    if stepping == "slot":
        np.testing.assert_array_equal(r2.cost, ref.cost)
        np.testing.assert_array_equal(r2.makespan, ref.makespan)
        np.testing.assert_array_equal(r2.billed_s, ref.billed_s)
    else:
        np.testing.assert_allclose(r2.cost, ref.cost, rtol=1e-6)
        np.testing.assert_allclose(r2.makespan, ref.makespan, rtol=1e-6)


def test_roundtrip_golden():
    """Two-engine golden: both steppings' uninterrupted runs are frozen
    across sessions, and the chained slot run must equal the golden too
    (the round trip can't drift away from the pin)."""
    with open(GOLDEN) as f:
        g = json.load(f)
    assert g["boundaries"] == _ac_boundaries()
    for stepping in ("slot", "adaptive"):
        sec = g[stepping]
        res = _uninterrupted(stepping)
        assert _counts(res) == sec["counts"]
        np.testing.assert_allclose(res.cost, sec["cost"], atol=1e-6)
        np.testing.assert_allclose(res.makespan, sec["makespan"],
                                   atol=1e-3)
    chained = _chained("slot", _ac_boundaries())
    assert _counts(chained) == g["slot"]["counts"]
    np.testing.assert_allclose(chained.cost, g["slot"]["cost"], atol=1e-6)


def test_state_injection_validation():
    """Malformed re-entries fail loudly: stop outside the horizon, state
    shaped for a different run, non-uniform clocks on the slot path."""
    params = _params("slot")
    with pytest.raises(ValueError, match="stop_s"):
        run_mc_events(_j60(), _plan(), CFG, _tensor(), params,
                      stop_s=1e9)
    r = run_mc_events(_j60(), _plan(), CFG, _tensor(), params,
                      stop_s=_ac_boundaries()[0], return_state=True)
    bad = r.state.pad_tasks(r.state.n_tasks + 3)
    with pytest.raises(ValueError, match="does not match the run"):
        run_mc_events(_j60(), _plan(), CFG, _tensor(), params, state=bad)
    skew = dataclasses.replace(
        r.state, slot=np.asarray(r.state.slot) + np.arange(RT_S))
    with pytest.raises(ValueError, match="lockstep"):
        run_mc_events(_j60(), _plan(), CFG, _tensor(), params, state=skew)


# ---------------------------------------------------------------------------
# Admission invariants
# ---------------------------------------------------------------------------
def _svc(**kw) -> Service:
    kw.setdefault("policy", "burst-hads")
    kw.setdefault("horizon_s", 8100.0)
    return Service(**kw)


#: a stream under pressure: all three verdicts appear (pinned below)
PRESSED = dict(n=60, rate_per_s=0.5, rel_deadline_s=480.0, seed=3)


@functools.lru_cache(maxsize=None)
def _pressed_run():
    return _svc().run(bursty_arrivals(**PRESSED))


def test_all_three_verdicts_render():
    res = _pressed_run()
    vc = res.verdict_counts
    assert min(vc.values()) >= 1, vc
    assert res.n_admitted + res.n_rejected == len(res.records) == 60
    assert 0.0 <= res.slo_met_frac <= 1.0
    assert res.replan_p95_ms > 0.0


def test_verdict_reasons_are_ordered():
    """DEADLINE_MISSED means even an empty column misses; CONGESTION
    means execution fits but the projected backlog does not; SUCCESS
    records a feasible ETA.  The recorded ETA bound certifies each."""
    for r in _pressed_run().records:
        if r.verdict == VERDICT_SUCCESS:
            assert r.eta_s <= r.deadline_s + 1e-6
            assert r.column >= 0
        else:
            assert r.eta_s > r.deadline_s
            assert r.column == -1


@settings(max_examples=2, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_verdicts_deterministic_per_seed(seed):
    """The verdict sequence is a pure function of (stream, seed): two
    fresh services replaying the same stream agree record-for-record."""
    arr = bursty_arrivals(24, rate_per_s=0.3, rel_deadline_s=600.0,
                          seed=seed)
    r1, r2 = _svc().run(arr), _svc().run(arr)
    assert r1.records == r2.records
    np.testing.assert_array_equal(r1.cost, r2.cost)
    np.testing.assert_array_equal(r1.done_at_s, r2.done_at_s)


def test_rejects_never_mutate_incumbent():
    """Pruning every rejected arrival from the stream is a no-op for the
    admitted ones: identical verdicts, placements and a bit-identical
    final engine outcome — a reject that mutated any ledger or the
    engine state would break the equality."""
    full = _pressed_run()
    assert full.n_rejected >= 1
    admitted = {r.tid for r in full.records
                if r.verdict == VERDICT_SUCCESS}
    arr = bursty_arrivals(**PRESSED)
    pruned_res = _svc().run([a for a in arr if a.task.tid in admitted])
    f_adm = [r for r in full.records if r.verdict == VERDICT_SUCCESS]
    assert pruned_res.records == f_adm
    np.testing.assert_array_equal(pruned_res.cost, full.cost)
    np.testing.assert_array_equal(pruned_res.makespan_s, full.makespan_s)
    np.testing.assert_array_equal(pruned_res.done_at_s, full.done_at_s)


def test_admitted_tasks_tracked_exactly():
    """The engine's task ledger holds exactly the admitted tasks — a
    reject never grows it."""
    res = _pressed_run()
    assert res.done_at_s.shape[1] == res.n_admitted
    assert len(res.deadlines_s) == res.n_admitted


def test_eviction_guard_blocks_deadline_push():
    """Unit pin of ``_eviction_safe``: a candidate that moves a pending
    task from its fast column to one whose projected finish misses the
    deadline is rejected; keeping the placement (or a harmless move) is
    accepted."""
    svc = _svc()
    speeds = svc._speed * svc._cores
    fast = int(np.argmax(np.where(svc._elig_static, speeds, -1.0)))
    slow = int(np.argmin(np.where(svc._elig_static, speeds, np.inf)))
    t_b = 300.0
    task = TaskSpec(tid=0, memory_mb=4.0, base_time=200.0)
    # deadline sits between the two columns' projected finishes in the
    # guard's own drain units (load / (cores * speed)): the incumbent
    # placement meets it with ~50s slack, the slow column misses it
    drain_fast = 220.0 / speeds[fast]
    a = Arrival(10.0, task,
                t_b + CFG.boot_overhead_s + drain_fast + 50.0)
    assert 220.0 / speeds[slow] > drain_fast + 50.0
    svc._ensure_cap(1)
    rec = svc._place(a, t_b, 220.0, 220.0, fast, 0.0)
    assert rec.verdict == VERDICT_SUCCESS
    idx = np.array([0])
    assert svc._eviction_safe(t_b, idx, np.array([fast]))
    assert not svc._eviction_safe(t_b, idx, np.array([slow]))


def test_replanning_never_evicts_admitted_past_deadline():
    """End-to-end guard check: with per-boundary warm-started ILS
    refinement, every admitted task that met its deadline without
    refinement still meets it with refinement (event-free timeline)."""
    arr = bursty_arrivals(40, rate_per_s=0.25, rel_deadline_s=1200.0,
                          seed=11)
    base = _svc().run(arr)
    ref = _svc(arrival=ArrivalPolicy(ils_every=1)).run(arr)
    assert {r.tid for r in base.records if r.verdict == VERDICT_SUCCESS} \
        == {r.tid for r in ref.records if r.verdict == VERDICT_SUCCESS}
    base_met = (base.done_at_s[0] <= base.deadlines_s + 1e-6)
    ref_met = (ref.done_at_s[0] <= ref.deadlines_s + 1e-6)
    assert np.all(ref_met | ~base_met), \
        "refinement evicted an admitted task past its deadline"
    assert int(ref.unfinished[0]) == 0


# ---------------------------------------------------------------------------
# Arrival streams
# ---------------------------------------------------------------------------
def test_arrival_generators_deterministic():
    a = stationary_arrivals(50, seed=4)
    b = stationary_arrivals(50, seed=4)
    assert a == b
    assert all(x.time_s < y.time_s for x, y in zip(a, b[1:]))
    assert all(x.deadline_s > x.time_s for x in a)
    c = bursty_arrivals(50, seed=4)
    assert c == bursty_arrivals(50, seed=4)
    assert c != a


def test_bursty_stream_is_bursty():
    """The on/off modulation shows: inter-arrival gaps inside bursts are
    much tighter than the stationary stream's at the same base rate."""
    arr = bursty_arrivals(400, rate_per_s=0.05, burst_factor=8.0,
                          seed=9)
    gaps = np.diff([a.time_s for a in arr])
    assert np.median(gaps) < 0.5 * (1.0 / 0.05)


def test_arrival_csv_roundtrip(tmp_path):
    arr = bursty_arrivals(20, seed=2)
    path = str(tmp_path / "trace.csv")
    arrivals_to_csv(arr, path)
    back = arrivals_from_csv(path)
    assert len(back) == len(arr)
    for x, y in zip(arr, back):
        assert x.task.tid == y.task.tid
        assert np.isclose(x.time_s, y.time_s)
        assert np.isclose(x.deadline_s, y.deadline_s)
        assert np.isclose(x.task.base_time, y.task.base_time)
    bad = tmp_path / "bad.csv"
    bad.write_text("time_s,tid\n1.0,0\n")
    with pytest.raises(ValueError, match="missing columns"):
        arrivals_from_csv(str(bad))


def test_service_is_one_shot_and_rejects_bad_streams():
    svc = _svc()
    svc.run(stationary_arrivals(3, seed=0))
    with pytest.raises(RuntimeError, match="one-shot"):
        svc.run(stationary_arrivals(3, seed=0))
    with pytest.raises(ValueError, match="negative"):
        _svc().run([Arrival(-1.0, TaskSpec(0, 4.0, 100.0), 100.0)])


def test_past_horizon_arrivals_rejected():
    """An arrival whose fold boundary lands beyond the service horizon
    can never be scheduled — rejected as CONGESTION, not dropped."""
    late = Arrival(8090.0, TaskSpec(tid=99, memory_mb=4.0,
                                    base_time=100.0), 9000.0)
    res = _svc().run([late])
    assert len(res.records) == 1
    assert res.records[0].verdict == VERDICT_CONGESTION


# ---------------------------------------------------------------------------
# Fault recovery: re-admission of stranded work (DESIGN.md §2.10)
# ---------------------------------------------------------------------------
def _stranded_pair():
    """Two admitted tasks; task 0's column is then surgically terminated
    in scenario 0 — the minimal stranded-work state (engine-produced
    stranding needs a migration failure, which on-demand fallback makes
    nearly impossible by design; the unit contract is what's pinned)."""
    svc = _svc(process="none", seed=0)
    svc._ensure_cap(1)
    recs = [svc._admit(Arrival(10.0 * (i + 1),
                               TaskSpec(tid=i, memory_mb=1000.0,
                                        base_time=400.0), 4000.0), 300.0)
            for i in range(2)]
    assert all(r.verdict == VERDICT_SUCCESS for r in recs)
    vstate = np.array(svc._state.vstate)
    vstate[0, recs[0].column] = VM_TERMINATED
    svc._state = dataclasses.replace(svc._state,
                                     vstate=jnp.asarray(vstate))
    return svc, recs


def test_requeue_relocates_stranded_task_in_place():
    svc, recs = _stranded_pair()
    rem_before = np.asarray(svc._state.rem[:, :2]).copy()
    svc._requeue_stranded(600.0)
    req = [r for r in svc._records if r.provenance == PROVENANCE_REQUEUED]
    assert [(r.tid, r.verdict) for r in req] == [(0, VERDICT_SUCCESS)]
    new_col = svc._assign[0]
    assert new_col != recs[0].column
    assert np.asarray(svc._state.vstate)[0, new_col] != VM_TERMINATED
    # relocation preserves per-scenario progress: rem untouched
    np.testing.assert_allclose(np.asarray(svc._state.rem[:, :2]),
                               rem_before)
    # the healthy task is untouched
    assert svc._assign[1] == recs[1].column


def test_requeue_noop_without_terminated_columns():
    svc = _svc(process="none", seed=0)
    svc._ensure_cap(1)
    svc._records.append(svc._admit(
        Arrival(10.0, TaskSpec(tid=0, memory_mb=1000.0, base_time=400.0),
                4000.0), 300.0))
    n = len(svc._records)
    svc._requeue_stranded(600.0)
    assert len(svc._records) == n


def test_requeue_deadline_missed_is_terminal_and_mutates_nothing():
    svc, recs = _stranded_pair()
    assign_before = list(svc._assign)
    rem_before = np.asarray(svc._state.rem).copy()
    # boundary so late even an empty column overruns the 4000 s deadline
    svc._requeue_stranded(3900.0)
    req = [r for r in svc._records if r.provenance == PROVENANCE_REQUEUED]
    assert [r.verdict for r in req] == [VERDICT_DEADLINE_MISSED]
    assert req[0].column == -1
    assert svc._assign == assign_before
    np.testing.assert_array_equal(np.asarray(svc._state.rem), rem_before)
    # terminal: a passed deadline is never re-litigated at a later fold
    svc._requeue_stranded(3950.0)
    assert len([r for r in svc._records
                if r.provenance == PROVENANCE_REQUEUED]) == 1


def test_service_under_chaos_storm_accounts_every_arrival():
    """End-to-end with a deterministic adversary: every arrival keeps
    exactly one ARRIVAL-provenance record, requeues ride on top (never
    replacing an arrival's verdict), and the run is reproducible."""
    from repro.sim.chaos import FaultPlan
    plan = FaultPlan(kind="storm", intensity=1.0, period_s=600.0,
                     name="storm")
    stream = list(bursty_arrivals(30, rate_per_s=0.02, burst_factor=6.0,
                                  rel_deadline_s=3600.0, seed=1))

    def once():
        return _svc(process=plan, seed=0).run(stream)

    res = once()
    arr = [r for r in res.records if r.provenance == PROVENANCE_ARRIVAL]
    assert len(arr) == len(stream)
    assert sorted(r.tid for r in arr) == sorted(a.task.tid for a in stream)
    assert res.n_admitted + res.n_rejected == len(stream)
    assert res.n_requeued == sum(
        1 for r in res.records if r.provenance == PROVENANCE_REQUEUED
        and r.verdict == VERDICT_SUCCESS)
    assert res.summary()["n_requeued"] == res.n_requeued
    # the adversary really fired — this is not a vacuous pass
    assert float(np.asarray(res.mc.n_terminations).sum()) > 0
    key = [(r.tid, r.verdict, r.column, r.provenance)
           for r in res.records]
    assert key == [(r.tid, r.verdict, r.column, r.provenance)
                   for r in once().records]


# ---------------------------------------------------------------------------
# Golden regeneration
# ---------------------------------------------------------------------------
def _write_golden():                                  # pragma: no cover
    g = {"note": "J60/sc5/burst-hads S=4 dt=30 mid-horizon round-trip; "
                 "pinned by tests/test_service.py",
         "boundaries": _ac_boundaries()}
    for stepping in ("slot", "adaptive"):
        res = _uninterrupted(stepping)
        g[stepping] = {"counts": _counts(res),
                       "cost": [round(float(c), 9) for c in res.cost],
                       "makespan": [round(float(m), 6)
                                    for m in res.makespan]}
    with open(GOLDEN, "w") as f:
        json.dump(g, f, indent=1)
    print(f"wrote {GOLDEN}")


if __name__ == "__main__":                            # pragma: no cover
    _write_golden()
