"""Dynamic-phase benchmark: DES vs fixed-slot vs event-horizon MC engine.

Measures scenarios/second for the dynamic phase at S ∈ {1, 64, 1024}
across a (policy × market process) grid that spans the two regimes the
engines care about:

* **dense** — Burst-HADS recovers from interruptions immediately, so the
  run is short and almost every slot is interesting (completions);
* **sparse** — HADS freezes tasks on hibernated VMs until the deferred
  migration near the deadline, so the horizon is long and dominated by
  empty slots — the regime event-horizon stepping (DESIGN.md §2.5) was
  built for; sparse processes (``sc1``, bursty Weibull) stretch it
  further.

The event tensor for each cell is pregenerated **outside the timed
region** (the engine's steady-state throughput is what the artifact
tracks; ``run_mc``-style sampling cost is left out) and both steppings are timed warm over
the *identical* tensor, so ``adaptive_vs_slot`` is pure hot-loop
efficiency.  The DES replays the same Poisson scenarios one trace per
python loop; non-Poisson processes have no DES equivalent and skip the
DES columns.  Rows carry mean cost/makespan from every engine so
BENCH_sim.json doubles as a coarse distribution-parity record (the exact
contract lives in tests/test_stepping.py).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro.core.dynamic import POLICIES, build_primary_map
from repro.core.ils import ILSParams
from repro.core.types import CloudConfig
from repro.sim.events import SCENARIOS
from repro.sim.market import WeibullProcess, as_process
from repro.sim.mc_engine import (MCParams, n_slots_for, plan_column_uids,
                                 run_mc_events)
from repro.sim.simulator import Simulator
from repro.sim.workloads import make_job

ILS_FAST = ILSParams(max_iteration=25, max_attempt=15, seed=3)

#: Table V sc5 (the paper's headline), sc1 (sparse Poisson), a bursty
#: sub-exponential Weibull — the sparse regimes of DESIGN.md §2.5 — and
#: sc5 with half its interruptions escalated to spot *terminations*
#: (§2.8): the terminating cell times the term-direction program (gated
#: at trace time, so the other cells still compile the historical
#: two-direction program).
def process_grid(deadline_s: float) -> list:
    sc5 = as_process("sc5")
    return [sc5, as_process("sc1"),
            WeibullProcess(shape_h=0.7, scale_h=deadline_s / 3.0,
                           shape_r=1.0, scale_r=deadline_s / 2.5,
                           name="weibull"),
            dataclasses.replace(sc5, termination_frac=0.5,
                                name="sc5-term")]


def _time_engine(job, plan, cfg, ev, params, reps: int):
    for _ in range(2):
        res = run_mc_events(job, plan, cfg, ev, params)   # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        res = run_mc_events(job, plan, cfg, ev, params)
    return (time.perf_counter() - t0) / reps, res


def run(job_name: str = "J60",
        policies: tuple[str, ...] = ("burst-hads", "hads"),
        sizes: tuple[int, ...] = (1, 64, 1024),
        dts: tuple[float, ...] = (30.0, 15.0),
        des_cap: int = 128) -> list[dict]:
    cfg = CloudConfig()
    job = make_job(job_name)
    rows = []
    for pol_name in policies:
        plan = build_primary_map(job, cfg, POLICIES[pol_name], ILS_FAST)
        for proc in process_grid(job.deadline_s):
            des = None
            if proc.name in SCENARIOS:       # Poisson rows get a DES race
                sc = SCENARIOS[proc.name]
                n_des = min(max(sizes), des_cap)
                t0 = time.perf_counter()
                runs = [Simulator(job, plan, cfg, sc, seed=i).run()
                        for i in range(n_des)]
                des = {"rate": n_des / max(time.perf_counter() - t0, 1e-9),
                       "cost": float(np.mean([r.cost for r in runs])),
                       "mkp": float(np.mean([r.makespan for r in runs]))}
            for s in sizes:
                for dt in dts:
                    p = MCParams(n_scenarios=s, dt=dt, seed=0)
                    # tensor generation hoisted out of the timed region
                    ev = proc.sample(
                        jax.random.PRNGKey(0), s=s,
                        n_slots=n_slots_for(job.deadline_s, p), dt=dt,
                        v=len(plan_column_uids(plan)),
                        deadline_s=job.deadline_s)
                    reps = 25 if s == 1 else 5 if s <= 64 else 2
                    t_ad, r_ad = _time_engine(
                        job, plan, cfg, ev,
                        MCParams(n_scenarios=s, dt=dt, seed=0,
                                 stepping="adaptive"), reps)
                    t_sl, r_sl = _time_engine(
                        job, plan, cfg, ev,
                        MCParams(n_scenarios=s, dt=dt, seed=0,
                                 stepping="slot"), reps)
                    row = {
                        "table": "sim_bench", "job": job_name,
                        "policy": pol_name, "process": proc.name,
                        "s": s, "dt": dt,
                        "adaptive_scen_per_s": round(s / t_ad, 1),
                        "slot_scen_per_s": round(s / t_sl, 1),
                        "adaptive_vs_slot": round(t_sl / t_ad, 2),
                        "steps_adaptive": r_ad.n_steps,
                        "steps_slot": r_sl.n_steps,
                        "slots_skipped_frac":
                            round(r_ad.slots_skipped_frac, 3),
                        "mc_cost_mean": round(float(r_ad.cost.mean()), 4),
                        "mc_mkp_mean": round(float(r_ad.makespan.mean()), 1),
                        "mc_met_frac":
                            round(float(r_ad.deadline_met.mean()), 3),
                        "mc_hib_mean":
                            round(float(r_ad.n_hibernations.mean()), 2),
                        "mc_term_mean":
                            round(float(r_ad.n_terminations.mean()), 2)
                            if r_ad.n_terminations is not None else 0.0,
                    }
                    if des is not None:
                        row.update({
                            "des_scen_per_s": round(des["rate"], 1),
                            "adaptive_vs_des":
                                round((s / t_ad) / des["rate"], 2),
                            "des_cost_mean": round(des["cost"], 4),
                            "des_mkp_mean": round(des["mkp"], 1),
                        })
                    rows.append(row)
    return rows


def smoke() -> list[dict]:
    """CI-sized variant: one policy per regime, tiny S, one dt."""
    return run(policies=("burst-hads", "hads"), sizes=(1, 16),
               dts=(30.0,), des_cap=16)


def recovery(jobs: tuple[str, ...] = ("J30",),
             intensities: tuple[float, ...] = (0.0, 0.4, 0.8),
             n_scenarios: int = 16) -> list[dict]:
    """Fault-recovery section (DESIGN.md §2.10): drive the chaos suite's
    adversarial fault grid through the megabatch engine and surface the
    *deterministic* recovery signals per cell — ``stranded_tasks`` (the
    orphan-retry ledger must recover every failed migration: the CI gate
    hard-fails on any nonzero value), ``orphan_retry_rounds_mean`` (how
    hard the ledger worked) and the conservation/degradation context.
    ``suite_ok`` folds in the suite's own invariant verdict (monotone
    degradation included), so a green bench row set implies a green
    ``python -m repro.chaos`` run on the same grid."""
    from repro.chaos import run_chaos_suite

    t0 = time.perf_counter()
    rep = run_chaos_suite(
        jobs=jobs, intensities=intensities,
        params=MCParams(n_scenarios=n_scenarios, dt=30.0, seed=0))
    wall = max(time.perf_counter() - t0, 1e-9)
    rows = []
    for r in rep.rows:
        rows.append({
            "table": "recovery", "job": r["job"], "policy": r["policy"],
            "process": r["process"], "s": r["s"], "dt": r["dt"],
            "stranded_tasks": int(r["stranded_tasks"]),
            "orphan_retry_rounds_mean":
                round(float(r["orphan_retry_rounds_mean"]), 3),
            "work_conserved": bool(r["work_conserved"]),
            "mean_terminations": round(float(r["mean_terminations"]), 2),
            "deadline_met_frac": round(float(r["deadline_met_frac"]), 3),
            "suite_ok": bool(rep.ok),
            "cells_per_s": round(len(rep.rows) / wall, 2),
        })
    return rows


def recovery_smoke() -> list[dict]:
    """CI-sized chaos-recovery bench: the suite's own smoke grid."""
    return recovery(jobs=("J12",), intensities=(0.0, 0.8), n_scenarios=4)
