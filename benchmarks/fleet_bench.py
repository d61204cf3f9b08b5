"""Fleet pipeline benchmark: grid evaluation, sharded-batch vs cell loop.

Evaluates a jobs × policies × market-processes grid two ways over the
*same* pregenerated event tensors:

* **loop** — one ``run_mc_events`` dispatch per grid cell (the only mode
  the repo had before ``sim.fleet``: every process its own engine call);
* **fleet** — processes concatenated along the scenario axis, one engine
  call per (job, policy), the axis sharded across available devices
  (single-device hosts fall back to the unsharded path, DESIGN.md §2.4).

Both paths are timed warm (the compile is paid once, before timing) and
produce identical per-scenario results, so the ``speedup`` column is pure
dispatch/batching efficiency.  Per-cell distribution rows ride along so
``results/BENCH_fleet.json`` doubles as a scenario-diversity record —
how each policy degrades from Poisson to bursty Weibull to MMPP storms.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core.dynamic import POLICIES, build_primary_map, policy
from repro.core.ils import ILSParams
from repro.core.ils_jax import BatchedILSParams
from repro.core.types import CloudConfig
from repro.sim.fleet import (evaluate_fleet, pad_scenarios,
                             sample_grid_events, scenario_sharding,
                             shard_events)
from repro.sim.market import (EventTensor, MarkovModulatedProcess,
                              PoissonProcess, WeibullProcess)
from repro.sim.mc_engine import MCParams, run_mc_events
from repro.sim.workloads import make_job

ILS_FAST = ILSParams(max_iteration=25, max_attempt=15, seed=3)
BATCHED_FAST = BatchedILSParams(iterations=25, seed=3)
POLICY_GRID = ("burst-hads", "hads", "ils-ondemand")
#: beyond-paper lattice cells: the paper policies ± one axis each.
LATTICE_GRID = ("burst-hads+nosteal", "hads+burst", "hads+steal",
                "burst-hads+freeze")


def process_grid(deadline_s: float) -> list:
    """Poisson (Table V sc5) + two beyond-paper processes with a similar
    event budget, so rows are comparable across the process axis."""
    return [PoissonProcess(k_h=3.0, k_r=2.5, name="sc5"),
            WeibullProcess(shape_h=0.7, scale_h=deadline_s / 3.0,
                           shape_r=1.0, scale_r=deadline_s / 2.5,
                           name="weibull"),
            MarkovModulatedProcess(k_h_calm=0.5, k_h_turb=12.0, k_r=2.5,
                                   name="mmpp")]


def run(job_names: tuple[str, ...] = ("J60", "J80"),
        s: int = 256, dt: float = 30.0) -> list[dict]:
    cfg = CloudConfig()
    params = MCParams(n_scenarios=s, dt=dt, seed=0)
    rows: list[dict] = []
    loop_wall = fleet_wall = 0.0
    n_cells = 0
    for job_name in job_names:
        job = make_job(job_name)
        procs = process_grid(job.deadline_s)
        for pol_name in POLICY_GRID:
            plan = build_primary_map(job, cfg, POLICIES[pol_name],
                                     ILS_FAST, engine="batched",
                                     batched_params=BATCHED_FAST)
            evs = sample_grid_events(job, plan, procs, params)
            sharding, s_run = scenario_sharding(len(procs) * s)
            ev_all = shard_events(
                pad_scenarios(EventTensor.concat(evs), s_run), sharding)

            # warm both paths (jit cache is keyed on shapes + policy)
            run_mc_events(job, plan, cfg, evs[0], params)
            res_all = run_mc_events(job, plan, cfg, ev_all, params)

            t0 = time.perf_counter()
            cell = [run_mc_events(job, plan, cfg, e, params) for e in evs]
            t_loop = time.perf_counter() - t0
            t0 = time.perf_counter()
            res_all = run_mc_events(job, plan, cfg, ev_all, params)
            t_fleet = time.perf_counter() - t0
            loop_wall += t_loop
            fleet_wall += t_fleet
            n_cells += len(procs)

            for i, (proc, r) in enumerate(zip(procs, cell)):
                sl = slice(i * s, (i + 1) * s)
                assert np.allclose(r.cost, res_all.cost[sl]), \
                    "fleet batch must reproduce the per-cell run"
                rows.append({
                    "table": "fleet", "job": job_name, "policy": pol_name,
                    "process": proc.name, "s": s, "dt": dt,
                    "cost_mean": round(float(r.cost.mean()), 4),
                    "cost_p95": round(float(np.percentile(r.cost, 95)), 4),
                    "mkp_mean": round(float(r.makespan.mean()), 1),
                    "met_frac": round(float(r.deadline_met.mean()), 3),
                    "hib_mean": round(float(r.n_hibernations.mean()), 2),
                    "res_mean": round(float(r.n_resumes.mean()), 2),
                    "slots_skipped_frac":
                        round(r.slots_skipped_frac, 3),
                })
    total = n_cells * s
    rows.append({
        "table": "fleet_throughput", "grid_cells": n_cells, "s": s,
        "scenarios_total": total, "stepping": params.stepping,
        "loop_scen_per_s": round(total / max(loop_wall, 1e-9), 1),
        "fleet_scen_per_s": round(total / max(fleet_wall, 1e-9), 1),
        "speedup": round(loop_wall / max(fleet_wall, 1e-9), 2),
        "n_devices": len(jax.devices()),
    })
    return rows


def smoke() -> list[dict]:
    """CI-sized variant: same ≥2 jobs × 3 policies × 3 processes grid,
    tiny scenario batch."""
    return run(job_names=("J12", "J16"), s=8)


def lattice(job_names: tuple[str, ...] = ("J60",), s: int = 64,
            dt: float = 30.0) -> list[dict]:
    """Policy-lattice cell grid: the paper policies perturbed one axis at
    a time (``LATTICE_GRID``), each (job, policy) run as one fused
    engine call over sc5 + bursty-Weibull tensors; ``steps`` is
    deterministic per grid+seed."""
    cfg = CloudConfig()
    params = MCParams(n_scenarios=s, dt=dt, seed=0)
    rows: list[dict] = []
    for job_name in job_names:
        job = make_job(job_name)
        procs = process_grid(job.deadline_s)[:2]      # sc5 + weibull
        for spec in LATTICE_GRID:
            plan = build_primary_map(job, cfg, policy(spec), ILS_FAST,
                                     engine="batched",
                                     batched_params=BATCHED_FAST)
            evs = sample_grid_events(job, plan, procs, params)
            sharding, s_run = scenario_sharding(len(procs) * s)
            ev_all = shard_events(
                pad_scenarios(EventTensor.concat(evs), s_run), sharding)
            run_mc_events(job, plan, cfg, ev_all, params)       # warm
            t0 = time.perf_counter()
            res = run_mc_events(job, plan, cfg, ev_all, params)
            wall = time.perf_counter() - t0
            for i, proc in enumerate(procs):
                sl = slice(i * s, (i + 1) * s)
                rows.append({
                    "table": "lattice", "job": job_name, "policy": spec,
                    "process": proc.name, "s": s, "dt": dt,
                    "scen_per_s": round(len(procs) * s / max(wall, 1e-9),
                                        1),
                    "steps": res.n_steps,
                    "slots_skipped_frac": round(
                        1.0 - float(res.visited[sl].sum())
                        / max(1, int(res.exit_slots[sl].sum())), 3),
                    "cost_mean": round(float(res.cost[sl].mean()), 4),
                    "met_frac":
                        round(float(res.deadline_met[sl].mean()), 3),
                    "hib_mean":
                        round(float(res.n_hibernations[sl].mean()), 2),
                })
    return rows


def lattice_smoke() -> list[dict]:
    """CI-sized lattice cells — same J60 grid at a tiny batch so the
    committed rollup baseline and the CI smoke run share keys."""
    return lattice(s=8)


def megabatch_grid(job_names: tuple[str, ...] = ("J50", "J56", "J60",
                                                 "J64"),
                   s: int = 64, dt: float = 30.0) -> list[dict]:
    """Megabatch engine (``sim.megabatch``, DESIGN.md §2.7) vs the
    per-cell fleet pipeline on a lattice grid, same planning knobs and
    bit-identical rows.

    Both engines are timed warm over their own ``mc_wall_s`` (engine
    calls only — planning is cached and excluded), so ``vs_loop`` is the
    pure fusion win: call count collapsing from cells to
    (engine_view, shape bucket) groups.  ``vs_loop`` and the call/group
    counts are what the CI gate diffs — the ratio is measured in one
    process over identical tensors, so hardware speed cancels.  A
    budgeted row rides along: same grid under sequential stopping,
    reporting the scenarios actually consumed for tight cost CIs."""
    from repro.sim.megabatch import ScenarioBudget, evaluate_grid

    params = MCParams(n_scenarios=s, dt=dt, seed=0)
    procs = process_grid(make_job(job_names[0]).deadline_s)[:2]
    kw = dict(cfg=CloudConfig(), params=params, ils_params=ILS_FAST,
              plan_engine="batched", batched_ils=BATCHED_FAST)
    grid = (job_names, LATTICE_GRID, procs)

    evaluate_fleet(*grid, **kw)                               # warm
    rg = evaluate_grid(*grid, **kw)                           # warm
    t_loop = min(evaluate_fleet(*grid, **kw).mc_wall_s for _ in range(3))
    t_mega = min(evaluate_grid(*grid, **kw).mc_wall_s for _ in range(3))
    n_cells = len(rg.rows)
    total = rg.total_scenarios

    bud = ScenarioBudget(chunk=max(4, s // 4), max_scenarios=s,
                         rel_ci95=0.1, min_chunks=2)
    rb = evaluate_grid(*grid, budget=bud, **kw)               # warm
    t_bud = min(evaluate_grid(*grid, budget=bud, **kw).mc_wall_s
                for _ in range(2))

    key = {"job": "+".join(job_names), "policy": "lattice4",
           "process": "+".join(p.name for p in procs), "s": s, "dt": dt}
    return [
        {"table": "megabatch", **key, "n_cells": n_cells,
         "scenarios_total": total,
         "loop_scen_per_s": round(total / max(t_loop, 1e-9), 1),
         "mega_scen_per_s": round(total / max(t_mega, 1e-9), 1),
         "vs_loop": round(t_loop / max(t_mega, 1e-9), 2),
         "n_engine_calls": rg.n_engine_calls, "n_groups": rg.n_groups,
         "n_devices": rg.n_devices},
        {"table": "megabatch_budget", **key,
         "scen_used": rb.total_scenarios, "scen_fixed": total,
         "saved_frac": round(1.0 - rb.total_scenarios / total, 3),
         # equal-precision throughput: fixed-S scenarios the budgeted
         # run replaces, per second of budgeted wall time
         "eff_scen_per_s": round(total / max(t_bud, 1e-9), 1),
         "n_engine_calls": rb.n_engine_calls},
    ]


def megabatch_smoke() -> list[dict]:
    """CI-sized megabatch grid: two small jobs sharing a shape bucket so
    the fused calls genuinely exercise the row-parametric layout."""
    return megabatch_grid(("J12", "J16"), s=8)
