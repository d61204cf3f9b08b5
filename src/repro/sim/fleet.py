"""Fleet evaluation pipeline — jobs × policies × market processes, one flow.

The two engines the repo grew separately — the batched ILS static phase
(``core.ils_jax``, DESIGN.md §2.1) and the batched Monte-Carlo dynamic
phase (``sim.mc_engine``, §2.3) — compose here into one policy-evaluation
system: ``evaluate_fleet`` plans every (job, policy) cell once (Algorithm
1 with the device-resident ILS by default), samples an event tensor per
market process (§2.4), **concatenates the processes along the scenario
axis** so each (job, policy) costs a single engine call over
``n_processes · S`` scenarios, and shards that axis across available
devices with ``jax.sharding`` (single-device CPU hosts fall back to the
unsharded path transparently — the engine is agnostic, see
``run_mc_events``).  The result is a tidy rows table, one row per
(job, policy, process) cell, plus throughput metadata;
``benchmarks/fleet_bench.py`` tracks it as ``results/BENCH_fleet.json``.
"""
from __future__ import annotations

import dataclasses
import json
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.dynamic import build_primary_map, policy as resolve_policy
from repro.core.ils import ILSParams
from repro.core.types import CloudConfig, Job
from .market import EventTensor, as_process
from .mc_engine import (MCParams, dist_stats, n_slots_for,
                        plan_column_uids, run_mc_events)
from .workloads import make_job




@dataclasses.dataclass
class FleetResult:
    """Tidy per-(job, policy, process) rows + pipeline metadata."""

    rows: list[dict]
    wall_s: float           # end-to-end, including planning
    mc_wall_s: float        # engine calls only (throughput numerator)
    plan_wall_s: float
    n_devices: int
    sharded: bool
    plan_engine: str
    stepping: str = "adaptive"
    slots_total: int = 0     # scenario-slots covered across the grid
    slots_visited: int = 0   # scenario-slots full-stepped (rest jumped)
    engine: str = "fleet"    # "fleet" (per-cell calls) | "megabatch"
    n_engine_calls: int = 0  # fused calls issued (megabatch only)
    n_groups: int = 0        # distinct (view, shape-bucket) groups
    budget: dict | None = None   # ScenarioBudget knobs when budgeting ran

    @property
    def total_scenarios(self) -> int:
        return sum(r["s"] for r in self.rows)

    @property
    def scen_per_s(self) -> float:
        return self.total_scenarios / max(self.mc_wall_s, 1e-9)

    @property
    def slots_skipped_frac(self) -> float:
        """Fraction of scenario-slots the event-horizon engine advanced
        in closed form instead of full-stepping (DESIGN.md §2.5); 0 for
        ``stepping="slot"``."""
        return 1.0 - self.slots_visited / max(1, self.slots_total)

    def meta(self) -> dict:
        return {"wall_s": round(self.wall_s, 3),
                "mc_wall_s": round(self.mc_wall_s, 3),
                "plan_wall_s": round(self.plan_wall_s, 3),
                "total_scenarios": self.total_scenarios,
                "scen_per_s": round(self.scen_per_s, 1),
                "n_devices": self.n_devices, "sharded": self.sharded,
                "plan_engine": self.plan_engine,
                "stepping": self.stepping,
                "slots_total": self.slots_total,
                "slots_visited": self.slots_visited,
                "slots_skipped_frac": round(self.slots_skipped_frac, 3),
                "engine": self.engine,
                "n_engine_calls": self.n_engine_calls,
                "n_groups": self.n_groups,
                "budget": self.budget}

    def write_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({"generated_by": "repro.sim.fleet",
                       "meta": self.meta(), "rows": self.rows}, f, indent=2)


_PAD_WARNED = False


def scenario_sharding(n_scenarios: int):
    """Scenario-axis placement plan: ``(sharding, n_padded)``.

    ``sharding`` is a NamedSharding over the scenario axis (None on a
    single-device host — the only replicated fallback left), and
    ``n_padded`` is the row count the caller must grow the tensor to
    (``pad_scenarios``) before placing it: S is rounded up to the next
    device multiple instead of silently dropping the sharding when the
    device count does not divide it.  Pad scenarios are event-free and
    excluded from every statistic (``slot_coverage`` and the row slices
    never reach them); a one-time warning flags that padding happened."""
    global _PAD_WARNED
    devs = jax.devices()
    if len(devs) <= 1:
        return None, n_scenarios
    pad = (-n_scenarios) % len(devs)
    if pad and not _PAD_WARNED:
        _PAD_WARNED = True
        warnings.warn(
            f"scenario axis S={n_scenarios} padded to "
            f"{n_scenarios + pad} for {len(devs)} devices (pad scenarios "
            f"are masked out of all statistics)", stacklevel=2)
    return (NamedSharding(Mesh(np.array(devs), ("s",)),
                          PartitionSpec("s")), n_scenarios + pad)


def pad_scenarios(ev: EventTensor, n_rows: int) -> EventTensor:
    """Grow the scenario axis to ``n_rows`` with event-free scenarios
    (zero request counts — they draw no events and finish on workload
    dynamics alone).  The next-event index is rebuilt by the caller's
    ``with_index`` pass; callers must keep their row slices inside the
    original S so pad rows never enter a statistic."""
    dn = n_rows - ev.n_scenarios
    if dn < 0:
        raise ValueError(f"cannot shrink S={ev.n_scenarios} to {n_rows}")
    if dn == 0:
        return ev
    pad_k = ((0, dn), (0, 0))
    pad_u = ((0, dn), (0, 0), (0, 0))
    return EventTensor(jnp.pad(ev.hib_k, pad_k),
                       jnp.pad(ev.hib_u, pad_u, constant_values=-2.0),
                       jnp.pad(ev.res_k, pad_k),
                       jnp.pad(ev.res_u, pad_u, constant_values=-2.0),
                       None,
                       None if ev.term_k is None
                       else jnp.pad(ev.term_k, pad_k),
                       None if ev.term_u is None
                       else jnp.pad(ev.term_u, pad_u,
                                    constant_values=-2.0))


def slot_coverage(res, sl: slice) -> tuple[int, int]:
    """(covered, full-stepped) scenario-slots for one row slice of an
    engine result — the one formula both the per-row
    ``slots_skipped_frac`` and the ``FleetResult`` aggregate are built
    from, so the two can never drift apart (and sharding's pad scenarios,
    which live past every real slice, never leak into either)."""
    if res.exit_slots is None or res.visited is None:
        return 0, 0
    return (int(res.exit_slots[sl].sum()), int(res.visited[sl].sum()))


def shard_events(ev: EventTensor, sharding) -> EventTensor:
    """Place an event tensor with its scenario axis split across devices;
    the engine then runs each device's scenarios in a loop of its own
    (``mc_engine._mc_run``)."""
    if sharding is None:
        return ev
    s3 = NamedSharding(sharding.mesh, PartitionSpec("s", None, None))
    return EventTensor(jax.device_put(ev.hib_k, sharding),
                       jax.device_put(ev.hib_u, s3),
                       jax.device_put(ev.res_k, sharding),
                       jax.device_put(ev.res_u, s3),
                       None if ev.nxt is None
                       else jax.device_put(ev.nxt, sharding),
                       None if ev.term_k is None
                       else jax.device_put(ev.term_k, sharding),
                       None if ev.term_u is None
                       else jax.device_put(ev.term_u, s3))


def sample_grid_events(job: Job, plan, processes, params: MCParams
                       ) -> list[EventTensor]:
    """One tensor per process for this (job, plan) cell.  Each process
    draws from ``fold_in(PRNGKey(params.seed), p.fingerprint)`` — keyed
    on the process's *parameterization*, not its grid position — so
    reordering, inserting or removing processes leaves every other
    process's tensor bit-identical (a position-keyed ``fold_in(i)``
    would silently re-roll the whole grid)."""
    v = len(plan_column_uids(plan))
    n = n_slots_for(job.deadline_s, params)
    base = jax.random.PRNGKey(params.seed)
    return [p.sample(jax.random.fold_in(base, p.fingerprint),
                     s=params.n_scenarios, n_slots=n, v=v, dt=params.dt,
                     deadline_s=job.deadline_s)
            for p in processes]


def evaluate_fleet(jobs, policies, processes,
                   cfg: CloudConfig | None = None,
                   params: MCParams = MCParams(n_scenarios=64),
                   ils_params: ILSParams | None = None,
                   plan_engine: str | None = "batched",
                   batched_ils=None,
                   shard: bool = True) -> FleetResult:
    """Evaluate every (job, policy, market process) cell of the grid.

    ``jobs``: Job objects or names (``make_job``); ``policies``:
    PolicyConfig, registry names, or lattice specs (``core.dynamic
    .policy`` — ``"hads+burst"`` works); ``processes``: MarketProcess /
    Table V Scenario / scenario names.  Per (job, policy) the static map
    is planned once (``plan_engine``: "batched" = ``run_batched_ils``
    hand-off with an optional ``batched_ils`` knob passthrough, "exact"
    = the paper's sequential chain, None = each policy's own ``planner``
    axis) and all processes run as one concatenated, scenario-sharded
    engine call.  Returns one row per cell with cost/makespan
    distribution summaries and deadline-met fractions.  The declarative
    front-end over this pipeline is ``repro.api.sweep``.
    """
    cfg = cfg or CloudConfig()
    jobs = [make_job(j) if isinstance(j, str) else j for j in jobs]
    policies = [resolve_policy(p) for p in policies]
    processes = [as_process(p) for p in processes]
    if not (jobs and policies and processes):
        raise ValueError("evaluate_fleet needs ≥1 job, policy and process")
    ils_params = ils_params or ILSParams(seed=params.seed)

    s = params.n_scenarios
    s_real = len(processes) * s
    sharding, s_run = scenario_sharding(s_real) if shard \
        else (None, s_real)
    rows: list[dict] = []
    t_start = time.perf_counter()
    plan_wall = mc_wall = 0.0
    slots_total = slots_visited = 0
    for job in jobs:
        for policy in policies:
            t0 = time.perf_counter()
            plan = build_primary_map(job, cfg, policy, ils_params,
                                     engine=plan_engine,
                                     batched_params=batched_ils)
            plan_wall += time.perf_counter() - t0
            evs = sample_grid_events(job, plan, processes, params)
            ev_all = pad_scenarios(EventTensor.concat(evs), s_run)
            ev_all = shard_events(ev_all.with_index(), sharding)
            t0 = time.perf_counter()
            res = run_mc_events(job, plan, cfg, ev_all, params,
                                label="fleet")
            mc_wall += time.perf_counter() - t0
            # aggregate over the *real* scenarios only — sharding's pad
            # rows (past s_real) run event-free and must not skew the
            # event-horizon coverage stats
            cov, stp = slot_coverage(res, slice(0, s_real))
            slots_total += cov
            slots_visited += stp
            for i, proc in enumerate(processes):
                sl = slice(i * s, (i + 1) * s)
                cov, stp = slot_coverage(res, sl)
                rows.append({
                    "job": job.name, "policy": policy.name,
                    "process": proc.name, "s": s, "dt": params.dt,
                    "n_vms": len(res.vm_uids),
                    "cost": dist_stats(res.cost[sl]),
                    "makespan": dist_stats(res.makespan[sl]),
                    "deadline_met_frac":
                        float(np.mean(res.deadline_met[sl])),
                    "unfinished_frac":
                        float(np.mean(res.unfinished[sl] > 0)),
                    "mean_hibernations":
                        float(np.mean(res.n_hibernations[sl])),
                    "mean_resumes": float(np.mean(res.n_resumes[sl])),
                    "mean_terminations": (
                        0.0 if res.n_terminations is None
                        else float(np.mean(res.n_terminations[sl]))),
                    # per-cell share of the event-horizon win: fraction
                    # of this slice's scenario-slots jumped in closed
                    # form — same slot_coverage formula as the aggregate
                    "slots_skipped_frac": round(
                        1.0 - stp / max(1, cov), 3),
                })
    return FleetResult(rows=rows, wall_s=time.perf_counter() - t_start,
                       mc_wall_s=mc_wall, plan_wall_s=plan_wall,
                       n_devices=len(jax.devices()),
                       sharded=sharding is not None,
                       plan_engine=plan_engine, stepping=params.stepping,
                       slots_total=slots_total,
                       slots_visited=slots_visited)
