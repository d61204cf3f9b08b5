"""Megabatch fleet engine — the whole grid in a handful of engine calls.

``fleet.evaluate_fleet`` fuses a cell's *processes* along the scenario
axis but still dispatches one ``run_mc_events`` call per (job, policy)
cell, so a lattice sweep pays per-call dispatch, per-call while-loop
fixed cost, and per-call transfers once per cell.  This module
(DESIGN.md §2.7, ROADMAP open item 2) fuses the *cells themselves*:

* every (job, policy, process) cell is padded into a **shape bucket** —
  tasks to a ``B_MULT`` multiple, columns to a ``V_MULT`` multiple, the
  slot horizon to a ``SLOT_MULT`` multiple — with inert pad values (pad
  columns can never launch, pad tasks carry zero work, pad slots carry
  zero events);
* cells sharing an ``engine_view`` and a bucket are stacked along the
  scenario axis as **row-parametric** engine inputs — plan arrays become
  ``[R, B]`` / ``[R, V]`` rows, job scalars (deadline, horizon) become
  ``[R]`` — and run as ONE ``_mc_run_impl`` call (the engine detects the
  layout by rank; ``sim.mc_engine``).  Same-view cells share one step
  profile, so fusing them does not inflate the while-loop iteration
  count the way a naive vmap over heterogeneous cells does;
* the fused row axis is the flattened (cell, S) mesh: sharding it across
  devices (``fleet.scenario_sharding``) splits whole cells first and
  scenarios within a cell second, with inert pad rows absorbing any
  remainder — linear multi-device scaling without a replicated fallback;
* planning is deduped through ``repro.api``'s cross-backend primary-plan
  cache, and the per-group event tensors are donated to XLA on
  accelerators exactly like ``run_mc``'s.

On top of the fused call, ``ScenarioBudget`` adds **adaptive scenario
budgeting**: scenarios run in fixed-size chunks and each cell stops as
soon as its cost confidence interval is tight (sequential stopping).
The chunk RNG schedule is keyed on (seed, process fingerprint, cell
discriminator, chunk index) — never on wall-clock or grid position — so
a budgeted sweep is bit-reproducible for a given seed.

The compile-count contract: one compilation per (engine_view, shape
bucket, row-count bucket).  Budget-off runs use the exact row count (one
compile per group); budgeted runs bucket the shrinking row count to
powers of two so a whole budgeted sweep stays within
O(groups · log2(max_rows)) compilations — for the policy lattice's ≤ 12
engine views that is a handful of programs, not one per cell.
"""
from __future__ import annotations

import dataclasses
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dynamic import PolicyConfig, policy as resolve_policy
from repro.core.ils import ILSParams
from repro.core.types import CloudConfig, Job

from .fleet import (FleetResult, pad_scenarios, scenario_sharding,
                    shard_events)
from .market import EventTensor, MarketProcess, as_process
from .mc_engine import (MCParams, _check_dt, _mc_jit, _plan_arrays_cached,
                        _scalars, dist_stats, engine_args, n_slots_for,
                        scenario_mesh)
from .workloads import make_job

__all__ = ["B_MULT", "SLOT_MULT", "V_MULT", "ScenarioBudget",
           "evaluate_grid"]

#: shape-bucket lane multiples — B and V match the fitness kernels'
#: tile/lane granularity (``kernels.sched_fitness``), the slot axis is
#: bucketed coarsely since events are sparse in it
B_MULT, V_MULT, SLOT_MULT = 16, 8, 32

#: pad values per plan-array field.  Tasks: zero work (never pending),
#: ``cp=1`` so the checkpoint floor never divides by zero.  Columns:
#: ``launched0=odm=False`` keeps a pad column NOT_LAUNCHED forever (no
#: billing, no migration target, no event eligibility); unit
#: cores/speed/memv keep masked-out arithmetic finite; zero
#: crate/cinit/ccap make pad columns inert in every credit bound.
_TASK_PAD = {"total": 0.0, "cp": 1.0, "mem_t": 0.0, "assign0": 0,
             "mode0": 0}
_COL_PAD = {"price": 0.0, "cores": 1.0, "speed": 1.0, "bfrac": 1.0,
            "memv": 1.0, "crate": 0.0, "cinit": 0.0, "ccap": 0.0,
            "spot": False, "burst": False, "odm": False,
            "launched0": False}


@dataclasses.dataclass(frozen=True)
class ScenarioBudget:
    """Sequential-stopping budget: run scenarios in ``chunk``-sized
    waves and stop a cell once its cost CI is tight.

    A cell stops after ``min_chunks`` chunks when ``ci95(cost) <=
    rel_ci95 * |mean(cost)|``, or unconditionally at ``max_scenarios``.
    The per-chunk RNG keys are a pure function of (seed, process
    fingerprint, cell name, chunk index), so two runs with the same seed
    stop at the same per-cell scenario counts with the same statistics —
    reproducibility is part of the stopping rule's contract."""

    chunk: int = 16
    max_scenarios: int = 128
    rel_ci95: float = 0.05
    min_chunks: int = 2


@dataclasses.dataclass
class _Cell:
    """One (job, policy, process) grid cell and its accumulators."""

    job: Job
    policy: PolicyConfig
    process: MarketProcess
    plan: object
    arr: dict
    mem_safe: bool
    n_vms: int
    n_slots: int
    key: tuple                      # fusion-group key (view + bucket)
    cost: list = dataclasses.field(default_factory=list)
    makespan: list = dataclasses.field(default_factory=list)
    deadline_met: list = dataclasses.field(default_factory=list)
    unfinished: list = dataclasses.field(default_factory=list)
    nhib: list = dataclasses.field(default_factory=list)
    nres: list = dataclasses.field(default_factory=list)
    nterm: list = dataclasses.field(default_factory=list)
    ndone: list = dataclasses.field(default_factory=list)
    norph: list = dataclasses.field(default_factory=list)
    nretry: list = dataclasses.field(default_factory=list)
    covered: int = 0
    stepped: int = 0
    done: bool = False

    @property
    def n(self) -> int:
        return sum(len(c) for c in self.cost)

    def harvest(self, out: dict, sl: slice) -> None:
        self.cost.append(out["cost"][sl])
        self.makespan.append(out["makespan"][sl])
        self.unfinished.append(out["unfinished"][sl].astype(int))
        self.nhib.append(out["n_hib"][sl].astype(int))
        self.nres.append(out["n_res"][sl].astype(int))
        self.nterm.append(out["n_term"][sl].astype(int))
        self.ndone.append(out["n_done"][sl].astype(int))
        self.norph.append(out["n_orphan"][sl].astype(int))
        self.nretry.append(out["n_retry"][sl].astype(int))
        self.covered += int(out["exit_slots"][sl].sum())
        self.stepped += int(out["visited"][sl].sum())

    def stop_now(self, budget: ScenarioBudget) -> bool:
        if self.n >= budget.max_scenarios:
            return True
        if len(self.cost) < budget.min_chunks:
            return False
        c = np.concatenate(self.cost)
        ci95 = 1.96 * float(np.std(c)) / max(1, len(c)) ** 0.5
        return ci95 <= budget.rel_ci95 * abs(float(np.mean(c)))

    def row(self, dt: float, deadline_s: float) -> dict:
        cost = np.concatenate(self.cost)
        mkp = np.concatenate(self.makespan)
        unf = np.concatenate(self.unfinished)
        ndone = np.concatenate(self.ndone)
        met = (unf == 0) & (mkp <= deadline_s + dt + 1e-6)
        return {"job": self.job.name, "policy": self.policy.name,
                "process": self.process.name, "s": len(cost), "dt": dt,
                "n_vms": self.n_vms,
                "cost": dist_stats(cost),
                "makespan": dist_stats(mkp),
                "deadline_met_frac": float(np.mean(met)),
                "unfinished_frac": float(np.mean(unf > 0)),
                "mean_hibernations":
                    float(np.mean(np.concatenate(self.nhib))),
                "mean_resumes":
                    float(np.mean(np.concatenate(self.nres))),
                "mean_terminations":
                    float(np.mean(np.concatenate(self.nterm))),
                # fault-recovery accounting (§2.10): conservation means
                # every task either completed or is reported unfinished —
                # in every scenario — and stranded counts the orphans the
                # retry ledger never recovered (the chaos/bench gates
                # require stranded_tasks == 0)
                "n_tasks": self.job.n_tasks,
                "stranded_tasks":
                    int(np.concatenate(self.norph).sum()),
                "orphan_retry_rounds_mean":
                    float(np.mean(np.concatenate(self.nretry))),
                "work_conserved":
                    bool(np.all(ndone + unf == self.job.n_tasks)),
                "slots_skipped_frac": round(
                    1.0 - self.stepped / max(1, self.covered), 3)}


def _bucket(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _cell_tag(cell: _Cell) -> int:
    """Stable per-cell discriminator for the budgeted RNG schedule."""
    return zlib.crc32(f"{cell.job.name}/{cell.policy.name}".encode())


def _pad1(x, n: int, fill) -> np.ndarray:
    x = np.asarray(x)
    out = np.full(n, fill, dtype=x.dtype)
    out[: x.shape[0]] = x
    return out


def _pad_cell_arrays(arr: dict, b_pad: int, v_pad: int) -> dict:
    """One cell's 1-d plan arrays padded to the bucket shape (numpy)."""
    out = {k: _pad1(arr[k], b_pad, fill) for k, fill in _TASK_PAD.items()}
    out.update({k: _pad1(arr[k], v_pad, fill)
                for k, fill in _COL_PAD.items()})
    return out


def _inert_rows(n: int, b_pad: int, v_pad: int) -> dict:
    """Pad rows (row-count bucketing / device alignment): zero work and
    a zero horizon, so they exit the while-loop before its first
    iteration and contribute nothing to any statistic."""
    padded = {k: np.full(b_pad, fill,
                         np.int32 if k in ("assign0", "mode0")
                         else np.float32)
              for k, fill in _TASK_PAD.items()}
    padded.update({k: np.full(v_pad, fill,
                              bool if isinstance(fill, bool)
                              else np.float32)
                   for k, fill in _COL_PAD.items()})
    return {k: np.broadcast_to(v, (n,) + v.shape)
            for k, v in padded.items()}


def _fused_inputs(cells: list[_Cell], evs: list[EventTensor],
                  b_pad: int, v_pad: int, slots_pad: int, n_rows: int,
                  cfg: CloudConfig, params: MCParams):
    """Assemble one fused call: row-parametric plan arrays, per-row
    scalars, and the stacked/padded event tensor, grown to ``n_rows``
    with inert rows.  Returns (arr, sc, ev, slices).

    Single-plan groups (one (job, policy) cell's processes, the common
    lattice case) take a fast path: the legacy 1-d plan layout with no
    shape padding — byte-identical engine programs to the per-cell
    pipeline's concat-S call, so fusing never costs those groups the
    row-parametric gather overhead.  Only groups that genuinely stack
    *different* plans pay for the [R, ·] layout that makes one program
    serve them all."""
    if len({id(c.plan) for c in cells}) == 1:
        cell = cells[0]
        slices, at = [], 0
        for ev in evs:
            slices.append(slice(at, at + ev.n_scenarios))
            at += ev.n_scenarios
        fused = pad_scenarios(EventTensor.concat(evs), n_rows).with_index()
        return (cell.arr, _scalars(cell.job, cfg, params, cell.n_slots),
                fused, slices)

    blocks, scal_rows, slices, at = [], [], [], 0
    for cell, ev in zip(cells, evs):
        s_c = ev.n_scenarios
        # pad-column audit: the fused call hands the fitness kernels
        # ``v = v_pad``, so pad columns look real to their reductions
        # (``kernels.sched_fitness.mc_step`` only parks columns outside
        # [0, v)).  They stay empty anyway: no initial assignment may
        # target one (asserted here), they can never launch
        # (launched0 = odm = False), and every event / migration / steal
        # destination is score-masked (the -2.0 opt-out sentinel) before
        # any kernel reduction sees it.
        assert int(np.max(np.asarray(cell.arr["assign0"]))) < cell.n_vms
        padded = _pad_cell_arrays(cell.arr, b_pad, v_pad)
        blocks.append({k: np.broadcast_to(v, (s_c,) + v.shape)
                       for k, v in padded.items()})
        scal_rows.append((cell.job.deadline_s, cell.n_slots, s_c))
        slices.append(slice(at, at + s_c))
        at += s_c
    if n_rows > at:
        blocks.append(_inert_rows(n_rows - at, b_pad, v_pad))
        scal_rows.append((1.0, 0, n_rows - at))

    arr = {k: jnp.asarray(np.concatenate([b[k] for b in blocks]))
           for k in blocks[0]}

    sc = _scalars(cells[0].job, cfg, params, slots_pad)
    sc["deadline"] = jnp.asarray(np.concatenate(
        [np.full(s_c, d, np.float32) for d, _, s_c in scal_rows]))
    sc["max_slots"] = jnp.asarray(np.concatenate(
        [np.full(s_c, m, np.int32) for _, m, s_c in scal_rows]))

    fused = EventTensor.concat(
        [ev.pad(n_slots=slots_pad, v=v_pad) for ev in evs])
    fused = pad_scenarios(fused, n_rows).with_index()
    return arr, sc, fused, slices


def _run_fused(arr, sc, ev, view, params: MCParams, cfg: CloudConfig,
               mem_safe: bool, donate: bool) -> dict:
    on_cpu = jax.default_backend() == "cpu"
    out = _mc_jit(donate and not on_cpu)(
        arr, sc, ev, s=ev.n_scenarios, mesh=scenario_mesh(ev),
        **engine_args(view, params, cfg, mem_safe))
    return jax.device_get(out)


def _row_count(n_real: int, n_dev: int, budgeted: bool) -> int:
    """Row-count bucket: exact (plus device alignment) for budget-off
    runs, next power of two for budgeted rounds so the shrinking live
    set maps onto O(log) compiled programs instead of one per round."""
    n = n_real
    if budgeted and n > 1:
        n = 1 << (n - 1).bit_length()
    return _bucket(n, n_dev) if n_dev > 1 else n


def evaluate_grid(jobs, policies, processes,
                  cfg: CloudConfig | None = None,
                  params: MCParams = MCParams(n_scenarios=64),
                  ils_params: ILSParams | None = None,
                  plan_engine: str | None = "batched",
                  batched_ils=None,
                  budget: ScenarioBudget | None = None,
                  shard: bool = True,
                  donate: bool = True) -> FleetResult:
    """Evaluate a jobs × policies × processes grid with the megabatch
    engine — same row schema as ``fleet.evaluate_fleet``, a fraction of
    the engine calls.

    With ``budget=None`` every cell runs exactly ``params.n_scenarios``
    scenarios from the same tensors ``sample_grid_events`` would draw,
    so rows match the per-cell pipeline to float tolerance (the fused
    call reassociates f32 reductions; everything else is identical).
    With a ``ScenarioBudget`` cells run in chunks and stop individually
    once their cost CI is tight — ``s`` in each row reports how many
    scenarios that cell actually consumed."""
    from repro.api import _plan          # cross-backend plan cache
    from .fleet import sample_grid_events

    cfg = cfg or CloudConfig()
    jobs = [make_job(j) if isinstance(j, str) else j for j in jobs]
    policies = [resolve_policy(p) for p in policies]
    processes = [as_process(p) for p in processes]
    if not (jobs and policies and processes):
        raise ValueError("evaluate_grid needs ≥1 job, policy and process")
    ils_params = ils_params or ILSParams(seed=params.seed)
    _check_dt(cfg, params)

    t_start = time.perf_counter()
    plan_wall = mc_wall = 0.0

    # ---- plan every (job, policy) once through the api cache, build the
    # cell table and its fusion groups --------------------------------------
    cells: list[_Cell] = []
    evs0: dict[int, EventTensor] = {}    # budget-off pregenerated tensors
    for job in jobs:
        for pol in policies:
            t0 = time.perf_counter()
            plan = _plan(job, cfg, pol, ils_params, batched_ils,
                         engine=plan_engine)
            plan_wall += time.perf_counter() - t0
            arr, uids, mem_safe = _plan_arrays_cached(job, plan, cfg,
                                                      params.ovh)
            v, n_slots = len(uids), n_slots_for(job.deadline_s, params)
            key = (pol.engine_view(), _bucket(job.n_tasks, B_MULT),
                   _bucket(v, V_MULT), _bucket(n_slots, SLOT_MULT))
            if budget is None:
                evs = sample_grid_events(job, plan, processes, params)
            for i, proc in enumerate(processes):
                cell = _Cell(job=job, policy=pol, process=proc, plan=plan,
                             arr=arr, mem_safe=mem_safe, n_vms=v,
                             n_slots=n_slots, key=key)
                if budget is None:
                    evs0[id(cell)] = evs[i]
                cells.append(cell)

    n_dev = len(jax.devices()) if shard else 1
    base = jax.random.PRNGKey(params.seed)
    n_calls = 0
    chunk_idx = 0
    while True:
        live = [c for c in cells if not c.done]
        if not live:
            break
        # one fused engine call per (engine_view, shape bucket) group
        groups: dict[tuple, list[_Cell]] = {}
        for c in live:
            groups.setdefault(c.key, []).append(c)
        for key, group in groups.items():
            view, b_pad, v_pad, slots_pad = key
            if budget is None:
                # budget-off: pad to the group max, not the bucket
                # ceiling — still one compile per group (the bucket only
                # decides membership), with less pad waste; budgeted
                # rounds keep the bucket shapes so shrinking groups
                # reuse their compiled programs across chunks
                b_pad = max(c.job.n_tasks for c in group)
                v_pad = max(c.n_vms for c in group)
                slots_pad = max(c.n_slots for c in group)
            if budget is None:
                evs = [evs0.pop(id(c)) for c in group]
            else:
                evs = [c.process.sample(
                    jax.random.fold_in(jax.random.fold_in(
                        jax.random.fold_in(base, c.process.fingerprint),
                        _cell_tag(c)), chunk_idx),
                    s=budget.chunk, n_slots=c.n_slots, v=c.n_vms,
                    dt=params.dt, deadline_s=c.job.deadline_s)
                    for c in group]
            n_real = sum(ev.n_scenarios for ev in evs)
            n_rows = _row_count(n_real, n_dev, budget is not None)
            arr, sc, fused, slices = _fused_inputs(
                group, evs, b_pad, v_pad, slots_pad, n_rows, cfg, params)
            if shard:
                sharding, _ = scenario_sharding(n_rows)
                fused = shard_events(fused, sharding)
            t0 = time.perf_counter()
            out = _run_fused(arr, sc, fused, view, params, cfg,
                             mem_safe=all(c.mem_safe for c in group),
                             donate=donate)
            mc_wall += time.perf_counter() - t0
            n_calls += 1
            for c, sl in zip(group, slices):
                c.harvest(out, sl)
        if budget is None:
            for c in cells:
                c.done = True
        else:
            chunk_idx += 1
            for c in live:
                c.done = c.stop_now(budget)

    rows = [c.row(params.dt, c.job.deadline_s) for c in cells]
    return FleetResult(
        rows=rows, wall_s=time.perf_counter() - t_start,
        mc_wall_s=mc_wall, plan_wall_s=plan_wall, n_devices=n_dev,
        sharded=shard and n_dev > 1, plan_engine=plan_engine,
        stepping=params.stepping,
        slots_total=sum(c.covered for c in cells),
        slots_visited=sum(c.stepped for c in cells),
        engine="megabatch", n_engine_calls=n_calls,
        n_groups=len({c.key for c in cells}),
        budget=None if budget is None else dataclasses.asdict(budget))
