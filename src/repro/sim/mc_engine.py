"""Batched Monte-Carlo hibernation engine — §III-D over *distributions*.

The discrete-event simulator (``sim.simulator``) replays ONE Poisson
interruption trace per run; Table V conclusions drawn from it are one-trace
anecdotes.  This module advances S independent hibernation scenarios on
device: time is discretized into slots of ``dt`` seconds and a
jit-compiled ``lax.while_loop`` steps the state

  * ``[S, V]`` VM columns — lifecycle (not-launched / active / hibernated /
    terminated), boot clocks, billing accumulators that *pause during
    hibernation*, and burstable CPU-credit buckets;
  * ``[S, B]`` tasks — remaining base work, current VM column, exec mode
    and completion times;

implementing vectorized equivalents of the paper's dynamic module:

  * **Alg. 4 (checkpoint-rollback migration)** — on a hibernation event the
    victim's unfinished tasks roll back to their checkpoint grid and are
    re-assigned via an argmin-over-columns rule (projected drain time +
    boot penalty + price tie-break) over spare burstable/on-demand
    capacity, spread across ``mig_rounds`` argmin rounds so a bag fans out
    over several columns (mirroring the per-task cascade), launching fresh
    on-demand columns when nothing active fits;
  * **Alg. 5 (work stealing)** — at Allocation-Cycle boundaries idle VMs
    steal the largest remaining task from the most-queued column's tail;
  * **AC termination** — idle non-burstable columns terminate at the AC
    boundary (after the stealing attempt), ending their billing;
  * **deferred-HADS migration** — under ``hibernation="defer"`` policies
    frozen tasks stay on the hibernated column until the latest safe
    instant, then migrate to on-demand capacity (``"freeze"`` policies
    skip the fire entirely — frozen tasks only ever resume in place).

Policy behaviour mirrors ``core.dynamic.PolicyConfig`` flags exactly; the
policy's ``engine_view()`` — its projection onto the axes the engine
actually branches on — is the (hashable) static jit argument, so the
whole ~48-point lattice (DESIGN.md §2.6) shares ≤12 engine builds per
shape (the declarative front-end over this module is ``repro.api``).
The per-slot
hot reduction — per-scenario per-VM remaining load / unfinished count /
max remaining task — is the ``mc_vm_stats`` Pallas kernel
(``kernels/sched_fitness/mc_step.py``) on accelerators and a shared
one-hot/cumsum pass on CPU; event handling (migration, stealing,
termination) is hoisted behind ``lax.cond`` on batch-wide predicates so
the common no-event slot touches only the progress/billing path.

Market events are **not sampled inline**: the engine consumes a
pregenerated ``sim.market.EventTensor`` (``[S, n_slots]`` request counts +
``[S, n_slots, V]`` priority scores, DESIGN.md §2.4) and resolves each
slot's requested victims/beneficiaries against live eligibility with one
top-k rank pass.  Any stochastic process — Table V Poisson, Weibull
renewal, Markov-modulated storms, correlated mass shocks, empirical trace
replay — therefore drives this same jitted engine unchanged, and the
engine itself is fully deterministic given the tensor.  ``run_mc``
generates the tensor from a process (or legacy Table V scenario) and
delegates to ``run_mc_events``, the raw-tensor entry point the fleet
pipeline (``sim.fleet``) batches over.  Slot-discretization error bounds
and the DES parity contract are documented in DESIGN.md §2.3.

The paper's dynamic module only *acts* at events — hibernations/resumes,
AC boundaries, task and boot completions — yet spot interruption
processes are bursty and sparse, so most slots are pure
progress/billing/credit updates with closed-form dynamics.  The default
``stepping="adaptive"`` hot loop therefore does **event-horizon
stepping** (DESIGN.md §2.5): per scenario (the slot clock ``i`` is
``[S]``) each iteration computes the next *interesting* slot — min over
the tensor's next-event pointer (``EventTensor.nxt``), the next AC
boundary, the first task completion, boot edge, burstable-credit
boundary, and the deferred-HADS fire instant — jumps straight to it,
advancing task progress, billing accumulators and the piecewise-linear
credit buckets in closed form across the span (the fused
``mc_span_advance`` kernel on accelerators), and full-steps only the
interesting slot.  ``stepping="slot"`` keeps the legacy fixed-slot walk
for parity testing; on dt-aligned tensors the two engines produce
identical event counts and cost/makespan to rounding
(``tests/test_stepping.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.dynamic import (BURST_HADS, PolicyConfig, PrimaryPlan,
                                build_primary_map)
from repro.core.fitness import pack_solution
from repro.core.ils import ILSParams
from repro.core.runtime import CHECKPOINT_WRITE_S
from repro.core.types import CloudConfig, Job, Market
from repro.ft.checkpoint import checkpoint_schedule
from repro.kernels.sched_fitness.ops import mc_span_advance, mc_vm_stats
from repro.obs import span
from .events import SC_NONE, Scenario
from .market import EventTensor, MarketProcess, as_process

BIG = 1e30
HIGHEST = jax.lax.Precision.HIGHEST

#: VM column lifecycle codes (``vstate``)
NOT_LAUNCHED, VM_ACTIVE, VM_HIBERNATED, VM_TERMINATED = 0, 1, 2, 3

#: the while-body's batch-wide ``lax.cond`` branches, in the order of the
#: loop-carried entry counters (``MCResult.branch_entries``): spot
#: termination with Alg. 4, hibernation with Alg. 4, deferred-HADS
#: migration, the Allocation-Cycle block, the orphan retry
BRANCHES = ("terminate", "hibernate", "defer", "ac", "retry")


def dist_stats(x: np.ndarray) -> dict:
    """mean/std/ci95/p95 summary — shared by ``MCResult.summary`` and the
    fleet rows so every results table reports identical statistics."""
    m, sd = float(np.mean(x)), float(np.std(x))
    return {"mean": m, "std": sd, "ci95": 1.96 * sd / max(1, len(x)) ** 0.5,
            "p95": float(np.percentile(x, 95))}


@dataclasses.dataclass(frozen=True)
class MCParams:
    """Engine knobs.

    ``stepping`` selects the hot loop: ``"adaptive"`` (default) is the
    event-horizon engine — each iteration jumps straight to the next
    interesting slot (event, AC boundary, task/boot completion, credit
    boundary) and advances the skipped span in closed form (DESIGN.md
    §2.5); ``"slot"`` is the legacy fixed-``dt`` walk kept for parity
    testing.  Under ``"slot"`` ``dt`` must divide both the boot overhead
    and the Allocation Cycle so AC boundaries land on slot edges; the
    adaptive engine lifts that restriction (boundaries are jump targets,
    not grid points).

    ``orphan_retry`` bounds the fault-recovery ledger (DESIGN.md §2.10):
    tasks whose Alg. 4 migration group found no feasible destination
    after a spot termination are recorded as *orphans* and re-attempted
    at every subsequent full step, mirroring the DES retry queue; the
    bound counts retry passes that actually *moved* work (infeasible
    no-op passes stay free, so orphans keep waiting for capacity).  The
    whole ledger is trace-time gated on the tensor's terminate direction
    — termination-free tensors add no ledger state or retry ops to the
    compiled program (the legacy goldens stay numerically exact)."""

    n_scenarios: int = 256
    dt: float = 30.0
    horizon_mult: float = 3.0     # run to horizon_mult * deadline, like DES
    seed: int = 0
    ovh: float = 0.10             # checkpoint overhead budget (paper §IV)
    hads_margin_s: float = 30.0   # deferred-migration safety margin
    steal_rounds: int = 2         # Alg. 5 attempts per AC boundary
    mig_rounds: int = 8           # Alg. 4 argmin rounds (bag fan-out width)
    orphan_retry: int = 16        # max *successful* orphan-retry passes
    dest_cascade: bool = False    # DES-literal Alg. 4 attempt order + the
    # check_migration deadline gate in _dest_column (parity mode); the
    # default drain-argmin scoring is pinned by the legacy goldens
    stepping: str = "adaptive"    # "adaptive" (event-horizon) | "slot"
    use_kernel: bool | None = None  # None: Pallas on accelerators, jnp on CPU


@dataclasses.dataclass(frozen=True)
class EngineState:
    """Mid-horizon engine state — the while-loop carry minus loop-local
    bookkeeping (DESIGN.md §2.9).

    Extracted by ``run_mc_events(..., stop_s=t)`` and re-injected via
    ``state=``: per-VM lifecycle/boot/billing/credit columns, per-task
    progress/placement/completion, event counters and each scenario's
    slot clock.  All times are *absolute* engine seconds (slot index ×
    dt), so a re-entered run continues the same timeline and the round
    trip ``run(plan) == run(run(plan, stop=t).state, from=t)`` is
    bit-exact on the slot path (tests/test_service.py pins it).  Leaves
    may be device or numpy arrays; the class is a registered pytree so
    ``jax.device_get`` / jit boundaries map over it.
    """

    slot: jnp.ndarray      # i32 [S] per-scenario slot clock
    vstate: jnp.ndarray    # i32 [S, V] lifecycle code
    boot: jnp.ndarray      # f32 [S, V] absolute boot-done instant
    billed: jnp.ndarray    # f32 [S, V] billed seconds so far
    credits: jnp.ndarray   # f32 [S, V] burstable credit buckets
    rem: jnp.ndarray       # f32 [S, B] remaining work
    assign: jnp.ndarray    # i32 [S, B] current column
    mode: jnp.ndarray      # i32 [S, B] exec mode
    done_at: jnp.ndarray   # f32 [S, B] completion instant (BIG if none)
    n_hib: jnp.ndarray     # i32 [S]
    n_res: jnp.ndarray     # i32 [S]
    n_term: jnp.ndarray    # i32 [S]
    #: bool [S, B] fault-recovery orphan mask (DESIGN.md §2.10): tasks
    #: stranded by an infeasible post-termination migration, awaiting a
    #: retry pass (engine) or re-admission (service).  ``None`` on runs
    #: whose tensor carries no terminate direction.
    orph: jnp.ndarray | None = None

    @property
    def n_scenarios(self) -> int:
        return self.rem.shape[0]

    @property
    def n_vms(self) -> int:
        return self.vstate.shape[1]

    @property
    def n_tasks(self) -> int:
        return self.rem.shape[1]

    def at_slot(self, slot: int) -> "EngineState":
        """Clock-forward stalled scenarios to ``slot``.  A scenario whose
        work all finished exits the loop with its clock parked early;
        nothing can happen in the skipped span (no pending work), so
        advancing the clock is exact — required before folding new
        arrivals in at a later boundary."""
        return dataclasses.replace(
            self, slot=jnp.maximum(jnp.asarray(self.slot, jnp.int32),
                                   jnp.int32(slot)))

    def with_tasks(self, total, assign, mode) -> "EngineState":
        """Append newly admitted tasks: full remaining work (``total``,
        checkpoint-adjusted seconds), a destination column and exec mode
        per task, broadcast across scenarios, completion unset."""
        s = self.n_scenarios
        tot = jnp.asarray(total, self.rem.dtype).reshape(1, -1)
        t = tot.shape[1]
        return dataclasses.replace(
            self,
            rem=jnp.concatenate([self.rem, jnp.tile(tot, (s, 1))], axis=1),
            assign=jnp.concatenate(
                [self.assign,
                 jnp.tile(jnp.asarray(assign, jnp.int32).reshape(1, -1),
                          (s, 1))], axis=1),
            mode=jnp.concatenate(
                [self.mode,
                 jnp.tile(jnp.asarray(mode, jnp.int32).reshape(1, -1),
                          (s, 1))], axis=1),
            done_at=jnp.concatenate(
                [self.done_at,
                 jnp.full((s, t), BIG, self.done_at.dtype)], axis=1),
            orph=None if self.orph is None else jnp.concatenate(
                [jnp.asarray(self.orph, bool),
                 jnp.zeros((s, t), bool)], axis=1))

    def set_tasks(self, idx, total, assign, mode) -> "EngineState":
        """Write admitted tasks into existing (inert pad) task slots
        ``idx`` — the shape-stable alternative to ``with_tasks`` used by
        the service layer to bound engine recompiles."""
        ix = jnp.asarray(idx, jnp.int32)
        tot = jnp.asarray(total, self.rem.dtype)
        return dataclasses.replace(
            self,
            rem=jnp.asarray(self.rem).at[:, ix].set(tot[None]),
            assign=jnp.asarray(self.assign).at[:, ix].set(
                jnp.asarray(assign, jnp.int32)[None]),
            mode=jnp.asarray(self.mode).at[:, ix].set(
                jnp.asarray(mode, jnp.int32)[None]),
            done_at=jnp.asarray(self.done_at).at[:, ix].set(BIG),
            orph=None if self.orph is None else
            jnp.asarray(self.orph, bool).at[:, ix].set(False))

    def reassign(self, idx, cols) -> "EngineState":
        """Move existing tasks ``idx`` to new columns ``cols`` keeping
        their *per-scenario* remaining work — the service layer's
        re-admission of orphans stranded on terminated columns
        (DESIGN.md §2.10).  Unlike ``set_tasks`` (which writes fresh full
        work), progress is preserved exactly as the engine left it (the
        checkpoint floor was applied at termination time); the exec mode
        resets to base and the orphan flag clears.  Scenarios where the
        task already finished keep their completion record — moving a
        done task's column is inert (no pending work, no billing)."""
        ix = jnp.asarray(idx, jnp.int32)
        cs = jnp.asarray(cols, jnp.int32)
        return dataclasses.replace(
            self,
            assign=jnp.asarray(self.assign).at[:, ix].set(cs[None]),
            mode=jnp.asarray(self.mode).at[:, ix].set(0),
            orph=None if self.orph is None else
            jnp.asarray(self.orph, bool).at[:, ix].set(False))

    def pad_tasks(self, b_pad: int) -> "EngineState":
        """Grow the task axis to ``b_pad`` with inert pads (no remaining
        work, never pending, completion unset)."""
        extra = b_pad - self.n_tasks
        if extra < 0:
            raise ValueError(f"cannot shrink task axis {self.n_tasks} -> "
                             f"{b_pad}")
        if extra == 0:
            return self
        return self.with_tasks(jnp.zeros(extra), jnp.zeros(extra, jnp.int32),
                               jnp.zeros(extra, jnp.int32))

    def launch(self, cols, boot_done_s: float) -> "EngineState":
        """Activate NOT_LAUNCHED columns ``cols`` with a boot edge at
        ``boot_done_s`` (absolute) — the service layer's on-admit launch
        of fresh on-demand capacity (mirrors ``_apply_launch``)."""
        ix = jnp.asarray(cols, jnp.int32)
        vstate = jnp.asarray(self.vstate)
        boot = jnp.asarray(self.boot)
        hit = vstate[:, ix] == NOT_LAUNCHED
        return dataclasses.replace(
            self,
            vstate=vstate.at[:, ix].set(
                jnp.where(hit, VM_ACTIVE, vstate[:, ix])),
            boot=boot.at[:, ix].set(
                jnp.where(hit, jnp.float32(boot_done_s), boot[:, ix])))


_STATE_FIELDS = tuple(f.name for f in dataclasses.fields(EngineState))
jax.tree_util.register_pytree_node(
    EngineState,
    lambda st: (tuple(getattr(st, f) for f in _STATE_FIELDS), None),
    lambda aux, leaves: EngineState(*leaves))


@dataclasses.dataclass
class MCResult:
    """Per-scenario outcome arrays + distribution summaries."""

    policy: str
    scenario: str
    dt: float
    deadline_s: float
    cost: np.ndarray          # f32 [S]
    makespan: np.ndarray      # f32 [S]
    deadline_met: np.ndarray  # bool [S]
    unfinished: np.ndarray    # int [S]
    n_hibernations: np.ndarray
    n_resumes: np.ndarray
    billed_s: np.ndarray      # f32 [S, V] billed seconds per column
    vm_uids: list[int]        # column -> VMInstance.uid
    stepping: str = "slot"
    n_steps: int = 0          # while-loop iterations
    exit_slots: np.ndarray | None = None  # int [S] per-scenario exit slot
    visited: np.ndarray | None = None     # bool [S, n_slots] stepped mask
    n_terminations: np.ndarray | None = None  # int [S] spot terminations
    state: EngineState | None = None      # mid-horizon state at stop_s
    #: fault-recovery outcomes (§2.10): tasks still stranded on a dead
    #: column at exit, successful retry passes, and completed tasks —
    #: ``n_done + unfinished == n_tasks`` is the conservation invariant
    #: the chaos harness asserts (zeros on termination-free tensors)
    n_orphans: np.ndarray | None = None   # int [S] stranded at exit
    retry_rounds: np.ndarray | None = None  # int [S] successful retries
    n_done: np.ndarray | None = None      # int [S] completed tasks
    #: loop iterations that entered each batch-wide branch of the
    #: while-body (``BRANCHES`` -> count; summed over the devices of a
    #: sharded run).  Bookkeeping only: no other output depends on it.
    branch_entries: dict | None = None

    @property
    def n(self) -> int:
        return len(self.cost)

    @property
    def slots_total(self) -> int:
        """Scenario-slots covered (sum of per-scenario exit slots)."""
        return 0 if self.exit_slots is None else int(self.exit_slots.sum())

    @property
    def slots_visited(self) -> int:
        """Scenario-slots actually full-stepped (the rest were jumped
        over in closed form; equal to ``slots_total`` for the fixed-slot
        engine, which visits every slot)."""
        return 0 if self.visited is None else int(self.visited.sum())

    @property
    def slots_skipped_frac(self) -> float:
        return 1.0 - self.slots_visited / max(1, self.slots_total)

    def summary(self) -> dict:
        return {"policy": self.policy, "scenario": self.scenario,
                "n": self.n, "cost": dist_stats(self.cost),
                "makespan": dist_stats(self.makespan),
                "deadline_met_frac": float(np.mean(self.deadline_met)),
                "mean_hibernations": float(np.mean(self.n_hibernations)),
                "mean_resumes": float(np.mean(self.n_resumes)),
                "mean_terminations": (
                    0.0 if self.n_terminations is None
                    else float(np.mean(self.n_terminations)))}


# ---------------------------------------------------------------------------
# Problem arrays
# ---------------------------------------------------------------------------
def plan_column_uids(plan: PrimaryPlan) -> list[int]:
    """Column -> VMInstance.uid map of a plan's launchable instances: the
    primary map's VMs plus every on-demand instance Alg. 4 may launch
    dynamically.  Shared with ``sim.fleet``, which needs the column count
    V to size event tensors before the engine runs."""
    pool = plan.solution.pool
    return sorted(set(plan.solution.selected_uids) |
                  {vm.uid for vm in pool if vm.market == Market.ONDEMAND})


def n_slots_for(deadline_s: float, params: MCParams) -> int:
    """Tensor/time horizon in slots — the engine runs to
    ``horizon_mult * deadline`` like the DES."""
    return int(math.ceil(deadline_s * params.horizon_mult / params.dt))


def _plan_arrays(job: Job, plan: PrimaryPlan, cfg: CloudConfig, ovh: float
                 ) -> tuple[dict, list[int]]:
    """Flatten (job, plan) into the engine's column/task arrays.

    Columns are the *launchable* instances only (``plan_column_uids`` —
    unselected spot and burstable instances can never enter a run).  The
    task axis is permuted to the DES dispatch order — packed start time,
    tid tie-break — so the per-column rank order reproduces each VM's
    queue order.
    """
    sol = plan.solution
    pool = sol.pool
    per_vm = pack_solution(sol, job.tasks, cfg)
    assert per_vm is not None, "primary map must be packable"
    uids = plan_column_uids(plan)
    col_of = {u: c for c, u in enumerate(uids)}

    b = job.n_tasks
    starts = np.zeros(b)
    for vs in per_vm.values():
        for a in vs.assignments:
            starts[a.task.tid] = a.start
    perm = np.lexsort((np.arange(b), starts))
    tasks = [job.tasks[int(i)] for i in perm]

    base = np.array([t.base_time for t in tasks], np.float64)
    # checkpoint-axis schedule (§2.8): "periodic" reproduces the historical
    # Daly grid bit-for-bit; "off"/"random" reshape only this data
    total, cp = checkpoint_schedule(
        base, ovh, getattr(plan.policy, "checkpoint", "periodic"),
        write_s=CHECKPOINT_WRITE_S, tids=[t.tid for t in tasks])

    vms = [pool[u] for u in uids]
    arr = {
        "total": jnp.asarray(total),
        "cp": jnp.asarray(cp),
        "mem_t": jnp.asarray([t.memory_mb for t in tasks], jnp.float32),
        "assign0": jnp.asarray([col_of[int(sol.alloc[i])] for i in perm],
                               jnp.int32),
        "mode0": jnp.asarray([int(sol.modes[i]) for i in perm], jnp.int32),
        "price": jnp.asarray([vm.price_per_sec for vm in vms], jnp.float32),
        "cores": jnp.asarray([vm.vcpus for vm in vms], jnp.float32),
        "speed": jnp.asarray([vm.vm_type.gflops / cfg.gflops_ref
                              for vm in vms], jnp.float32),
        "bfrac": jnp.asarray([vm.vm_type.baseline_frac for vm in vms],
                             jnp.float32),
        "memv": jnp.asarray([vm.memory_mb for vm in vms], jnp.float32),
        "crate": jnp.asarray([vm.vm_type.credit_rate_per_hour / 3600.0
                              for vm in vms], jnp.float32),
        "cinit": jnp.asarray([vm.vm_type.initial_credits for vm in vms],
                             jnp.float32),
        "ccap": jnp.asarray([vm.vm_type.credit_rate_per_hour * 24.0
                             for vm in vms], jnp.float32),
        "spot": jnp.asarray([vm.is_spot for vm in vms], bool),
        "burst": jnp.asarray([vm.is_burstable for vm in vms], bool),
        "odm": jnp.asarray([vm.market == Market.ONDEMAND for vm in vms],
                           bool),
        "launched0": jnp.asarray([u in sol.selected_uids for u in uids],
                                 bool),
    }
    return arr, uids


def _scalars(job: Job, cfg: CloudConfig, params: MCParams,
             n_slots: int) -> dict:
    """Engine scalars.  Event probabilities no longer appear here — the
    market process bakes them into the event tensor (DESIGN.md §2.4)."""
    d = job.deadline_s
    dt = params.dt
    od_speed = min(t.gflops for t in cfg.ondemand_types) / cfg.gflops_ref
    return {
        "dt": jnp.float32(dt),
        "deadline": jnp.float32(d),
        "omega": jnp.float32(cfg.boot_overhead_s),
        "restore": jnp.float32(cfg.checkpoint_restore_s),
        "bperiod": jnp.float32(cfg.burst_period_s),
        "margin": jnp.float32(params.hads_margin_s),
        "od_speed": jnp.float32(od_speed),
        "ac_seconds": jnp.float32(cfg.allocation_cycle_s),
        "boot_slots": jnp.int32(round(cfg.boot_overhead_s / dt)),
        "ac_slots": jnp.int32(round(cfg.allocation_cycle_s / dt)),
        "max_slots": jnp.int32(n_slots),
        # mid-horizon entry (§2.9): first absolute slot the event tensor
        # covers, and the absolute slot the run stops at.  Defaults — a
        # tensor anchored at t=0 and a run to the horizon — reproduce
        # the historical one-shot program's values exactly.
        "slot0": jnp.int32(0),
        "stop_slots": jnp.int32(n_slots),
    }


# ---------------------------------------------------------------------------
# Jitted engine helpers
# ---------------------------------------------------------------------------
def _rowp_helpers(ref):
    """Accessor trio over a plan-array leaf layout: legacy 1-d per-cell
    arrays ([B] tasks / [V] columns) or the megabatch row-parametric
    layout ([S, B] / [S, V] — one plan row per scenario, DESIGN.md §2.7).
    ``gv`` gathers a column array by a [S, B] index, ``g1`` by a [S]
    index, ``bc`` broadcasts against [S, ·] state.  The branches are
    trace-time Python, so the legacy path compiles exactly as before."""
    if ref.ndim == 2:
        return (lambda x, idx: jnp.take_along_axis(x, idx, axis=1),
                lambda x, idx: jnp.take_along_axis(
                    x, idx[:, None], axis=1)[:, 0],
                lambda x: x)
    return (lambda x, idx: x[idx], lambda x, idx: x[idx],
            lambda x: x[None])


def _dest_column(load, vstate, boot, credits, aff_load, aff_mem, arr, sc, t,
                 *, allow_burstable: bool, cascade: bool = False,
                 dl2=None):
    """Alg. 4's cascade as one argmin-over-columns rule: score every column
    by projected drain time (+ remaining boot, + a price tie-break for
    fresh launches, - a small burstable preference), mask the ineligible
    ones, argmin.  Returns (dest [S], feasible [S]).

    ``cascade=True`` (``MCParams.dest_cascade``) scores by the DES's
    literal Alg. 4 attempt order instead — idle burstable → idle
    non-burstable (spot first) → busy non-burstable (spot first) → fresh
    cheapest on-demand launch, every attempt gated by the
    ``check_migration`` deadline rule (projected completion ≤ D, so a
    late migration with no deadline-feasible destination is *infeasible*
    and falls to the §2.10 orphan-retry ledger, exactly like a DES
    migration failure).  Off by default: the legacy goldens pin the
    drain-argmin scores; the DES-parity suites opt in."""
    cores, speed = arr["cores"], arr["speed"]
    burst, odm, memv, price = (arr["burst"], arr["odm"], arr["memv"],
                               arr["price"])
    _, _, bc = _rowp_helpers(speed)
    fits = aff_mem[:, None] <= bc(memv) + 1e-6
    ok_active = (vstate == VM_ACTIVE) & fits
    if allow_burstable:
        # enough credits to run the whole moved load at full speed
        cred_ok = credits * sc["bperiod"] * bc(speed) > aff_load[:, None]
        ok_active &= ~bc(burst) | cred_ok
    else:
        ok_active &= ~bc(burst)
    # A dynamic on-demand slot is *reusable*: the DES allocates a fresh
    # VM object per launch, so releasing one (AC idle termination — the
    # only way an on-demand column dies, market terminations target spot)
    # never shrinks launchable capacity.  The column analogue is letting a
    # TERMINATED on-demand column relaunch; without this, a late deferred
    # migration whose odm pool was used and drained earlier finds no
    # destination ever and the bag strands (DESIGN.md §2.10).
    ok_new = ((vstate == NOT_LAUNCHED) | (vstate == VM_TERMINATED)) \
        & bc(odm) & fits

    drain = load / bc(cores * speed)
    boot_left = jnp.clip(boot - t[:, None], 0.0, sc["omega"])
    if cascade:
        spot_c, is_b = bc(arr["spot"]), bc(burst)
        idle = load <= 1e-6
        # check_migration's completion bound, per destination class
        comp = t[:, None] + boot_left + \
            (load + aff_load[:, None]) / bc(cores * speed)
        ok_active &= comp <= dl2 + 1e-6   # dl2: [S, 1] (rowp) or scalar
        if allow_burstable:
            ok_active &= ~is_b | idle    # Alg. 4 never queues a burstable
        comp_new = t[:, None] + sc["omega"] + \
            (aff_load[:, None] + sc["restore"]) / bc(cores * speed)
        ok_new &= comp_new <= dl2 + 1e-6
        # attempt tiers; argmin's lower-index tie-break IS the DES's
        # lowest-uid pick within a tier, and the price term its
        # cheapest-first launch order
        tier = jnp.where(is_b, 0.0,
                         jnp.where(idle, 0.0, 2.0)
                         + jnp.where(spot_c, 1.0, 2.0))
        score = jnp.where(ok_active, tier,
                          jnp.where(ok_new, 5.0 + bc(price), BIG))
    else:
        score = jnp.where(
            ok_active,
            drain + boot_left - jnp.where(bc(burst), 1.0, 0.0),
            jnp.where(ok_new, sc["omega"] + bc(price) * 3600.0, BIG))
    dest = jnp.argmin(score, axis=1).astype(jnp.int32)
    feasible = jnp.min(score, axis=1) < BIG * 0.5
    return dest, feasible


def _checkpoint_floor(rem, total, cp, mask):
    """Roll masked tasks' progress back to their checkpoint grid (§III-E)."""
    _, _, bc = _rowp_helpers(total)
    done = jnp.maximum(bc(total) - rem, 0.0)
    done_cp = jnp.floor(done / bc(cp) + 1e-6) * bc(cp)
    return jnp.where(mask, bc(total) - done_cp, rem)


def _apply_launch(vstate, boot, dest, do, t, sc, iota_v):
    """Launch ``dest`` columns that were NOT_LAUNCHED or released
    (TERMINATED on-demand — a recycled dynamic slot, see ``_dest_column``).
    Only ``_dest_column``-feasible dests reach here with ``do`` set, so the
    state guard below can never resurrect a market-terminated spot column.
    ``t`` is per-scenario [S] — scenarios step their own clocks under
    event-horizon stepping (DESIGN.md §2.5)."""
    hit = do[:, None] & (iota_v == dest[:, None]) & \
        ((vstate == NOT_LAUNCHED) | (vstate == VM_TERMINATED))
    vstate = jnp.where(hit, VM_ACTIVE, vstate)
    boot = jnp.where(hit, t[:, None] + sc["omega"], boot)
    return vstate, boot


def _migrate_spread(do_ev, aff, rem, load, vstate, boot, credits, assign,
                    mode, rcv, arr, sc, t1, *, allow_burstable: bool,
                    rounds: int, track_moved: bool = False,
                    cascade: bool = False, dl2=None):
    """Vectorized Alg. 4: checkpoint rollback, then ``rounds`` argmin
    re-assignment rounds — group g (every rounds-th affected task) goes to
    the current argmin column, whose projected load is then updated — so a
    hibernated bag fans out instead of dog-piling one target.

    ``track_moved=True`` additionally returns the [S, B] mask of tasks a
    round actually re-placed — what the fault-recovery ledger needs to
    tell a stranded group (infeasible: nothing mutated) from a recovered
    one (DESIGN.md §2.10).  The accumulation is pure bookkeeping on
    already-computed masks, so the six shared outputs stay bit-identical
    either way."""
    total, cp, mem_t, speed = arr["total"], arr["cp"], arr["mem_t"], \
        arr["speed"]
    _, g1, bc = _rowp_helpers(speed)
    iota_v = jnp.arange(vstate.shape[1])[None]
    rem = _checkpoint_floor(rem, total, cp, aff & do_ev[:, None])
    aff_rank = jnp.cumsum(aff.astype(jnp.int32), axis=1) - 1
    moved_all = jnp.zeros_like(aff) if track_moved else None
    for g in range(rounds):
        mg = aff & (aff_rank % rounds == g)
        load_g = jnp.sum(jnp.where(mg, rem, 0.0), axis=1)
        mem_g = jnp.max(jnp.where(mg, bc(mem_t), 0.0), axis=1)
        dest, feasible = _dest_column(load, vstate, boot, credits, load_g,
                                      mem_g, arr, sc, t1,
                                      allow_burstable=allow_burstable,
                                      cascade=cascade, dl2=dl2)
        do_g = do_ev & jnp.any(mg, axis=1) & feasible
        moved = mg & do_g[:, None]
        if track_moved:
            moved_all = moved_all | moved
        has_prog = (bc(total) - rem) > 1e-6
        rem = rem + jnp.where(moved & has_prog,
                              sc["restore"] * g1(speed, dest)[:, None], 0.0)
        assign = jnp.where(moved, dest[:, None], assign)
        mode = jnp.where(moved, 0, mode)
        vstate, boot = _apply_launch(vstate, boot, dest, do_g, t1, sc,
                                     iota_v)
        hit = do_g[:, None] & (iota_v == dest[:, None])
        load = load + jnp.where(hit, (load_g + sc["restore"])[:, None], 0.0)
        rcv = rcv | hit
    if track_moved:
        return rem, assign, mode, vstate, boot, rcv, moved_all
    return rem, assign, mode, vstate, boot, rcv


def _select(u, elig, k):
    """Resolve one slot of the event-tensor contract (DESIGN.md §2.4):
    the top-``k[s]`` *eligible* columns by priority score, ties toward the
    lower index; a negative score opts a column out regardless of rank.
    With uniform scores and k=1 this is exactly the legacy Gumbel-max
    'random eligible column' pick (argmax of where(elig, u, -1))."""
    score = jnp.where(elig, u, -1.0)
    iota = jnp.arange(score.shape[1])
    beats = (score[:, None, :] > score[:, :, None]) | \
        ((score[:, None, :] == score[:, :, None]) &
         (iota[None, None, :] < iota[None, :, None]))
    rank = jnp.sum(beats, axis=2)          # [S, V] columns scoring higher
    return elig & (u >= 0.0) & (rank < k[:, None])


# ---------------------------------------------------------------------------
# Jitted engine
# ---------------------------------------------------------------------------
def _mc_run_impl(arr: dict, sc: dict, ev: EventTensor,
                 state: EngineState | None = None, *, s: int,
                 policy: PolicyConfig, steal_rounds: int, mig_rounds: int,
                 mem_safe: bool, use_kernel: bool,
                 stepping: str, ac_aligned: bool,
                 orphan_retry: int = 16, dest_cascade: bool = False,
                 return_state: bool = False) -> dict:
    total, mem_t = arr["total"], arr["mem_t"]
    price, cores, speed = arr["price"], arr["cores"], arr["speed"]
    bfrac, memv = arr["bfrac"], arr["memv"]
    crate, ccap = arr["crate"], arr["ccap"]
    spot, burst = arr["spot"], arr["burst"]
    # row-parametric megabatch mode (sim.megabatch, DESIGN.md §2.7): plan
    # leaves arrive as [S, B] / [S, V] rows — one plan per scenario row —
    # and the job-dependent scalars (deadline, max_slots) as [S].  All
    # branches below are trace-time Python on array rank, so the legacy
    # per-cell layout compiles to exactly the program it always did.
    rowp = speed.ndim == 2
    gv, g1, bc = _rowp_helpers(speed)
    b, v = total.shape[-1], price.shape[-1]
    dt = sc["dt"]
    iota_v = jnp.arange(v)[None]
    rows = jnp.arange(s)
    adaptive = stepping == "adaptive"
    # trace-time gate: a termination-free tensor (term_k is None) compiles
    # to exactly the historical pre-termination program (§2.8)
    has_term = ev.term_k is not None
    # fault-recovery ledger gate (§2.10): carried on terminating tensors,
    # and on re-entry from a state that already holds orphans (a service
    # fold whose later tensor slice happens to be termination-free must
    # still retry the strandings of the earlier one)
    track_orph = has_term or (state is not None and state.orph is not None)
    n_slots = ev.hib_k.shape[1]
    # per-row deadline broadcasts against [S, V] work maxima in the
    # deferred-HADS safe-time rule; a scalar everywhere else
    dl2 = sc["deadline"][:, None] if rowp else sc["deadline"]
    init2 = (lambda x: x) if rowp else \
        (lambda x: jnp.tile(x[None], (s, 1)))

    # mid-horizon entry (§2.9): the tensor's slot axis is anchored at the
    # absolute slot ``slot0`` (0 for one-shot runs), and the run exits at
    # ``stop`` — an early stop boundary freezes a scenario exactly like
    # its own horizon exit would (no billing, events or progress past it)
    # so the extracted carry re-enters bit-exactly.
    slot0 = sc["slot0"]
    stop = jnp.minimum(sc["max_slots"], sc["stop_slots"])

    launched0 = arr["launched0"]
    if state is None:
        carry = (
            jnp.zeros(s, jnp.int32) + slot0,                      # slot i[S]
            init2(jnp.where(launched0, VM_ACTIVE,
                            NOT_LAUNCHED).astype(jnp.int32)),
            init2(jnp.where(launched0, sc["omega"], BIG)),
            jnp.zeros((s, v), jnp.float32),                       # billed
            init2(jnp.where(launched0 & burst, arr["cinit"],
                            0.0)),                                # credits
            init2(total),                                         # rem
            init2(arr["assign0"]),                                # assign
            init2(arr["mode0"]),                                  # mode
            jnp.full((s, b), BIG, jnp.float32),                   # done_at
            jnp.zeros(s, jnp.int32),                              # n_hib
            jnp.zeros(s, jnp.int32),                              # n_res
            jnp.zeros(s, jnp.int32),                              # n_term
            jnp.int32(0),                                         # n_steps
            jnp.zeros((s, n_slots), bool),                        # visited
        )
        if track_orph:
            carry = carry + (jnp.zeros((s, b), bool),             # orph
                             jnp.zeros(s, jnp.int32))             # oret
    else:
        # re-enter from an extracted state: scenarios that exited early
        # (no pending work) clock-forward to slot0 — exact, nothing can
        # happen in a span with no pending work
        carry = (
            jnp.maximum(state.slot.astype(jnp.int32), slot0),
            state.vstate.astype(jnp.int32),
            state.boot.astype(jnp.float32),
            state.billed.astype(jnp.float32),
            state.credits.astype(jnp.float32),
            state.rem.astype(jnp.float32),
            state.assign.astype(jnp.int32),
            state.mode.astype(jnp.int32),
            state.done_at.astype(jnp.float32),
            state.n_hib.astype(jnp.int32),
            state.n_res.astype(jnp.int32),
            state.n_term.astype(jnp.int32),
            jnp.int32(0),                                         # n_steps
            jnp.zeros((s, n_slots), bool),                        # visited
        )
        if track_orph:
            # the retry-round counter restarts per segment — the bound
            # caps per-segment move churn, not the orphan's total wait
            carry = carry + (
                jnp.asarray(state.orph, bool) if state.orph is not None
                else jnp.zeros((s, b), bool),                     # orph
                jnp.zeros(s, jnp.int32))                          # oret
    # entries into each batch-wide branch (``BRANCHES``), last in the carry
    carry = carry + (jnp.zeros(len(BRANCHES), jnp.int32),)

    def entered(ctr, branch, pred):
        return ctr.at[BRANCHES.index(branch)].add(pred.astype(jnp.int32))

    def cond(c):
        # a scenario is live while it has pending work inside the horizon
        # (or before an early stop boundary); the loop runs until every
        # scenario has exited its own clock
        return jnp.any((c[0] < stop) & jnp.any(c[5] > 0.0, axis=1))

    def step(c):
        (i, vstate, boot, billed, credits, rem, assign, mode, done_at,
         nhib, nres, nterm, nsteps, visited) = c[:14]
        if track_orph:
            orph, oret = c[14], c[15]
        ctr = c[-1]

        pending = rem > 0.0
        # a row is live while it has pending work *inside* the horizon
        # (and before any early stop boundary): under per-scenario clocks
        # a row can sit at its exit slot unfinished while others still
        # run — it must freeze (no billing, events or progress), exactly
        # as the lockstep slot walk's global exit would have frozen it
        in_h = i < stop
        gate = jnp.any(pending, axis=1) & in_h                # [S] live

        with jax.named_scope("mc.stats"):
            # ---- per-step stats: the hot [S, B] -> [S, V] reduction ---------
            # One shared pending one-hot feeds every column reduction; its
            # task-axis cumsum yields both per-column counts and each task's
            # queue rank within its column (B-axis order = dispatch priority).
            # All of it is span-invariant — spans are completion/event-free
            # by construction (DESIGN.md §2.5) — so one computation serves
            # the span jump *and* the full step that follows it.
            ohp = ((assign[:, :, None] == iota_v[None]) &
                   pending[:, :, None]).astype(jnp.float32)       # [S, B, V]
            cum = jnp.cumsum(ohp, axis=1)
            cnt = cum[:, -1, :]

            def col_sum(w):
                """Per-column sum of the [S, B] weight vector ``w`` (f32 on
                every backend: a TPU's default dot precision is bf16)."""
                return jnp.einsum("sbv,sb->sv", ohp, w, precision=HIGHEST)

            rank = jnp.take_along_axis(cum, assign[:, :, None],
                                       axis=2)[:, :, 0] - 1.0
            burst_t = gv(burst, assign)
            run0 = pending & (rank < gv(cores, assign))
            if not mem_safe:
                memcum = jnp.take_along_axis(
                    jnp.cumsum(ohp * (mem_t[:, :, None] if rowp
                                      else mem_t[None, :, None]), axis=1),
                    assign[:, :, None], axis=2)[:, :, 0]
                run0 &= memcum <= gv(memv, assign) + 1e-6

            # the credit buckets run full width, masked to the burstable
            # columns (a non-burst column has crate = ccap = 0).  Reading
            # and writing them through a traced list of burstable columns
            # (gather + scatter) computed wrong buckets inside this loop on
            # the TPU v5e: a bucket read 1.3 credits from its first booted
            # slot on and never moved again (PERF.md §6)
            burst_v, cap, crate_v = bc(burst), bc(ccap), bc(crate)

        with jax.named_scope("mc.span_advance"):
            if adaptive:
                # ============================================================
                # Event-horizon jump (DESIGN.md §2.5): per scenario, find the
                # largest span of *uniform* slots — no tensor event, no AC
                # boundary, no task completion, no boot edge, no
                # credit-bucket boundary, no deferred-HADS fire instant — and
                # advance it in closed form.  Scenarios step their own clocks
                # (``i`` is [S]): a storm in one scenario never forces the
                # calm ones to slot-crawl, so iterations track the *worst*
                # scenario's interesting-slot count, not the batch union.
                # Each bound below is the first non-uniform slot offset (or
                # BIG); the multiplicative backoff on the float-derived
                # bounds (x * (1 - 1e-6), >= 10x the accumulated rounding
                # error of the divisions producing x) keeps a span from ever
                # overshooting into the non-uniform region when a ratio
                # rounds up across an integer, without paying a systematic
                # one-slot creep at every boundary.
                # ============================================================
                BACK = 1.0 - 1e-6
                t0 = i.astype(jnp.float32) * dt
                active0 = vstate == VM_ACTIVE
                live01 = (active0 & (boot <= t0[:, None])).astype(jnp.float32)
                rate0 = jnp.take_along_axis(live01, assign, axis=1)
                cred_ok0 = jnp.take_along_axis(credits > 1e-9, assign, axis=1)
                sfac0 = jnp.where((mode == 1) | (burst_t & ~cred_ok0),
                                  gv(bfrac, assign), 1.0)
                drem0 = dt * rate0 * gv(speed, assign) * sfac0 * run0
                spend0 = jnp.einsum("sbv,sb->sv", ohp,
                                    (run0 & (mode == 0)).astype(jnp.float32))

                # (1) next nonzero event slot, O(1) from the per-scenario
                # tensor pointer (EventTensor.nxt, built at generation time);
                # the pointer is tensor-relative — shift by slot0
                it = i - slot0
                m_ev = (ev.nxt[rows, jnp.minimum(it, n_slots - 1)] - it
                        ).astype(jnp.float32)
                # (2) next AC boundary (edge e is handled by the step at e-1)
                if ac_aligned:
                    base, ac = sc["boot_slots"], sc["ac_slots"]
                    q = jnp.maximum(i + 1 - base, 1)
                    e = base + ac * ((q + ac - 1) // ac)
                    m_ac = (e - 1 - i).astype(jnp.float32)
                else:
                    k_next = jnp.maximum(
                        jnp.floor((t0 - sc["omega"]) / sc["ac_seconds"]),
                        0.0) + 1.0
                    e_t = sc["omega"] + sc["ac_seconds"] * k_next
                    m_ac = jnp.maximum(
                        jnp.ceil(e_t / dt * BACK) - 1.0
                        - i.astype(jnp.float32), 0.0)
                # (3) first task completion among running tasks
                ratio = jnp.where(drem0 > 0.0,
                                  rem / jnp.maximum(drem0, 1e-30), BIG)
                m_comp = jnp.maximum(
                    jnp.min(jnp.ceil(ratio * BACK), axis=1) - 1.0, 0.0)
                # (4) boot edges of still-booting active columns
                kb = jnp.where(active0 & (boot > t0[:, None]),
                               jnp.floor((boot - t0[:, None]) / dt * BACK),
                               BIG)
                m_boot = jnp.maximum(jnp.min(kb, axis=1), 0.0)
                # (5) burstable credit boundaries: a bucket emptying (speed
                # factor flips), refilling from empty, or reaching cap —
                # between them the buckets are piecewise linear
                r_c = dt * live01 * crate_v - (dt / sc["bperiod"]) * spend0
                c0 = credits
                act_b = active0 & burst_v
                rising = act_b & (r_c > 1e-12)
                kc = jnp.full_like(r_c, BIG)
                kc = jnp.where(rising & (c0 <= 1e-9), 1.0, kc)
                kc = jnp.where(rising & (c0 > 1e-9) & (c0 < cap - 1e-6),
                               jnp.maximum(
                                   jnp.ceil((cap - c0) / r_c * BACK), 1.0),
                               kc)
                kc = jnp.where(act_b & (r_c < -1e-12) & (c0 > 1e-9),
                               jnp.maximum(
                                   jnp.ceil((c0 - 1e-9) / (-r_c) * BACK),
                                   1.0), kc)
                m_cred = jnp.min(kc, axis=1)
                # (6) deferred-HADS fire instant — frozen columns' max
                # remaining work is span-invariant, so t_safe is a fixed time
                # (pure-freeze policies never fire: resume is their only out)
                if policy.deferred_migration:
                    maxw0 = jnp.max(ohp * rem[:, :, None], axis=1)
                    t_safe0 = dl2 - (
                        sc["omega"] + maxw0 / sc["od_speed"] + sc["restore"]
                        + sc["margin"])
                    kf = jnp.where((vstate == VM_HIBERNATED) & (cnt > 0.5),
                                   jnp.floor((t_safe0 - t0[:, None]) / dt
                                             - 2.0),
                                   BIG)
                    m_fire = jnp.maximum(jnp.min(kf, axis=1), 0.0)
                else:
                    m_fire = jnp.full(s, BIG, jnp.float32)

                # finished scenarios have no bounds left — they jump straight
                # to their exit slot (horizon or stop boundary)
                m_max = jnp.maximum(stop - 1 - i, 0).astype(jnp.float32)
                bounds = jnp.stack([m_ev, m_ac, m_comp, m_boot, m_cred,
                                    m_fire])                     # [6, S]
                mf = jnp.clip(jnp.where(gate, jnp.min(bounds, axis=0), BIG),
                              0.0, m_max)
                m = mf.astype(jnp.int32)
                mf = m.astype(jnp.float32)

                # ---- closed-form span advance: m uniform slots at once -----
                if use_kernel:
                    # fused Pallas kernel: progress decrement + the [S, V]
                    # reductions of the advanced state in one streamed pass
                    rem, _, cnt, maxw = mc_span_advance(
                        assign, rem, drem0, mf, v=v)
                else:
                    rem = jnp.where(pending,
                                    jnp.maximum(rem - mf[:, None] * drem0,
                                                0.0),
                                    rem)
                    # recompute from the advanced state: a column hibernated
                    # by *this* step's events needs its post-span max (the
                    # m_fire bound above could reuse maxw0 only because it
                    # reads already-hibernated, hence frozen, columns)
                    maxw = jnp.max(ohp * rem[:, :, None], axis=1) \
                        if policy.deferred_migration else None
                billed = billed + mf[:, None] * dt * live01 * gate[:, None]
                credits = jnp.where(
                    act_b, jnp.clip(c0 + mf[:, None] * r_c, 0.0, cap), c0)
                i = i + m
            elif use_kernel:
                # accelerator path: the Pallas kernel supplies the [S, V]
                # reductions — counts/max here, migration loads post-progress
                # inside the event branches.  The one-hot/cumsum above remains
                # only for the queue rank; a TPU-native rank kernel is the
                # open item (DESIGN.md §2.3).
                _, cnt, maxw = mc_vm_stats(assign, rem, v=v)
            else:
                maxw = jnp.max(ohp * rem[:, :, None], axis=1) \
                    if policy.deferred_migration else None

        # ================================================================
        # Full step at slot i (per-scenario) — under "slot" stepping
        # every slot lands here; under "adaptive" only interesting ones.
        # ================================================================
        with jax.named_scope("mc.progress_billing"):
            t = i.astype(jnp.float32) * dt     # [S]; slot covers [t, t + dt)
            t1 = t + dt
            # this slot's pregenerated market events (DESIGN.md §2.4)
            if adaptive:
                # scenarios sit on different slots: per-row gather
                # (tensor-relative index — the tensor is anchored at slot0)
                ir = jnp.minimum(i - slot0, n_slots - 1)
                hib_k, hib_u = ev.hib_k[rows, ir], ev.hib_u[rows, ir]
                res_k, res_u = ev.res_k[rows, ir], ev.res_u[rows, ir]
                if has_term:
                    term_k, term_u = ev.term_k[rows, ir], ev.term_u[rows, ir]
            else:
                # lockstep slot walk: one dynamic slice, as before
                i0 = i[0] - slot0
                hib_k = jax.lax.dynamic_index_in_dim(ev.hib_k, i0, 1,
                                                     keepdims=False)
                hib_u = jax.lax.dynamic_index_in_dim(ev.hib_u, i0, 1,
                                                     keepdims=False)
                res_k = jax.lax.dynamic_index_in_dim(ev.res_k, i0, 1,
                                                     keepdims=False)
                res_u = jax.lax.dynamic_index_in_dim(ev.res_u, i0, 1,
                                                     keepdims=False)
                if has_term:
                    term_k = jax.lax.dynamic_index_in_dim(ev.term_k, i0, 1,
                                                          keepdims=False)
                    term_u = jax.lax.dynamic_index_in_dim(ev.term_u, i0, 1,
                                                          keepdims=False)

            # ---- progress over [t, t + dt) ----------------------------------
            active = vstate == VM_ACTIVE
            live = jnp.clip((t1[:, None] - boot) / dt, 0.0, 1.0) * active \
                * in_h[:, None]
            rate_t = jnp.take_along_axis(live, assign, axis=1)
            cred_ok = jnp.take_along_axis(credits > 1e-9, assign, axis=1)
            sfac = jnp.where((mode == 1) | (burst_t & ~cred_ok),
                             gv(bfrac, assign), 1.0)
            run = run0
            drem = dt * rate_t * gv(speed, assign) * sfac * run
            rem2 = jnp.maximum(rem - drem, 0.0)
            newly = pending & (rem2 <= 0.0)
            frac = jnp.clip(rem / jnp.maximum(drem, 1e-9), 0.0, 1.0)
            done_at = jnp.where(newly, t[:, None] + dt * frac, done_at)

            # ---- billing (pauses during hibernation, ends at termination /
            # scenario completion) + burstable credit accrual -----------------
            billed = billed + dt * live * gate[:, None]
            spend = jnp.einsum("sbv,sb->sv", ohp,
                               (run & (mode == 0)).astype(jnp.float32))
            credits = jnp.where(
                active & burst_v,
                jnp.clip(credits + dt * live * crate_v
                         - (dt / sc["bperiod"]) * spend, 0.0, cap),
                credits)

            rcv = jnp.zeros((s, v), bool)      # columns given tasks this slot

        with jax.named_scope("mc.terminate"):
            # victims for both loss events: active, booted, spot columns
            hib_elig = active & bc(spot) & (boot <= t1[:, None])

            # ---- terminate events (§2.8): the provider reclaims the
            # column — state lost.  Resolved BEFORE hibernation (terminate
            # wins slot collisions) over the same eligible set; a terminated
            # column is then excluded from this slot's hibernation picks.
            # Billing stops structurally: live requires VM_ACTIVE, and
            # resume eligibility is VM_HIBERNATED, so a terminated column
            # never bills or revives.
            # Unfinished tasks roll back to the checkpoint floor and ALWAYS
            # re-enter Alg. 4 migration — with memory lost there is no state
            # to freeze in place, whatever the hibernation axis says.
            if has_term:
                trm = _select(term_u, hib_elig, term_k) & gate[:, None]
                do_trm = jnp.any(trm, axis=1)
                nterm = nterm + jnp.sum(trm, axis=1)
                vstate = jnp.where(trm, VM_TERMINATED, vstate)
                hib_elig = hib_elig & ~trm
                aff_t = jnp.take_along_axis(trm, assign, axis=1) & (rem2 > 0)

                def migt(ops):
                    rem2, assign, mode, vstate, boot, rcv, orph = ops
                    load = mc_vm_stats(assign, rem2, v=v)[0] \
                        if use_kernel else col_sum(rem2 * (rem2 > 0))
                    (rem2, assign, mode, vstate, boot, rcv,
                     moved) = _migrate_spread(
                        do_trm, aff_t, rem2, load, vstate, boot, credits,
                        assign, mode, rcv, arr, sc, t1,
                        allow_burstable=policy.use_burstables,
                        rounds=mig_rounds, track_moved=True,
                        cascade=dest_cascade, dl2=dl2)
                    # ledger (§2.10): an affected task no round re-placed is
                    # stranded on its (now dead) column — record it for the
                    # retry pass below / service re-admission
                    orph = orph | (aff_t & ~moved)
                    return rem2, assign, mode, vstate, boot, rcv, orph

                ctr = entered(ctr, "terminate", jnp.any(aff_t))
                (rem2, assign, mode, vstate, boot, rcv, orph) = jax.lax.cond(
                    jnp.any(aff_t), migt, lambda ops: ops,
                    (rem2, assign, mode, vstate, boot, rcv, orph))

        with jax.named_scope("mc.hibernate_alg4"):
            # ---- hibernation events (victims: requested count resolved
            # against the live eligible set — active, booted, spot) -------
            hib = _select(hib_u, hib_elig, hib_k) & \
                gate[:, None]
            do_hib = jnp.any(hib, axis=1)
            nhib = nhib + jnp.sum(hib, axis=1)
            vstate = jnp.where(hib, VM_HIBERNATED, vstate)

            if policy.immediate_migration:
                # Alg. 4: checkpoint rollback + spread argmin re-assignment
                affected = jnp.take_along_axis(hib, assign, axis=1) & \
                    (rem2 > 0)

                def mig(ops):
                    if track_orph:
                        rem2, assign, mode, vstate, boot, rcv, orph = ops
                    else:
                        rem2, assign, mode, vstate, boot, rcv = ops
                    load = mc_vm_stats(assign, rem2, v=v)[0] \
                        if use_kernel else col_sum(rem2 * (rem2 > 0))
                    out = _migrate_spread(
                        do_hib, affected, rem2, load, vstate, boot, credits,
                        assign, mode, rcv, arr, sc, t1,
                        allow_burstable=policy.use_burstables,
                        rounds=mig_rounds, track_moved=track_orph,
                        cascade=dest_cascade, dl2=dl2)
                    if track_orph:
                        # ledger (§2.10): a group no round re-placed stays
                        # frozen on its hibernated column — retry below
                        rem2, assign, mode, vstate, boot, rcv, moved = out
                        return (rem2, assign, mode, vstate, boot, rcv,
                                orph | (affected & ~moved))
                    return out

                ops0 = (rem2, assign, mode, vstate, boot, rcv) + \
                    ((orph,) if track_orph else ())
                ctr = entered(ctr, "hibernate", jnp.any(affected))
                out = jax.lax.cond(jnp.any(affected), mig,
                                   lambda ops: ops, ops0)
                if track_orph:
                    (rem2, assign, mode, vstate, boot, rcv, orph) = out
                else:
                    (rem2, assign, mode, vstate, boot, rcv) = out
            # else: freeze in place (HADS) — tasks stay attached, no progress
            # while the column is hibernated, exact progress preserved.

        with jax.named_scope("mc.resume_defer"):
            # ---- resume events (beneficiaries among hibernated columns) -----
            res = _select(res_u, vstate == VM_HIBERNATED, res_k) & \
                gate[:, None]
            nres = nres + jnp.sum(res, axis=1)
            vstate = jnp.where(res, VM_ACTIVE, vstate)

            if policy.deferred_migration:
                # deferred-HADS migration at the latest safe instant
                # (conservative single-wave estimate on the slowest on-demand
                # type, mirroring Simulator._hads_latest_safe_time); under
                # hibernation="freeze" tasks stay frozen until resume instead
                t_safe = dl2 - (sc["omega"] + maxw / sc["od_speed"]
                                + sc["restore"] + sc["margin"])
                fire = (vstate == VM_HIBERNATED) & (cnt > 0.5) & \
                    (t1[:, None] >= t_safe - dt) & gate[:, None]
                aff2 = (rem2 > 0) & jnp.take_along_axis(fire, assign, axis=1)
                do2 = jnp.any(aff2, axis=1)

                def defer(ops):
                    if track_orph:
                        rem2, assign, mode, vstate, boot, rcv, orph = ops
                    else:
                        rem2, assign, mode, vstate, boot, rcv = ops
                    load = mc_vm_stats(assign, rem2, v=v)[0] \
                        if use_kernel else col_sum(rem2 * (rem2 > 0))
                    out = _migrate_spread(
                        do2, aff2, rem2, load, vstate, boot, credits, assign,
                        mode, rcv, arr, sc, t1, allow_burstable=False,
                        rounds=mig_rounds, track_moved=track_orph,
                        cascade=dest_cascade, dl2=dl2)
                    if track_orph:
                        # ledger (§2.10): a fired-but-infeasible deferred bag
                        # is past its safe instant — every later boundary's
                        # retry is its only remaining route to completion
                        rem2, assign, mode, vstate, boot, rcv, moved = out
                        return (rem2, assign, mode, vstate, boot, rcv,
                                orph | (aff2 & ~moved))
                    return out

                ops0 = (rem2, assign, mode, vstate, boot, rcv) + \
                    ((orph,) if track_orph else ())
                ctr = entered(ctr, "defer", jnp.any(aff2))
                out = jax.lax.cond(jnp.any(aff2), defer, lambda ops: ops,
                                   ops0)
                if track_orph:
                    (rem2, assign, mode, vstate, boot, rcv, orph) = out
                else:
                    (rem2, assign, mode, vstate, boot, rcv) = out

        with jax.named_scope("mc.ac_block"):
            # ---- Allocation-Cycle boundary: work stealing + idle termination
            # is_ac is per-scenario [S] — scenarios on different clocks reach
            # their AC edges in different loop iterations
            i1 = i + 1
            if ac_aligned:
                is_ac = (i1 > sc["boot_slots"]) & \
                    ((i1 - sc["boot_slots"]) % sc["ac_slots"] == 0)
            else:
                # dt need not divide ω/AC under adaptive stepping: the slot
                # whose (t, t1] interval contains an AC edge handles it
                f1 = jnp.floor((t1 - sc["omega"]) / sc["ac_seconds"])
                f0 = jnp.floor((t - sc["omega"]) / sc["ac_seconds"])
                is_ac = (t1 >= sc["omega"] + sc["ac_seconds"]) & (f1 > f0)
            booted = boot <= t1[:, None]

            def ac_block(ops):
                vstate, assign, mode = ops
                cnt_live = cnt - col_sum(newly.astype(jnp.float32))
                if policy.work_stealing:
                    a, m, cl = assign, mode, cnt_live
                    for _ in range(steal_rounds):
                        idle = (vstate == VM_ACTIVE) & booted & (cl < 0.5) & \
                            (is_ac & gate)[:, None]
                        thief = jnp.argmin(jnp.where(idle, iota_v, v + 1),
                                           axis=1).astype(jnp.int32)
                        has_thief = jnp.any(idle, axis=1)
                        queued = jnp.where(bc(burst), 0.0,
                                           jnp.maximum(cl - bc(cores), 0.0))
                        vict = jnp.argmax(queued, axis=1).astype(jnp.int32)
                        has_q = jnp.max(queued, axis=1) > 0.5
                        on_vict = (rem2 > 0) & (a == vict[:, None]) & \
                            (rank >= g1(cores, vict)[:, None])
                        tsk = jnp.argmax(jnp.where(on_vict, rem2, -1.0),
                                         axis=1).astype(jnp.int32)
                        do_steal = has_thief & has_q & gate & \
                            jnp.any(on_vict, axis=1) & \
                            (g1(mem_t, tsk) <= g1(memv, thief) + 1e-6)
                        a = a.at[rows, tsk].set(
                            jnp.where(do_steal, thief, a[rows, tsk]))
                        m = m.at[rows, tsk].set(
                            jnp.where(do_steal,
                                      g1(burst, thief).astype(jnp.int32),
                                      m[rows, tsk]))
                        shift = do_steal[:, None].astype(jnp.float32)
                        cl = cl + shift * (iota_v == thief[:, None]) \
                            - shift * (iota_v == vict[:, None])
                    assign, mode, cnt_live = a, m, cl
                term = (vstate == VM_ACTIVE) & booted & (cnt_live < 0.5) & \
                    ~bc(burst) & ~rcv & (is_ac & gate)[:, None]
                vstate = jnp.where(term, VM_TERMINATED, vstate)
                return vstate, assign, mode

            ctr = entered(ctr, "ac", jnp.any(is_ac))
            (vstate, assign, mode) = jax.lax.cond(
                jnp.any(is_ac), ac_block, lambda ops: ops,
                (vstate, assign, mode))

        with jax.named_scope("mc.retry"):
            if track_orph:
                # ---- fault-recovery retry (§2.10): re-attempt Alg. 4 for
                # orphans still stranded on a non-running column.  Runs at
                # every full step — under adaptive stepping those ARE the
                # event/AC boundaries, matching the DES retry hooks (boot
                # done / resume / AC check), and a step where capacity has
                # not returned is an infeasible no-op (nothing mutates, the
                # round bound is not consumed).  Ordered after the AC block
                # so destinations reflect this slot's resumes, launches and
                # idle terminations — the same world the DES retries see.
                # stuck = parked on any non-running column: terminated (both
                # ledger sites) or still hibernated (a deferred bag past its
                # safe instant).  An orphan whose column resumed or that a
                # steal re-placed is live again — excluded here, and its
                # stale ledger bit is dropped so a later hibernation of its new
                # column doesn't resurrect it.
                stuck = jnp.take_along_axis(vstate != VM_ACTIVE, assign,
                                            axis=1)
                orph = orph & stuck
                want = orph & (rem2 > 0.0) & gate[:, None]
                can = jnp.any(want, axis=1) & (oret < orphan_retry)

                def retry(ops):
                    rem2, assign, mode, vstate, boot, rcv, orph, oret = ops
                    load = mc_vm_stats(assign, rem2, v=v)[0] \
                        if use_kernel else col_sum(rem2 * (rem2 > 0))
                    (rem2, assign, mode, vstate, boot, rcv,
                     moved) = _migrate_spread(
                        can, want, rem2, load, vstate, boot, credits,
                        assign, mode, rcv, arr, sc, t1,
                        allow_burstable=policy.use_burstables,
                        rounds=mig_rounds, track_moved=True,
                        cascade=dest_cascade, dl2=dl2)
                    orph = orph & ~moved
                    oret = oret + jnp.any(moved, axis=1).astype(jnp.int32)
                    return rem2, assign, mode, vstate, boot, rcv, orph, oret

                ctr = entered(ctr, "retry", jnp.any(want & can[:, None]))
                (rem2, assign, mode, vstate, boot, rcv, orph,
                 oret) = jax.lax.cond(
                    jnp.any(want & can[:, None]), retry, lambda ops: ops,
                    (rem2, assign, mode, vstate, boot, rcv, orph, oret))

        # exited rows park at their own exit slot — under the
        # row-parametric layout that can sit strictly inside the padded
        # slot axis, so route them to the (dropped) pad index explicitly;
        # for the legacy layout i == max_slots == n_slots was already out
        # of range
        i_mark = jnp.where(i < stop, i - slot0, n_slots)
        nxt = (jnp.minimum(i1, stop), vstate, boot, billed,
               credits, rem2, assign, mode, done_at, nhib, nres, nterm,
               nsteps + 1, visited.at[rows, i_mark].set(True, mode="drop"))
        if track_orph:
            nxt = nxt + (orph, oret)
        return nxt + (ctr,)

    out = jax.lax.while_loop(cond, step, carry)
    (i_fin, vstate_f, boot_f, billed, credits_f, rem, assign_f, mode_f,
     done_at, nhib, nres, nterm, nsteps, visited) = out[:14]
    makespan = jnp.max(jnp.where(done_at < BIG * 0.5, done_at, 0.0), axis=1)
    if track_orph:
        orph_f, oret_f = out[14], out[15]
        # stranded = still orphaned, unfinished, and parked on a non-
        # running column at exit (the retry pass drops the ledger bit of
        # any orphan whose column came back, so live bits here are real
        # fault losses, not deadline misses)
        stuck_f = jnp.take_along_axis(vstate_f != VM_ACTIVE, assign_f,
                                      axis=1)
        n_orphan = jnp.sum(orph_f & (rem > 0.0) & stuck_f, axis=1)
        n_retry = oret_f
    else:
        orph_f = None
        n_orphan = jnp.zeros(s, jnp.int32)
        n_retry = jnp.zeros(s, jnp.int32)
    res = {"cost": jnp.sum(billed * bc(price), axis=1),
           "makespan": makespan,
           "unfinished": jnp.sum(rem > 0.0, axis=1),
           "billed": billed, "n_hib": nhib, "n_res": nres,
           "n_term": nterm, "n_steps": nsteps, "exit_slots": i_fin,
           "visited": visited,
           "n_done": jnp.sum(done_at < BIG * 0.5, axis=1),
           "n_orphan": n_orphan, "n_retry": n_retry,
           "branch_entries": out[-1]}
    if return_state:
        res["state"] = EngineState(
            slot=i_fin, vstate=vstate_f, boot=boot_f, billed=billed,
            credits=credits_f, rem=rem, assign=assign_f, mode=mode_f,
            done_at=done_at, n_hib=nhib, n_res=nres, n_term=nterm,
            orph=orph_f)
    return res


def scenario_mesh(ev: EventTensor):
    """The device mesh an event tensor's scenario axis is split over
    (``sim.fleet.shard_events``), or None for a single-device tensor."""
    sh = getattr(ev.hib_k, "sharding", None)
    if isinstance(sh, NamedSharding) and len(sh.device_set) > 1:
        return sh.mesh
    return None


def _mc_run(arr: dict, sc: dict, ev: EventTensor,
            state: EngineState | None = None, *, s: int,
            mesh: Mesh | None = None, policy: PolicyConfig,
            steal_rounds: int, mig_rounds: int, mem_safe: bool,
            use_kernel: bool, stepping: str, ac_aligned: bool,
            orphan_retry: int = 16, dest_cascade: bool = False,
            return_state: bool = False) -> dict:
    """The engine over S scenarios, on one device or — with ``mesh`` —
    under ``shard_map`` with every device running its own S / n slice.

    Scenarios are independent, so each device steps its slice in its own
    while-loop with no collective; the Pallas kernels cannot be
    partitioned by GSPMD and need exactly this per-device program.  Plan
    arrays are replicated, except the megabatch row-parametric layout's
    [S, ·] rows (and the [S] per-row scalars), which split with the
    scenarios.  ``n_steps`` is the maximum over the devices' loops,
    ``branch_entries`` the sum."""
    kw = dict(policy=policy, steal_rounds=steal_rounds,
              mig_rounds=mig_rounds, mem_safe=mem_safe,
              use_kernel=use_kernel, stepping=stepping,
              ac_aligned=ac_aligned, orphan_retry=orphan_retry,
              dest_cascade=dest_cascade, return_state=return_state)
    if mesh is None:
        return _mc_run_impl(arr, sc, ev, state, s=s, **kw)
    rows, rep = PartitionSpec(mesh.axis_names[0]), PartitionSpec()
    rowp = arr["speed"].ndim == 2
    arr_spec = {k: rows if rowp else rep for k in arr}
    sc_spec = {k: rows if jnp.ndim(x) == 1 else rep for k, x in sc.items()}

    def local(arr, sc, ev, state):
        out = _mc_run_impl(arr, sc, ev, state, s=s // mesh.size, **kw)
        out["n_steps"] = out["n_steps"][None]
        out["branch_entries"] = out["branch_entries"][None]
        return out

    out = jax.shard_map(local, mesh=mesh,
                        in_specs=(arr_spec, sc_spec, rows, rows),
                        out_specs=rows, check_vma=False)(arr, sc, ev, state)
    out["n_steps"] = jnp.max(out["n_steps"])
    out["branch_entries"] = jnp.sum(out["branch_entries"], axis=0)
    return out


@functools.lru_cache(maxsize=2)
def _mc_jit(donate: bool):
    """jit the engine, optionally donating the event tensor's buffers —
    the dominant HBM allocation (two f32 [S, N, V] score tensors) — so
    XLA may alias them into the while-loop carry workspace on
    accelerators.  ``run_mc`` donates (it owns a fresh tensor per call);
    ``run_mc_events`` defaults to not donating because callers routinely
    reuse pregenerated tensors (parity tests, fleet warm-up runs)."""
    return jax.jit(_mc_run, static_argnames=(
        "s", "mesh", "policy", "steal_rounds", "mig_rounds", "mem_safe",
        "use_kernel", "stepping", "ac_aligned",
        "orphan_retry", "dest_cascade", "return_state"),
        donate_argnums=(2,) if donate else ())


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
def engine_args(view: PolicyConfig, params: MCParams, cfg: CloudConfig,
                mem_safe: bool) -> dict:
    """The engine's static keyword arguments for one call.  ``view`` is
    the policy's ``engine_view()``: the engine branches only on the
    dynamics axes, so same-dynamics lattice policies share one
    compilation.  Reductions run in the Pallas kernels on accelerators
    and on the jnp one-hot path on the CPU unless ``params.use_kernel``
    says otherwise; the kernels' interpret mode follows the backend
    (``kernels.sched_fitness.resolve_interpret``)."""
    use_kernel = jax.default_backend() != "cpu" \
        if params.use_kernel is None else params.use_kernel
    return dict(policy=view, steal_rounds=params.steal_rounds,
                mig_rounds=params.mig_rounds, mem_safe=mem_safe,
                use_kernel=use_kernel,
                stepping=params.stepping,
                ac_aligned=_dt_aligned(cfg, params.dt),
                orphan_retry=params.orphan_retry,
                dest_cascade=params.dest_cascade)


def _dt_aligned(cfg: CloudConfig, dt: float) -> bool:
    """True when ``dt`` divides both ω and AC, so boundary slots can use
    exact integer arithmetic (and the slot engine is admissible)."""
    return all(abs(q / dt - round(q / dt)) <= 1e-9
               for q in (cfg.boot_overhead_s, cfg.allocation_cycle_s))


def _check_dt(cfg: CloudConfig, params: MCParams) -> None:
    """The fixed-slot engine can only handle boundaries on grid points;
    the adaptive engine treats them as first-class jump targets and
    accepts any ``dt`` (DESIGN.md §2.5)."""
    if params.stepping == "slot" and not _dt_aligned(cfg, params.dt):
        raise ValueError(
            f"dt={params.dt} must divide the boot overhead "
            f"({cfg.boot_overhead_s}s) and the allocation cycle "
            f"({cfg.allocation_cycle_s}s) under stepping='slot' — use "
            f"the adaptive engine for off-grid boundaries")


#: (job, plan, cfg, ovh) -> flattened engine arrays; keyed by object
#: identity with strong refs so repeated ``run_mc``/``mc_sweep`` calls on
#: the same plan (the S=1 hot case) skip the numpy flattening pass.  The
#: jitted engine itself is cached by jax on (shapes, PolicyConfig, flags),
#: which the ``MCParams`` knobs map onto — together the two caches make
#: every warm call dispatch-only.
_PLAN_CACHE: dict = {}
_PLAN_CACHE_MAX = 64


def _plan_arrays_cached(job: Job, plan: PrimaryPlan, cfg: CloudConfig,
                        ovh: float) -> tuple[dict, list[int], bool]:
    key = (id(job), id(plan), id(cfg), float(ovh))
    hit = _PLAN_CACHE.get(key)
    if hit is not None and hit[0] is job and hit[1] is plan \
            and hit[2] is cfg:
        return hit[3], hit[4], hit[5]
    arr, uids = _plan_arrays(job, plan, cfg, ovh)
    # memory can never bind: even a full complement of the largest tasks
    # fits every column -> skip the per-slot memory-cumsum pass
    mem_safe = bool(float(np.max(np.asarray(arr["mem_t"])))
                    * float(np.max(np.asarray(arr["cores"])))
                    <= float(np.min(np.asarray(arr["memv"]))) + 1e-6)
    if len(_PLAN_CACHE) >= _PLAN_CACHE_MAX:
        _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)))
    _PLAN_CACHE[key] = (job, plan, cfg, arr, uids, mem_safe)
    return arr, uids, mem_safe


def _slot_of(t_s: float, dt: float, what: str) -> int:
    k = int(round(t_s / dt))
    if abs(k * dt - t_s) > 1e-6:
        raise ValueError(f"{what}={t_s} must sit on the dt={dt} slot grid")
    return k


def run_mc_events(job: Job, plan: PrimaryPlan, cfg: CloudConfig,
                  ev: EventTensor, params: MCParams = MCParams(),
                  label: str = "custom", donate: bool = False, *,
                  stop_s: float | None = None,
                  state: EngineState | None = None,
                  t0_s: float = 0.0,
                  return_state: bool | None = None,
                  arrays: tuple[dict, list[int], bool] | None = None
                  ) -> MCResult:
    """Run the dynamic phase over a pregenerated event tensor.

    The tensor defines the run: S scenarios (``params.n_scenarios`` is
    ignored here), a V axis that must match the plan's launchable columns,
    and a slot horizon the engine runs to (events never fire past the
    deadline by the tensor contract, but the run continues to the tensor's
    horizon so late scenarios finish).  ``ev`` may be split over a
    device mesh on its scenario axis (``sim.fleet.shard_events``); the
    run then splits with it, one engine loop per device (``_mc_run``).
    Under
    adaptive stepping the tensor's next-event index is used (and built
    here if the tensor arrived without one).  ``donate=True`` lets XLA
    consume the tensor's buffers (don't reuse ``ev`` afterwards).

    Mid-horizon entry (DESIGN.md §2.9): ``stop_s`` exits every scenario
    at that absolute instant and (by default) returns the frozen
    ``EngineState`` on ``MCResult.state``; ``state=`` re-enters a run
    from an extracted state, continuing the same absolute timeline.
    ``t0_s`` anchors the tensor's slot axis at a later absolute instant
    (use with ``EventTensor.slice_slots`` to drop already-consumed
    slots); both must sit on the slot grid.  The round trip is bit-exact
    on the slot path and within the §2.5 span bound under adaptive
    stepping.  ``arrays`` bypasses the plan-flattening cache with
    caller-built engine arrays ``(arr, uids, mem_safe)`` — the service
    layer owns its task axis (arrival order, inert pads) and hands it in
    here.
    """
    _check_dt(cfg, params)
    if params.stepping not in ("adaptive", "slot"):
        raise ValueError(f"unknown stepping {params.stepping!r} "
                         "(adaptive/slot)")
    if arrays is not None:
        arr, uids, mem_safe = arrays
    else:
        arr, uids, mem_safe = _plan_arrays_cached(job, plan, cfg,
                                                  params.ovh)
    with span("mc.run", S=ev.n_scenarios, B=arr["total"].shape[-1],
              V=ev.n_vms, policy=plan.policy.name):
        ev.validate()                   # diagnose malformed tensors first —
        if params.stepping == "adaptive":   # with_index would crash rawly
            ev = ev.with_index()
        if ev.n_vms != len(uids):
            raise ValueError(
                f"event tensor has V={ev.n_vms} columns, plan has "
                f"{len(uids)} launchable instances — regenerate the tensor "
                f"for this plan (see plan_column_uids)")
        slot0 = _slot_of(t0_s, params.dt, "t0_s")
        n_abs = slot0 + ev.n_slots      # absolute horizon in slots
        sc = _scalars(job, cfg, params, n_abs)
        sc["slot0"] = jnp.int32(slot0)
        if stop_s is not None:
            stop_slots = _slot_of(stop_s, params.dt, "stop_s")
            if not slot0 < stop_slots <= n_abs:
                raise ValueError(
                    f"stop_s={stop_s} must land strictly after t0_s={t0_s} "
                    f"and inside the tensor horizon ({n_abs} slots)")
            sc["stop_slots"] = jnp.int32(stop_slots)
        if state is not None:
            b = arr["total"].shape[-1]
            if (state.n_scenarios, state.n_vms, state.n_tasks) != \
                    (ev.n_scenarios, ev.n_vms, b):
                raise ValueError(
                    f"state shape (S={state.n_scenarios}, V={state.n_vms}, "
                    f"B={state.n_tasks}) does not match the run "
                    f"(S={ev.n_scenarios}, V={ev.n_vms}, B={b})")
            if params.stepping == "slot":
                slots = np.unique(np.asarray(jax.device_get(state.slot)))
                if len(slots) > 1:
                    raise ValueError(
                        "stepping='slot' walks all scenarios in lockstep — "
                        f"re-entry needs a uniform slot clock, got {slots}")
        if os.environ.get("REPRO_SCHEMA_CHECKS"):
            # aval-level boundary contract (DESIGN.md §2.11) — shape/dtype/
            # weak-type schemas beyond validate()'s shape checks; no compute.
            from repro.analysis.schema import (check_engine_state,
                                               check_event_tensor)
            dims = check_event_tensor(ev)
            if state is not None:
                check_engine_state(
                    state, bind={"S": dims["S"], "V": dims["V"]})
        want_state = bool(stop_s is not None) if return_state is None \
            else return_state
        on_cpu = jax.default_backend() == "cpu"
        out = _mc_jit(donate and not on_cpu)(
            arr, sc, ev, state, s=ev.n_scenarios, mesh=scenario_mesh(ev),
            **engine_args(plan.policy.engine_view(), params, cfg, mem_safe),
            return_state=want_state)
        out = jax.device_get(out)
        unfinished = out["unfinished"].astype(int)
        makespan = out["makespan"]
        met = (unfinished == 0) & \
            (makespan <= job.deadline_s + params.dt + 1e-6)
        return MCResult(
            policy=plan.policy.name, scenario=label, dt=params.dt,
            deadline_s=job.deadline_s,
            cost=out["cost"], makespan=makespan, deadline_met=met,
            unfinished=unfinished,
            n_hibernations=out["n_hib"].astype(int),
            n_resumes=out["n_res"].astype(int),
            billed_s=out["billed"], vm_uids=list(uids),
            stepping=params.stepping, n_steps=int(out["n_steps"]),
            exit_slots=out["exit_slots"].astype(int), visited=out["visited"],
            n_terminations=out["n_term"].astype(int),
            state=out.get("state"),
            n_orphans=out["n_orphan"].astype(int),
            retry_rounds=out["n_retry"].astype(int),
            n_done=out["n_done"].astype(int),
            branch_entries=dict(zip(
                BRANCHES, np.asarray(out["branch_entries"]).tolist())))


def run_mc(job: Job, plan: PrimaryPlan, cfg: CloudConfig,
           scenario: Scenario | MarketProcess | str = SC_NONE,
           params: MCParams = MCParams()) -> MCResult:
    """Run S Monte-Carlo scenarios of (job, plan, policy, market process).

    ``scenario`` accepts a Table V ``Scenario`` (or its name) — mapped to
    the bit-compatible ``market.PoissonProcess`` — or any
    ``market.MarketProcess``.  The process is sampled into an event tensor
    for this plan's columns and handed to ``run_mc_events`` (with its
    buffers donated on accelerators — the tensor is owned by this call).
    """
    process = as_process(scenario)
    _check_dt(cfg, params)
    ev = process.sample(
        jax.random.PRNGKey(params.seed), s=params.n_scenarios,
        n_slots=n_slots_for(job.deadline_s, params),
        v=len(plan_column_uids(plan)), dt=params.dt,
        deadline_s=job.deadline_s)
    return run_mc_events(job, plan, cfg, ev, params, label=process.name,
                         donate=True)


def simulate_mc(job: Job, cfg: CloudConfig,
                policy: PolicyConfig = BURST_HADS,
                scenario: Scenario | MarketProcess | str = SC_NONE,
                params: MCParams = MCParams(),
                ils_params: ILSParams | None = None) -> MCResult:
    """Deprecated shim — plan + Monte-Carlo in one call.

    Use ``repro.api.run(job=..., policy=..., process=..., backend=
    "mc-adaptive")`` instead; this wrapper delegates there (sharing the
    facade's cross-backend plan cache) and returns the raw ``MCResult``.
    """
    from repro.api import run as _api_run
    from repro.compat import warn_deprecated
    warn_deprecated("sim.mc_engine.simulate_mc", "repro.api.run")
    backend = "mc-slot" if params.stepping == "slot" else "mc-adaptive"
    return _api_run(job=job, policy=policy, process=scenario,
                    backend=backend, cfg=cfg, mc=params,
                    ils=ils_params).raw


def mc_sweep(job: Job, cfg: CloudConfig, policies, scenarios=None,
             params: MCParams = MCParams(),
             ils_params: ILSParams | None = None) -> list[dict]:
    """Deprecated shim — per-(policy, process) distribution summaries.

    Use ``repro.api.sweep`` instead; this wrapper delegates there, which
    routes the grid through the fleet pipeline's concat-S fusion (one
    engine call per (job, policy) instead of one per cell) and maps the
    unified ``Result`` rows back onto the legacy row schema."""
    from repro.api import sweep as _api_sweep
    from repro.compat import warn_deprecated
    warn_deprecated("sim.mc_engine.mc_sweep", "repro.api.sweep")
    backend = "mc-slot" if params.stepping == "slot" else "mc-adaptive"
    results = _api_sweep(jobs=[job], policies=list(policies),
                         processes=scenarios, backend=backend, cfg=cfg,
                         mc=params, ils=ils_params)
    return [r.legacy_summary() for r in results]
