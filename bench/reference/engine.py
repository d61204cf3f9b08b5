"""A plain reference of the Monte-Carlo engine's stated semantics.

One scenario at a time, one slot of ``dt`` seconds at a time, in float64
numpy: the state layout and per-slot order of DESIGN.md section 2.3, the
event-tensor contract of section 2.4, and no jumps (section 2.5 states
that the event-horizon stepping equals this slot walk).  It imports
nothing of the program; the plan (task -> VM uid, exec modes, selected
VMs) is its input, and the engine's parameters come in as plain numbers
(``EngineParams``).

State.  Per column (the plan's selected VMs and every on-demand VM, in
uid order): lifecycle (not launched, active, hibernated, terminated),
boot-done instant, billed seconds, credit bucket.  Per task, in the order
the exact packer starts them (start time, then task id): remaining work
in reference-VM seconds, column, exec mode, completion instant.  A
task's work is its base time inflated by the checkpoint budget ``ovh``;
its checkpoints lie every ``total / (n + 1)`` seconds of work, ``n =
max(1, floor(ovh * base / write_s))``.

A slot [t, t + dt) of a scenario with unfinished work, in order:

1. dispatch: on each column the first ``cores`` unfinished tasks in task
   order run (while their memory, summed in task order, fits);
2. progress: a running task advances ``dt * live * speed * f`` where
   ``live`` is the booted share of the slot of an active column and ``f``
   is the baseline fraction for a task in baseline mode or on a
   burstable column whose bucket is empty; a task that reaches zero
   completes at ``t + dt * rem / progress``;
3. billing: every active column bills ``dt * live`` (a hibernated or
   terminated column bills nothing); a burstable column's bucket gains
   ``dt * live * rate`` and loses ``dt / burst_period`` per full-mode
   task it ran, clipped to [0, cap];
4. hibernations: the slot's requested count is resolved against the
   eligible columns (active, spot, booted by ``t + dt``): the top-k by
   score, ties to the lower index, a negative score opting out;
5. under ``migrate``, Alg. 4 for the victims' unfinished tasks (below);
6. resumes: the requested count over the hibernated columns, same rule;
7. under ``defer``, a hibernated column with unfinished tasks whose
   latest safe instant ``D - (omega + maxw / od_speed + restore +
   margin)`` is at most ``t + 2 dt`` sends its tasks through Alg. 4
   (burstables not allowed); ``maxw`` is its largest remaining task at
   the slot's start;
8. at an Allocation-Cycle boundary (``t + dt = omega + k AC``, k >= 1),
   with work stealing, ``steal_rounds`` times: the lowest idle booted
   active column takes the queued task with the most remaining work from
   the non-burstable column with the most queued tasks (first on ties);
   then every idle booted active non-burstable column that received no
   migrated task this slot terminates.

Alg. 4 for a set of tasks: roll each back to its last checkpoint; split
them, in task order, into ``mig_rounds`` groups (task j of the set into
group j mod rounds); for each group pick the column of least score: an
active column (a burstable one only if allowed and its bucket can run
the group's whole work at full speed) scores its drain time ``load /
(cores * speed)`` plus its remaining boot, less 1 for a burstable; a
not-launched or released on-demand column scores ``omega + hourly
price`` and is launched (boot done at ``t + dt + omega``); any column
the group's largest task does not fit is out.  Each moved task that had
progress pays ``restore * speed`` of work, and the chosen column's load
grows by the group's work plus ``restore``.

Where DESIGN.md is silent the reference follows the engine's code, and
says so here:

* the idle counts of step 8 are those of the slot's start less the tasks
  that completed in the slot; tasks migrated in the slot count on the
  column they left, and queue positions are those of the slot's start;
* a task stolen by a burstable column runs in baseline mode, and a stolen
  task is neither rolled back nor charged a restore;
* the load that scores Alg. 4's destinations is the remaining work of the
  unfinished tasks after the slot's progress, on their columns of the
  moment; a group with no task, or with no column left, moves nothing;
* a column hibernated in a slot may resume in the same slot;
* the run ends at ``horizon_mult * D`` or when no work remains;
  ``deadline_met`` grants one slot of grace (makespan <= D + dt).

Spot terminations (the third direction of the tensor contract) and the
orphan ledger are not modelled: the configurations that use this module
set ``termination_frac`` to 0.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from .fitness import pack_solution
from .types import CloudConfig, Market, Solution, TaskSpec

NOT_LAUNCHED, ACTIVE, HIBERNATED, TERMINATED = 0, 1, 2, 3
BIG = 1e30


@dataclasses.dataclass(frozen=True)
class EngineParams:
    """The engine's knobs as plain numbers (the program's defaults are
    copied into the traffic file)."""

    dt: float = 30.0
    horizon_mult: float = 3.0
    ovh: float = 0.10
    checkpoint_write_s: float = 5.0
    hads_margin_s: float = 30.0
    steal_rounds: int = 2
    mig_rounds: int = 8


@dataclasses.dataclass(frozen=True)
class Policy:
    """The dynamic-phase axes the engine branches on."""

    burstables: bool
    hibernation: str        # "migrate" | "defer" | "freeze"
    work_stealing: bool


@dataclasses.dataclass
class Outcome:
    cost: float
    makespan: float
    billed: np.ndarray      # [V] billed seconds per column
    n_hib: int
    n_res: int
    unfinished: int
    deadline_met: bool


class Case:
    """One (bag, plan, policy) as the engine's per-column and per-task
    arrays, in float64."""

    def __init__(self, tasks: tuple[TaskSpec, ...], alloc, modes,
                 selected: set[int], cfg: CloudConfig, deadline_s: float,
                 policy: Policy, params: EngineParams):
        pool = cfg.instance_pool()
        self.cfg, self.policy, self.params = cfg, policy, params
        self.deadline = float(deadline_s)
        self.uids = sorted(set(int(u) for u in selected) |
                           {vm.uid for vm in pool
                            if vm.market == Market.ONDEMAND})
        col_of = {u: c for c, u in enumerate(self.uids)}
        sol = Solution(alloc=np.asarray(alloc, np.int32),
                       modes=np.asarray(modes, np.int8), pool=pool,
                       selected_uids=set(selected))
        starts = np.zeros(len(tasks))
        for vs in pack_solution(sol, tasks, cfg).values():
            for a in vs.assignments:
                starts[a.task.tid] = a.start
        order = np.lexsort((np.arange(len(tasks)), starts))
        base = np.array([tasks[i].base_time for i in order])
        self.total = base * (1.0 + params.ovh)
        n_cp = np.maximum(1, np.floor(params.ovh * base /
                                      params.checkpoint_write_s))
        self.cp = self.total / (n_cp + 1.0)
        self.mem_t = np.array([tasks[i].memory_mb for i in order])
        self.assign0 = np.array([col_of[int(alloc[i])] for i in order])
        self.mode0 = np.array([int(modes[i]) for i in order])
        vms = [pool[u] for u in self.uids]
        self.price = np.array([vm.price_per_sec for vm in vms])
        self.cores = np.array([float(vm.vcpus) for vm in vms])
        self.speed = np.array([vm.vm_type.gflops / cfg.gflops_ref
                               for vm in vms])
        self.bfrac = np.array([vm.vm_type.baseline_frac for vm in vms])
        self.memv = np.array([vm.memory_mb for vm in vms])
        self.crate = np.array([vm.vm_type.credit_rate_per_hour / 3600.0
                               for vm in vms])
        self.cinit = np.array([vm.vm_type.initial_credits for vm in vms])
        self.ccap = np.array([vm.vm_type.credit_rate_per_hour * 24.0
                              for vm in vms])
        self.spot = np.array([vm.is_spot for vm in vms])
        self.burst = np.array([vm.is_burstable for vm in vms])
        self.odm = np.array([vm.market == Market.ONDEMAND for vm in vms])
        self.launched0 = np.array([u in selected for u in self.uids])
        self.od_speed = min(t.gflops for t in cfg.ondemand_types) / \
            cfg.gflops_ref
        self.n_slots = int(math.ceil(self.deadline * params.horizon_mult /
                                     params.dt))

    @property
    def n_vms(self) -> int:
        return len(self.uids)


def select(u: np.ndarray, eligible: np.ndarray, k: int) -> np.ndarray:
    """The event contract: the ``k`` eligible columns of highest score,
    ties to the lower index, a negative score opting out."""
    out = np.zeros(len(u), bool)
    if k <= 0:
        return out
    cand = np.flatnonzero(eligible & (u >= 0.0))
    # stable sort on the negated score keeps the lower index first on ties
    out[cand[np.argsort(-u[cand], kind="stable")[:k]]] = True
    return out


def run_scenario(case: Case, hib_k, hib_u, res_k, res_u, *,
                 bill_hibernated: bool = False,
                 work_stealing: bool | None = None) -> Outcome:
    """One scenario of the event arrays ``hib_k``/``res_k`` [N] and
    ``hib_u``/``res_u`` [N, V].  ``bill_hibernated`` and ``work_stealing``
    break a stated guarantee for the benchmark's controls."""
    c, p, pol = case, case.params, case.policy
    steal = pol.work_stealing if work_stealing is None else work_stealing
    dt, omega = p.dt, c.cfg.boot_overhead_s
    restore, bperiod = c.cfg.checkpoint_restore_s, c.cfg.burst_period_s
    boot_slots = round(omega / dt)
    ac_slots = round(c.cfg.allocation_cycle_s / dt)
    v, b = c.n_vms, len(c.total)
    cols = np.arange(v)
    vstate = np.where(c.launched0, ACTIVE, NOT_LAUNCHED)
    boot = np.where(c.launched0, omega, BIG)
    billed = np.zeros(v)
    credits = np.where(c.launched0 & c.burst, c.cinit, 0.0)
    rem = c.total.copy()
    assign = c.assign0.copy()
    mode = c.mode0.copy()
    done_at = np.full(b, BIG)
    n_hib = n_res = 0

    def migrate(aff, rem, load, t1, allow_burstable):
        nonlocal vstate, boot
        done = np.maximum(c.total - rem, 0.0)
        floor = np.floor(done / c.cp + 1e-6) * c.cp
        rem = np.where(aff, c.total - floor, rem)
        members = np.flatnonzero(aff)
        rcv = np.zeros(v, bool)
        for g in range(p.mig_rounds):
            grp = members[g::p.mig_rounds]
            if not len(grp):
                continue
            work = rem[grp].sum()
            fits = c.mem_t[grp].max() <= c.memv + 1e-6
            ok_active = (vstate == ACTIVE) & fits
            if allow_burstable:
                ok_active &= ~c.burst | (credits * bperiod * c.speed > work)
            else:
                ok_active &= ~c.burst
            ok_new = ((vstate == NOT_LAUNCHED) | (vstate == TERMINATED)) \
                & c.odm & fits
            score = np.where(
                ok_active,
                load / (c.cores * c.speed) + np.clip(boot - t1, 0.0, omega)
                - np.where(c.burst, 1.0, 0.0),
                np.where(ok_new, omega + c.price * 3600.0, BIG))
            dest = int(np.argmin(score))
            if score[dest] >= BIG * 0.5:
                continue
            prog = c.total[grp] - rem[grp] > 1e-6
            rem[grp] += np.where(prog, restore * c.speed[dest], 0.0)
            assign[grp] = dest
            mode[grp] = 0
            if vstate[dest] in (NOT_LAUNCHED, TERMINATED):
                vstate[dest] = ACTIVE
                boot[dest] = t1 + omega
            load[dest] += work + restore
            rcv[dest] = True
        return rem, rcv

    for i in range(c.n_slots):
        pending = rem > 0.0
        if not pending.any():
            break
        t, t1 = i * dt, (i + 1) * dt
        start_assign = assign.copy()
        # 1. dispatch: queue position and memory in task order per column
        onehot = (assign[:, None] == cols[None]) & pending[:, None]
        cum = np.cumsum(onehot, axis=0)
        rank = cum[np.arange(b), assign] - 1
        memcum = np.cumsum(onehot * c.mem_t[:, None], axis=0)[
            np.arange(b), assign]
        run = pending & (rank < c.cores[assign]) & \
            (memcum <= c.memv[assign] + 1e-6)
        cnt = cum[-1]
        maxw = np.max(onehot * rem[:, None], axis=0)
        # 2. progress
        active = vstate == ACTIVE
        live = np.clip((t1 - boot) / dt, 0.0, 1.0) * active
        if bill_hibernated:
            billed_live = np.where(vstate == HIBERNATED, 1.0, live)
        else:
            billed_live = live
        empty = credits[assign] <= 1e-9
        f = np.where((mode == 1) | (c.burst[assign] & empty),
                     c.bfrac[assign], 1.0)
        drem = dt * live[assign] * c.speed[assign] * f * run
        rem2 = np.maximum(rem - drem, 0.0)
        newly = pending & (rem2 <= 0.0)
        frac = np.clip(rem / np.maximum(drem, 1e-9), 0.0, 1.0)
        done_at = np.where(newly, t + dt * frac, done_at)
        # 3. billing and credits
        billed += dt * billed_live
        spend = np.bincount(assign[run & (mode == 0)], minlength=v)
        credits = np.where(
            c.burst & active,
            np.clip(credits + dt * live * c.crate - dt / bperiod * spend,
                    0.0, c.ccap), credits)
        rcv = np.zeros(v, bool)
        # 4-5. hibernations and Alg. 4
        hib = select(hib_u[i], active & c.spot & (boot <= t1),
                     int(hib_k[i]))
        n_hib += int(hib.sum())
        vstate = np.where(hib, HIBERNATED, vstate)
        if pol.hibernation == "migrate":
            aff = hib[assign] & (rem2 > 0.0)
            if aff.any():
                load = np.bincount(assign, weights=rem2, minlength=v)
                rem2, got = migrate(aff, rem2, load, t1, pol.burstables)
                rcv |= got
        # 6. resumes
        res = select(res_u[i], vstate == HIBERNATED, int(res_k[i]))
        n_res += int(res.sum())
        vstate = np.where(res, ACTIVE, vstate)
        # 7. deferred HADS
        if pol.hibernation == "defer":
            t_safe = c.deadline - (omega + maxw / c.od_speed + restore +
                                   p.hads_margin_s)
            fire = (vstate == HIBERNATED) & (cnt > 0) & (t1 >= t_safe - dt)
            aff = (rem2 > 0.0) & fire[assign]
            if aff.any():
                load = np.bincount(assign, weights=rem2, minlength=v)
                rem2, got = migrate(aff, rem2, load, t1, False)
                rcv |= got
        # 8. Allocation-Cycle boundary
        if i + 1 > boot_slots and (i + 1 - boot_slots) % ac_slots == 0:
            booted = boot <= t1
            n_live = np.bincount(start_assign[pending & ~newly],
                                 minlength=v).astype(float)
            for _ in range(p.steal_rounds if steal else 0):
                idle = (vstate == ACTIVE) & booted & (n_live < 0.5)
                queued = np.where(c.burst, 0.0,
                                  np.maximum(n_live - c.cores, 0.0))
                vict = int(np.argmax(queued))
                on_vict = (rem2 > 0.0) & (assign == vict) & \
                    (rank >= c.cores[vict])
                if not idle.any() or queued[vict] <= 0.5 or \
                        not on_vict.any():
                    break
                thief = int(np.argmax(idle))
                task = int(np.argmax(np.where(on_vict, rem2, -1.0)))
                if c.mem_t[task] > c.memv[thief] + 1e-6:
                    break
                assign[task] = thief
                mode[task] = int(c.burst[thief])
                n_live[thief] += 1.0
                n_live[vict] -= 1.0
            term = (vstate == ACTIVE) & booted & (n_live < 0.5) & \
                ~c.burst & ~rcv
            vstate = np.where(term, TERMINATED, vstate)
        rem = rem2
    makespan = float(np.max(np.where(done_at < BIG * 0.5, done_at, 0.0)))
    unfinished = int(np.sum(rem > 0.0))
    return Outcome(
        cost=float(np.sum(billed * c.price)), makespan=makespan,
        billed=billed, n_hib=n_hib, n_res=n_res, unfinished=unfinished,
        deadline_met=bool(unfinished == 0 and
                          makespan <= c.deadline + dt + 1e-6))
