"""Discrete-event cloud simulator executing a Burst-HADS primary map.

Implements the paper's dynamic scheduling module (§III-D) as a classic
single-trace DES: glues the runtime state (``repro.core.runtime``) to the
dynamic policies (Alg. 4 migration, Alg. 5 work-stealing, AC termination,
deferred HADS migration) under the Poisson hibernation scenarios of
Table V (event lists sampled by ``sim.market.sample_market_events`` via
``sim.events``).  It is the exact oracle the batched Monte-Carlo engine
is pinned against — parity contract and the engines' regime split
(S=1: DES wins; distributions: MC wins) in DESIGN.md §2.3.

Semantics reproduced from the paper:
  * VM boots cost ω seconds; billing starts *after* boot and pauses during
    hibernation (EBS-only charges are taken as 0);
  * an idle non-burstable VM is terminated at the end of its current
    Allocation Cycle, after one last work-stealing attempt;
  * Burst-HADS migrates immediately on hibernation (checkpoint rollback);
    HADS freezes tasks in place and defers migration to the latest instant
    that still meets the deadline via a new on-demand VM;
  * when every task is done the framework terminates all remaining VMs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

from repro.core.dynamic import (BURST_HADS, PolicyConfig, PrimaryPlan,
                                build_primary_map)
from repro.core.fitness import pack_solution
from repro.core.ils import ILSParams
from repro.core.migration import burst_migration
from repro.core.runtime import (Cluster, TaskRun, TaskState, VMRuntime,
                                VMState)
from repro.core.types import CloudConfig, ExecMode, Job, Market
from repro.core.worksteal import burst_work_steal
from .events import Event, EventKind, EventQueue, Scenario, SC_NONE, \
    sample_market_events


@dataclasses.dataclass
class SimResult:
    policy: str
    scenario: str
    cost: float
    makespan: float
    deadline_met: bool
    n_hibernations: int
    n_resumes: int
    n_dynamic_ondemand: int
    counters: dict[str, int]
    unfinished: int
    per_vm_cost: dict[str, float]
    trace: list[str]
    n_terminations: int = 0
    n_completed: int = 0          # DONE tasks; + unfinished == n_tasks


class Simulator:
    """One simulation run of (job, plan, policy, scenario)."""

    def __init__(self, job: Job, plan: PrimaryPlan, cfg: CloudConfig,
                 scenario: "Scenario | object" = SC_NONE, seed: int = 0,
                 ovh: float = 0.10, keep_trace: bool = False):
        # ``scenario`` is a Table V ``Scenario``, a duck-compatible
        # ``market.PoissonProcess`` (k_h/k_r/termination_frac), or a
        # ``market.TraceReplayProcess`` replayed event-for-event (§2.8)
        self.job = job
        self.plan = plan
        self.policy: PolicyConfig = plan.policy
        self.cfg = cfg
        self.deadline = job.deadline_s
        self.scenario = scenario
        self.rng = np.random.default_rng(seed)
        self.now = 0.0
        self.events = EventQueue()
        self.counters: dict[str, int] = {}
        self.keep_trace = keep_trace
        self.trace: list[str] = []

        pool = plan.solution.pool
        ckpt = getattr(plan.policy, "checkpoint", "periodic")
        self.cluster = Cluster(
            cfg=cfg,
            vms={vm.uid: VMRuntime(vm=vm, cfg=cfg) for vm in pool},
            tasks={t.tid: TaskRun(spec=t, ovh=ovh, ckpt=ckpt)
                   for t in job.tasks},
        )
        self._n_hib = 0
        self._n_res = 0
        self._n_term = 0
        self._n_dyn_od = 0
        self._primary_uids = set(plan.solution.selected_uids)
        self._orphans: list[TaskRun] = []   # failed migrations awaiting retry
        self._ac_scheduled: set[tuple[int, int]] = set()
        #: structured execution records: one dict per dispatch, preempt
        #: and complete event (the trace goldens read them)
        self.records: list[dict] = []

    # ------------------------------------------------------------------
    # Engine protocol (used by migration.py / worksteal.py)
    # ------------------------------------------------------------------
    def count(self, key: str) -> None:
        self.counters[key] = self.counters.get(key, 0) + 1

    def _push_ac(self, vmrt: VMRuntime, t: float) -> None:
        key = (vmrt.vm.uid, int(round(t)))
        if key in self._ac_scheduled:
            return
        self._ac_scheduled.add(key)
        self.events.push(t, EventKind.AC_CHECK, uid=vmrt.vm.uid)

    def _migrate(self, affected: list[TaskRun], allow_burstable: bool,
                 count_failures: bool = True) -> None:
        failed = burst_migration(self, affected, self.now,
                                 allow_burstable=allow_burstable)
        for t in failed:
            if count_failures:
                self.count("migration_failures")
                self.log(f"MIGRATION FAILED t{t.spec.tid} (orphaned)")
            self._orphans.append(t)

    def _retry_orphans(self) -> None:
        if not self._orphans:
            return
        pending = [t for t in self._orphans if t.state == TaskState.PENDING
                   and t.vm_uid < 0]
        self._orphans = []
        if pending:
            for _ in pending:
                self.count("orphan_retries")
            self._migrate(pending, self.policy.use_burstables,
                          count_failures=False)

    def log(self, msg: str) -> None:
        if self.keep_trace:
            self.trace.append(f"[{self.now:8.1f}] {msg}")

    def launch_vm(self, vmrt: VMRuntime, now: float) -> None:
        boot = vmrt.launch(now)
        self.events.push(boot, EventKind.BOOT_DONE, uid=vmrt.vm.uid)
        if vmrt.vm.market == Market.ONDEMAND and \
                vmrt.vm.uid not in self._primary_uids:
            self._n_dyn_od += 1
        self.log(f"launch {vmrt.vm.name} (boot at {boot:.0f})")

    def assign(self, vmrt: VMRuntime, task: TaskRun, now: float,
               mode: ExecMode) -> None:
        """Place a task on a VM: dispatch if possible, queue otherwise."""
        task.mode = mode
        task.vm_uid = vmrt.vm.uid
        if task.state == TaskState.RUNNING:
            raise RuntimeError("assign() on a running task")
        if vmrt.state in (VMState.BUSY, VMState.IDLE) and \
                vmrt.can_dispatch(task):
            end = vmrt.dispatch(task, now, mode)
            self.events.push(end, EventKind.TASK_DONE, tid=task.spec.tid,
                             epoch=task.epoch)
            self.records.append({"t": now, "ev": "dispatch",
                                 "tid": task.spec.tid, "vm": vmrt.vm.name,
                                 "mode": mode.value,
                                 "from_base": task.done_base})
            self.log(f"dispatch t{task.spec.tid} -> {vmrt.vm.name} "
                     f"({mode.value}, end {end:.0f})")
        else:
            vmrt.queue.append(task)
            if vmrt.state == VMState.IDLE:
                vmrt.state = VMState.BUSY
            self.log(f"queue t{task.spec.tid} -> {vmrt.vm.name} ({mode.value})")

    # ------------------------------------------------------------------
    def _materialize_primary(self) -> None:
        """Launch the primary map's VMs at t=0 and queue their tasks in
        packed start order."""
        sol = self.plan.solution
        per_vm = pack_solution(sol, self.job.tasks, self.cfg)
        assert per_vm is not None, "primary map must be packable"
        for uid in sorted(sol.selected_uids):
            vmrt = self.cluster.vms[uid]
            self.launch_vm(vmrt, 0.0)
        for uid, vs in per_vm.items():
            vmrt = self.cluster.vms[uid]
            for a in sorted(vs.assignments, key=lambda a: (a.start, a.task.tid)):
                tr = self.cluster.tasks[a.task.tid]
                tr.mode = a.mode
                tr.vm_uid = uid
                vmrt.queue.append(tr)

    def _dispatch_from_queue(self, vmrt: VMRuntime) -> None:
        """Start queued tasks while cores + memory allow."""
        if not vmrt.is_active:
            return
        progressed = True
        while progressed:
            progressed = False
            for task in list(vmrt.queue):
                if task.state != TaskState.PENDING:
                    vmrt.queue.remove(task)
                    continue
                if vmrt.can_dispatch(task):
                    vmrt.queue.remove(task)
                    end = vmrt.dispatch(task, self.now, task.mode)
                    self.events.push(end, EventKind.TASK_DONE,
                                     tid=task.spec.tid, epoch=task.epoch)
                    self.records.append({"t": self.now, "ev": "dispatch",
                                         "tid": task.spec.tid,
                                         "vm": vmrt.vm.name,
                                         "mode": task.mode.value,
                                         "from_base": task.done_base})
                    self.log(f"start t{task.spec.tid} on {vmrt.vm.name} "
                             f"(end {end:.0f})")
                    progressed = True
        if vmrt.running and vmrt.state == VMState.IDLE:
            vmrt.state = VMState.BUSY
        if not vmrt.running and not vmrt.queue and vmrt.state == VMState.BUSY:
            vmrt.state = VMState.IDLE

    # ------------------------------------------------------------------
    # Event handlers
    # ------------------------------------------------------------------
    def _on_boot_done(self, ev: Event) -> None:
        vmrt = self.cluster.vms[ev.payload["uid"]]
        if vmrt.state != VMState.LAUNCHING:
            return
        vmrt.on_boot_done(self.now)
        self._dispatch_from_queue(vmrt)
        self._push_ac(vmrt, vmrt.next_ac_boundary(self.now))
        self._retry_orphans()

    def _on_task_done(self, ev: Event) -> None:
        task = self.cluster.tasks[ev.payload["tid"]]
        if task.epoch != ev.payload["epoch"] or task.state != TaskState.RUNNING:
            return  # stale (task migrated/preempted since dispatch)
        vmrt = self.cluster.vms[task.vm_uid]
        if task.reserved_rcc > 0.0 and vmrt.vm.is_burstable:
            # burst-mode completion releases the credit reservation
            vmrt.accrue(self.now)
            vmrt.reserved_credits = max(0.0, vmrt.reserved_credits -
                                        task.reserved_rcc)
            task.reserved_rcc = 0.0
        vmrt.complete(task, self.now)
        self.records.append({"t": self.now, "ev": "complete",
                             "tid": task.spec.tid, "vm": vmrt.vm.name})
        self.log(f"done t{task.spec.tid} on {vmrt.vm.name}")
        self._dispatch_from_queue(vmrt)
        # §III-D: an idle VM work-steals at the *start of its next AC*
        # (the AC_CHECK handler performs the attempt).

    def _spot_victim(self, ev: Event) -> VMRuntime | None:
        """Victim of a hibernate/terminate event: an explicit ``uid``
        payload (trace replay) targets that VM — skipped if it is not an
        active spot VM right now, exactly like the tensor contract's
        eligibility pass — while anonymous events draw a random active
        spot VM.  Explicit events never consume rng, so Poisson trace
        goldens are untouched."""
        uid = ev.payload.get("uid", -1)
        if uid is not None and uid >= 0:
            vmrt = self.cluster.vms.get(uid)
            if vmrt is None or vmrt.state not in (VMState.BUSY,
                                                  VMState.IDLE) \
                    or not vmrt.vm.is_spot:
                return None
            return vmrt
        candidates = [v for v in self.cluster.by_state(VMState.BUSY,
                                                       VMState.IDLE)
                      if v.vm.is_spot]
        if not candidates:
            return None
        return candidates[int(self.rng.integers(len(candidates)))]

    def _on_hibernate(self, ev: Event) -> None:
        vmrt = self._spot_victim(ev)
        if vmrt is None:
            return
        self._n_hib += 1
        running_tids = [t.spec.tid for t in vmrt.running.values()]
        affected = vmrt.hibernate(self.now,
                                  freeze_in_place=self.policy.freeze_in_place)
        for t in affected:
            if t.spec.tid in running_tids:
                self.records.append({"t": self.now, "ev": "preempt",
                                     "tid": t.spec.tid, "vm": vmrt.vm.name,
                                     "to_base": t.done_base})
        self.log(f"HIBERNATE {vmrt.vm.name} affected={len(affected)} "
                 f"frozen={len(vmrt.frozen)}")
        if self.policy.immediate_migration:
            self._migrate(affected, self.policy.use_burstables)
        elif self.policy.deferred_migration and vmrt.frozen:
            t_safe = self._hads_latest_safe_time(vmrt)
            if t_safe <= self.now:
                self._hads_migrate(vmrt)
            else:
                self.events.push(t_safe, EventKind.DEFERRED_MIGRATION,
                                 uid=vmrt.vm.uid, gen=vmrt.n_hibernations)
                self.log(f"defer migration of {vmrt.vm.name} to {t_safe:.0f}")
        # hibernation="freeze": tasks stay frozen on the column and only
        # ever progress again on resume — the pure-optimist lattice point

    def _on_terminate(self, ev: Event) -> None:
        """Spot termination (§2.8): like hibernation but the state is
        lost — billing stops permanently and unfinished tasks roll back
        to the checkpoint floor and ALWAYS re-enter Alg. 4 migration
        (there is nothing left to freeze in place, whatever the
        hibernation axis says)."""
        vmrt = self._spot_victim(ev)
        if vmrt is None:
            return
        self._n_term += 1
        running_tids = [t.spec.tid for t in vmrt.running.values()]
        affected = vmrt.fail(self.now)
        for t in affected:
            if t.spec.tid in running_tids:
                self.records.append({"t": self.now, "ev": "preempt",
                                     "tid": t.spec.tid, "vm": vmrt.vm.name,
                                     "to_base": t.done_base})
        self.log(f"TERMINATE {vmrt.vm.name} affected={len(affected)} "
                 "(state lost)")
        self._migrate(affected, self.policy.use_burstables)

    def _hads_latest_safe_time(self, vmrt: VMRuntime) -> float:
        """Latest instant at which migrating the frozen bag still meets D.

        Conservative wave estimate: the bag runs on the free on-demand cores
        (unlaunched pool + currently idle VMs) in ceil(n/cores) waves of the
        longest remaining task."""
        if not vmrt.frozen:
            return self.now
        # Conservative: migration targets may be as slow as the slowest
        # on-demand type (Alg. 4 launches cheapest-first), and every frozen
        # bag cluster-wide competes for the same free on-demand cores.
        fallback = min(self.cfg.ondemand_types, key=lambda vt: vt.gflops)
        speed = fallback.gflops / self.cfg.gflops_ref
        all_frozen = [t for v in self.cluster.hibernated for t in v.frozen] \
            or vmrt.frozen
        worst = max(t.remaining_base() / speed for t in vmrt.frozen)
        free_cores = sum(v.vm.vcpus
                         for v in self.cluster.unlaunched(Market.ONDEMAND))
        free_cores += sum(len(v.free_cores()) for v in self.cluster.idle)
        waves = math.ceil(len(all_frozen) / max(1, free_cores))
        margin = 30.0
        return self.deadline - (self.cfg.boot_overhead_s + waves * worst +
                                self.cfg.checkpoint_restore_s + margin)

    def _hads_migrate(self, vmrt: VMRuntime) -> None:
        self._migrate(vmrt.take_frozen(), allow_burstable=False)

    def _on_deferred_migration(self, ev: Event) -> None:
        vmrt = self.cluster.vms[ev.payload["uid"]]
        if vmrt.state != VMState.HIBERNATED or \
                vmrt.n_hibernations != ev.payload["gen"] or not vmrt.frozen:
            return  # resumed (or re-hibernated) since scheduling
        self.log(f"deferred migration fires for {vmrt.vm.name}")
        self._hads_migrate(vmrt)

    def _on_resume(self, ev: Event) -> None:
        uid = ev.payload.get("uid", -1)
        if uid is not None and uid >= 0:
            vmrt = self.cluster.vms.get(uid)
            if vmrt is None or vmrt.state != VMState.HIBERNATED:
                return   # skipped, like the tensor eligibility pass
        else:
            if not self.cluster.hibernated:
                return
            hib = sorted(self.cluster.hibernated, key=lambda v: v.vm.uid)
            vmrt = hib[int(self.rng.integers(len(hib)))]
        self._n_res += 1
        vmrt.resume(self.now)
        self.log(f"RESUME {vmrt.vm.name}")
        self._push_ac(vmrt, vmrt.next_ac_boundary(self.now))
        self._retry_orphans()
        if vmrt.frozen:  # HADS: frozen tasks continue where they stopped
            for t in vmrt.take_frozen_in_place():
                self.assign(vmrt, t, self.now, t.mode)
            self._dispatch_from_queue(vmrt)
        if self.policy.work_stealing:
            burst_work_steal(self, vmrt, self.now)

    def _on_ac_check(self, ev: Event) -> None:
        vmrt = self.cluster.vms[ev.payload["uid"]]
        if vmrt.state in (VMState.TERMINATED, VMState.NOT_LAUNCHED):
            return
        if vmrt.state == VMState.IDLE:
            stolen = 0
            if self.policy.work_stealing:
                stolen = burst_work_steal(self, vmrt, self.now)
            if stolen == 0 and not vmrt.vm.is_burstable:
                vmrt.terminate(self.now)
                self.log(f"terminate idle {vmrt.vm.name} at AC end")
                return
        if vmrt.state != VMState.HIBERNATED:
            self._push_ac(vmrt, vmrt.next_ac_boundary(self.now))
        self._retry_orphans()

    # ------------------------------------------------------------------
    def _push_market_events(self) -> None:
        """Queue this run's market events: a ``TraceReplayProcess``
        replays its (time, kind, vm) records — explicit columns mapped to
        VM uids via ``plan_column_uids``, the shared column order of the
        MC engine (the S=1 parity bridge, §2.8) — while Table V scenarios
        (or duck-compatible ``PoissonProcess`` instances, whose
        ``termination_frac`` is forwarded) sample the Poisson lists."""
        from .market import TraceReplayProcess
        if isinstance(self.scenario, TraceReplayProcess):
            from .mc_engine import plan_column_uids
            uids = plan_column_uids(self.plan)
            kind_of = {"hibernate": EventKind.HIBERNATE,
                       "resume": EventKind.RESUME,
                       "terminate": EventKind.TERMINATE}
            frac = float(getattr(self.scenario, "termination_frac", 0.0))
            for t, kind, vm in zip(self.scenario.times,
                                   self.scenario.kinds, self.scenario.vms):
                if not 0.0 <= t < self.deadline:
                    continue   # the tensor sampler's event window
                k = kind_of[kind]
                if k == EventKind.HIBERNATE and frac > 0.0 and \
                        self.rng.random() < frac:
                    k = EventKind.TERMINATE
                self.events.push(t, k, uid=(uids[vm] if vm >= 0 else -1))
            return
        frac = float(getattr(self.scenario, "termination_frac", 0.0))
        for t, kind in sample_market_events(self.scenario, self.deadline,
                                            self.rng,
                                            termination_frac=frac):
            self.events.push(t, kind)

    def run(self) -> SimResult:
        self._materialize_primary()
        horizon = self.deadline * 3.0
        self._push_market_events()

        handlers = {
            EventKind.BOOT_DONE: self._on_boot_done,
            EventKind.TASK_DONE: self._on_task_done,
            EventKind.HIBERNATE: self._on_hibernate,
            EventKind.RESUME: self._on_resume,
            EventKind.TERMINATE: self._on_terminate,
            EventKind.AC_CHECK: self._on_ac_check,
            EventKind.DEFERRED_MIGRATION: self._on_deferred_migration,
        }
        while self.events and self.cluster.unfinished():
            ev = self.events.pop()
            if ev.time > horizon:
                break
            self.now = max(self.now, ev.time)
            handlers[ev.kind](ev)

        unfinished = self.cluster.unfinished()
        makespan = max((t.finished_at for t in self.cluster.tasks.values()
                        if t.state == TaskState.DONE), default=0.0)
        end = makespan if not unfinished else self.now
        for v in self.cluster.vms.values():
            if v.state in (VMState.BUSY, VMState.IDLE, VMState.LAUNCHING):
                v.terminate(max(end, v.launched_at))
            elif v.state == VMState.HIBERNATED:
                v.accrue(end)
        cost = sum(v.cost for v in self.cluster.vms.values())
        return SimResult(
            policy=self.policy.name, scenario=self.scenario.name,
            cost=cost, makespan=makespan,
            deadline_met=(not unfinished) and makespan <= self.deadline + 1e-6,
            n_hibernations=self._n_hib, n_resumes=self._n_res,
            n_dynamic_ondemand=self._n_dyn_od, counters=dict(self.counters),
            n_terminations=self._n_term,
            unfinished=len(unfinished),
            n_completed=sum(1 for t in self.cluster.tasks.values()
                            if t.state == TaskState.DONE),
            per_vm_cost={v.vm.name: v.cost for v in self.cluster.vms.values()
                         if v.cost > 0},
            trace=self.trace)


def simulate(job: Job, cfg: CloudConfig, policy: PolicyConfig = BURST_HADS,
             scenario: Scenario = SC_NONE, seed: int = 0,
             params: ILSParams | None = None,
             keep_trace: bool = False) -> SimResult:
    """Deprecated shim — plan (Algorithm 1) + simulate one DES trace.

    Use ``repro.api.run(job=..., policy=..., process=...,
    backend="des")`` instead; this wrapper delegates there (sharing the
    facade's cross-backend plan cache) and returns the raw ``SimResult``.
    """
    from repro.api import run as _api_run
    from repro.compat import warn_deprecated
    warn_deprecated("sim.simulator.simulate", "repro.api.run")
    return _api_run(job=job, policy=policy, process=scenario,
                    backend="des", cfg=cfg, seed=seed, ils=params,
                    keep_trace=keep_trace).raw
