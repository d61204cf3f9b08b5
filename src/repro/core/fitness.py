"""Exact fitness evaluation (Eq. 1/8) with time-indexed core+memory packing.

Two evaluators exist by design (DESIGN.md §2.1):

* here: ``evaluate`` — the exact packer.  Deterministic LPT order per VM,
  per-core free lists, and a timeline memory check equivalent to the paper's
  Eq. 2/3 constraints.  Used by ``check_schedule`` (which the greedy
  constructor calls only where a VM's summed task memory could exceed its
  capacity; it answers the other checks from its kept LPT packing, see
  ``core/greedy``), by the simulator to materialise the primary map, and to
  re-validate every incumbent the ILS accepts.
* ``repro.core.ils_jax.fitness_fast`` — the vectorised bound used inside the
  batched search (backed by the ``sched_fitness`` Pallas kernel).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

from .types import (Assignment, CloudConfig, ExecMode, Solution, TaskSpec,
                    VMInstance)

INFEASIBLE = float("inf")


@dataclasses.dataclass
class VMSchedule:
    """Packed schedule of one VM: per-task (start, end) plus core layout."""

    vm: VMInstance
    assignments: list[Assignment]
    end_time: float          # Z_j — last period of execution (0 if empty)
    billed_s: float          # end_time - boot overhead (never negative)
    cost: float


@dataclasses.dataclass
class FitnessResult:
    feasible: bool
    cost: float
    makespan: float
    fitness: float
    per_vm: dict[int, VMSchedule]
    violation: str = ""


def _pack_vm(vm: VMInstance, tasks: list[TaskSpec], modes: list[ExecMode],
             cfg: CloudConfig, release_s: float = 0.0) -> list[Assignment] | None:
    """Pack tasks onto the VM's cores, exactly honouring Eq. 2 (memory) and
    Eq. 3 (cores).  Returns assignments or None if memory can never fit.

    Deterministic LPT order (longest execution first) over per-core free
    times; if placing a task would exceed the memory capacity at any instant
    of its run, its start is delayed to the next task-completion event.
    ``release_s`` is the earliest possible start (VM boot completion ω, or
    'now' for dynamic re-packing).
    """
    order = sorted(range(len(tasks)),
                   key=lambda i: (-tasks[i].exec_time(vm.vm_type, cfg.gflops_ref),
                                  tasks[i].tid))
    core_free = [release_s] * vm.vcpus
    placed: list[Assignment] = []

    for i in order:
        t, mode = tasks[i], modes[i]
        if t.memory_mb > vm.memory_mb:
            return None
        e = t.exec_time(vm.vm_type, cfg.gflops_ref, mode)
        # earliest-free core
        k = min(range(len(core_free)), key=core_free.__getitem__)
        start = core_free[k]
        # delay start until the memory constraint holds over [start, start+e)
        while True:
            overlap = [a for a in placed if a.start < start + e and a.end > start]
            mem = t.memory_mb + sum(a.task.memory_mb for a in overlap)
            if mem <= vm.memory_mb:
                break
            nxt = min((a.end for a in overlap if a.end > start), default=None)
            if nxt is None:  # cannot happen: overlap non-empty when mem exceeds
                return None
            start = nxt
        a = Assignment(task=t, vm_uid=vm.uid, mode=mode,
                       start=start, end=start + e)
        placed.append(a)
        core_free[k] = a.end
    return placed


def pack_solution(sol: Solution, tasks: Sequence[TaskSpec], cfg: CloudConfig,
                  ) -> dict[int, VMSchedule] | None:
    """Materialise per-VM schedules for a full solution.  None if impossible."""
    per_vm: dict[int, VMSchedule] = {}
    boot = cfg.boot_overhead_s
    for uid in sol.used_uids():
        vm = sol.pool[uid]
        idx = sol.tasks_on(uid)
        ts = [tasks[i] for i in idx]
        ms = [ExecMode.BASELINE if sol.modes[i] else ExecMode.FULL for i in idx]
        packed = _pack_vm(vm, ts, ms, cfg, release_s=boot)
        if packed is None:
            return None
        end = max((a.end for a in packed), default=0.0)
        billed = max(0.0, end - boot)
        per_vm[uid] = VMSchedule(vm=vm, assignments=packed, end_time=end,
                                 billed_s=billed,
                                 cost=billed * vm.price_per_sec)
    return per_vm


def cost_scale(tasks: Sequence[TaskSpec], cfg: CloudConfig) -> float:
    """Normalisation constant for the monetary-cost objective term.

    Total work priced at the most expensive on-demand core-second — an
    instance-independent scale so that Eq. 8's weighted sum is dimensionless.
    """
    worst = max((t.price_ondemand / 3600.0 / t.vcpus)
                for t in cfg.ondemand_types + cfg.spot_types)
    total_base = sum(t.base_time for t in tasks)
    return max(worst * total_base, 1e-12)


def evaluate(sol: Solution, tasks: Sequence[TaskSpec], cfg: CloudConfig,
             dspot: float, deadline: float, alpha: float = 0.5,
             _scale: float | None = None) -> FitnessResult:
    """fitness(S, D_spot) — Eq. 8 with exact packing.

    * spot VMs must finish by ``dspot`` (Eq. 5),
    * every VM must finish by ``deadline``,
    * unassigned tasks or impossible packings are infeasible (Eq. 4).
    """
    if np.any(sol.alloc < 0):
        return FitnessResult(False, INFEASIBLE, INFEASIBLE, INFEASIBLE, {},
                             "unassigned tasks")
    per_vm = pack_solution(sol, tasks, cfg)
    if per_vm is None:
        return FitnessResult(False, INFEASIBLE, INFEASIBLE, INFEASIBLE, {},
                             "memory capacity exceeded")
    violation = ""
    for uid, vs in per_vm.items():
        if vs.vm.is_spot and vs.end_time > dspot + 1e-9:
            violation = f"{vs.vm.name} exceeds D_spot ({vs.end_time:.0f}s > {dspot:.0f}s)"
            break
        if vs.end_time > deadline + 1e-9:
            violation = f"{vs.vm.name} exceeds deadline ({vs.end_time:.0f}s)"
            break
    cost = sum(vs.cost for vs in per_vm.values())
    makespan = max((vs.end_time for vs in per_vm.values()), default=0.0)
    if violation:
        return FitnessResult(False, cost, makespan, INFEASIBLE, per_vm, violation)
    scale = _scale if _scale is not None else cost_scale(tasks, cfg)
    fit = alpha * (cost / scale) + (1.0 - alpha) * (makespan / deadline)
    return FitnessResult(True, cost, makespan, fit, per_vm)


def check_schedule(task: TaskSpec, vm: VMInstance, current: list[TaskSpec],
                   current_modes: list[ExecMode], cfg: CloudConfig,
                   limit_s: float, mode: ExecMode = ExecMode.FULL) -> bool:
    """The paper's ``check_schedule``: does adding ``task`` to ``vm`` keep the
    VM's completion within ``limit_s`` (D_spot for spots, D otherwise) while
    satisfying memory/cores?"""
    packed = _pack_vm(vm, current + [task], current_modes + [mode], cfg,
                      release_s=cfg.boot_overhead_s)
    if packed is None:
        return False
    return max(a.end for a in packed) <= limit_s + 1e-9


def spot_spare_time_ok(vm: VMInstance, tasks_on_vm: list[TaskSpec],
                       end_time: float, deadline: float,
                       cfg: CloudConfig) -> bool:
    """Dynamic-module guard (§III-E): a spot VM receiving a migrated task must
    keep spare time ≥ its longest task's execution time before the deadline,
    so a *further* hibernation can still be absorbed."""
    if not tasks_on_vm:
        return True
    longest = max(t.exec_time(vm.vm_type, cfg.gflops_ref) for t in tasks_on_vm)
    return (deadline - end_time) >= longest - 1e-9
