"""The exact packer (Eq. 2/3): per VM, deterministic LPT order over
per-core free lists with a timeline memory check.  The greedy seed's
``check_schedule`` and the burstable allocation read it; the batched ILS's
bound is ``plans.Problem``.  ``cost_scale`` normalises Eq. 8's cost term.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from .types import (Assignment, CloudConfig, ExecMode, Solution, TaskSpec,
                    VMInstance)


@dataclasses.dataclass
class VMSchedule:
    """Packed schedule of one VM: per-task (start, end) plus core layout."""

    vm: VMInstance
    assignments: list[Assignment]
    end_time: float          # Z_j — last period of execution (0 if empty)
    billed_s: float          # end_time - boot overhead (never negative)
    cost: float


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
