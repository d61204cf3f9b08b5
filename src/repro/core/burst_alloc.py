"""Algorithm 1 (part 2) — burstable-instance allocation.

After the ILS, ``n = ceil(burst_rate * |selected VMs|)`` burstable VMs join
the map:

* every task violating the original D_spot (a by-product of the relaxing
  perturbation) moves to a burstable VM — at most one task per burstable,
  executed in *baseline* mode (credits keep accruing, making these VMs the
  best migration targets on hibernation);
* leftover violations go to the cheapest regular on-demand VMs;
* an idle burstable takes the latest-finishing task of the map (baseline
  mode) when that actually improves the task's completion — keeping the
  paper's makespan intent without letting a 5x baseline slowdown blow D.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

from ..obs import span
from .fitness import pack_solution
from .types import (CloudConfig, ExecMode, Market, Solution, TaskSpec,
                    VMInstance)


@dataclasses.dataclass
class BurstAllocation:
    solution: Solution
    burstable_uids: list[int]     # launched burstables (busy or idle)
    moved_to_burstable: list[int]
    moved_to_ondemand: list[int]


def _baseline_end(task: TaskSpec, vm: VMInstance, cfg: CloudConfig) -> float:
    return cfg.boot_overhead_s + task.exec_time(vm.vm_type, cfg.gflops_ref,
                                                ExecMode.BASELINE)


def burst_allocation(sol: Solution, tasks: Sequence[TaskSpec],
                     cfg: CloudConfig, dspot: float, deadline: float,
                     burst_rate: float) -> BurstAllocation:
    with span("burst.alloc", n_tasks=len(tasks)):
        sol = sol.copy()
        pool = sol.pool
        n_burst = math.ceil(burst_rate * max(1, len(sol.selected_uids)))
        free_burst = [vm.uid for vm in pool if vm.market == Market.BURSTABLE]
        free_burst = free_burst[:n_burst]
        free_od = sorted((vm.uid for vm in pool
                          if vm.market == Market.ONDEMAND
                          and vm.uid not in sol.selected_uids),
                         key=lambda u: pool[u].price_per_sec)

        per_vm = pack_solution(sol, tasks, cfg)
        assert per_vm is not None, "ILS returned a memory-infeasible map"

        # Tasks whose completion violates the original D_spot, latest first.
        violating: list[tuple[float, int]] = []
        ends: dict[int, float] = {}
        for uid, vs in per_vm.items():
            for a in vs.assignments:
                ti = a.task.tid
                ends[ti] = a.end
                if pool[uid].is_spot and a.end > dspot + 1e-9:
                    violating.append((a.end, ti))
        violating.sort(reverse=True)

        moved_b: list[int] = []
        moved_o: list[int] = []
        busy_burst: set[int] = set()

        for _, ti in violating:
            placed = False
            for uid in free_burst:
                if uid in busy_burst:
                    continue
                if _baseline_end(tasks[ti], pool[uid], cfg) <= deadline + 1e-9:
                    sol.alloc[ti] = uid
                    sol.modes[ti] = 1  # BASELINE
                    busy_burst.add(uid)
                    moved_b.append(ti)
                    placed = True
                    break
            if placed:
                continue
            for uid in list(free_od):
                e = tasks[ti].exec_time(pool[uid].vm_type, cfg.gflops_ref)
                if cfg.boot_overhead_s + e <= deadline + 1e-9:
                    sol.alloc[ti] = uid
                    sol.modes[ti] = 0
                    sol.selected_uids.add(uid)
                    free_od.remove(uid)
                    moved_o.append(ti)
                    placed = True
                    break
            if not placed:
                raise RuntimeError(
                    f"burst_allocation: task {ti} violates D_spot and fits no "
                    f"burstable/on-demand VM before the deadline")

        # Idle burstables pull the latest-finishing task (makespan reduction).
        idle = [u for u in free_burst if u not in busy_burst]
        if idle:
            per_vm = pack_solution(sol, tasks, cfg)
            assert per_vm is not None
            latest: list[tuple[float, int]] = []
            for uid, vs in per_vm.items():
                if pool[uid].market == Market.BURSTABLE:
                    continue
                for a in vs.assignments:
                    latest.append((a.end, a.task.tid))
            latest.sort(reverse=True)
            li = 0
            for uid in idle:
                while li < len(latest):
                    end, ti = latest[li]
                    li += 1
                    new_end = _baseline_end(tasks[ti], pool[uid], cfg)
                    if new_end < end and new_end <= deadline + 1e-9:
                        sol.alloc[ti] = uid
                        sol.modes[ti] = 1
                        busy_burst.add(uid)
                        break

        # all n are launched (credit accrual)
        sol.selected_uids |= set(free_burst)
        return BurstAllocation(solution=sol, burstable_uids=list(free_burst),
                               moved_to_burstable=moved_b,
                               moved_to_ondemand=moved_o)
