"""Algorithm 1 (part 2): burstable allocation after the ILS.

A copy of the program's ``repro.core.burst_alloc`` at the commit that
added the benchmark, on this package's types and packer.
``n = ceil(burst_rate * |selected VMs|)`` burstable VMs join the map:

* every task that ends after D_spot on a spot VM, latest first, moves to
  a free burstable VM (one task each, baseline mode) where its baseline
  run ends by the deadline, else to the cheapest unselected on-demand VM
  that ends it by the deadline;
* each still idle burstable takes the latest-finishing task of the map
  (baseline mode) where that ends it earlier and by the deadline;
* all n burstables are selected.
"""
from __future__ import annotations

import math

from .fitness import pack_solution
from .types import CloudConfig, ExecMode, Market, Solution


def _baseline_end(task, vm, cfg: CloudConfig) -> float:
    return cfg.boot_overhead_s + task.exec_time(vm.vm_type, cfg.gflops_ref,
                                                ExecMode.BASELINE)


def burst_allocation(sol: Solution, tasks, cfg: CloudConfig, dspot: float,
                     deadline: float, burst_rate: float) -> Solution:
    sol = sol.copy()
    pool = sol.pool
    n_burst = math.ceil(burst_rate * max(1, len(sol.selected_uids)))
    free_burst = [vm.uid for vm in pool
                  if vm.market == Market.BURSTABLE][:n_burst]
    free_od = sorted((vm.uid for vm in pool
                      if vm.market == Market.ONDEMAND
                      and vm.uid not in sol.selected_uids),
                     key=lambda u: pool[u].price_per_sec)

    per_vm = pack_solution(sol, tasks, cfg)
    if per_vm is None:
        raise ValueError("the ILS winner is memory-infeasible")
    violating = sorted(((a.end, a.task.tid) for uid, vs in per_vm.items()
                        for a in vs.assignments
                        if pool[uid].is_spot and a.end > dspot + 1e-9),
                       reverse=True)

    busy_burst: set[int] = set()
    for _, ti in violating:
        uid = next((u for u in free_burst if u not in busy_burst and
                    _baseline_end(tasks[ti], pool[u], cfg)
                    <= deadline + 1e-9), None)
        if uid is not None:
            sol.alloc[ti], sol.modes[ti] = uid, 1
            busy_burst.add(uid)
            continue
        uid = next((u for u in free_od if cfg.boot_overhead_s +
                    tasks[ti].exec_time(pool[u].vm_type, cfg.gflops_ref)
                    <= deadline + 1e-9), None)
        if uid is None:
            raise ValueError(f"task {ti} ends after D_spot and fits no "
                             "burstable or on-demand VM by the deadline")
        sol.alloc[ti], sol.modes[ti] = uid, 0
        sol.selected_uids.add(uid)
        free_od.remove(uid)

    idle = [u for u in free_burst if u not in busy_burst]
    if idle:
        per_vm = pack_solution(sol, tasks, cfg)
        latest = sorted(((a.end, a.task.tid) for uid, vs in per_vm.items()
                         if pool[uid].market != Market.BURSTABLE
                         for a in vs.assignments), reverse=True)
        li = 0
        for uid in idle:
            while li < len(latest):
                end, ti = latest[li]
                li += 1
                new_end = _baseline_end(tasks[ti], pool[uid], cfg)
                if new_end < end and new_end <= deadline + 1e-9:
                    sol.alloc[ti], sol.modes[ti] = uid, 1
                    break

    sol.selected_uids |= set(free_burst)
    return sol
