"""Algorithm 2 — greedy initial solution with WRR spot selection (Eq. 7).

The feasibility test of a candidate placement is the paper's
``check_schedule``: the VM's tasks plus the candidate packed in LPT order
(``fitness._pack_vm``), every end within D_spot.  ``initial_solution``
answers it from each selected VM's LPT packing, kept for the one call:
LPT places a prefix of its order without looking at later tasks, so only
the candidate and the tasks after its LPT position are placed again, from
the per-core free times saved after that prefix, with ``_pack_vm``'s own
core choice and additions.  While the VM's summed task memory, the
candidate's included, stays within the VM's capacity no overlap set can
exceed it, so ``_pack_vm``'s memory delay never moves a start and the
answer is ``check_schedule``'s bit for bit; otherwise the check is
``check_schedule`` itself.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Sequence

import numpy as np

from ..obs import span
from .fitness import check_schedule
from .types import (CloudConfig, ExecMode, Market, Solution, TaskSpec,
                    VMInstance, empty_solution)

#: relative margin under a VM's memory for answering from the kept packing:
#: far above the rounding of any float sum of the bag's task memories, so
#: that every overlap sum ``_pack_vm`` forms stays within the capacity
_MEM_MARGIN = 1.0 - 1e-9


class SmoothWRR:
    """Smooth weighted round-robin over spot VM *types* (weight = Gflops/c_j).

    Matches the paper's WRR [13] usage: heterogeneous spot types are selected
    in proportion to their cost-efficiency, which also hedges hibernation risk
    across types (Kumar et al. [15]).
    """

    def __init__(self, names: Sequence[str], weights: Sequence[float]):
        self.names = list(names)
        self.weights = np.asarray(weights, dtype=np.float64)
        self.current = np.zeros(len(self.names), dtype=np.float64)

    def next(self, available: set[str]) -> str | None:
        if not available:
            return None
        self.current += self.weights
        order = np.argsort(-self.current, kind="stable")
        for k in order:
            if self.names[k] in available:
                self.current[k] -= self.weights.sum()
                return self.names[k]
        return None


@dataclasses.dataclass
class GreedyCounters:
    """What one ``initial_solution`` call did (``Solution.greedy_counters``).

    ``checks`` = ``kept`` + ``fallbacks``: the feasibility checks made, those
    answered from a VM's kept packing and those answered by
    ``check_schedule``; ``replayed`` counts the tasks placed while answering
    from the kept packings, the candidates included."""

    checks: int = 0
    kept: int = 0
    fallbacks: int = 0
    replayed: int = 0


class _KeptPacking:
    """The LPT packing of one VM's tasks, all in ``ExecMode.FULL``.

    ``keys`` are the LPT order keys ``(-exec_time, tid)``, ``times`` the
    execution times in that order, ``free[q]`` the per-core free times after
    the first ``q`` tasks, ``mem`` the summed task memory and ``tasks`` the
    task indices in the order they joined (``check_schedule``'s input)."""

    __slots__ = ("vm", "exec_s", "keys", "times", "free", "mem", "tasks")

    def __init__(self, vm: VMInstance, exec_s: list[float], release_s: float):
        self.vm = vm
        self.exec_s = exec_s    # every task's execution time on this VM
        self.keys: list[tuple[float, int]] = []
        self.times: list[float] = []
        self.free: list[list[float]] = [[release_s] * vm.vcpus]
        self.mem = 0.0
        self.tasks: list[int] = []

    def fits(self, task: TaskSpec, i: int, limit: float) -> tuple[bool, int]:
        """Whether every end stays within ``limit`` once task ``i`` joins,
        and how many tasks were placed to tell."""
        e = self.exec_s[i]
        p = bisect.bisect_right(self.keys, (-e, task.tid))
        free = self.free[p][:]
        for n, d in enumerate([e, *self.times[p:]], 1):
            k = free.index(min(free))  # earliest-free core, lowest index
            free[k] = free[k] + d
            if free[k] > limit:
                return False, n
        return True, n

    def add(self, task: TaskSpec, i: int) -> None:
        e = self.exec_s[i]
        key = (-e, task.tid)
        p = bisect.bisect_right(self.keys, key)
        self.keys.insert(p, key)
        self.times.insert(p, e)
        del self.free[p + 1:]
        free = self.free[p]
        for d in self.times[p:]:
            free = free[:]
            k = free.index(min(free))
            free[k] = free[k] + d
            self.free.append(free)
        self.mem = self.mem + task.memory_mb
        self.tasks.append(i)


def initial_solution(tasks: Sequence[TaskSpec], pool: list[VMInstance],
                     cfg: CloudConfig, dspot: float,
                     market: Market = Market.SPOT) -> Solution:
    """Greedy constructor: tasks by memory (desc); phase 1 tries already
    selected VMs (price asc); phase 2 opens a new VM chosen by WRR.

    ``market`` selects the candidate set: M^s (paper default) or M^o for the
    ILS-on-demand baseline of §IV.  The solution carries the call's
    ``GreedyCounters``."""
    with span("greedy.seed", n_tasks=len(tasks)):
        sol = empty_solution(len(tasks), pool)
        market_uids = [vm.uid for vm in pool if vm.market == market]
        free_by_type: dict[str, list[int]] = {}
        for uid in market_uids:
            free_by_type.setdefault(pool[uid].vm_type.name, []).append(uid)

        types = cfg.spot_types if market == Market.SPOT else cfg.ondemand_types
        wrr = SmoothWRR([t.name for t in types],
                        [t.weight(market) for t in types])

        counters = GreedyCounters()
        limit = dspot + 1e-9            # check_schedule's test of each end
        exec_s: dict[str, list[float]] = {}  # VM type name -> per task

        def packing(uid: int) -> _KeptPacking:
            vt = pool[uid].vm_type
            if vt.name not in exec_s:
                exec_s[vt.name] = [u.exec_time(vt, cfg.gflops_ref)
                                   for u in tasks]
            return _KeptPacking(pool[uid], exec_s[vt.name],
                                cfg.boot_overhead_s)

        def fits(i: int, st: _KeptPacking) -> bool:
            t, vm = tasks[i], st.vm
            counters.checks += 1
            if st.mem + t.memory_mb <= vm.memory_mb * _MEM_MARGIN:
                ok, n = st.fits(t, i, limit)
                counters.kept += 1
                counters.replayed += n
                return ok
            counters.fallbacks += 1
            cur = [tasks[k] for k in st.tasks]
            return check_schedule(t, vm, cur, [ExecMode.FULL] * len(cur),
                                  cfg, dspot)

        def place(i: int, st: _KeptPacking) -> None:
            st.add(tasks[i], i)
            sol.alloc[i] = st.vm.uid

        selected: list[_KeptPacking] = []
        order = sorted(range(len(tasks)),
                       key=lambda i: (-tasks[i].memory_mb, tasks[i].tid))
        for i in order:
            t = tasks[i]
            # Phase 1: already-selected VMs, cheapest first.
            st = next((st for st in sorted(
                selected, key=lambda s: s.vm.price_per_sec)
                if fits(i, st)), None)
            if st is not None:
                place(i, st)
                continue
            # Phase 2: open a new spot VM via WRR.
            # types that cannot host this task at all
            excluded: set[str] = set()
            while True:
                avail = {n for n, lst in free_by_type.items()
                         if lst and n not in excluded}
                tname = wrr.next(avail)
                if tname is None:
                    raise RuntimeError(
                        f"greedy: task {t.tid} cannot be scheduled within "
                        f"D_spot={dspot:.0f}s — deadline too tight for the "
                        "pool")
                uid = free_by_type[tname].pop(0)
                st = packing(uid)
                if fits(i, st):
                    place(i, st)
                    selected.append(st)
                    break
                # Empty VM of this type cannot host the task: exclude the type
                # for this task (put the instance back for later tasks).
                free_by_type[tname].insert(0, uid)
                excluded.add(tname)

        sol.selected_uids = {st.vm.uid for st in selected}
        sol.greedy_counters = counters
        return sol
