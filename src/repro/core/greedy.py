"""Algorithm 2 — greedy initial solution with WRR spot selection (Eq. 7)."""
from __future__ import annotations

from typing import Sequence

import numpy as np

from ..obs import span
from .fitness import check_schedule
from .types import (CloudConfig, ExecMode, Market, Solution, TaskSpec,
                    VMInstance, empty_solution)


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


def initial_solution(tasks: Sequence[TaskSpec], pool: list[VMInstance],
                     cfg: CloudConfig, dspot: float,
                     market: Market = Market.SPOT) -> Solution:
    """Greedy constructor: tasks by memory (desc); phase 1 tries already
    selected VMs (price asc); phase 2 opens a new VM chosen by WRR.

    ``market`` selects the candidate set: M^s (paper default) or M^o for the
    ILS-on-demand baseline of §IV."""
    with span("greedy.seed", n_tasks=len(tasks)):
        sol = empty_solution(len(tasks), pool)
        market_uids = [vm.uid for vm in pool if vm.market == market]
        free_by_type: dict[str, list[int]] = {}
        for uid in market_uids:
            free_by_type.setdefault(pool[uid].vm_type.name, []).append(uid)

        types = cfg.spot_types if market == Market.SPOT else cfg.ondemand_types
        wrr = SmoothWRR([t.name for t in types],
                        [t.weight(market) for t in types])

        selected: list[int] = []          # uids, kept price-sorted on access
        on_vm: dict[int, list[int]] = {}  # uid -> task indices

        def _modes(uid: int) -> list[ExecMode]:
            return [ExecMode.FULL] * len(on_vm.get(uid, []))

        order = sorted(range(len(tasks)),
                       key=lambda i: (-tasks[i].memory_mb, tasks[i].tid))
        for i in order:
            t = tasks[i]
            placed = False
            # Phase 1: already-selected VMs, cheapest first.
            for uid in sorted(selected, key=lambda u: pool[u].price_per_sec):
                cur = [tasks[k] for k in on_vm.get(uid, [])]
                if check_schedule(t, pool[uid], cur, _modes(uid), cfg, dspot):
                    sol.alloc[i] = uid
                    on_vm.setdefault(uid, []).append(i)
                    placed = True
                    break
            if placed:
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
                if check_schedule(t, pool[uid], [], [], cfg, dspot):
                    sol.alloc[i] = uid
                    on_vm[uid] = [i]
                    selected.append(uid)
                    placed = True
                    break
                # Empty VM of this type cannot host the task: exclude the type
                # for this task (put the instance back for later tasks).
                free_by_type[tname].insert(0, uid)
                excluded.add(tname)

        sol.selected_uids = set(selected)
        return sol
