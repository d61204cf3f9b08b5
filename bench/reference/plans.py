"""Numpy evaluation of batched-ILS plans.

The batched ILS scores an allocation by Eq. 8 with the LPT makespan bound
(the program's ``population_fitness_ref``): per VM the summed execution
time over its cores, or its longest task if that is longer, plus the boot
overhead; infeasible where memory or the VM's finish limit (D_spot for spot
VMs, D otherwise) is exceeded.  ``Problem`` computes it in float64, or in
a lower precision operation by operation for the control.
"""
from __future__ import annotations

import numpy as np

from .dspot import compute_dspot
from .fitness import cost_scale
from .greedy import initial_solution
from .types import CloudConfig, Market, Solution, TaskSpec


def tasks_of(memory_mb, base_time_s) -> tuple[TaskSpec, ...]:
    return tuple(TaskSpec(tid=i, memory_mb=float(m), base_time=float(b))
                 for i, (m, b) in enumerate(zip(memory_mb, base_time_s)))


class Problem:
    """One bag on one pool: the arrays Eq. 8 reads."""

    def __init__(self, tasks, pool, cfg: CloudConfig, deadline_s: float,
                 alpha: float):
        self.tasks, self.pool, self.cfg = tasks, pool, cfg
        self.deadline, self.alpha = float(deadline_s), float(alpha)
        self.dspot = compute_dspot(deadline_s, tasks, cfg)
        self.scale = cost_scale(tasks, cfg)
        self.boot = float(cfg.boot_overhead_s)
        self.e = np.array([[t.exec_time(vm.vm_type, cfg.gflops_ref)
                            for vm in pool] for t in tasks], np.float64)
        self.rm = np.array([t.memory_mb for t in tasks], np.float64)
        self.cores = np.array([vm.vcpus for vm in pool], np.float64)
        self.mem = np.array([vm.memory_mb for vm in pool], np.float64)
        self.price = np.array([vm.price_per_sec for vm in pool], np.float64)
        self.limit = np.array([self.dspot if vm.is_spot else self.deadline
                               for vm in pool], np.float64)

    def fitness(self, alloc, dtype=np.float64) -> float:
        """Eq. 8 with the LPT bound for one allocation vector.  In
        float64 it is ``fitness_batch``'s; in a lower ``dtype`` every
        operation rounds to it, accumulating task by task."""
        alloc = np.asarray(alloc, np.int64)
        if dtype == np.float64:
            return float(self.fitness_batch(alloc[None])[0])
        v = len(self.pool)
        z = dtype(0.0)
        loads, maxe = [z] * v, [z] * v
        cnt, maxmem = [z] * v, [z] * v
        for i, a in enumerate(alloc):
            e = dtype(self.e[i, a])
            loads[a] = dtype(loads[a] + e)
            cnt[a] = dtype(cnt[a] + dtype(1.0))
            maxe[a] = max(maxe[a], e)
            maxmem[a] = max(maxmem[a], dtype(self.rm[i]))
        return self._finish(*(np.array(x, dtype) for x in
                              (loads, maxe, cnt, maxmem)), dtype)

    def fitness_batch(self, allocs) -> np.ndarray:
        """Eq. 8 with the LPT bound for each row of ``allocs`` [C, B], in
        float64."""
        allocs = np.asarray(allocs, np.int64)
        c, b = allocs.shape
        v = len(self.pool)
        flat = (np.arange(c)[:, None] * v + allocs).ravel()
        e = self.e[np.arange(b)[None, :], allocs].ravel()
        loads = np.bincount(flat, weights=e, minlength=c * v)
        cnt = np.bincount(flat, minlength=c * v).astype(np.float64)
        maxe = np.zeros(c * v)
        np.maximum.at(maxe, flat, e)
        maxmem = np.zeros(c * v)
        np.maximum.at(maxmem, flat, np.tile(self.rm, c))
        loads, cnt, maxe, maxmem = (x.reshape(c, v) for x in
                                    (loads, cnt, maxe, maxmem))
        busy = cnt > 0
        mksp = np.where(busy, np.maximum(loads / self.cores, maxe)
                        + self.boot, 0.0)
        mem_peak = maxmem * np.minimum(cnt, self.cores)
        bad = np.any(mem_peak > self.mem + 1e-6, axis=1) | \
            np.any(mksp > self.limit + 1e-6, axis=1)
        cost = np.sum(self.price * np.maximum(mksp - self.boot, 0.0), axis=1)
        fit = self.alpha * cost / self.scale + \
            (1.0 - self.alpha) * np.max(mksp, axis=1) / self.deadline
        return np.where(bad, np.inf, fit)

    def _finish(self, loads, maxe, cnt, maxmem, dtype) -> float:
        c = lambda x: np.asarray(x, np.float64).astype(dtype)   # noqa: E731
        boot, cores = c(self.boot), c(self.cores)
        busy = cnt > 0
        mksp = np.where(busy, np.maximum(loads / cores, maxe) + boot,
                        c(0.0)).astype(dtype)
        mem_peak = (maxmem * np.minimum(cnt, cores)).astype(dtype)
        bad = bool(np.any(mem_peak > c(self.mem) + c(1e-6)) or
                   np.any(mksp > c(self.limit) + c(1e-6)))
        billed = np.maximum(mksp - boot, c(0.0)).astype(dtype)
        cost = dtype(0.0)
        for x in (c(self.price) * billed).astype(dtype):
            cost = dtype(cost + x)
        a = c(self.alpha)
        fit = dtype(a * cost / c(self.scale) +
                    (c(1.0) - a) * np.max(mksp) / c(self.deadline))
        return float("inf") if bad else float(fit)

    def initial_population(self, market: Market, population: int,
                           seed: int) -> tuple[np.ndarray, list[int]]:
        """The batched ILS's starting chains (``run_batched_ils``) and the
        VMs its moves may target: the Alg. 2 greedy seed, and chains
        1..P-1 with a tenth of the tasks sent to random active VMs by
        ``default_rng(seed)``; the active VMs are the seed's and every VM
        of the policy's market."""
        seed_sol = initial_solution(self.tasks, self.pool, self.cfg,
                                    self.dspot, market=market)
        active = sorted(set(seed_sol.used_uids()) |
                        {vm.uid for vm in self.pool if vm.market == market})
        rng = np.random.default_rng(seed)
        alloc0 = np.tile(seed_sol.alloc, (population, 1)).astype(np.int32)
        b = len(self.tasks)
        for i in range(1, population):
            idx = rng.integers(0, b, size=max(1, b // 10))
            alloc0[i, idx] = rng.choice(active, size=len(idx))
        return alloc0, active
