"""Population-based (batched) ILS — the TPU-resident search (DESIGN.md §2.1).

The paper's single sequential chain becomes P parallel chains; each
iteration proposes K candidate moves per chain (n tasks relocated to one
destination VM — the paper's move type) and scores them with the
``sched_fitness`` Pallas kernels (interpret mode on CPU, native on TPU).

Two engines share one proposal RNG stream (identical moves per seed, and —
barring float near-ties between candidates, where last-ulp reduction-order
differences could flip an argmin — identical trajectories):

``scan``  — the default hot path.  The whole iteration loop is a single
jitted ``jax.lax.scan``; candidates are scored *incrementally* with
``delta_fitness`` against once-per-iteration base reductions, the incumbent
update touches only the accepted move's tasks, and ``population_reduce``
re-bases the reductions after each accept.  Nothing leaves the device until
the final result (the best-fitness history is a scan output).

``step``  — the fallback loop: one fused full ``population_fitness`` call
per iteration over all P·K materialised candidates, one host dispatch per
iteration (history still stays on device until the end).

Search uses the LPT lower-bound fitness (``fitness_fast``); every accepted
incumbent is re-validated with the exact packer before being returned, so
the paper's semantics hold for all reported solutions.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sched_fitness.ops import delta_fitness, population_fitness
from repro.kernels.sched_fitness.ref import apply_moves
from repro.kernels.sched_fitness.sched_fitness import population_reduce
from ..obs import span
from .fitness import cost_scale
from .greedy import initial_solution
from .types import (CloudConfig, Market, Solution, TaskSpec, VMInstance,
                    exec_time_matrix)


@dataclasses.dataclass(frozen=True)
class BatchedILSParams:
    population: int = 32
    iterations: int = 60
    proposals: int = 16        # candidate moves per chain per iteration
    swap_tasks: int = 4        # tasks relocated per candidate
    alpha: float = 0.5
    seed: int = 0
    engine: str = "scan"       # "scan" (fused delta path) | "step" (full)


def _problem_arrays(tasks: Sequence[TaskSpec], pool: list[VMInstance],
                    cfg: CloudConfig):
    e = jnp.asarray(exec_time_matrix(tasks, pool, cfg), jnp.float32)
    rm = jnp.asarray([t.memory_mb for t in tasks], jnp.float32)
    cores = jnp.asarray([vm.vcpus for vm in pool], jnp.float32)
    mem = jnp.asarray([vm.memory_mb for vm in pool], jnp.float32)
    price = jnp.asarray([vm.price_per_sec for vm in pool], jnp.float32)
    spot = jnp.asarray([1.0 if vm.is_spot else 0.0 for vm in pool],
                       jnp.float32)
    return e, rm, cores, mem, price, spot


def _propose(key, p: int, b: int, k: int, n: int, active_uids):
    """Sample K candidate moves per chain (shared by both engines)."""
    kt, kd, _ka = jax.random.split(key, 3)
    t_idx = jax.random.randint(kt, (p, k, n), 0, b)
    d_pos = jax.random.randint(kd, (p, k), 0, active_uids.shape[0])
    return t_idx, active_uids[d_pos]


@functools.partial(jax.jit, static_argnames=("k", "n"))
def _ils_step(alloc, best_fit, key, active_uids, e, rm, cores, mem, price,
              spot, *, k: int, n: int, dspot, deadline, alpha, scale,
              boot_s):
    """One batched iteration, full path: materialise + re-reduce P·K."""
    p, b = alloc.shape
    t_idx, dest = _propose(key, p, b, k, n, active_uids)
    cand = apply_moves(alloc, t_idx, dest)                   # [P, K, B]

    fit, _, _ = population_fitness(
        cand.reshape(p * k, b), e, rm, cores, mem, price, spot,
        dspot=dspot, deadline=deadline, alpha=alpha, cost_scale=scale,
        boot_s=boot_s)
    fit = fit.reshape(p, k)
    j = jnp.argmin(fit, axis=1)
    best_cand_fit = jnp.take_along_axis(fit, j[:, None], axis=1)[:, 0]
    best_cand = jnp.take_along_axis(
        cand, j[:, None, None], axis=1)[:, 0]                # [P, B]

    improved = best_cand_fit < best_fit
    alloc = jnp.where(improved[:, None], best_cand, alloc)
    best_fit = jnp.where(improved, best_cand_fit, best_fit)
    return alloc, best_fit


def _ils_scan_impl(alloc, best_fit, keys, active_uids, e, rm, cores, mem,
                   price, spot, *, k: int, n: int, dspot, deadline, alpha,
                   scale, boot_s):
    """The whole search as one fused scan; returns (alloc, fit, history)."""
    p, b = alloc.shape
    rows = jnp.arange(p)

    def step(carry, key):
        alloc, best_fit, base = carry
        t_idx, dest = _propose(key, p, b, k, n, active_uids)
        fit, _, _ = delta_fitness(
            alloc, t_idx, dest, base, e, rm, cores, mem, price, spot,
            dspot=dspot, deadline=deadline, alpha=alpha, cost_scale=scale,
            boot_s=boot_s)
        j = jnp.argmin(fit, axis=1)
        cand_fit = jnp.take_along_axis(fit, j[:, None], axis=1)[:, 0]
        improved = cand_fit < best_fit

        # apply the accepted move in place: only its n tasks change
        ct = t_idx[rows, j]                                  # [P, n]
        cd = dest[rows, j]                                   # [P]
        cur = alloc[rows[:, None], ct]
        alloc = alloc.at[rows[:, None], ct].set(
            jnp.where(improved[:, None], cd[:, None], cur))
        best_fit = jnp.where(improved, cand_fit, best_fit)
        base = population_reduce(alloc, e, rm)
        return (alloc, best_fit, base), jnp.min(best_fit)

    base0 = population_reduce(alloc, e, rm)
    (alloc, best_fit, _), hist = jax.lax.scan(
        step, (alloc, best_fit, base0), keys)
    return alloc, best_fit, hist


@functools.partial(jax.jit, static_argnames=("iterations",))
def _iteration_keys(key, iterations: int):
    """The engines' per-iteration keys, ``[iterations, 2]`` uint32: the
    chain ``key, k1 = split(key)`` as one program (one compile per
    ``iterations``) rather than one eager dispatch per split."""
    def step(key, _):
        key, k1 = jax.random.split(key)
        return key, k1

    _, keys = jax.lax.scan(step, key, None, length=iterations)
    return keys


@functools.lru_cache(maxsize=2)
def _ils_scan(donate: bool):
    """jit the scan engine, donating the alloc/best_fit carry buffers on
    accelerators.  The backend query happens at first call, not import —
    donation is a no-op (plus a warning) on CPU, and callers may still be
    configuring platforms at import time."""
    return jax.jit(_ils_scan_impl, static_argnames=("k", "n"),
                   donate_argnums=(0, 1) if donate else ())


@dataclasses.dataclass
class BatchedILSResult:
    solution: Solution
    fitness_bound: float       # LPT-bound fitness of the winner
    history: np.ndarray        # best bound per iteration
    evaluations: int


def run_batched_ils(tasks: Sequence[TaskSpec], pool: list[VMInstance],
                    cfg: CloudConfig, dspot: float, deadline: float,
                    params: BatchedILSParams = BatchedILSParams(),
                    market: Market = Market.SPOT,
                    initial: Solution | None = None) -> BatchedILSResult:
    """Device-resident population search over P parallel ILS chains.

    ``initial`` warm-starts the population from an incumbent solution
    (the online service's rolling-horizon replans, DESIGN.md §2.9)
    instead of the Alg. 2 greedy seed: chain 0 keeps the incumbent
    verbatim, chains 1..P-1 diversify from it — so a replan can only
    improve on the plan already running."""
    seed_sol = initial if initial is not None else \
        initial_solution(tasks, pool, cfg, dspot, market=market)
    p = params.population
    with span("ils.prepare", population=p, n_tasks=len(tasks)):
        rng = np.random.default_rng(params.seed)
        e, rm, cores, mem, price, spot = _problem_arrays(tasks, pool, cfg)
        scale = cost_scale(tasks, cfg)
        active = sorted(set(seed_sol.used_uids()) |
                        {vm.uid for vm in pool if vm.market == market})
        active_uids = jnp.asarray(active, jnp.int32)

        alloc0 = np.tile(seed_sol.alloc, (p, 1)).astype(np.int32)
        # diversify chains 1..P-1 with random relocations
        for i in range(1, p):
            idx = rng.integers(0, len(tasks), size=max(1, len(tasks) // 10))
            alloc0[i, idx] = rng.choice(active, size=len(idx))
        alloc = jnp.asarray(alloc0)

        kw = dict(k=params.proposals, n=params.swap_tasks, dspot=dspot,
                  deadline=deadline, alpha=params.alpha, scale=scale,
                  boot_s=cfg.boot_overhead_s)
        fit0, _, _ = population_fitness(
            alloc, e, rm, cores, mem, price, spot, dspot=dspot,
            deadline=deadline, alpha=params.alpha, cost_scale=scale,
            boot_s=cfg.boot_overhead_s)

    # per-iteration keys, derived identically for both engines
    with span("ils.keys", iterations=params.iterations):
        keys = _iteration_keys(jax.random.PRNGKey(params.seed),
                               iterations=params.iterations)

    # from the enqueue to the winner on the host
    with span("ils.search", engine=params.engine):
        if params.engine == "scan":
            scan_fn = _ils_scan(donate=jax.default_backend() != "cpu")
            alloc, best_fit, hist = scan_fn(alloc, fit0, keys, active_uids,
                                            e, rm, cores, mem, price, spot,
                                            **kw)
        elif params.engine == "step":
            best_fit = fit0
            hist = []
            for i in range(params.iterations):
                alloc, best_fit = _ils_step(alloc, best_fit, keys[i],
                                            active_uids, e, rm, cores, mem,
                                            price, spot, **kw)
                hist.append(jnp.min(best_fit))   # device scalar: no sync
            hist = jnp.stack(hist) if hist else jnp.zeros((0,), jnp.float32)
        else:
            raise ValueError(f"unknown engine {params.engine!r} (scan/step)")
        # one transfer; numpy's argmin takes the first minimum, as jnp's
        history, alloc, best_fit = jax.device_get((hist, alloc, best_fit))
        win = int(np.argmin(best_fit))
        row = np.array(alloc[win])
        fitness_bound = float(best_fit[win])

    sol = Solution(alloc=row, modes=np.zeros(len(tasks), np.int8),
                   pool=list(pool))
    sol.selected_uids = set(sol.used_uids())
    evals = p + params.population * params.proposals * params.iterations
    return BatchedILSResult(solution=sol, fitness_bound=fitness_bound,
                            history=history, evaluations=evals)
