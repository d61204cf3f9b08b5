"""jit'd wrappers: Pallas reductions + jnp fitness finalisation.

``population_fitness`` re-reduces the full [B, V] problem per candidate;
``delta_fitness`` scores candidate *moves* against once-per-iteration base
reductions, re-reducing only the touched VM columns (DESIGN.md §2.1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .mc_step import mc_span_reduce, mc_vm_reduce
from .sched_fitness import delta_population_fitness, population_reduce


@jax.jit
def population_fitness(alloc, e, rm, vm_cores, vm_mem, vm_price, vm_is_spot,
                       *, dspot, deadline, alpha, cost_scale, boot_s):
    """Fitness of P candidate schedules (Eq. 8, LPT makespan bound).

    Returns (fitness [P], cost [P], makespan [P]).
    """
    loads, maxe, cnt, maxmem = population_reduce(alloc, e, rm)
    busy = cnt > 0
    makespan = jnp.where(
        busy, jnp.maximum(loads / vm_cores[None], maxe) + boot_s, 0.0)
    mem_peak = maxmem * jnp.minimum(cnt, vm_cores[None])
    mem_bad = jnp.any(mem_peak > vm_mem[None] + 1e-6, axis=1)
    limit = jnp.where(vm_is_spot[None] > 0, dspot, deadline)
    time_bad = jnp.any(makespan > limit + 1e-6, axis=1)
    cost = jnp.sum(vm_price[None] * jnp.maximum(makespan - boot_s, 0.0),
                   axis=1)
    mkp = jnp.max(makespan, axis=1)
    fit = alpha * cost / cost_scale + (1 - alpha) * mkp / deadline
    bad = mem_bad | time_bad
    return jnp.where(bad, jnp.inf, fit), cost, mkp


@jax.jit
def delta_fitness(alloc, t_idx, dest, base, e, rm, vm_cores, vm_mem,
                  vm_price, vm_is_spot, *, dspot, deadline, alpha,
                  cost_scale, boot_s):
    """Fitness of P·K candidate moves, evaluated incrementally (Eq. 8).

    ``alloc`` [P, B] is the incumbent; candidate (p, k) relocates tasks
    ``t_idx[p, k, :]`` to VM ``dest[p, k]``.  ``base`` is the 4-tuple of
    [P, V] reductions of ``alloc`` from ``population_reduce`` — computed
    once per iteration, not per candidate.  Returns (fitness [P, K],
    cost [P, K], makespan [P, K]); identical semantics to calling
    ``population_fitness`` on the materialised candidates.
    """
    limit = jnp.where(vm_is_spot > 0, dspot, deadline).astype(jnp.float32)
    params = jnp.stack([jnp.asarray(x, jnp.float32)
                        for x in (alpha, cost_scale, boot_s, deadline)])
    return delta_population_fitness(alloc, t_idx, dest, base, e, rm,
                                    vm_cores, vm_mem, vm_price, limit,
                                    params)


@jax.jit
def insert_tasks(alloc, dest, base, e, rm, e_new, rm_new, vm_cores, vm_mem,
                 vm_price, vm_is_spot, *, dspot, deadline, alpha,
                 cost_scale, boot_s):
    """Score single-task insertions without re-reducing untouched columns.

    The service layer's admission fast path (DESIGN.md §2.9): candidate
    (p, k) inserts ONE new task — exec-time row ``e_new`` [V], memory
    ``rm_new`` — into incumbent ``alloc[p]`` [P, B] at column
    ``dest[p, k]``.  Implemented as a delta *move* on an extended
    problem: the new task starts parked on a phantom column (index V —
    zero exec time, zero price, unit cores) and relocates to its real
    destination, so the delta kernel re-reduces exactly {phantom, dest}
    per candidate and the phantom empties every time (an empty column
    contributes nothing to Eq. 8).  Because the kernel recomputes the
    touched columns from scratch, the result equals full re-evaluation
    of the real B+1 problem — ``ref.insert_tasks_ref`` pins the match
    exactly (tests/test_kernels.py).  ``dest`` must index real columns
    (< V; the phantom itself scores memory-infeasible).  ``base`` is the
    incumbent's ``population_reduce`` 4-tuple over the *original* [P, V]
    problem.  Returns (fitness, cost, makespan) [P, K].
    """
    p, b = alloc.shape
    v = e.shape[1]
    k = dest.shape[1]
    e_ext = jnp.concatenate([
        jnp.concatenate([e, jnp.zeros((b, 1), e.dtype)], axis=1),
        jnp.concatenate([jnp.asarray(e_new, e.dtype),
                         jnp.zeros(1, e.dtype)])[None]], axis=0)
    rm_ext = jnp.concatenate([rm, jnp.asarray(rm_new, rm.dtype).reshape(1)])
    alloc_ext = jnp.concatenate(
        [alloc, jnp.full((p, 1), v, alloc.dtype)], axis=1)
    loads, maxe, cnt, maxmem = base
    # phantom base row: one parked task of zero work — never read (the
    # phantom is in every candidate's touched set) but kept consistent
    base_ext = (
        jnp.concatenate([loads, jnp.zeros((p, 1), loads.dtype)], axis=1),
        jnp.concatenate([maxe, jnp.zeros((p, 1), maxe.dtype)], axis=1),
        jnp.concatenate([cnt, jnp.ones((p, 1), cnt.dtype)], axis=1),
        jnp.concatenate([maxmem, jnp.broadcast_to(
            jnp.asarray(rm_new, maxmem.dtype), (p, 1))], axis=1))
    t_idx = jnp.full((p, k, 1), b, jnp.int32)
    return delta_fitness(
        alloc_ext, t_idx, dest, base_ext, e_ext, rm_ext,
        jnp.concatenate([vm_cores, jnp.ones(1, vm_cores.dtype)]),
        jnp.concatenate([vm_mem, jnp.zeros(1, vm_mem.dtype)]),
        jnp.concatenate([vm_price, jnp.zeros(1, vm_price.dtype)]),
        jnp.concatenate([vm_is_spot,
                         jnp.zeros(1, jnp.asarray(vm_is_spot).dtype)]),
        dspot=dspot, deadline=deadline, alpha=alpha, cost_scale=cost_scale,
        boot_s=boot_s)


@functools.partial(jax.jit, static_argnames=("v",))
def mc_vm_stats(assign, rem, *, v: int):
    """Per-scenario per-VM remaining-load / unfinished-count / max-remaining,
    one streamed pass over the [S, B] assignment (the Monte-Carlo engine's
    hot per-slot reduction, DESIGN.md §2.3).  Tasks with ``rem <= 0`` or an
    out-of-range column are ignored; ``cnt == 0`` is the idle mask.
    Returns (load, cnt, maxw) each f32 [S, v]."""
    pending = rem > 0.0
    cols = jnp.where(pending, assign, -1)
    w = jnp.where(pending, rem, 0.0).astype(jnp.float32)
    return mc_vm_reduce(cols, w, v)


@functools.partial(jax.jit, static_argnames=("v",))
def mc_span_advance(assign, rem, drem, m, *, v: int):
    """Event-horizon span advance fused with the VM reductions
    (DESIGN.md §2.5): jump ``m`` uniform slots in closed form
    (``rem_new = max(rem − m·drem, 0)``, exact — the engine only requests
    spans that are completion-free) and reduce the advanced state to
    per-(scenario, VM) load / unfinished count / max remaining in the
    same streamed pass.  Returns (rem_new [S, B], load, cnt, maxw each
    f32 [S, v])."""
    pending = rem > 0.0
    cols = jnp.where(pending, assign, -1)
    return mc_span_reduce(cols, rem, jnp.where(pending, drem, 0.0), m, v)
