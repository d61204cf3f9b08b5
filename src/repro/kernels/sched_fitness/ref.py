"""Pure-jnp oracle for the population-fitness kernel.

Given P candidate allocation vectors, compute per-(solution, VM) reductions:
  loads[p, v]  = sum of exec times of tasks assigned to v
  maxe[p, v]   = longest single task on v
  cnt[p, v]    = number of tasks on v
  maxmem[p, v] = largest task memory on v
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: the oracles reduce in full f32 on every backend — a TPU's default dot
#: precision would round the weights to bf16
HIGHEST = jax.lax.Precision.HIGHEST


def population_reduce_ref(alloc: jax.Array, e: jax.Array, rm: jax.Array
                          ) -> tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array]:
    """alloc: int32 [P, B]; e: f32 [B, V]; rm: f32 [B]."""
    p, b = alloc.shape
    v = e.shape[1]
    onehot = jax.nn.one_hot(alloc, v, dtype=e.dtype)         # [P, B, V]
    loads = jnp.einsum("pbv,bv->pv", onehot, e, precision=HIGHEST)
    cnt = onehot.sum(axis=1)
    maxe = jnp.max(jnp.where(onehot > 0, e[None], 0.0), axis=1)
    maxmem = jnp.max(jnp.where(onehot > 0, rm[None, :, None], 0.0), axis=1)
    return loads, maxe, cnt, maxmem


def population_fitness_ref(alloc, e, rm, vm_cores, vm_mem, vm_price,
                           vm_is_spot, *, dspot, deadline, alpha, cost_scale,
                           boot_s):
    """Full fitness (Eq. 8 with the LPT makespan bound) — jnp reference."""
    loads, maxe, cnt, maxmem = population_reduce_ref(alloc, e, rm)
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


def apply_moves(alloc: jax.Array, t_idx: jax.Array, dest: jax.Array
                ) -> jax.Array:
    """Materialise the [P, K, B] candidates the delta path never builds:
    candidate (p, k) = alloc[p] with tasks t_idx[p, k, :] sent to
    dest[p, k] (duplicate task ids are harmless — same destination)."""
    p, b = alloc.shape
    _, k, n = t_idx.shape
    cand = jnp.broadcast_to(alloc[:, None], (p, k, b))
    pi = jax.lax.broadcasted_iota(jnp.int32, (p, k, n), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (p, k, n), 1)
    return cand.at[pi, ki, t_idx].set(
        jnp.broadcast_to(dest[:, :, None], (p, k, n)))


def mc_vm_stats_ref(cols: jax.Array, w: jax.Array, v: int
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for ``mc_step.mc_vm_reduce``: per-(scenario, VM) remaining
    load / unfinished count / max single remaining task.

    cols int32 [S, B] (entries outside [0, v) are ignored); w f32 [S, B].
    Returns (load, cnt, maxw) each f32 [S, v]."""
    keep = (cols >= 0) & (cols < v)
    onehot = jax.nn.one_hot(jnp.where(keep, cols, v), v, dtype=w.dtype)
    load = jnp.einsum("sbv,sb->sv", onehot, w, precision=HIGHEST)
    cnt = onehot.sum(axis=1)
    maxw = jnp.max(onehot * w[:, :, None], axis=1)
    return load, cnt, maxw


def mc_span_advance_ref(assign: jax.Array, rem: jax.Array, drem: jax.Array,
                        m, v: int):
    """Oracle for ``ops.mc_span_advance`` / ``mc_step.mc_span_reduce``:
    closed-form jump over ``m`` uniform (completion-free) slots followed
    by the three VM reductions of the advanced remaining-work vector.

    assign int32 [S, B]; rem/drem f32 [S, B]; m f32 [S] per-scenario
    slot counts.
    Returns (rem_new [S, B], load, cnt, maxw each f32 [S, v])."""
    pending = rem > 0.0
    m = jnp.asarray(m, jnp.float32).reshape(-1, 1)     # [S, 1] span slots
    rem_new = jnp.where(
        pending, jnp.maximum(rem - m * drem, 0.0), rem)
    load, cnt, maxw = mc_vm_stats_ref(
        jnp.where(rem_new > 0.0, assign, -1),
        jnp.where(rem_new > 0.0, rem_new, 0.0), v)
    return rem_new, load, cnt, maxw


def insert_tasks_ref(alloc, dest, e, rm, e_new, rm_new, vm_cores, vm_mem,
                     vm_price, vm_is_spot, *, dspot, deadline, alpha,
                     cost_scale, boot_s):
    """Oracle for ``ops.insert_tasks``: append the new task to every
    incumbent and fully re-evaluate the real B+1 problem (no phantom
    column).  Returns (fitness, cost, makespan) [P, K]."""
    p, b = alloc.shape
    k = dest.shape[1]
    e1 = jnp.concatenate([e, jnp.asarray(e_new, e.dtype)[None]], axis=0)
    rm1 = jnp.concatenate([rm, jnp.asarray(rm_new, rm.dtype).reshape(1)])
    cand = jnp.concatenate(
        [jnp.broadcast_to(alloc[:, None], (p, k, b)),
         dest[:, :, None].astype(alloc.dtype)], axis=2).reshape(p * k, b + 1)
    fit, cost, mkp = population_fitness_ref(
        cand, e1, rm1, vm_cores, vm_mem, vm_price, vm_is_spot, dspot=dspot,
        deadline=deadline, alpha=alpha, cost_scale=cost_scale, boot_s=boot_s)
    return fit.reshape(p, k), cost.reshape(p, k), mkp.reshape(p, k)


def delta_fitness_ref(alloc, t_idx, dest, e, rm, vm_cores, vm_mem, vm_price,
                      vm_is_spot, *, dspot, deadline, alpha, cost_scale,
                      boot_s):
    """Oracle for the incremental path: full re-evaluation of every
    materialised candidate.  Returns (fitness, cost, makespan) [P, K]."""
    p, b = alloc.shape
    _, k, _ = t_idx.shape
    cand = apply_moves(alloc, t_idx, dest).reshape(p * k, b)
    fit, cost, mkp = population_fitness_ref(
        cand, e, rm, vm_cores, vm_mem, vm_price, vm_is_spot, dspot=dspot,
        deadline=deadline, alpha=alpha, cost_scale=cost_scale, boot_s=boot_s)
    return fit.reshape(p, k), cost.reshape(p, k), mkp.reshape(p, k)
