"""The batched ILS (DESIGN.md section 2.1) as a plain numpy loop.

P chains start from ``Problem.initial_population``.  Each iteration
proposes K moves per chain, each relocating n tasks drawn with repetition
to one active VM, scores every candidate allocation by the float64 Eq. 8
LPT bound (``Problem.fitness_batch``), takes each chain's best candidate
(lowest index on ties) and keeps it where it is strictly better than the
chain's incumbent.  The winner is the best chain at the end.

The moves are the program's stated proposal stream, drawn with
``jax.random`` from ``PRNGKey(seed)``: one ``split`` per iteration, then
``split(key, 3)`` into task and destination keys and two ``randint``
draws.  The same seed therefore proposes the same moves, and a sound
search reaches the same winner.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from .plans import Problem
from .types import Market


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _draws(key, iterations: int, p: int, k: int, n: int, b: int,
           n_active: int):
    def body(key, _):
        key, k1 = jax.random.split(key)
        kt, kd, _ka = jax.random.split(k1, 3)
        return key, (jax.random.randint(kt, (p, k, n), 0, b),
                     jax.random.randint(kd, (p, k), 0, n_active))

    _, out = jax.lax.scan(body, key, None, length=iterations)
    return out


def proposals(seed: int, iterations: int, p: int, k: int, n: int, b: int,
              n_active: int) -> tuple[np.ndarray, np.ndarray]:
    """Task indices [I, P, K, n] and destination positions in the active
    VM list [I, P, K] of every iteration's moves."""
    t_idx, d_pos = _draws(jax.random.PRNGKey(seed), iterations, p, k, n, b,
                          n_active)
    return np.asarray(t_idx, np.int64), np.asarray(d_pos, np.int64)


def batched_ils(prob: Problem, market: Market, population: int,
                proposals_per_chain: int, swap_tasks: int, iterations: int,
                seed: int) -> dict:
    """The search's winner ``alloc`` and ``fitness``, and the best
    fitness of the starting chains (``start``)."""
    alloc, active = prob.initial_population(market, population, seed)
    alloc = alloc.astype(np.int64)
    p, b = alloc.shape
    k = proposals_per_chain
    cur = prob.fitness_batch(alloc)
    start = float(np.min(cur))
    t_idx, d_pos = proposals(seed, iterations, p, k, swap_tasks, b,
                             len(active))
    dest = np.asarray(active, np.int64)[d_pos]
    rows = np.arange(p)
    pi, ki = rows[:, None, None], np.arange(k)[None, :, None]
    for it in range(iterations):
        cand = np.repeat(alloc[:, None, :], k, axis=1)
        cand[pi, ki, t_idx[it]] = dest[it][:, :, None]
        fit = prob.fitness_batch(cand.reshape(p * k, b)).reshape(p, k)
        j = np.argmin(fit, axis=1)
        best = fit[rows, j]
        better = best < cur
        alloc[better] = cand[rows, j][better]
        cur = np.where(better, best, cur)
    win = int(np.argmin(cur))
    return {"alloc": alloc[win].copy(), "fitness": float(cur[win]),
            "start": start}
