"""Kernel microbenches (interpret-mode correctness + jnp-reference timing).

The container is CPU-only: wall-times here are for the *reference* paths
(the Pallas bodies run in interpret mode for validation, not speed); the
kernels' TPU rooflines come from the benchmark's traced runs (``bench/``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.time() - t0) / reps * 1e6


def run() -> list[dict]:
    rows = []
    rng = np.random.default_rng(0)

    # sched_fitness ref throughput (the ILS inner loop)
    from repro.kernels.sched_fitness.ref import population_fitness_ref
    p_, b_, v_ = 256, 100, 35
    alloc = jnp.asarray(rng.integers(0, v_, (p_, b_)), jnp.int32)
    e = jnp.asarray(rng.uniform(50, 400, (b_, v_)), jnp.float32)
    rm = jnp.asarray(rng.uniform(2, 14, b_), jnp.float32)
    cores = jnp.asarray(rng.choice([2.0, 4.0], v_))
    mem = jnp.full((v_,), 3840.0)
    price = jnp.asarray(rng.uniform(1e-5, 6e-5, v_), jnp.float32)
    spot = jnp.asarray(rng.integers(0, 2, v_), jnp.float32)
    kw = dict(dspot=2240.0, deadline=2700.0, alpha=0.5, cost_scale=0.2,
              boot_s=60.0)
    fit = jax.jit(lambda a: population_fitness_ref(
        a, e, rm, cores, mem, price, spot, **kw))
    us = _time(fit, alloc)
    rows.append({"table": "kernels", "kernel": "sched_fitness",
                 "shape": f"P{p_} B{b_} V{v_}",
                 "us_per_call": round(us),
                 "evals_per_s": round(p_ / (us / 1e6))})

    # Monte-Carlo per-slot VM reduction (the dynamic-phase hot loop):
    # per-scenario per-VM remaining-load/count/max in one pass over [S, B]
    from repro.kernels.sched_fitness.ops import mc_vm_stats
    from repro.kernels.sched_fitness.ref import mc_vm_stats_ref
    s_, b2, v2 = 1024, 100, 35
    assign = jnp.asarray(rng.integers(0, v2, (s_, b2)), jnp.int32)
    remw = jnp.asarray(rng.uniform(0.0, 400.0, (s_, b2)), jnp.float32)
    ref_fn = jax.jit(lambda a, w: mc_vm_stats_ref(a, w, v2))
    pal_fn = lambda a, w: mc_vm_stats(a, w, v=v2)
    rows.append({"table": "kernels", "kernel": "mc_vm_reduce",
                 "shape": f"S{s_} B{b2} V{v2}",
                 "ref_us": round(_time(ref_fn, assign, remw)),
                 "interpret_us": round(_time(pal_fn, assign, remw))})

    # delta vs full candidate scoring (interpret-mode Pallas, the ILS step):
    # P chains x K proposals, full path re-reduces [P*K, B], delta path
    # splices C=n+1 re-reduced columns into once-per-step base reductions.
    from repro.kernels.sched_fitness.ops import (delta_fitness,
                                                 population_fitness)
    from repro.kernels.sched_fitness.ref import apply_moves
    from repro.kernels.sched_fitness.sched_fitness import population_reduce
    k_, n_ = 16, 4
    for pop in (8, 32, 128):
        al = jnp.asarray(rng.integers(0, v_, (pop, b_)), jnp.int32)
        t_idx = jnp.asarray(rng.integers(0, b_, (pop, k_, n_)), jnp.int32)
        dst = jnp.asarray(rng.integers(0, v_, (pop, k_)), jnp.int32)
        cand = apply_moves(al, t_idx, dst).reshape(pop * k_, b_)
        full_fn = lambda c: population_fitness(
            c, e, rm, cores, mem, price, spot, **kw)[0]
        base = population_reduce(al, e, rm)
        delta_fn = lambda t: delta_fitness(
            al, t, dst, base, e, rm, cores, mem, price, spot, **kw)[0]
        full_us = _time(full_fn, cand)
        delta_us = _time(delta_fn, t_idx)
        rows.append({"table": "kernels", "kernel": "sched_fitness_delta",
                     "shape": f"P{pop} K{k_} n{n_} B{b_} V{v_}",
                     "full_us": round(full_us),
                     "delta_us": round(delta_us),
                     "full_evals_per_s": round(pop * k_ / (full_us / 1e6)),
                     "delta_evals_per_s": round(pop * k_ / (delta_us / 1e6)),
                     "speedup": round(full_us / delta_us, 1)})
    return rows
