"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles.

The scheduling kernels run in Pallas interpret mode here because the test
backend is the CPU (``sched_fitness.resolve_interpret``)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.sched_fitness.mc_step import mc_vm_reduce
from repro.kernels.sched_fitness.ops import (delta_fitness, insert_tasks,
                                             mc_vm_stats,
                                             population_fitness)
from repro.kernels.sched_fitness.ref import (apply_moves, delta_fitness_ref,
                                             insert_tasks_ref,
                                             mc_vm_stats_ref,
                                             population_fitness_ref)
from repro.kernels.sched_fitness.sched_fitness import population_reduce


# ---------------------------------------------------------------- fitness
@pytest.mark.parametrize("p,b,v", [(1, 1, 1), (5, 33, 7), (16, 128, 35),
                                   (9, 200, 64)])
def test_sched_fitness_matches_ref(p, b, v):
    rng = np.random.default_rng(p * 100 + b)
    alloc = jnp.asarray(rng.integers(0, v, (p, b)), jnp.int32)
    e = jnp.asarray(rng.uniform(50, 400, (b, v)), jnp.float32)
    rm = jnp.asarray(rng.uniform(2, 180, b), jnp.float32)
    cores = jnp.asarray(rng.choice([2.0, 4.0], v))
    mem = jnp.asarray(rng.uniform(3000, 8000, v), jnp.float32)
    price = jnp.asarray(rng.uniform(1e-5, 6e-5, v), jnp.float32)
    spot = jnp.asarray(rng.integers(0, 2, v), jnp.float32)
    kw = dict(dspot=2240.0, deadline=2700.0, alpha=0.5, cost_scale=0.2,
              boot_s=60.0)
    got = population_fitness(alloc, e, rm, cores, mem, price, spot, **kw)
    want = population_fitness_ref(alloc, e, rm, cores, mem, price, spot,
                                  **kw)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------- MC per-slot reduce
@pytest.mark.parametrize("s,b,v", [(1, 1, 1), (3, 17, 5), (8, 60, 27),
                                   (9, 200, 64), (16, 130, 128)])
def test_mc_vm_reduce_matches_ref(s, b, v):
    """Monte-Carlo VM reductions: kernel == jnp oracle, including ignored
    tasks (done / unassigned / out-of-range columns)."""
    rng = np.random.default_rng(s * 1000 + b)
    cols = rng.integers(-1, v + 1, (s, b))          # -1 and v are ignored
    w = rng.uniform(0.0, 400.0, (s, b))
    w[rng.uniform(size=(s, b)) < 0.3] = 0.0         # done tasks
    cols_j = jnp.asarray(cols, jnp.int32)
    w_j = jnp.asarray(w, jnp.float32)
    got = mc_vm_reduce(cols_j, w_j, v)
    want = mc_vm_stats_ref(cols_j, w_j, v)
    for name, g, ww in zip(("load", "cnt", "maxw"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(ww),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


def test_mc_vm_stats_masks_done_tasks():
    """The ops wrapper ignores rem <= 0 regardless of the column value."""
    assign = jnp.asarray([[0, 0, 1, 2]], jnp.int32)
    rem = jnp.asarray([[10.0, 0.0, 5.0, 0.0]], jnp.float32)
    load, cnt, maxw = mc_vm_stats(assign, rem, v=3)
    np.testing.assert_allclose(np.asarray(load), [[10.0, 5.0, 0.0]])
    np.testing.assert_allclose(np.asarray(cnt), [[1.0, 1.0, 0.0]])
    np.testing.assert_allclose(np.asarray(maxw), [[10.0, 5.0, 0.0]])


def test_mc_vm_reduce_megabatch_pad_columns_stay_empty():
    """The megabatch fused layout (sim.megabatch) hands the kernel
    ``v = v_pad`` > the plan's real column count; as long as no task is
    assigned past the real columns — the engine's invariant, asserted at
    fusion time — every pad column's reductions are exactly zero, and
    out-of-range columns still park on the reserved kernel pad lane."""
    rng = np.random.default_rng(0)
    v_real, v_pad = 5, 8
    cols = jnp.asarray(rng.integers(0, v_real, (4, 16)), jnp.int32)
    w = jnp.asarray(rng.uniform(1.0, 9.0, (4, 16)), jnp.float32)
    load, cnt, maxw = mc_vm_reduce(cols, w, v=v_pad)
    for name, x in (("load", load), ("cnt", cnt), ("maxw", maxw)):
        assert not np.asarray(x)[:, v_real:].any(), name
    # a stray out-of-range column is ignored, not misattributed
    load2, cnt2, _ = mc_vm_reduce(cols.at[0, 0].set(v_pad + 3), w,
                                  v=v_pad)
    assert not np.asarray(cnt2)[:, v_real:].any()
    np.testing.assert_allclose(np.asarray(cnt2).sum(),
                               np.asarray(cnt).sum() - 1.0)


# ---------------------------------------------------------- delta fitness
def _fitness_problem(rng, b, v):
    e = jnp.asarray(rng.uniform(50, 400, (b, v)), jnp.float32)
    rm = jnp.asarray(rng.uniform(2, 180, b), jnp.float32)
    cores = jnp.asarray(rng.choice([2.0, 4.0], v))
    mem = jnp.asarray(rng.uniform(3000, 8000, v), jnp.float32)
    price = jnp.asarray(rng.uniform(1e-5, 6e-5, v), jnp.float32)
    spot = jnp.asarray(rng.integers(0, 2, v), jnp.float32)
    return e, rm, cores, mem, price, spot


def _assert_delta_matches(got, want):
    """Same inf (infeasibility) mask exactly; non-inf entries to 1e-5."""
    for name, g, w in zip(("fitness", "cost", "makespan"), got, want):
        g, w = np.asarray(g), np.asarray(w)
        np.testing.assert_array_equal(np.isinf(g), np.isinf(w),
                                      err_msg=f"{name}: inf masks differ")
        fin = ~np.isinf(w)
        np.testing.assert_allclose(g[fin], w[fin], rtol=1e-5, atol=1e-5,
                                   err_msg=name)


def _delta_vs_oracles(alloc, t_idx, dest, e, rm, cores, mem, price, spot,
                      **kw):
    base = population_reduce(alloc, e, rm)
    got = delta_fitness(alloc, t_idx, dest, base, e, rm, cores, mem, price,
                        spot, **kw)
    want = delta_fitness_ref(alloc, t_idx, dest, e, rm, cores, mem, price,
                             spot, **kw)
    _assert_delta_matches(got, want)
    # and against the full Pallas path on materialised candidates
    p, b = alloc.shape
    k = t_idx.shape[1]
    cand = apply_moves(alloc, t_idx, dest).reshape(p * k, b)
    full = population_fitness(cand, e, rm, cores, mem, price, spot, **kw)
    _assert_delta_matches(got, [f.reshape(p, k) for f in full])
    return got


@pytest.mark.parametrize("p,b,v,k,n", [
    (1, 1, 1, 1, 1),
    (5, 33, 7, 3, 2),
    (8, 100, 35, 16, 4),
    (4, 200, 130, 5, 3),     # V > LANE and not a multiple of 128
    (3, 64, 128, 4, 2),      # V exactly the lane width (pad-column case)
])
def test_delta_fitness_matches_oracles(p, b, v, k, n):
    rng = np.random.default_rng(p * 1000 + b)
    alloc = jnp.asarray(rng.integers(0, v, (p, b)), jnp.int32)
    t_idx = jnp.asarray(rng.integers(0, b, (p, k, n)), jnp.int32)
    dest = jnp.asarray(rng.integers(0, v, (p, k)), jnp.int32)
    kw = dict(dspot=2240.0, deadline=2700.0, alpha=0.5, cost_scale=0.2,
              boot_s=60.0)
    _delta_vs_oracles(alloc, t_idx, dest,
                      *_fitness_problem(rng, b, v), **kw)


def test_delta_fitness_infeasibility_masks_agree():
    """A mix of feasible and D_spot-violating candidates: the delta path
    must agree with the oracle exactly on which candidates are inf."""
    p, b, v, k, n = 6, 40, 20, 8, 4
    rng = np.random.default_rng(3)
    alloc = jnp.asarray(rng.integers(0, v, (p, b)), jnp.int32)
    t_idx = jnp.asarray(rng.integers(0, b, (p, k, n)), jnp.int32)
    dest = jnp.asarray(rng.integers(0, v, (p, k)), jnp.int32)
    kw = dict(dspot=600.0, deadline=2700.0, alpha=0.5, cost_scale=0.2,
              boot_s=60.0)
    fit, _, _ = _delta_vs_oracles(alloc, t_idx, dest,
                                  *_fitness_problem(rng, b, v), **kw)
    infs = np.isinf(np.asarray(fit))
    assert infs.any() and not infs.all()   # the mask check actually bites


def test_delta_fitness_noop_move_keeps_base_fitness():
    """Relocating tasks onto their current VM must reproduce the incumbent
    fitness bit-for-bit semantics (feasibility) and to float tolerance."""
    p, b, v, k, n = 4, 50, 12, 3, 2
    rng = np.random.default_rng(11)
    alloc = jnp.asarray(rng.integers(0, v, (p, b)), jnp.int32)
    # every candidate moves n copies of one task to its own VM
    t0 = jnp.asarray(rng.integers(0, b, (p, k, 1)), jnp.int32)
    t_idx = jnp.broadcast_to(t0, (p, k, n))
    dest = alloc[jnp.arange(p)[:, None], t0[:, :, 0]]
    e, rm, cores, mem, price, spot = _fitness_problem(rng, b, v)
    kw = dict(dspot=2240.0, deadline=2700.0, alpha=0.5, cost_scale=0.2,
              boot_s=60.0)
    base = population_reduce(alloc, e, rm)
    fit, _, _ = delta_fitness(alloc, t_idx, dest, base, e, rm, cores, mem,
                              price, spot, **kw)
    fit0, _, _ = population_fitness(alloc, e, rm, cores, mem, price, spot,
                                    **kw)
    np.testing.assert_allclose(np.asarray(fit),
                               np.tile(np.asarray(fit0)[:, None], (1, k)),
                               rtol=1e-6, atol=1e-6)


def test_delta_fitness_emptied_vm():
    """Moving every task off a VM: the source column must go idle (no boot
    cost, no makespan contribution) exactly as in a full re-evaluation."""
    p, b, v, k, n = 1, 3, 4, 1, 3
    alloc = jnp.asarray([[2, 2, 2]], jnp.int32)       # all tasks on VM 2
    t_idx = jnp.asarray([[[0, 1, 2]]], jnp.int32)     # ... all moved
    dest = jnp.asarray([[0]], jnp.int32)              # ... to VM 0
    rng = np.random.default_rng(21)
    e, rm, cores, mem, price, spot = _fitness_problem(rng, b, v)
    kw = dict(dspot=2240.0, deadline=2700.0, alpha=0.5, cost_scale=0.2,
              boot_s=60.0)
    _delta_vs_oracles(alloc, t_idx, dest, e, rm, cores, mem, price, spot,
                      **kw)


def test_delta_fitness_duplicate_move_tasks():
    """Duplicate task ids within one candidate move are legal (the sampler
    draws with replacement) and must count the task once, not n times."""
    p, b, v, k, n = 2, 30, 9, 4, 4
    rng = np.random.default_rng(5)
    alloc = jnp.asarray(rng.integers(0, v, (p, b)), jnp.int32)
    t_idx = jnp.asarray(rng.integers(0, 4, (p, k, n)), jnp.int32)  # dups
    dest = jnp.asarray(rng.integers(0, v, (p, k)), jnp.int32)
    kw = dict(dspot=2240.0, deadline=2700.0, alpha=0.5, cost_scale=0.2,
              boot_s=60.0)
    _delta_vs_oracles(alloc, t_idx, dest,
                      *_fitness_problem(rng, b, v), **kw)


# ---------------------------------------------------- single-task insert
def _insert_problem(rng, b, v):
    e, rm, cores, mem, price, spot = _fitness_problem(rng, b, v)
    e_new = jnp.asarray(rng.uniform(50, 400, v), jnp.float32)
    rm_new = jnp.float32(rng.uniform(2, 180))
    return e, rm, e_new, rm_new, cores, mem, price, spot


def _insert_vs_oracle(alloc, dest, e, rm, e_new, rm_new, cores, mem,
                      price, spot, **kw):
    base = population_reduce(alloc, e, rm)
    got = insert_tasks(alloc, dest, base, e, rm, e_new, rm_new, cores,
                       mem, price, spot, **kw)
    want = insert_tasks_ref(alloc, dest, e, rm, e_new, rm_new, cores,
                            mem, price, spot, **kw)
    _assert_delta_matches(got, want)
    return got


@pytest.mark.parametrize("p,b,v,k", [
    (1, 1, 1, 1),
    (3, 37, 11, 9),          # the service layer's shape class
    (5, 33, 7, 3),
    (2, 64, 128, 8),         # V exactly the lane width (pad-column case)
])
def test_insert_tasks_matches_ref_oracle(p, b, v, k):
    """The admission fast path (phantom-column delta move) must equal a
    full re-evaluation of the real B+1 problem — exact inf masks, finite
    entries to the kernel suite's 1e-5 tolerance."""
    rng = np.random.default_rng(p * 1000 + b)
    alloc = jnp.asarray(rng.integers(0, v, (p, b)), jnp.int32)
    dest = jnp.asarray(rng.integers(0, v, (p, k)), jnp.int32)
    kw = dict(dspot=2240.0, deadline=2700.0, alpha=0.5, cost_scale=0.2,
              boot_s=60.0)
    _insert_vs_oracle(alloc, dest, *_insert_problem(rng, b, v), **kw)


def test_insert_tasks_infeasibility_masks_agree():
    """Tight deadline + oversized memory rows: both paths must agree
    exactly on which insertions are infeasible."""
    p, b, v, k = 4, 30, 9, 6
    rng = np.random.default_rng(17)
    alloc = jnp.asarray(rng.integers(0, v, (p, b)), jnp.int32)
    dest = jnp.asarray(rng.integers(0, v, (p, k)), jnp.int32)
    e, rm, e_new, _, cores, mem, price, spot = _insert_problem(rng, b, v)
    # the feasibility check is per-column count x max-task-memory: 900
    # trips it on the small-memory columns only (a genuine mixed mask)
    rm_new = jnp.float32(900.0)
    kw = dict(dspot=2240.0, deadline=2700.0, alpha=0.5, cost_scale=0.2,
              boot_s=60.0)
    fit, _, _ = _insert_vs_oracle(alloc, dest, e, rm, e_new, rm_new,
                                  cores, mem, price, spot, **kw)
    infs = np.isinf(np.asarray(fit))
    assert infs.any() and not infs.all()


def test_insert_tasks_with_parked_incumbents():
    """The service's ledger style: completed / not-yet-folded tasks sit
    on the phantom column (index V) with zero work and zero memory —
    they must not contribute to any insertion's score."""
    p, b, v, k = 2, 24, 8, 4
    rng = np.random.default_rng(23)
    alloc = np.asarray(rng.integers(0, v, (p, b)), np.int32)
    parked = rng.random(b) < 0.4                  # shared [B] ledger mask
    alloc = jnp.asarray(np.where(parked[None], v, alloc))
    e, rm, e_new, rm_new, cores, mem, price, spot = \
        _insert_problem(rng, b, v)
    e = jnp.where(jnp.asarray(parked)[:, None], 0.0, e)
    rm = jnp.where(jnp.asarray(parked), 0.0, rm)
    dest = jnp.asarray(rng.integers(0, v, (p, k)), jnp.int32)
    kw = dict(dspot=2240.0, deadline=2700.0, alpha=0.5, cost_scale=0.2,
              boot_s=60.0)
    _insert_vs_oracle(alloc, dest, e, rm, e_new, rm_new, cores, mem,
                      price, spot, **kw)

