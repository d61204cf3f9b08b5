"""The benchmark's references reproduce the program's own at the commit
that copied them: the greedy seed, the starting chains and the proposal
stream of the batched ILS, the LPT-bound fitness and the burstable
allocation."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import deploy, generator
from bench.reference.burst import burst_allocation
from bench.reference.ils import batched_ils, proposals
from bench.reference.plans import Problem
from bench.reference.types import Market, Solution

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "bench", "configs", "j100-sc5.json")) as f:
    CONF = json.load(f)


@pytest.fixture(scope="module")
def job():
    from repro.sim.workloads import make_job
    return make_job("J24")


def _problem(job):
    rcfg = deploy.reference_cloud(CONF)
    rjob = deploy.reference_job(job.name, [t.memory_mb for t in job.tasks],
                                [t.base_time for t in job.tasks],
                                job.deadline_s)
    return Problem(rjob.tasks, rcfg.instance_pool(), rcfg, job.deadline_s,
                   0.5)


def test_greedy_seed_and_starting_chains_equal_the_programs(job):
    from repro.core.dspot import compute_dspot
    from repro.core.greedy import initial_solution
    from repro.core.types import CloudConfig, Market as PMarket
    cfg = CloudConfig()
    prob = _problem(job)
    assert prob.dspot == compute_dspot(job.deadline_s, job.tasks, cfg)
    want = initial_solution(job.tasks, cfg.instance_pool(), cfg, prob.dspot,
                            market=PMarket.SPOT)
    chains, active = prob.initial_population(Market.SPOT, 8, seed=5)
    np.testing.assert_array_equal(chains[0], want.alloc)
    # chains 1.. diversify a tenth of the tasks with default_rng(seed)
    rng = np.random.default_rng(5)
    want_active = sorted(set(want.used_uids()) |
                         {vm.uid for vm in cfg.instance_pool()
                          if vm.market == PMarket.SPOT})
    assert active == want_active
    idx = rng.integers(0, job.n_tasks, size=max(1, job.n_tasks // 10))
    row = want.alloc.copy()
    row[idx] = rng.choice(active, size=len(idx))
    np.testing.assert_array_equal(chains[1], row)


def test_lpt_fitness_equals_the_programs_oracle(job):
    """float64 numpy against the program's float32 jnp oracle, on the
    starting chains (feasible and infeasible alike), one by one and in a
    batch."""
    from repro.core.fitness import cost_scale
    from repro.core.ils_jax import _problem_arrays
    from repro.core.types import CloudConfig
    from repro.kernels.sched_fitness.ref import population_fitness_ref
    cfg = CloudConfig()
    prob = _problem(job)
    chains, _ = prob.initial_population(Market.SPOT, 16, seed=2)
    e, rm, cores, mem, price, spot = _problem_arrays(
        job.tasks, cfg.instance_pool(), cfg)
    fit, _, _ = population_fitness_ref(
        jnp.asarray(chains), e, rm, cores, mem, price, spot,
        dspot=prob.dspot, deadline=job.deadline_s, alpha=0.5,
        cost_scale=cost_scale(job.tasks, cfg), boot_s=60.0)
    ours = np.array([prob.fitness(a) for a in chains])
    np.testing.assert_array_equal(ours, prob.fitness_batch(chains))
    theirs = np.asarray(fit, np.float64)
    np.testing.assert_array_equal(np.isinf(ours), np.isinf(theirs))
    ok = np.isfinite(ours)
    assert ok.any()
    np.testing.assert_allclose(ours[ok], theirs[ok], rtol=1e-6)


@pytest.mark.parametrize("seed", [0, 77, 2 ** 31 - 1])
def test_proposal_stream_is_the_programs(seed):
    """Draw for draw: the program's per-iteration keys and ``_propose``."""
    from repro.core.ils_jax import _propose
    active = jnp.arange(3, 18, dtype=jnp.int32)
    t_idx, d_pos = proposals(seed, 5, 4, 3, 2, 24, int(active.shape[0]))
    key = jax.random.PRNGKey(seed)
    for it in range(5):
        key, k1 = jax.random.split(key)
        t, d = _propose(k1, 4, 24, 3, 2, active)
        np.testing.assert_array_equal(t_idx[it], np.asarray(t))
        np.testing.assert_array_equal(np.asarray(active)[d_pos[it]],
                                      np.asarray(d))


def test_reference_search_improves_on_its_start(job):
    prob = _problem(job)
    res = batched_ils(prob, Market.SPOT, 8, 4, 2, 20, seed=3)
    assert res["fitness"] < res["start"]
    assert prob.fitness(res["alloc"]) == res["fitness"]


def _burst_pair(k):
    """The program's burstable allocation of its ILS winner for bag ``k``
    of the plan cell's pool, and the copy's of the same winner."""
    from repro.core.burst_alloc import burst_allocation as prog_burst
    from repro.core.ils_jax import BatchedILSParams, run_batched_ils
    from repro.core.types import CloudConfig, Market as PMarket
    mem, base = generator.bag(CONF["bag"], generator.request_bag_rng(0, k))
    job = deploy.program_job("J100", mem, base, CONF["deadline_s"])
    cfg = CloudConfig()
    prob = _problem(job)
    res = run_batched_ils(job.tasks, cfg.instance_pool(), cfg, prob.dspot,
                          job.deadline_s,
                          BatchedILSParams(iterations=30, seed=k),
                          market=PMarket.SPOT)
    want = prog_burst(res.solution, job.tasks, cfg, prob.dspot,
                      job.deadline_s, 0.2).solution
    winner = Solution(alloc=np.asarray(res.solution.alloc, np.int32),
                      modes=np.zeros(job.n_tasks, np.int8), pool=prob.pool,
                      selected_uids=set(res.solution.selected_uids))
    return burst_allocation(winner, prob.tasks, prob.cfg, prob.dspot,
                            job.deadline_s, 0.2), want


@pytest.mark.parametrize("k", [0, 1, 4])
def test_burst_allocation_copy_equals_the_programs(k):
    """Every task, mode and selected VM as the program places them."""
    got, want = _burst_pair(k)
    np.testing.assert_array_equal(got.alloc, want.alloc)
    np.testing.assert_array_equal(got.modes, want.modes)
    assert got.selected_uids == {int(u) for u in want.selected_uids}


def test_burst_allocation_moves_tasks_on_the_pool_bags():
    assert sum(int(np.sum(_burst_pair(k)[0].modes == 1))
               for k in (0, 1, 4)) > 0
