"""Batched-ILS engines: the fused delta-eval scan vs the full-eval loop.

Both engines share one proposal RNG stream, and the delta kernel scores
candidates exactly (up to float tolerance), so for a fixed seed the two
engines must walk the same search trajectory.

Problems are built directly from TaskSpec (not make_job) so instances are
identical across processes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ils_jax
from repro.core.dspot import compute_dspot
from repro.core.evaluator import CachedEvaluator
from repro.core.ils_jax import BatchedILSParams, run_batched_ils
from repro.core.types import CloudConfig, TaskSpec

CFG = CloudConfig()
DEADLINE = 2700.0


def _tasks(n=24, seed=0):
    rng = np.random.default_rng(seed)
    return [TaskSpec(tid=i, memory_mb=float(rng.uniform(3.0, 13.0)),
                     base_time=float(rng.uniform(102.0, 330.0)))
            for i in range(n)]


def _run(engine, tasks, dspot, **over):
    kw = dict(population=8, iterations=8, proposals=8, swap_tasks=3,
              seed=0, engine=engine)
    kw.update(over)
    return run_batched_ils(tasks, CFG.instance_pool(), CFG, dspot, DEADLINE,
                           BatchedILSParams(**kw))


@pytest.fixture(scope="module")
def problem():
    tasks = _tasks()
    return tasks, compute_dspot(DEADLINE, tasks, CFG)


def test_scan_engine_matches_step_engine(problem):
    tasks, dspot = problem
    scan = _run("scan", tasks, dspot)
    step = _run("step", tasks, dspot)
    np.testing.assert_allclose(scan.history, step.history, rtol=1e-5)
    np.testing.assert_allclose(scan.fitness_bound, step.fitness_bound,
                               rtol=1e-5)
    np.testing.assert_array_equal(scan.solution.alloc, step.solution.alloc)


def test_scan_engine_monotone_and_device_resident_history(problem):
    tasks, dspot = problem
    res = _run("scan", tasks, dspot)
    assert res.history.shape == (8,)
    assert np.isfinite(res.fitness_bound)
    assert (np.diff(res.history) <= 1e-9).all()   # per-chain best is monotone
    assert res.fitness_bound <= res.history[0] + 1e-9


def test_scan_engine_deterministic(problem):
    tasks, dspot = problem
    a, b = _run("scan", tasks, dspot), _run("scan", tasks, dspot)
    np.testing.assert_array_equal(a.history, b.history)
    np.testing.assert_array_equal(a.solution.alloc, b.solution.alloc)


def test_scan_winner_survives_exact_packer(problem):
    """Search runs on the LPT bound; the winner must re-validate with the
    exact evaluator under the relaxed RD_spot (paper semantics)."""
    tasks, dspot = problem
    res = _run("scan", tasks, dspot)
    ev = CachedEvaluator(tasks, CFG, DEADLINE)
    assert np.isfinite(ev.fitness(res.solution, dspot * 1.3))


def test_unknown_engine_raises(problem):
    tasks, dspot = problem
    with pytest.raises(ValueError, match="engine"):
        _run("warp", tasks, dspot)


@pytest.mark.parametrize("engine", ["scan", "step"])
def test_zero_iterations_returns_seed_population_best(problem, engine):
    tasks, dspot = problem
    res = _run(engine, tasks, dspot, iterations=0)
    assert res.history.shape == (0,)
    assert np.isfinite(res.fitness_bound)


def _eager_keys(key, iterations):
    """The per-iteration key chain as eager dispatches, one split each."""
    per_iter = []
    for _ in range(iterations):
        key, k1 = jax.random.split(key)
        per_iter.append(k1)
    return (jnp.stack(per_iter) if per_iter
            else jnp.zeros((0,) + key.shape, key.dtype))


@pytest.mark.parametrize("iterations", [0, 1, 200])
@pytest.mark.parametrize("seed", [0, 3, 2**31 - 1])
def test_iteration_keys_match_the_eager_split_chain(seed, iterations):
    key = jax.random.PRNGKey(seed)
    got = np.asarray(ils_jax._iteration_keys(key, iterations=iterations))
    want = np.asarray(_eager_keys(key, iterations))
    assert got.shape == (iterations, 2) and got.dtype == np.uint32
    np.testing.assert_array_equal(got, want)


def test_iteration_keys_compile_once_per_iteration_count(problem):
    tasks, dspot = problem
    ils_jax._iteration_keys.clear_cache()
    _run("scan", tasks, dspot, iterations=5)
    _run("scan", tasks, dspot, iterations=5, seed=1)
    assert ils_jax._iteration_keys._cache_size() == 1


@pytest.mark.parametrize("engine", ["scan", "step"])
def test_one_key_program_and_host_winner_change_no_result(problem, engine,
                                                          monkeypatch):
    """The jitted key chain and the host argmin give what the eager split
    chain and the device argmin, row and fitness gave."""
    tasks, dspot = problem
    new = _run(engine, tasks, dspot)

    finals = []
    if engine == "scan":
        scan = ils_jax._ils_scan

        def recording(donate):
            def run(*args, **kw):
                out = scan(False)(*args, **kw)
                finals.append(out[:2])
                return out
            return run
        monkeypatch.setattr(ils_jax, "_ils_scan", recording)
    else:
        step = ils_jax._ils_step

        def recording(*args, **kw):
            out = step(*args, **kw)
            finals.append(out)
            return out
        monkeypatch.setattr(ils_jax, "_ils_step", recording)
    monkeypatch.setattr(ils_jax, "_iteration_keys", _eager_keys)
    old = _run(engine, tasks, dspot)

    alloc, best_fit = finals[-1]
    win = int(jnp.argmin(best_fit))
    np.testing.assert_array_equal(new.history, old.history)
    np.testing.assert_array_equal(new.solution.alloc, np.asarray(alloc[win]))
    np.testing.assert_array_equal(new.solution.alloc, old.solution.alloc)
    assert new.fitness_bound == float(best_fit[win]) == old.fitness_bound
