"""The scheduling kernels compile for a TPU v5e at the main path's shapes.

Interpret mode (the CPU tests) cannot see what Mosaic refuses: unaligned
tiles, splitting reshapes, scratch that does not fit.  These tests
compile each Pallas kernel for a described ``v5e:2x2`` topology, with no
chip attached, at the sizes the main path runs: the batched ILS at its
defaults (P=32 chains, K=16 proposals, n=4 tasks per move) on ED200
(B=200) over the 35-VM EC2 pool, the service's ``insert_tasks`` (the
delta kernel on the phantom-column problem, B+1 tasks and V+1 columns),
and the Monte-Carlo engine's reductions at S=1024 scenarios over a
plan's launchable columns (32 for ED200 burst-hads, 35 for the whole
pool).  ``interpret=False`` is passed explicitly: the process's backend
is the CPU, which would otherwise select interpret mode.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.sched_fitness.mc_step import mc_span_reduce, mc_vm_reduce
from repro.kernels.sched_fitness.sched_fitness import (
    delta_population_fitness, population_reduce)

P, K, N, B, V, S = 32, 16, 4, 200, 35, 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_population_reduce_compiles(shape):
    _compile(lambda a, e, rm: population_reduce(a, e, rm, interpret=False),
             shape((P, B), jnp.int32), shape((B, V)), shape((B,)))


@pytest.mark.parametrize("p,k,n,b,v", [
    (P, K, N, B, V),               # batched ILS delta_fitness
    (1, 8, 1, B + 1, V + 1),       # service insert_tasks (phantom column)
], ids=["ils", "insert_tasks"])
def test_delta_population_fitness_compiles(shape, p, k, n, b, v):
    def delta(alloc, t_idx, dest, bl, bx, bc, bm, e, rm, cores, mem, price,
              limit, params):
        return delta_population_fitness(
            alloc, t_idx, dest, (bl, bx, bc, bm), e, rm, cores, mem, price,
            limit, params, interpret=False)

    _compile(delta, shape((p, b), jnp.int32), shape((p, k, n), jnp.int32),
             shape((p, k), jnp.int32), *[shape((p, v))] * 4, shape((b, v)),
             shape((b,)), *[shape((v,))] * 4, shape((4,)))


@pytest.mark.parametrize("v", [32, V])
def test_mc_vm_reduce_compiles(shape, v):
    _compile(lambda c, w: mc_vm_reduce(c, w, v, interpret=False),
             shape((S, B), jnp.int32), shape((S, B)))


@pytest.mark.parametrize("v", [32, V])
def test_mc_span_reduce_compiles(shape, v):
    _compile(lambda c, r, d, m: mc_span_reduce(c, r, d, m, v,
                                               interpret=False),
             shape((S, B), jnp.int32), shape((S, B)), shape((S, B)),
             shape((S,)))
