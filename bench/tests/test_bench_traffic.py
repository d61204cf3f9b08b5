"""The benchmark's copies of the traffic and of the catalog reproduce the
program's at the commit that added them, draw for draw."""
import dataclasses
import json
import os

import numpy as np
import pytest

from bench import deploy, generator

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def conf(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 1, 7])
def test_synthetic_law_matches_program_sampler(seed):
    """Fresh bags follow the program's Table III law for J<n> jobs."""
    from repro.sim.workloads import _synthetic_tasks
    law = conf("j100-sc5")["bag"]
    mem, base = generator.bag(law, np.random.default_rng(seed))
    want = _synthetic_tasks(law["n_tasks"], np.random.default_rng(seed))
    np.testing.assert_array_equal(mem, [t.memory_mb for t in want])
    np.testing.assert_array_equal(base, [t.base_time for t in want])


def test_seeds_are_stable():
    # seeds above 32 bits map to distinct 31-bit seeds, deterministically
    big = 2 ** 40
    s = {generator.sub_seed(big, i) for i in range(100)}
    assert len(s) == 100 and max(s) < 2 ** 31
    assert generator.sub_seed(big, 3) == generator.sub_seed(big, 3)
    assert generator.sub_seed(big, 3) != generator.sub_seed(big + 1, 3)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5, 9876543210])
def test_every_seed_takes_the_whole_pool(seed):
    """Runs of different seeds send the same bags, in another order."""
    order = [generator.pool_member(seed, 64, i) for i in range(128)]
    assert sorted(order[:64]) == list(range(64))
    assert order[64:] == order[:64]


@pytest.mark.parametrize("config", ["j100-sc5"])
def test_catalog_is_the_programs_default(config):
    """The configuration's catalog builds the program's default EC2
    catalog (Table II) and the reference's copy of it."""
    from repro.core.types import CloudConfig
    c = conf(config)
    assert deploy.program_cloud(c) == CloudConfig()
    ref = deploy.reference_cloud(c).instance_pool()
    prog = CloudConfig().instance_pool()
    assert len(ref) == len(prog) == 35
    for a, b in zip(ref, prog):
        assert (a.uid, a.market.value, a.vcpus, a.memory_mb,
                a.price_per_sec) == (b.uid, b.market.value, b.vcpus,
                                     b.memory_mb, b.price_per_sec)
        assert dataclasses.asdict(a.vm_type) == dataclasses.asdict(b.vm_type)
