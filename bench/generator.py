"""The benchmark's one traffic generator: bags and seeds.

Everything a request carries is drawn here from the run's ``--seed`` and
the parameters of a configuration file (``configs/``) and a traffic file
(``traffic/``); the program receives only the generated inputs.

* Bags follow the laws of the paper's Table III, copied from the program's
  ``repro.sim.workloads`` so that a change there cannot move the traffic:
  ``synthetic`` (Alves template: memory and base time both affine in one
  uniform draw, then a multiplicative jitter, clipped to the time band)
  and ``uniform`` (NAS Grid ED: memory and base time drawn independently).
* Seeds: ``--seed`` may exceed 32 bits, and JAX keys take 32, so every
  per-request seed is drawn from ``numpy.random.SeedSequence``.
* Pools: a traffic file may fix a pool of request inputs (``pool``,
  ``pool_seed``); a run takes them in an order drawn from its seed.
"""
from __future__ import annotations

import numpy as np

#: request index offset of the warm-up requests, far from the window's
WARMUP_BASE = 1 << 30


def sub_seed(seed: int, *path: int) -> int:
    """A 31-bit seed for request ``path`` of run ``seed``."""
    ss = np.random.SeedSequence([int(seed), *(int(p) for p in path)])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def bag(law: dict, rng: np.random.Generator) -> tuple[np.ndarray,
                                                      np.ndarray]:
    """(memory_mb [n], base_time_s [n]) of one bag drawn by ``law``."""
    n = int(law["n_tasks"])
    (m0, m1), (t0, t1) = law["memory_mb"], law["base_time_s"]
    if law["law"] == "synthetic":
        u = rng.uniform(0.0, 1.0, size=n)
        mem = m0 + u * (m1 - m0)
        base = t0 + u * (t1 - t0)
        base *= rng.uniform(*law["jitter"], size=n)
        return mem, np.clip(base, t0, t1)
    if law["law"] == "uniform":
        mem = rng.uniform(m0, m1, size=n)
        return mem, rng.uniform(t0, t1, size=n)
    raise ValueError(f"unknown bag law {law['law']!r}")


def request_bag_rng(seed: int, i: int) -> np.random.Generator:
    """The generator of bag ``i`` of a pool drawn from ``seed``."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), i]))


def pool_member(seed: int, pool: int, i: int) -> int:
    """Which member of a traffic's fixed pool request ``i`` of run
    ``seed`` takes: the pool in an order drawn from the seed, cycled.
    Every run then does the same set of work in another order, so that
    runs of different seeds measure the same thing."""
    order = np.random.default_rng(
        np.random.SeedSequence([int(seed), pool])).permutation(pool)
    return int(order[i % pool])
