"""The benchmark's market generator: Table V Poisson event tensors.

A copy of the program's sampler contract (DESIGN.md section 2.4), so that
a change in ``repro.sim.market`` cannot move the traffic.  A Table V
scenario expects ``k_h`` hibernations and ``k_r`` resumes over the
deadline ``D``; in slots of ``dt`` seconds each is a Bernoulli draw of
probability ``min(1, k dt / D)``, in slots that start before ``D`` only.
A request count of 1 asks for the eligible column of highest score.

The counts are drawn with numpy from a ``SeedSequence``; the [S, N, V]
scores, which hold the tensor's bulk, are drawn on the device from a JAX
key derived from the same seed, so that a pool of tensors is made in a
few device calls.
"""
from __future__ import annotations

import numpy as np


def n_slots(deadline_s: float, dt: float, horizon_mult: float) -> int:
    return int(np.ceil(deadline_s * horizon_mult / dt))


def poisson_counts(rng: np.random.Generator, s: int, n: int, k: float,
                   dt: float, deadline_s: float) -> np.ndarray:
    """int32 [S, N]: 1 where the slot requests an event."""
    p = min(1.0, k * dt / deadline_s)
    live = np.arange(n) * dt < deadline_s
    return ((rng.random((s, n)) < p) & live[None]).astype(np.int32)


def draw(seed: int, market: dict, *, s: int, n: int, v: int, dt: float,
         deadline_s: float) -> dict:
    """One tensor of the market: numpy counts ``hib_k``/``res_k`` and
    device scores ``hib_u``/``res_u``, all from ``seed``."""
    import jax
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    hib_k = poisson_counts(rng, s, n, float(market["k_h"]), dt, deadline_s)
    res_k = poisson_counts(rng, s, n, float(market["k_r"]), dt, deadline_s)
    key_seed = int(np.random.SeedSequence([int(seed), 2]).generate_state(1)
                   [0] & 0x7FFFFFFF)
    kh, kr = jax.random.split(jax.random.PRNGKey(key_seed))
    return {"hib_k": hib_k, "res_k": res_k,
            "hib_u": jax.random.uniform(kh, (s, n, v)),
            "res_u": jax.random.uniform(kr, (s, n, v))}
