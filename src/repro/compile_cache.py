"""Persistent XLA compilation cache for the repo's entry points.

The MC engine compiles once per (S, engine view, dt, stepping) and the
batched ILS once per problem shape, so a cold run on an accelerator
spends much of its time compiling.  ``enable`` points JAX's persistent
cache at a directory that outlives the process.  Entry points
(``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``) call it before
their first compile; importing ``repro`` never does.
"""
from __future__ import annotations

import os

import jax

#: fixed, so that every run of the same checkout finds the same entries
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself
    and nothing else is configured here.  Otherwise the cache lives at
    ``<repo>/.jax_cache`` (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
