"""Fault-Tolerance Module: checkpoint schedules for preemptible tasks.

The paper checkpoints a running task (a CRIU snapshot) so that a
hibernated or migrated task resumes from its last checkpoint instead of
from scratch.  This module holds the arithmetic the engines share: the
period that keeps checkpoint overhead within the paper's ``ovh`` budget,
and the per-task ``(total, cp)`` schedules of the policy lattice's
checkpoint axis (``periodic``, ``off`` and ``random``; DESIGN.md §2.8).
"""
from __future__ import annotations

import numpy as np


def ovh_checkpoint_period(step_time_s: float, ckpt_time_s: float,
                          ovh: float = 0.10) -> int:
    """Steps between checkpoints so that overhead stays within ``ovh``."""
    if ovh <= 0:
        raise ValueError(f"ovh={ovh} must be positive — a zero overhead "
                         "budget affords no checkpoints at all")
    if step_time_s <= 0:
        return 1
    return max(1, int(np.ceil(ckpt_time_s / (ovh * step_time_s))))


#: checkpoint-policy axis vocabulary (core.dynamic.PolicyConfig.checkpoint)
CHECKPOINT_MODES = ("periodic", "off", "random")


def _tid_jitter(tids) -> np.ndarray:
    """Deterministic per-task uniform in [0.5, 1.5) — a Knuth
    multiplicative hash of the task id, so the randomized schedule is a
    pure function of task identity (the DES's per-``TaskRun`` view and
    the MC engine's permuted plan arrays agree bit-for-bit)."""
    h = (np.asarray(tids, np.uint64) * np.uint64(2654435761)) \
        % np.uint64(2 ** 32)
    return 0.5 + h.astype(np.float64) / 2.0 ** 32


def daly_checkpoint_count(base_s, ovh: float, *, write_s: float):
    """Number of checkpoints the ``ovh`` budget affords over ``base_s``
    seconds of work — one per ``write_s / ovh`` base-seconds, i.e. the
    array form of ``ovh_checkpoint_period`` at a 1 s work step, with the
    engines' historical truncation semantics (so the default periodic
    schedule is bit-identical to the pre-axis formula)."""
    base = np.asarray(base_s, np.float64)
    return np.maximum(1, (ovh * base / write_s).astype(np.int64))


def randomized_checkpoint_count(base_s, ovh: float, *, write_s: float,
                                tids):
    """Randomized checkpoint schedule (arxiv 2601.14612): each task's
    interval is the Daly period ``ovh_checkpoint_period(1.0, write_s,
    ovh)`` scaled by a deterministic per-task factor in [0.5, 1.5), so
    the fleet's checkpoints de-synchronize while the expected overhead
    stays on the ``ovh`` budget."""
    base = np.asarray(base_s, np.float64)
    period = float(ovh_checkpoint_period(1.0, write_s, ovh))
    per = np.maximum(1.0, np.floor(period * _tid_jitter(tids)))
    return np.maximum(1, (base / per).astype(np.int64))


def checkpoint_schedule(base_s, ovh: float, mode: str = "periodic", *,
                        write_s: float, tids=None
                        ) -> tuple[np.ndarray, np.ndarray]:
    """(total, cp) float32 arrays for the engines' task axis.

    ``total`` is the work inflated by the checkpoint overhead budget and
    ``cp`` the checkpoint grid spacing a preemption rolls back to:

    * ``"periodic"`` — the paper's Daly-style uniform grid; bit-identical
      to the historical ``sim.mc_engine._plan_arrays`` /
      ``core.runtime.TaskRun`` formula;
    * ``"off"`` — no checkpoints are ever written: no overhead is paid
      (``total == base``) and ``cp == total``, so a preempted task loses
      *all* progress;
    * ``"random"`` — per-task randomized intervals via
      ``randomized_checkpoint_count`` (requires ``tids``).
    """
    base = np.asarray(base_s, np.float64)
    if mode == "off":
        total = base.astype(np.float32)
        return total, total.copy()
    total = (base * (1.0 + ovh)).astype(np.float32)
    if mode == "periodic":
        n_cp = daly_checkpoint_count(base, ovh, write_s=write_s)
    elif mode == "random":
        if tids is None:
            raise ValueError("checkpoint mode 'random' needs task ids")
        n_cp = randomized_checkpoint_count(base, ovh, write_s=write_s,
                                           tids=tids)
    else:
        raise ValueError(f"unknown checkpoint mode {mode!r} "
                         f"(one of {CHECKPOINT_MODES})")
    cp = (total / (n_cp + 1)).astype(np.float32)
    return total, cp
