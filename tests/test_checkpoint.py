"""Fault-tolerance module unit tests (``repro.ft.checkpoint``).

Covers the Daly-period arithmetic behind the policy lattice's checkpoint
axis (periodic | off | random — DESIGN.md §2.8): the ``ovh`` budget's
period, the per-task jitter and the engines' ``(total, cp)`` schedules.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.runtime import CHECKPOINT_WRITE_S
from repro.ft.checkpoint import (CHECKPOINT_MODES, _tid_jitter,
                                 checkpoint_schedule,
                                 daly_checkpoint_count,
                                 ovh_checkpoint_period,
                                 randomized_checkpoint_count)


# ---------------------------------------------------------------------------
# ovh_checkpoint_period edges
# ---------------------------------------------------------------------------
def test_ovh_period_rejects_nonpositive_budget():
    for ovh in (0.0, -0.1):
        with pytest.raises(ValueError, match="must be positive"):
            ovh_checkpoint_period(60.0, 5.0, ovh=ovh)


def test_ovh_period_degenerate_step_time():
    # a zero/negative step time can't amortize anything: checkpoint every
    # step rather than divide by zero
    assert ovh_checkpoint_period(0.0, 5.0, ovh=0.10) == 1
    assert ovh_checkpoint_period(-3.0, 5.0, ovh=0.10) == 1


def test_ovh_period_grows_as_budget_shrinks():
    """ovh -> 0+ means ever sparser checkpoints (monotone, unbounded)."""
    periods = [ovh_checkpoint_period(60.0, 5.0, ovh=o)
               for o in (0.4, 0.2, 0.1, 0.05, 0.01, 0.001)]
    assert periods == sorted(periods)
    assert periods[0] >= 1 and periods[-1] >= 80
    # exact form: ceil(ckpt / (ovh * step))
    assert ovh_checkpoint_period(60.0, 5.0, ovh=0.10) == 1
    assert ovh_checkpoint_period(10.0, 5.0, ovh=0.10) == 5


@given(step_time=st.floats(0.01, 10.0), ovh=st.floats(0.01, 0.5))
@settings(max_examples=50, deadline=None)
def test_ovh_period_bounds_overhead(step_time, ovh):
    """Checkpoint cadence honours the paper's ovh budget."""
    ckpt = 0.5
    period = ovh_checkpoint_period(step_time, ckpt, ovh)
    assert period >= 1
    # overhead fraction with this period stays within ~budget
    assert ckpt / (period * step_time) <= ovh * 1.5 + 1e-9


# ---------------------------------------------------------------------------
# checkpoint_schedule modes
# ---------------------------------------------------------------------------
def test_schedule_periodic_matches_historical_formula():
    """'periodic' must stay bit-identical to the pre-axis engine formula
    (the goldens depend on it)."""
    base = np.array([30.0, 102.0, 333.3, 600.0])
    ovh = 0.10
    total, cp = checkpoint_schedule(base, ovh, "periodic",
                                    write_s=CHECKPOINT_WRITE_S)
    want_total = (base * (1.0 + ovh)).astype(np.float32)
    want_n = np.maximum(1, (ovh * base / CHECKPOINT_WRITE_S).astype(np.int64))
    np.testing.assert_array_equal(total, want_total)
    np.testing.assert_array_equal(cp, (want_total / (want_n + 1)
                                       ).astype(np.float32))
    assert total.dtype == cp.dtype == np.float32


def test_schedule_off_pays_nothing_and_loses_everything():
    base = np.array([30.0, 102.0, 600.0])
    total, cp = checkpoint_schedule(base, 0.10, "off",
                                    write_s=CHECKPOINT_WRITE_S)
    np.testing.assert_array_equal(total, base.astype(np.float32))
    np.testing.assert_array_equal(cp, total)    # one period == whole task
    assert cp is not total                      # caller may mutate either


def test_schedule_random_is_deterministic_per_tid():
    base = np.full(64, 240.0)
    tids = np.arange(64)
    t1, c1 = checkpoint_schedule(base, 0.10, "random",
                                 write_s=CHECKPOINT_WRITE_S, tids=tids)
    t2, c2 = checkpoint_schedule(base, 0.10, "random",
                                 write_s=CHECKPOINT_WRITE_S, tids=tids)
    np.testing.assert_array_equal(c1, c2)       # pure function of identity
    np.testing.assert_array_equal(t1, t2)
    # same work, different tids -> de-synchronized periods
    assert len(np.unique(c1)) > 1
    # overhead inflation identical to periodic; only the grid is jittered
    np.testing.assert_array_equal(t1, (base * 1.1).astype(np.float32))


def test_schedule_random_requires_tids_and_rejects_unknown_mode():
    with pytest.raises(ValueError, match="needs task ids"):
        checkpoint_schedule([60.0], 0.10, "random",
                            write_s=CHECKPOINT_WRITE_S)
    with pytest.raises(ValueError, match="unknown checkpoint mode"):
        checkpoint_schedule([60.0], 0.10, "adaptive",
                            write_s=CHECKPOINT_WRITE_S)
    assert set(CHECKPOINT_MODES) == {"periodic", "off", "random"}


def test_tid_jitter_bounds_and_counts():
    j = _tid_jitter(np.arange(10_000))
    assert (0.5 <= j).all() and (j < 1.5).all()
    assert len(np.unique(j)) > 9_000            # hash, not a constant
    # randomized counts stay within the 2x jitter band of the Daly count
    base = np.full(256, 300.0)
    n_daly = daly_checkpoint_count(base, 0.10, write_s=CHECKPOINT_WRITE_S)
    n_rand = randomized_checkpoint_count(base, 0.10,
                                         write_s=CHECKPOINT_WRITE_S,
                                         tids=np.arange(256))
    assert (n_rand >= 1).all()
    assert (n_rand >= n_daly // 2).all() and (n_rand <= n_daly * 2 + 1).all()

