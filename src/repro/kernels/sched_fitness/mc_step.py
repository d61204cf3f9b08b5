"""Pallas TPU kernels: Monte-Carlo VM reductions + fused span advance.

The batched hibernation engine (``repro.sim.mc_engine``) advances S
scenarios in lockstep; every step it needs, per scenario and per VM column,
the remaining committed load, the unfinished-task count (whose zero set is
the idle mask driving Alg. 5 stealing and AC termination) and the largest
single remaining task (the deferred-HADS safety bound).  All three are
reductions of the [S, B] assignment against the [S, B] remaining-work
vector, so — like ``population_reduce`` — the kernels stream task tiles
over a ``(S / sb, B / tb)`` grid with the task axis as the sequential minor
grid dim, accumulating into revisited [sb, V] VMEM output tiles; the VM
axis is padded to the 128-lane register width with ≥ 1 pad column reserved
for masked-out tasks (done, unassigned, or padding).

Two kernels share that tiling:

* ``mc_vm_reduce`` — the per-step [S, B] → [S, V] reduction alone
  (DESIGN.md §2.3);
* ``mc_span_reduce`` — the event-horizon engine's fused span advance
  (DESIGN.md §2.5): remaining work is decremented by ``m`` uniform slots'
  progress (``rem - m·drem``, exact because the span is completion-free
  by construction) *and* the three reductions of the advanced vector are
  accumulated in the same pass, so the [S, B] state makes one HBM round
  trip per engine iteration instead of two (progress write + stats read).
  The span length rides in as a ``[1, 1]`` VMEM scalar, following the
  params-row idiom of ``delta_population_fitness``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .sched_fitness import _pad_vms, resolve_interpret


def _mc_kernel(cols_ref, w_ref, load_ref, cnt_ref, maxw_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        load_ref[...] = jnp.zeros_like(load_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        maxw_ref[...] = jnp.zeros_like(maxw_ref)

    cols = cols_ref[...]                                    # [sb, tb] int32
    w = w_ref[...]                                          # [sb, tb] f32
    v_ids = jax.lax.broadcasted_iota(jnp.int32, (1, 1, load_ref.shape[1]), 2)
    onehot = (cols[:, :, None] == v_ids).astype(w.dtype)    # [sb, tb, V]

    load_ref[...] += jnp.sum(onehot * w[:, :, None], axis=1)
    cnt_ref[...] += jnp.sum(onehot, axis=1)
    maxw_ref[...] = jnp.maximum(
        maxw_ref[...], jnp.max(onehot * w[:, :, None], axis=1))


@functools.partial(jax.jit, static_argnames=("v", "sb", "tb", "interpret"))
def mc_vm_reduce(cols: jax.Array, w: jax.Array, v: int, *, sb: int = 8,
                 tb: int = 128, interpret: bool | None = None):
    """cols int32 [S, B] (VM column per task, anything outside [0, v) is
    ignored); w f32 [S, B] (per-task weight, e.g. remaining base work) ->
    (load, cnt, maxw) each f32 [S, v]."""
    s, b = cols.shape
    v_pad = _pad_vms(v)
    b_pad = ((b + tb - 1) // tb) * tb
    s_pad = ((s + sb - 1) // sb) * sb
    # ignored tasks (and all padding) park on the reserved pad column
    cols = jnp.where((cols >= 0) & (cols < v), cols, v_pad - 1)
    cols = jnp.pad(cols, ((0, s_pad - s), (0, b_pad - b)),
                   constant_values=v_pad - 1)
    w = jnp.pad(w.astype(jnp.float32), ((0, s_pad - s), (0, b_pad - b)))

    grid = (s_pad // sb, b_pad // tb)
    out_spec = pl.BlockSpec((sb, v_pad), lambda i, j: (i, 0))
    load, cnt, maxw = pl.pallas_call(
        _mc_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((sb, tb), lambda i, j: (i, j)),
                  pl.BlockSpec((sb, tb), lambda i, j: (i, j))],
        out_specs=[out_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((s_pad, v_pad), jnp.float32)] * 3,
        interpret=resolve_interpret(interpret),
    )(cols, w)
    return load[:s, :v], cnt[:s, :v], maxw[:s, :v]


def _span_kernel(m_ref, cols_ref, rem_ref, drem_ref,
                 rem_out_ref, load_ref, cnt_ref, maxw_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        load_ref[...] = jnp.zeros_like(load_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        maxw_ref[...] = jnp.zeros_like(maxw_ref)

    m = m_ref[...]                                # [sb, 1] f32 span slots
    cols = cols_ref[...]                                    # [sb, tb] int32
    rem = rem_ref[...]                                      # [sb, tb] f32
    new = jnp.maximum(rem - m * drem_ref[...], 0.0)
    rem_out_ref[...] = new

    # reductions of the *advanced* remaining work; the pending set is
    # span-invariant (no completions inside a span), so masking on the new
    # vector equals masking on the old one
    w = jnp.where(new > 0.0, new, 0.0)
    v_ids = jax.lax.broadcasted_iota(jnp.int32, (1, 1, load_ref.shape[1]), 2)
    onehot = ((cols[:, :, None] == v_ids) &
              (new[:, :, None] > 0.0)).astype(rem.dtype)    # [sb, tb, V]
    load_ref[...] += jnp.sum(onehot * w[:, :, None], axis=1)
    cnt_ref[...] += jnp.sum(onehot, axis=1)
    maxw_ref[...] = jnp.maximum(
        maxw_ref[...], jnp.max(onehot * w[:, :, None], axis=1))


@functools.partial(jax.jit, static_argnames=("v", "sb", "tb", "interpret"))
def mc_span_reduce(cols: jax.Array, rem: jax.Array, drem: jax.Array,
                   m: jax.Array, v: int, *, sb: int = 8, tb: int = 128,
                   interpret: bool | None = None):
    """Fused event-horizon span advance + VM reductions (DESIGN.md §2.5).

    cols int32 [S, B] (VM column per task; anything outside [0, v) is
    ignored); rem f32 [S, B] remaining work; drem f32 [S, B] per-slot
    progress (constant across the span by construction); m f32 [S] — the
    per-scenario number of uniform slots to jump (scenarios step their
    own clocks).  Returns ``(rem_new, load, cnt, maxw)``:
    ``rem_new = max(rem − m·drem, 0)`` f32 [S, B] and the three
    reductions of ``rem_new``, each f32 [S, v].
    """
    s, b = cols.shape
    v_pad = _pad_vms(v)
    b_pad = ((b + tb - 1) // tb) * tb
    s_pad = ((s + sb - 1) // sb) * sb
    cols = jnp.where((cols >= 0) & (cols < v), cols, v_pad - 1)
    cols = jnp.pad(cols, ((0, s_pad - s), (0, b_pad - b)),
                   constant_values=v_pad - 1)
    rem = jnp.pad(rem.astype(jnp.float32), ((0, s_pad - s), (0, b_pad - b)))
    drem = jnp.pad(drem.astype(jnp.float32),
                   ((0, s_pad - s), (0, b_pad - b)))
    m_col = jnp.pad(jnp.asarray(m, jnp.float32).reshape(s, 1),
                    ((0, s_pad - s), (0, 0)))

    grid = (s_pad // sb, b_pad // tb)
    tile = pl.BlockSpec((sb, tb), lambda i, j: (i, j))
    out_spec = pl.BlockSpec((sb, v_pad), lambda i, j: (i, 0))
    rem_new, load, cnt, maxw = pl.pallas_call(
        _span_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((sb, 1), lambda i, j: (i, 0)),
                  tile, tile, tile],
        out_specs=[tile, out_spec, out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((s_pad, b_pad), jnp.float32),
                   jax.ShapeDtypeStruct((s_pad, v_pad), jnp.float32),
                   jax.ShapeDtypeStruct((s_pad, v_pad), jnp.float32),
                   jax.ShapeDtypeStruct((s_pad, v_pad), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(m_col, cols, rem, drem)
    return (rem_new[:s, :b], load[:s, :v], cnt[:s, :v], maxw[:s, :v])
