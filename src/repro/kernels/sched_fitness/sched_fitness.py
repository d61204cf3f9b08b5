"""Pallas TPU kernels: population scheduling-fitness reductions.

The ILS hot-spot is evaluating thousands of candidate allocation vectors per
step (DESIGN.md §2.1).  The MXU is useless here (integer compare/select
reductions), so both kernels target the VPU.

``population_reduce`` — the full path: one [pb, V] accumulator set in VMEM
per population tile, streaming task tiles; the VM axis (padded to the
128-lane register width) is the minor dimension.  Grid: (P / pb, B / tb) —
the task axis is the *sequential* minor grid dim so output tiles are
revisited and accumulated in place.

``delta_population_fitness`` — the incremental path: a candidate move only
touches its n source columns plus one destination column, so instead of
re-reducing the whole [B, V] problem per candidate it re-reduces just those
C = n + 1 columns (streamed over task tiles), splices them into the
once-per-iteration base reductions, and finalises Eq. 8 in-kernel.  Work per
candidate drops from O(B·V) to O(C·B + V); candidate allocation vectors
([P, K, B]) are never built — the path's footprint is the gathered
e-columns tensor, O(P·K·C·B) f32, traded for the V-fold compute win.
Grid: (P, B / tb); a chain's C·K (touched column, proposal) pairs ride
as the rows of 2-D [C·K, tb] tiles, so Mosaic never splits the sublane
dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128   # TPU vector lane width; V is padded to this


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The one rule for Pallas interpret mode: interpret exactly when the
    default backend is the CPU.  An explicit bool wins — a test compiling
    for a described TPU from a CPU process passes ``False``."""
    return jax.default_backend() == "cpu" if interpret is None else interpret


def _pad_vms(v: int) -> int:
    """Pad the VM axis to the lane width, always reserving >= 1 pad column
    (padded tasks are parked on column ``v_pad - 1``, which must not be a
    real VM even when V is an exact lane multiple)."""
    return ((v + LANE) // LANE) * LANE


def _kernel(alloc_ref, e_ref, rm_ref, loads_ref, maxe_ref, cnt_ref,
            maxmem_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        loads_ref[...] = jnp.zeros_like(loads_ref)
        maxe_ref[...] = jnp.zeros_like(maxe_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        maxmem_ref[...] = jnp.zeros_like(maxmem_ref)

    alloc = alloc_ref[...]                                  # [pb, tb] int32
    e = e_ref[...]                                          # [tb, V]
    rm = rm_ref[...]                                        # [tb, 1]
    v_ids = jax.lax.broadcasted_iota(jnp.int32, (1, 1, e.shape[1]), 2)
    onehot = (alloc[:, :, None] == v_ids).astype(e.dtype)   # [pb, tb, V]

    loads_ref[...] += jnp.sum(onehot * e[None], axis=1)
    cnt_ref[...] += jnp.sum(onehot, axis=1)
    maxe_ref[...] = jnp.maximum(
        maxe_ref[...], jnp.max(onehot * e[None], axis=1))
    maxmem_ref[...] = jnp.maximum(
        maxmem_ref[...], jnp.max(onehot * rm[None], axis=1))


@functools.partial(jax.jit, static_argnames=("pb", "tb", "interpret"))
def population_reduce(alloc: jax.Array, e: jax.Array, rm: jax.Array,
                      *, pb: int = 8, tb: int = 128,
                      interpret: bool | None = None):
    """alloc int32 [P, B]; e f32 [B, V]; rm f32 [B] ->
    (loads, maxe, cnt, maxmem) each f32 [P, V]."""
    p, b = alloc.shape
    v = e.shape[1]
    # pad: V to LANE (mapping padded tasks to a padded VM column), B to tb,
    # P to pb
    v_pad = _pad_vms(v)
    b_pad = ((b + tb - 1) // tb) * tb
    p_pad = ((p + pb - 1) // pb) * pb
    alloc = jnp.pad(alloc, ((0, p_pad - p), (0, b_pad - b)),
                    constant_values=v_pad - 1)   # padded tasks -> pad VM
    e = jnp.pad(e.astype(jnp.float32), ((0, b_pad - b), (0, v_pad - v)))
    rm = jnp.pad(rm.astype(jnp.float32), (0, b_pad - b))[:, None]

    grid = (p_pad // pb, b_pad // tb)
    out_shape = [jax.ShapeDtypeStruct((p_pad, v_pad), jnp.float32)
                 for _ in range(4)]
    out_spec = pl.BlockSpec((pb, v_pad), lambda i, j: (i, 0))
    loads, maxe, cnt, maxmem = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((pb, tb), lambda i, j: (i, j)),
                  pl.BlockSpec((tb, v_pad), lambda i, j: (j, 0)),
                  pl.BlockSpec((tb, 1), lambda i, j: (j, 0))],
        out_specs=[out_spec] * 4,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(alloc, e, rm)
    return (loads[:p, :v], maxe[:p, :v], cnt[:p, :v], maxmem[:p, :v])


def _delta_kernel(alloc_ref, ecols_ref, info_ref, rm_ref,
                  bl_ref, bx_ref, bc_ref, bm_ref,
                  cores_ref, mem_ref, price_ref, limit_ref, par_ref,
                  fit_ref, cost_ref, mkp_ref,
                  sl, sx, sc, sm):
    """Incremental candidate scoring for one population chain.

    Rows are (touched column slot c, candidate k) pairs, slot-major
    (``r = c·Kp + k``), so every operand is a 2-D tile with tasks on the
    lanes.  Streams task tiles (sequential grid dim 1) and re-reduces each
    row's column into [R, 1] scratch; the last tile splices the C slots
    into the chain's base [V] row per candidate and finalises Eq. 8.
    ``info`` lanes: 0 = column id, 1 = is-destination flag, 2.. = the
    candidate's moved task ids.
    """
    j = pl.program_id(1)
    tb = alloc_ref.shape[1]
    kp = fit_ref.shape[0]
    c = ecols_ref.shape[0] // kp
    n = info_ref.shape[1] - 2

    @pl.when(j == 0)
    def _init():
        sl[...] = jnp.zeros_like(sl)
        sx[...] = jnp.zeros_like(sx)
        sc[...] = jnp.zeros_like(sc)
        sm[...] = jnp.zeros_like(sm)

    alloc = alloc_ref[...]                        # [1, tb] int32
    ecols = ecols_ref[...]                        # [R, tb] e[t, col(r)]
    info = info_ref[...]                          # [R, 2 + n] int32
    rm = rm_ref[...]                              # [1, tb]

    # new occupancy of column col(r) under candidate k(r), this task
    # tile: a task sits there iff it stayed (assigned and not moved) or
    # the column is the destination and the task was moved there.
    t_glob = j * tb + jax.lax.broadcasted_iota(jnp.int32, (1, tb), 1)
    moved = info[:, 2:3] == t_glob                            # [R, tb]
    for i in range(1, n):
        moved = moved | (info[:, 2 + i:3 + i] == t_glob)
    stay = (alloc == info[:, 0:1]) & jnp.logical_not(moved)
    on = stay | (moved & (info[:, 1:2] > 0))
    w = jnp.where(on, ecols, 0.0)

    sl[...] += jnp.sum(w, axis=1, keepdims=True)
    sc[...] += jnp.sum(jnp.where(on, 1.0, 0.0), axis=1, keepdims=True)
    sx[...] = jnp.maximum(sx[...], jnp.max(w, axis=1, keepdims=True))
    sm[...] = jnp.maximum(sm[...], jnp.max(jnp.where(on, rm, 0.0), axis=1,
                                           keepdims=True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalise():
        vp = bl_ref.shape[1]
        viota = jax.lax.broadcasted_iota(jnp.int32, (kp, vp), 1)
        rows_l = jnp.broadcast_to(bl_ref[...], (kp, vp))
        rows_x = jnp.broadcast_to(bx_ref[...], (kp, vp))
        rows_c = jnp.broadcast_to(bc_ref[...], (kp, vp))
        rows_m = jnp.broadcast_to(bm_ref[...], (kp, vp))
        for i in range(c):    # splice the C re-reduced columns (duplicates
            lo = i * kp       # carry equal values)
            msk = viota == info_ref[lo:lo + kp, 0:1]
            rows_l = jnp.where(msk, sl[lo:lo + kp, :], rows_l)
            rows_x = jnp.where(msk, sx[lo:lo + kp, :], rows_x)
            rows_c = jnp.where(msk, sc[lo:lo + kp, :], rows_c)
            rows_m = jnp.where(msk, sm[lo:lo + kp, :], rows_m)

        par = par_ref[...]                         # [1, LANE]
        alpha, scale = par[:, 0:1], par[:, 1:2]
        boot_s, deadline = par[:, 2:3], par[:, 3:4]
        cores = cores_ref[...]                     # [1, vp]
        mksp = jnp.where(rows_c > 0,
                         jnp.maximum(rows_l / cores, rows_x) + boot_s, 0.0)
        mem_peak = rows_m * jnp.minimum(rows_c, cores)
        bad = jnp.where((mem_peak > mem_ref[...] + 1e-6) |
                        (mksp > limit_ref[...] + 1e-6), 1.0, 0.0)
        cost = jnp.sum(price_ref[...] * jnp.maximum(mksp - boot_s, 0.0),
                       axis=1, keepdims=True)
        mkp = jnp.max(mksp, axis=1, keepdims=True)
        fit = alpha * cost / scale + (1 - alpha) * mkp / deadline
        fit_ref[...] = jnp.where(jnp.max(bad, axis=1, keepdims=True) > 0,
                                 jnp.inf, fit)
        cost_ref[...] = cost
        mkp_ref[...] = mkp


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def delta_population_fitness(alloc: jax.Array, t_idx: jax.Array,
                             dest: jax.Array, base, e: jax.Array,
                             rm: jax.Array, vm_cores, vm_mem, vm_price,
                             limit, params, *, tb: int = 128,
                             interpret: bool | None = None):
    """Score P·K candidate moves incrementally against base reductions.

    alloc int32 [P, B]; t_idx int32 [P, K, n] (task ids relocated per
    candidate); dest int32 [P, K]; base = (loads, maxe, cnt, maxmem) each
    f32 [P, V] for ``alloc`` (from ``population_reduce``); limit f32 [V] is
    the per-VM finish deadline; params f32 [4] = (alpha, cost_scale, boot_s,
    deadline).  Returns (fitness, cost, makespan) each f32 [P, K].
    Grid: (P, B / tb) — one chain per program.
    """
    p, b = alloc.shape
    _, k, n = t_idx.shape
    v = e.shape[1]
    c = n + 1
    v_pad = _pad_vms(v)
    b_pad = ((b + tb - 1) // tb) * tb
    k_pad = ((k + 7) // 8) * 8
    r = c * k_pad

    pi = jnp.arange(p)[:, None, None]
    src = alloc[pi, t_idx]                                    # [P, K, n]
    cols = jnp.concatenate([src, dest[:, :, None]], axis=2)   # [P, K, C]
    pad_k = ((0, 0), (0, k_pad - k), (0, 0))
    cols = jnp.pad(cols, pad_k,
                   constant_values=v_pad - 1)    # pad candidates -> pad VM
    m = jnp.pad(t_idx, pad_k, constant_values=b_pad - 1)
    # slot-major rows r = c·Kp + k: column id, is-destination, moved ids
    is_dest = (cols == cols[:, :, c - 1:c]).astype(jnp.int32)
    info = jnp.concatenate(
        [cols.transpose(0, 2, 1).reshape(p, r, 1),
         is_dest.transpose(0, 2, 1).reshape(p, r, 1),
         jnp.broadcast_to(m[:, None], (p, c, k_pad, n)).reshape(p, r, n)],
        axis=2)

    ep = jnp.pad(e.astype(jnp.float32), ((0, b_pad - b), (0, v_pad - v)))
    ecols = ep.T[info[:, :, 0]]                   # one O(PKCB) gather
    alloc_p = jnp.pad(alloc, ((0, 0), (0, b_pad - b)),
                      constant_values=v_pad - 1)[:, None]
    rm_p = jnp.pad(rm.astype(jnp.float32), (0, b_pad - b))[None]

    pad_v = ((0, 0), (0, v_pad - v))
    bl, bx, bc, bm = (jnp.pad(x.astype(jnp.float32), pad_v)[:, None]
                      for x in base)
    cores = jnp.pad(vm_cores.astype(jnp.float32), (0, v_pad - v),
                    constant_values=1.0)[None]   # 1.0: keep pad cols /-safe
    memv = jnp.pad(vm_mem.astype(jnp.float32), (0, v_pad - v))[None]
    price = jnp.pad(vm_price.astype(jnp.float32), (0, v_pad - v))[None]
    limit = jnp.pad(limit.astype(jnp.float32), (0, v_pad - v))[None]
    par = jnp.zeros((1, LANE), jnp.float32).at[0, :4].set(
        params.astype(jnp.float32))

    grid = (p, b_pad // tb)
    row_spec = pl.BlockSpec((None, 1, v_pad), lambda i, j: (i, 0, 0))
    vm_spec = pl.BlockSpec((1, v_pad), lambda i, j: (0, 0))
    out_spec = pl.BlockSpec((None, k_pad, 1), lambda i, j: (i, 0, 0))
    fit, cost, mkp = pl.pallas_call(
        _delta_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, tb), lambda i, j: (i, 0, j)),     # alloc
            pl.BlockSpec((None, r, tb), lambda i, j: (i, 0, j)),     # ecols
            pl.BlockSpec((None, r, 2 + n), lambda i, j: (i, 0, 0)),  # info
            pl.BlockSpec((1, tb), lambda i, j: (0, j)),              # rm
            row_spec, row_spec, row_spec, row_spec,                  # base
            vm_spec, vm_spec, vm_spec, vm_spec,                      # vm data
            pl.BlockSpec((1, LANE), lambda i, j: (0, 0)),            # params
        ],
        out_specs=[out_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((p, k_pad, 1), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((r, 1), jnp.float32) for _ in range(4)],
        interpret=resolve_interpret(interpret),
    )(alloc_p, ecols, info, rm_p, bl, bx, bc, bm,
      cores, memv, price, limit, par)
    return fit[:, :k, 0], cost[:, :k, 0], mkp[:, :k, 0]
