"""Operations and bytes of the Monte-Carlo engine's Pallas kernels.

As in ``bench.roofline``: what the algorithm of one kernel call needs at
its real (unpadded) sizes, each operand and result moved once between HBM
and the core, and the arithmetic operations per element of its
reductions.  ``s`` scenarios, ``b`` tasks, ``v`` plan columns.

The engine runs one ``mc_span_reduce`` call per loop iteration (the
event-horizon span advance with its reductions) and one ``mc_vm_reduce``
call per entry into a migration branch (the destination loads), so the
calls of a plan are counted by its ``MCResult.n_steps`` and
``MCResult.branch_entries``; in a TPU trace they are the operations named
``%mc_span_reduce.N`` and ``%mc_vm_reduce.N``.
"""
from __future__ import annotations

import sys

from bench import roofline

F32 = I32 = 4

#: the engine branches that call ``mc_vm_reduce`` once per entry
VM_REDUCE_BRANCHES = ("terminate", "hibernate", "defer", "retry")


def mc_span_reduce(s: int, b: int, v: int) -> tuple[float, float]:
    """cols i32 [S, B], rem and drem f32 [S, B], the span lengths f32 [S]
    in; the advanced rem f32 [S, B] and load, cnt, maxw f32 [S, V] out.
    Per task: the advance (a multiply and a subtract, a maximum) and the
    two sums and one maximum of the reductions."""
    return (I32 * s * b + 3 * F32 * s * b + F32 * s + 3 * F32 * s * v,
            6.0 * s * b)


def mc_vm_reduce(s: int, b: int, v: int) -> tuple[float, float]:
    """cols i32 [S, B], w f32 [S, B] in; load, cnt, maxw f32 [S, V] out;
    two sums and one maximum per task."""
    return I32 * s * b + F32 * s * b + 3 * F32 * s * v, 3.0 * s * b


def calls(rec: dict, name: str, kernel: str) -> int:
    """Calls of ``kernel`` in one plan evaluation of an eval record."""
    res = rec["results"][name]
    if kernel == "mc_span_reduce":
        return int(res["n_steps"])
    return sum(int(res["branch_entries"].get(k, 0))
               for k in VM_REDUCE_BRANCHES)


def kernel_roofline(run, kernel: str):
    """Roofline share of one engine kernel over the run's requests: the
    bytes and operations of each call at its plan's shapes, averaged over
    the calls the engine's counters report, against the kernel's device
    time in the trace (``bench.roofline.share``); None where the trace or
    the counters show no call."""
    records = getattr(run.cell, "records", None)
    if not run.trace or run.peaks is None or not records:
        return None
    fn = globals()[kernel]
    s, b = run.cell.shapes()["s"], run.cell.shapes()["b"]
    n = nbytes = ops = 0.0
    for rec in records:
        for name in rec["results"]:
            k = calls(rec, name, kernel)
            by, op = fn(s, b, run.cell.n_vms(name))
            n, nbytes, ops = n + k, nbytes + k * by, ops + k * op
    if n == 0:
        return None
    durations = roofline.durations(run.trace, kernel)
    got = roofline.share(durations, nbytes / n, ops / n, run.peaks)
    if got is None:
        return None
    pct, bound = got
    print(f"{kernel}_roofline: {pct!r} % bound by {bound} ({nbytes / n:.0f} "
          f"bytes, {ops / n:.0f} ops per call, {n:.0f} calls counted, "
          f"{len(durations)} traced)", file=sys.stderr)
    return pct
