"""Operations and bytes of the program's Pallas kernels, from their shapes.

Each function gives what the algorithm of one kernel call needs: the
bytes of its operands and results at their real (unpadded) sizes, each
moved once between HBM and the core, and the arithmetic operations per
element of its reductions.  Padding, the one-hot lanes and the gathers
the wrappers build are the implementation's, not the algorithm's, so a
kernel's share can only rise when a later change removes them.

In a TPU trace a kernel's calls are the ``tpu_custom_call`` operations
named after the jitted wrapper that holds the ``pallas_call``
(``%population_reduce.3 = (...) custom-call(...)``); the ``pallas_call``s have
no explicit ``name=``, so ``durations`` matches on that name.
"""
from __future__ import annotations

F32 = I32 = 4


def population_reduce(p: int, b: int, v: int) -> tuple[float, float]:
    """alloc i32 [P, B], e f32 [B, V], rm f32 [B] in; loads, maxe, cnt,
    maxmem f32 [P, V] out; two sums and two maxima per task."""
    return (I32 * p * b + F32 * b * v + F32 * b + 4 * F32 * p * v,
            4.0 * p * b)


def delta_population_fitness(p: int, k: int, n: int, b: int, v: int
                             ) -> tuple[float, float]:
    """alloc i32 [P, B], e f32 [B, V], rm f32 [B], the moves (t_idx
    [P, K, n], dest [P, K]), the base reductions 4 x f32 [P, V] and four
    VM rows in; fitness, cost, makespan f32 [P, K] out.  Per candidate,
    the n + 1 touched columns are re-reduced over the tasks (two sums,
    two maxima per task) and Eq. 8 is finished over the V columns (about
    ten operations each)."""
    c = n + 1
    nbytes = (I32 * p * b + F32 * b * v + F32 * b + I32 * p * k * c
              + 4 * F32 * p * v + 4 * F32 * v + 3 * F32 * p * k)
    return nbytes, 4.0 * p * k * c * b + 10.0 * p * k * v


def share(durations_s: list[float], nbytes: float, ops: float,
          peaks: dict) -> tuple[float, str] | None:
    """Least time over measured time, in percent, for calls of one shape,
    and which of the two bounds sets the least time."""
    if not durations_s:
        return None
    t_mem = nbytes / peaks["bytes_per_s"]
    t_ops = ops / peaks["flops_per_s"]
    least = max(t_mem, t_ops) * len(durations_s)
    bound = "bytes" if t_mem >= t_ops else "ops"
    return 100.0 * least / sum(durations_s), bound


def durations(reduced: dict, kernel: str) -> list[float]:
    """Device seconds of each call of ``kernel`` in the reduced trace."""
    out = []
    for name, ds in reduced["op_durations"].items():
        if name == "%" + kernel or name.startswith("%" + kernel + "."):
            out += ds
    return out
