"""Arithmetic shared by the metric readers in ``bench/metrics/``."""
from __future__ import annotations

import inspect
import sys

from bench import roofline


def _requests(run) -> int:
    return run.trace["n_requests"] if run.trace else 0


def module_ms_per_request(run, fragment: str):
    """Device milliseconds per request of the programs whose name holds
    ``fragment``; None where the trace shows none."""
    if not _requests(run):
        return None
    s = sum(t for name, t in run.trace["per_module_s"].items()
            if fragment in name)
    return 1e3 * s / _requests(run) if s > 0 else None


def idle_pct(run):
    if not run.trace or run.trace["window_s"] <= 0 or \
            run.trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def kernel_roofline(run, kernel: str):
    """Roofline share of one kernel at the cell's shapes; None where the
    trace shows no call of it or the cell has no such shapes."""
    if not run.trace or run.peaks is None:
        return None
    shapes = run.cell.shapes()
    fn = getattr(roofline, kernel)
    params = inspect.signature(fn).parameters
    if not set(params) <= set(shapes):
        return None
    nbytes, ops = fn(**{k: shapes[k] for k in params})
    got = roofline.share(roofline.durations(run.trace, kernel), nbytes, ops,
                         run.peaks)
    if got is None:
        return None
    pct, bound = got
    print(f"{kernel}_roofline: {pct!r} % bound by {bound} "
          f"({nbytes:.0f} bytes, {ops:.0f} ops per call)", file=sys.stderr)
    return pct
