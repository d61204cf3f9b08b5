"""The program's spans in a traced window: where a request's host time and
the device's idle time go.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs ``bench/run.py``'s traced window (``--trace 1``) unchanged, result
line included, and prints one more line on standard error, ``program
spans: {...}``, reduced from the same trace:

* ``self_ms_per_request``: each program span's self time (its duration
  less the part its child program spans cover) over the requests;
* ``idle_by_span_s``: the busiest device's idle time inside the requests,
  each stretch named after the innermost program span running then, or
  ``outside program spans``; a gap that spans several phases is split
  between them;
* ``modules_per_request``: device program launches (``XLA Modules``
  executions) over the requests;
* ``plan_ms_mean``, the mean duration of the ``plan`` span, and
  ``requests_per_s``, requests over the traced window.

The program names its spans in ``repro.obs.SPANS``; ``PROGRAM_SPANS`` is a
literal copy, held to it by a test.  Python-tracer frames and runtime
events are not program spans.
"""
from __future__ import annotations

import heapq
import json
import os
import sys
from collections import defaultdict

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (_ROOT, os.path.join(_ROOT, "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)

from bench import trace_reduce as tr  # noqa: E402

#: the program's span names (``repro.obs.SPANS``)
PROGRAM_SPANS = ("plan", "greedy.seed", "ils.prepare", "ils.keys",
                 "ils.search", "burst.alloc")
OUTSIDE = "outside program spans"


def _timeline(trace: tr.Trace, lo: int, hi: int) -> list:
    """[lo, hi] cut into disjoint (start, end, name) pieces, each named
    after the innermost (shortest) program span covering it, or
    ``OUTSIDE``."""
    spans = sorted((max(s, lo), min(e, hi), n) for s, e, n in trace.host
                   if n in PROGRAM_SPANS and min(e, hi) > max(s, lo))
    points = sorted({lo, hi} | {x for s, e, _ in spans for x in (s, e)})
    pieces: list = []
    heap: list = []
    j = 0
    for a, b in zip(points, points[1:]):
        while j < len(spans) and spans[j][0] <= a:
            s, e, n = spans[j]
            heapq.heappush(heap, (e - s, e, n))
            j += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        name = heap[0][2] if heap else OUTSIDE
        if pieces and pieces[-1][2] == name:
            pieces[-1][1] = b
        else:
            pieces.append([a, b, name])
    return pieces


def _idle(trace: tr.Trace, lo: int, hi: int) -> list:
    """The busiest device's idle (start, end) gaps inside [lo, hi]."""
    by_dev = defaultdict(list)
    for dev, s, e, _, _ in trace.ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            by_dev[dev].append((s, e))
    if not by_dev:
        return [(lo, hi)]
    busy = max((tr.union(iv) for iv in by_dev.values()),
               key=lambda iv: sum(e - s for s, e in iv))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0]


def span_self_s(trace: tr.Trace) -> dict:
    """Program span name -> summed self time (s) inside the requests."""
    lo, hi = tr.request_window(trace)
    out: dict = defaultdict(float)
    for s, e, name in _timeline(trace, lo, hi):
        if name != OUTSIDE:
            out[name] += (e - s) * 1e-9
    return dict(out)


def idle_by_span_s(trace: tr.Trace) -> dict:
    """Program span name (or ``OUTSIDE``) -> the busiest device's idle
    seconds inside the requests while it was the innermost span."""
    lo, hi = tr.request_window(trace)
    pieces = _timeline(trace, lo, hi)
    out: dict = defaultdict(float)
    k = 0
    for g0, g1 in _idle(trace, lo, hi):
        while pieces[k][1] <= g0:
            k += 1
        i = k
        while i < len(pieces) and pieces[i][0] < g1:
            s, e, name = pieces[i]
            out[name] += (min(e, g1) - max(s, g0)) * 1e-9
            i += 1
    return dict(out)


def _n_requests(trace: tr.Trace) -> int:
    return sum(1 for _, _, n in trace.host if n == tr.REQUEST_SPAN)


def modules_per_request(trace: tr.Trace) -> float:
    """Device program launches inside the requests, per request."""
    lo, hi = tr.request_window(trace)
    n = sum(1 for _, s, e, _ in trace.modules if min(e, hi) > max(s, lo))
    return n / _n_requests(trace)


def reduce_spans(trace: tr.Trace) -> dict:
    """Every number of the ``program spans`` line."""
    lo, hi = tr.request_window(trace)
    n = _n_requests(trace)
    plans = [e - s for s, e, name in trace.host
             if name == "plan" and s >= lo and e <= hi]
    return {
        "n_requests": n,
        "self_ms_per_request": {k: 1e3 * v / n
                                for k, v in span_self_s(trace).items()},
        "plan_ms_mean": 1e-6 * sum(plans) / len(plans) if plans else None,
        "idle_by_span_s": idle_by_span_s(trace),
        "modules_per_request": modules_per_request(trace),
        "requests_per_s": n / ((hi - lo) * 1e-9),
    }


def reporting(reduce):
    """``reduce`` (``trace_reduce.reduce``), printing the program spans of
    the trace on standard error besides; its result is unchanged."""
    def reduce_and_report(trace: tr.Trace) -> dict:
        got = reduce(trace)
        print("program spans: " + json.dumps(reduce_spans(trace)),
              file=sys.stderr, flush=True)
        return got
    return reduce_and_report


def main(argv=None, require_tpu: bool = True) -> int:
    from bench import run
    plain = tr.reduce
    tr.reduce = reporting(plain)
    try:
        args = list(sys.argv[1:] if argv is None else argv)
        return run.main(args + ["--trace", "1"], require_tpu=require_tpu)
    finally:
        tr.reduce = plain


if __name__ == "__main__":
    sys.exit(main())
