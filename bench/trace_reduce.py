"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The trace is read with ``jax.profiler.ProfileData`` alone.  Three kinds of
events are kept:

* device operations: on an accelerator, the ``XLA Ops`` line of each
  ``/device:...`` plane; in a trace recorded on the CPU (the tests), the
  host events that carry an ``hlo_op`` stat;
* program executions: the ``XLA Modules`` line of each device plane, or,
  on the CPU, the span of each (module, run) group of operations;
* host spans: every other event of the non-device planes, among them the
  benchmark's own ``bench.request`` annotations.

``reduce`` clips all of it to the traced window (the first request's start
to the last request's end) and returns busy time per device (the union of
operation intervals), device time per program and per operation name, and
the idle gaps, each named after the innermost host span running at its
midpoint (with the Python tracer on, a host function such as
``$greedy.py:38 initial_solution``).
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
from collections import defaultdict

REQUEST_SPAN = "bench.request"
#: host spans that wrap whole requests; a gap is named after what runs
#: inside them
_WRAPPERS = (REQUEST_SPAN,)
#: operations that only hold others (a device loop, a branch)
_CONTAINERS = ("%while", "%cond", "%conditional", "%call")


@dataclasses.dataclass
class Trace:
    ops: list          # (device, start_ns, end_ns, name, module)
    modules: list      # (device, start_ns, end_ns, name)
    host: list         # (start_ns, end_ns, name)


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    cpu_groups: dict = {}
    planes = list(pd.planes)
    # only a trace with no device plane (recorded on the CPU) keeps its
    # operations among the host events; reading stats is slow, so the
    # host events of a device trace are never searched for them
    on_host = not any(p.name.startswith("/device:") and
                      any(line.name == "XLA Ops" for line in p.lines)
                      for p in planes)
    for plane in planes:
        if plane.name.startswith("/device:"):
            dev = plane.name
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        s = int(ev.start_ns)
                        ops.append((dev, s, s + int(ev.duration_ns),
                                    ev.name, None))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        s = int(ev.start_ns)
                        modules.append((dev, s, s + int(ev.duration_ns),
                                        ev.name))
            continue
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                hlo = _stat(ev, "hlo_op") if on_host else None
                if hlo is None:
                    host.append((s, e, ev.name))
                    continue
                dev = f"/device:CPU:{_stat(ev, 'device_ordinal') or 0}"
                mod = str(_stat(ev, "hlo_module"))
                ops.append((dev, s, e, ev.name, mod))
                key = (dev, mod, _stat(ev, "run_id"))
                lo, hi = cpu_groups.get(key, (s, e))
                cpu_groups[key] = (min(lo, s), max(hi, e))
    if not modules:
        modules = [(dev, lo, hi, mod)
                   for (dev, mod, _), (lo, hi) in cpu_groups.items()]
    return Trace(ops=ops, modules=modules, host=host)


def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def request_window(trace: Trace) -> tuple[int, int]:
    spans = [(s, e) for s, e, n in trace.host if n == REQUEST_SPAN]
    if not spans:
        raise ValueError(f"no {REQUEST_SPAN!r} span in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def op_name(text: str) -> str:
    """An operation's name: on a TPU the event carries the whole HLO
    instruction (``%fusion.12 = f32[...] fusion(...)``); keep its left
    side."""
    return text.split(" = ", 1)[0]


def _is_container(name: str) -> bool:
    """Control-flow operations whose interval holds other operations."""
    return name.startswith(_CONTAINERS)


def name_gaps(gaps, host) -> list[str]:
    """Name each (start, end) gap after the innermost host span running
    at its midpoint (the shortest one that contains it), request
    wrappers aside."""
    events = sorted((s, e, n) for s, e, n in host if n not in _WRAPPERS)
    order = sorted(range(len(gaps)), key=lambda k: gaps[k][0] + gaps[k][1])
    names = ["host: no span"] * len(gaps)
    heap: list = []
    j = 0
    for k in order:
        mid = (gaps[k][0] + gaps[k][1]) / 2
        while j < len(events) and events[j][0] <= mid:
            s, e, n = events[j]
            heapq.heappush(heap, (e - s, e, n))
            j += 1
        while heap and heap[0][1] < mid:
            heapq.heappop(heap)
        if heap:
            names[k] = heap[0][2]
    return names


def reduce(trace: Trace) -> dict:
    """Per-device busy time, device time per program and per operation
    name, and idle gaps by host span, all inside the request window.
    Times in seconds; ``busy_s`` is the mean over the devices that ran an
    operation."""
    lo, hi = request_window(trace)
    by_dev = defaultdict(list)
    per_op: dict = defaultdict(float)
    for dev, s, e, name, _ in trace.ops:
        s, e = _clip(s, e, lo, hi)
        if e > s:
            by_dev[dev].append((s, e))
            short = op_name(name)
            if not _is_container(short):
                per_op[short] += (e - s) * 1e-9
    per_module: dict = defaultdict(float)
    for dev, s, e, name in trace.modules:
        s, e = _clip(s, e, lo, hi)
        if e > s:
            per_module[name] += (e - s) * 1e-9
    busy = {dev: union(iv) for dev, iv in by_dev.items()}
    busy_s = {dev: sum(e - s for s, e in iv) * 1e-9
              for dev, iv in busy.items()}
    gaps: dict = defaultdict(float)
    if busy:
        # gaps of the busiest device, named by what the host was doing
        dev = max(busy_s, key=busy_s.get)
        edges = [lo] + [x for iv in busy[dev] for x in iv] + [hi]
        spans = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                 if g1 > g0]
        for (g0, g1), name in zip(spans, name_gaps(spans, trace.host)):
            gaps[name] += (g1 - g0) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": (sum(busy_s.values()) / len(busy_s)) if busy_s else 0.0,
        "per_module_s": dict(per_module),
        "per_op_s": dict(per_op),
        "op_durations": _durations(trace, lo, hi),
        "idle_gaps_s": dict(gaps),
        "n_requests": sum(1 for _, _, n in trace.host if n == REQUEST_SPAN),
    }


def _durations(trace: Trace, lo: int, hi: int) -> dict:
    """Operation name (``op_name``) -> list of (clipped) durations in
    seconds, for the per-kernel roofline readers."""
    out = defaultdict(list)
    for _, s, e, name, _ in trace.ops:
        s, e = _clip(s, e, lo, hi)
        if e > s:
            out[op_name(name)].append((e - s) * 1e-9)
    return dict(out)


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
