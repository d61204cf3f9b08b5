"""Program spans: the host phases of a plan, written into the profiler's trace.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``.  While a
profiler trace runs, it records the phase on the trace's clock, beside
the device's operations; with none running it costs about two
microseconds of host time.  The profiler is the only collector: there is
no switch and no buffer here.

Spans go around host code only.  Inside a jitted or traced function a
span would fire once, while tracing, and time nothing.
"""
from __future__ import annotations

import jax

#: every span name the program emits; ``plan`` holds the others
SPANS = ("plan", "greedy.seed", "ils.prepare", "ils.keys", "ils.search",
         "burst.alloc")


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """The span ``name`` (one of ``SPANS``), carrying ``args``; use it as
    a context manager."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r} (one of {SPANS})")
    return jax.profiler.TraceAnnotation(name, **args)
