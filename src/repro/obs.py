"""Program spans: the host phases of a plan and of an engine run, written
into the profiler's trace.

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

#: the spans of a plan; ``plan`` holds the others
SPANS = ("plan", "greedy.seed", "ils.prepare", "ils.keys", "ils.search",
         "burst.alloc")
#: the spans of the Monte-Carlo engine: ``mc.run`` wraps one
#: ``run_mc_events`` call, from its checks to the fetch of its outputs
ENGINE_SPANS = ("mc.run",)
_NAMES = SPANS + ENGINE_SPANS


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """The span ``name`` (one of ``SPANS`` or ``ENGINE_SPANS``), carrying
    ``args``; use it as a context manager."""
    if name not in _NAMES:
        raise ValueError(f"unknown span {name!r} (one of {_NAMES})")
    return jax.profiler.TraceAnnotation(name, **args)
