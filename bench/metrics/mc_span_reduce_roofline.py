"""Roofline share of the engine's ``mc_span_reduce`` Pallas kernel: the
least time of its calls by the bytes and operations of
``bench.roofline_engine.mc_span_reduce`` at each plan's shapes, over their
summed device time in the trace."""
from bench.roofline_engine import kernel_roofline


def read(run):
    return kernel_roofline(run, "mc_span_reduce")
