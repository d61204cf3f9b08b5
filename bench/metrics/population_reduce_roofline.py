"""Roofline share of the ``population_reduce`` Pallas kernel: the least time its calls
need by the bytes and operations of ``bench.roofline.population_reduce`` at the
cell's shapes, over their summed device time in the trace."""
from bench.metrics_util import kernel_roofline


def read(run):
    return kernel_roofline(run, "population_reduce")
