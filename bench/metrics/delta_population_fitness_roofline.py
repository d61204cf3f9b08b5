"""Roofline share of the ``delta_population_fitness`` Pallas kernel: the least time its calls
need by the bytes and operations of ``bench.roofline.delta_population_fitness`` at the
cell's shapes, over their summed device time in the trace."""
from bench.metrics_util import kernel_roofline


def read(run):
    return kernel_roofline(run, "delta_population_fitness")
