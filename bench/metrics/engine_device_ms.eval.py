"""Device time of the Monte-Carlo engine program (``mc_engine._mc_run``)
per plan evaluated, from the device trace."""
from bench.metrics_util import module_ms_per_request


def read(run):
    ms = module_ms_per_request(run, "_mc_run")
    return None if ms is None else ms / len(run.cell.policies)
