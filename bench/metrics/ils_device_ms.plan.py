"""Device time of the batched ILS scan program (``ils_jax._ils_scan``) per
plan, from the device trace."""
from bench.metrics_util import module_ms_per_request


def read(run):
    return module_ms_per_request(run, "_ils_scan")
