"""Share of the traced window in which no operation ran on the device:
100 (1 - busy / window), busy being the union of operation intervals."""
from bench.metrics_util import idle_pct


def read(run):
    return idle_pct(run)
