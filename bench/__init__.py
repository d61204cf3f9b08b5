"""The benchmark: ``python3 bench/run.py --workload <cell> ...`` (see
``run.py``), driven by ``BENCHMARK.json`` at the root of the checkout."""
