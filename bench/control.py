"""Readings of a cell's correctness numbers on many seeds, sound and
controlled, in one process.

    python3 bench/control.py --workload <cell> --requests <n> --seeds <s> ...

Builds the cell once, then for each seed sends ``--requests`` requests of
the cell's own traffic through the timed path and prints one JSON line:
the numbers ``check()`` compares for the program (``sound``) and for each
control of the request kind (the reference put in the program's place
with one stated guarantee broken, or in the precision below the one the
configuration states).  The limits in ``bench/checks/`` are set from
these readings; the benchmark's own runs never run a control.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def main(argv=None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--requests", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = run.resolve(run.load_json(os.path.join(run.ROOT,
                                                  "BENCHMARK.json")),
                       args.workload)
    run.enable_cache()
    devs = run.devices_or_exit(int(spec["workload"]["chips"]), require_tpu)
    kind = importlib.import_module("bench.kinds." + spec["traffic"]["kind"])
    cell = kind.Cell(spec["conf"], spec["traffic"], spec["limits"],
                     args.seeds[0])
    cell.warm_up()
    for seed in args.seeds:
        cell.seed, cell.records = seed, []
        for i in range(args.requests):
            cell.request(i)
        t0 = time.perf_counter()
        line = {"workload": args.workload, "seed": seed,
                "device": devs[0].device_kind,
                "sound": cell.check()}
        line["sound_per_request"] = getattr(cell, "gaps", None)
        for c in kind.CONTROLS:
            line[c] = cell.check(control=c)
            line[c + "_per_request"] = getattr(cell, "gaps", None)
        line["check_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
