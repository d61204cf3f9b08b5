"""Benchmark orchestrator — one section per paper table + framework benches.

Usage:  PYTHONPATH=src python -m benchmarks.run [--fast] [--smoke]
                                                [--csv out.csv]
Prints ``name,key=value,...`` CSV-ish lines per row.

``--smoke`` runs the CI-sized subset (catalog tables + a tiny sim bench).
Every section is validated: a bench that emits no rows, or any NaN/inf
value, fails the whole run with a nonzero exit code so CI catches silent
benchmark rot.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import compile_cache  # noqa: E402


class BenchError(RuntimeError):
    pass


def _validate(section: str, rows: list[dict]) -> list[dict]:
    if not rows:
        raise BenchError(f"section {section!r} emitted no rows")
    for r in rows:
        for k, v in r.items():
            if isinstance(v, float) and not math.isfinite(v):
                raise BenchError(
                    f"section {section!r} emitted non-finite {k}={v}: {r}")
    return rows


def emit(section: str, rows: list[dict], fh=None) -> list[dict]:
    rows = _validate(section, rows)
    for r in rows:
        line = ",".join(f"{k}={v}" for k, v in r.items())
        print(line, flush=True)
        if fh:
            fh.write(line + "\n")
    return rows


def _write_json(path: str, rows: list[dict]) -> None:
    with open(path, "w") as jf:
        json.dump({"generated_by": "benchmarks/run.py",
                   "unix_time": round(time.time()), "rows": rows}, jf,
                  indent=2)
    print(f"# artifact -> {path}")


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="J60-only Table VI and smaller ILS bench")
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: catalog tables + tiny sim bench")
    ap.add_argument("--csv", default="results/bench.csv")
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.csv) or ".", exist_ok=True)
    fh = open(args.csv, "w")
    t0 = time.time()
    outdir = os.path.dirname(args.csv) or "."

    from benchmarks import paper_tables as pt

    print("# Table II — VM catalog / WRR weights (Eq. 7)")
    emit("table2", pt.table2_catalog(), fh)
    print("# Table III — job characteristics")
    emit("table3", pt.table3_jobs(), fh)

    print("# Dynamic phase: DES vs fixed-slot vs event-horizon MC engine")
    from benchmarks import fleet_bench, sim_bench
    sim_rows = emit("sim_bench",
                    sim_bench.smoke() if args.smoke else sim_bench.run(), fh)
    _write_json(os.path.join(outdir, "BENCH_sim.json"), sim_rows)

    print("# Policy-lattice cells (paper policies ± one axis, fused)")
    emit("lattice", fleet_bench.lattice_smoke() if args.smoke
         else fleet_bench.lattice(), fh)

    print("# Megabatch engine: whole grid fused vs per-cell pipeline")
    emit("megabatch", fleet_bench.megabatch_smoke() if args.smoke
         else fleet_bench.megabatch_grid(), fh)

    print("# Online service mode: streaming admission + rolling replans")
    from benchmarks import service_bench
    emit("service", service_bench.smoke() if args.smoke
         else service_bench.run(), fh)

    print("# Fault recovery: chaos grid, orphan-retry + stranded signals")
    emit("recovery", sim_bench.recovery_smoke() if args.smoke
         else sim_bench.recovery(), fh)

    print("# Market/fleet: jobs x policies x market-process grid "
          "(sharded batch vs per-cell loop)")
    fleet_rows = emit(
        "fleet",
        fleet_bench.smoke() if args.smoke
        else fleet_bench.run(("J60",), s=64) if args.fast
        else fleet_bench.run(), fh)
    _write_json(os.path.join(outdir, "BENCH_fleet.json"), fleet_rows)

    if args.smoke:
        fh.close()
        print(f"# smoke ok, total {time.time() - t0:.0f}s -> {args.csv}")
        return

    print(f"# Table IV — no-hibernation comparison (avg of {pt.REPEATS} runs)")
    t4 = emit("table4", pt.table4_no_hibernation(), fh)
    print("# Table V — hibernation/resume scenarios")
    emit("table5", pt.table5_scenarios(), fh)
    print("# Table VI — scenario sweep (Burst-HADS vs HADS)")
    jobs = ("J60",) if args.fast else pt.ALL_JOBS
    t6 = emit("table6", pt.table6_scenarios(jobs), fh)
    print("# Headline claims vs paper")
    emit("headline", pt.headline_claims(t4, t6), fh)

    print("# Stress ablation (beyond paper): k_h sweep +/- burstables")
    from benchmarks import stress_ablation
    emit("stress", stress_ablation.run("J60" if args.fast else "J80"), fh)

    print("# ILS search: sequential vs batched JAX (full vs delta engine)")
    from benchmarks import ils_bench, kernel_bench
    ils_rows = emit("ils_bench", ils_bench.run("J60" if args.fast
                                               else "J100"), fh)
    if not args.fast:
        print("# ILS population sweep (scan engine)")
        ils_rows += emit("ils_pop_sweep",
                         ils_bench.population_sweep("J100"), fh)
    # perf-trajectory artifact, tracked across PRs (DESIGN.md §2.1)
    _write_json(os.path.join(outdir, "BENCH_ils.json"), ils_rows)

    print("# Kernel microbenches (CPU reference paths)")
    emit("kernels", kernel_bench.run(), fh)

    fh.close()
    print(f"# total {time.time() - t0:.0f}s -> {args.csv}")


if __name__ == "__main__":
    try:
        main()
    except BenchError as e:
        print(f"# BENCH FAILED: {e}", file=sys.stderr)
        sys.exit(2)
