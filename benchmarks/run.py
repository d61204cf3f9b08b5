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


#: repo-root rollup the CI bench-regression gate diffs against
#: (scripts/check_bench_regression.py); keep it at the root so the
#: committed baseline rides every checkout.
DYNAMIC_ROLLUP = os.path.join(os.path.dirname(__file__), "..",
                              "BENCH_dynamic.json")


def dynamic_rollup(sim_rows: list[dict], smoke: bool,
                   outdir: str, lattice_rows: list[dict] = (),
                   mega_rows: list[dict] = (),
                   service_rows: list[dict] = (),
                   recovery_rows: list[dict] = ()) -> list[dict]:
    """Headline dynamic-engine throughput per (job, policy, process, S,
    dt, stepping) + slots-skipped fraction, written to the root-level
    ``BENCH_dynamic.json`` and appended to ``results/trajectory.jsonl``
    so the perf history stays machine-readable across PRs.

    Rollup rows for keys not re-measured by this run (e.g. the committed
    full-size rows during a ``--smoke`` CI run) are carried over from the
    existing artifact, so the baseline keys survive partial runs.
    """
    rows = []
    for r in sim_rows:
        if r.get("table") != "sim_bench":
            continue
        key = {k: r[k] for k in ("job", "policy", "process", "s", "dt")}
        for stepping in ("adaptive", "slot"):
            row = {"table": "dynamic", **key, "stepping": stepping,
                   "scen_per_s": r[f"{stepping}_scen_per_s"],
                   "steps": r[f"steps_{stepping}"],
                   "slots_skipped_frac":
                       r["slots_skipped_frac"] if stepping == "adaptive"
                       else 0.0}
            if "des_scen_per_s" in r:
                row["des_scen_per_s"] = r["des_scen_per_s"]
                row["vs_des"] = round(r[f"{stepping}_scen_per_s"]
                                      / r["des_scen_per_s"], 2)
            row["vs_slot"] = round(r[f"{stepping}_scen_per_s"]
                                   / r["slot_scen_per_s"], 2)
            rows.append(row)
    # policy-lattice cells (fleet_bench.lattice): adaptive-only fused
    # runs — `steps` is the deterministic signal the CI gate diffs
    for r in lattice_rows:
        if r.get("table") != "lattice":
            continue
        rows.append({"table": "dynamic",
                     **{k: r[k] for k in ("job", "policy", "process",
                                          "s", "dt")},
                     "stepping": "adaptive",
                     "scen_per_s": r["scen_per_s"], "steps": r["steps"],
                     "slots_skipped_frac": r["slots_skipped_frac"]})

    # megabatch grid rows (fleet_bench.megabatch_grid): whole-grid fused
    # vs per-cell throughput — `vs_loop` (same-process ratio, hardware
    # cancels) and the call/group counts are the gate's signals
    for r in mega_rows:
        if r.get("table") != "megabatch":
            continue
        rows.append({"table": "megabatch",
                     **{k: r[k] for k in ("job", "policy", "process",
                                          "s", "dt")},
                     "stepping": "adaptive",
                     "scen_per_s": r["mega_scen_per_s"],
                     "vs_loop": r["vs_loop"],
                     "n_engine_calls": r["n_engine_calls"],
                     "n_groups": r["n_groups"],
                     "n_cells": r["n_cells"]})

    # online service-mode rows (service_bench): streaming admission over
    # the mid-horizon engine — `admitted` and `slo_met_frac` are the
    # deterministic gate signals, the wall rates ride informationally
    for r in service_rows:
        if r.get("table") != "service":
            continue
        rows.append({"table": "service",
                     **{k: r[k] for k in ("job", "policy", "process",
                                          "s", "dt")},
                     "stepping": "service",
                     "scen_per_s": r["arrivals_per_wall_s"],
                     "arrivals": r["arrivals"],
                     "admitted": r["admitted"],
                     "rejected": r["rejected"],
                     "slo_met_frac": r["slo_met_frac"],
                     "replan_p95_ms": r["replan_p95_ms"]})

    # fault-recovery rows (sim_bench.recovery, DESIGN.md §2.10): the
    # chaos grid's deterministic recovery signals — the gate hard-fails
    # any fresh stranded_tasks > 0 and watches the retry effort
    for r in recovery_rows:
        if r.get("table") != "recovery":
            continue
        rows.append({"table": "recovery",
                     **{k: r[k] for k in ("job", "policy", "process",
                                          "s", "dt")},
                     "stepping": "recovery",
                     "stranded_tasks": r["stranded_tasks"],
                     "orphan_retry_rounds_mean":
                         r["orphan_retry_rounds_mean"],
                     "work_conserved": r["work_conserved"],
                     "mean_terminations": r["mean_terminations"],
                     "deadline_met_frac": r["deadline_met_frac"]})

    def key_of(row):
        return tuple(row.get(k) for k in ("job", "policy", "process",
                                          "s", "dt", "stepping"))

    fresh = {key_of(r) for r in rows}
    try:
        with open(DYNAMIC_ROLLUP) as f:
            for old in json.load(f).get("rows", []):
                if key_of(old) not in fresh:
                    # flagged so readers and the CI gate can tell a
                    # carried-over number from a re-measured one
                    rows.append({**old, "carried": True})
    except (OSError, ValueError):
        pass
    _write_json(os.path.abspath(DYNAMIC_ROLLUP), rows)

    traj = os.path.join(outdir, "trajectory.jsonl")
    with open(traj, "a") as f:
        f.write(json.dumps({"unix_time": round(time.time()),
                            "smoke": smoke,
                            "rows": [r for r in rows
                                     if key_of(r) in fresh]}) + "\n")
    print(f"# trajectory -> {traj}")
    return rows


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
    lattice_rows = emit("lattice",
                        fleet_bench.lattice_smoke() if args.smoke
                        else fleet_bench.lattice(), fh)

    print("# Megabatch engine: whole grid fused vs per-cell pipeline")
    mega_rows = emit("megabatch",
                     fleet_bench.megabatch_smoke() if args.smoke
                     else fleet_bench.megabatch_grid(), fh)

    print("# Online service mode: streaming admission + rolling replans")
    from benchmarks import service_bench
    service_rows = emit("service",
                        service_bench.smoke() if args.smoke
                        else service_bench.run(), fh)

    print("# Fault recovery: chaos grid, orphan-retry + stranded signals")
    recovery_rows = emit("recovery",
                         sim_bench.recovery_smoke() if args.smoke
                         else sim_bench.recovery(), fh)
    dynamic_rollup(sim_rows, args.smoke, outdir, lattice_rows, mega_rows,
                   service_rows, recovery_rows)

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

    # Roofline summary (if dry-run artifacts exist)
    try:
        from repro.launch.roofline import load_all
        rows = load_all("results/dryrun")
        if rows:
            print("# Roofline (baseline dry-run artifacts)")
            emit("roofline",
                 [{"table": "roofline", "arch": r["arch"],
                   "shape": r["shape"], "dominant": r["dominant"],
                   "roofline_fraction": round(r["roofline_fraction"], 3),
                   "mfu_bound": round(r["mfu_bound"], 3)}
                  for r in rows], fh)
    except BenchError:
        raise
    except Exception as e:  # pragma: no cover
        print(f"# roofline skipped: {e}")

    fh.close()
    print(f"# total {time.time() - t0:.0f}s -> {args.csv}")


if __name__ == "__main__":
    try:
        main()
    except BenchError as e:
        print(f"# BENCH FAILED: {e}", file=sys.stderr)
        sys.exit(2)
