"""Market-process zoo: one policy, five interruption models, one call.

  PYTHONPATH=src python examples/market_models.py [J60|J80|J100] [S]

Walks the spot-market process library (DESIGN.md §2.4): the same
Burst-HADS plan is stress-tested under (1) the paper's Poisson sc5,
(2) bursty Weibull renewals, (3) a Markov-modulated calm/turbulent
storm, (4) correlated mass-hibernation shocks, and (5) an empirical
trace written to and replayed from CSV — every process compiles to the
same event-tensor interface, and ``repro.api.sweep`` fuses all five
into ONE scenario-sharded engine call.  Finishes with a lattice grid
across policies (including beyond-paper points like ``hads+burst``) on
the fleet backend.
"""
import os
import sys
import tempfile

sys.path.insert(0, "src")

from repro import api, compile_cache
from repro.core.ils import ILSParams
from repro.core.ils_jax import BatchedILSParams
from repro.sim import (CorrelatedShockProcess, MarkovModulatedProcess,
                       PoissonProcess, TraceReplayProcess, WeibullProcess,
                       make_job)
from repro.sim.mc_engine import MCParams


def main() -> None:
    compile_cache.enable()
    job_name = sys.argv[1] if len(sys.argv) > 1 else "J60"
    s = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    job = make_job(job_name)
    d = job.deadline_s
    params = ILSParams(max_iteration=60, max_attempt=25, seed=0)

    # an "empirical" trace: two early interruptions, one recovery
    trace = TraceReplayProcess.from_events(
        [(0.03 * d, "hibernate", -1), (0.06 * d, "hibernate", -1),
         (0.12 * d, "resume", -1)], name="trace-csv")
    path = os.path.join(tempfile.gettempdir(), "market_trace.csv")
    trace.to_csv(path)
    trace = TraceReplayProcess.from_csv(path, name="trace-csv")

    processes = [
        PoissonProcess(k_h=3.0, k_r=2.5, name="sc5-poisson"),
        WeibullProcess(shape_h=0.7, scale_h=d / 3, shape_r=1.0,
                       scale_r=d / 2.5, name="weibull-bursty"),
        MarkovModulatedProcess(k_h_calm=0.5, k_h_turb=12.0, k_r=2.5,
                               name="mmpp-storm"),
        CorrelatedShockProcess(k_shock=1.5, severity=0.6, k_h_base=0.5,
                               k_r_base=1.0, k_r_recovery=4.0,
                               name="mass-shock"),
        trace,
    ]

    print(f"{job.name}: Burst-HADS plan under {len(processes)} market "
          f"processes, S={s} scenarios each (one fused engine call)")
    print(f"{'process':16s} {'cost':>8s} {'p95':>8s} {'makespan':>9s} "
          f"{'met%':>6s} {'hib':>5s} {'res':>5s}")
    rows = api.sweep(job, "burst-hads", processes=processes,
                     backend="mc-adaptive",
                     mc=MCParams(n_scenarios=s, seed=1), ils=params)
    for r in rows:
        print(f"{r.process:16s} {r.cost['mean']:8.4f} "
              f"{r.cost['p95']:8.4f} {r.makespan['mean']:9.0f} "
              f"{100 * r.deadline_met_frac:6.1f} "
              f"{r.mean_hibernations:5.2f} {r.mean_resumes:5.2f}")

    print("\nlattice grid: 1 job x 4 policies x 3 processes on the fleet "
          "backend (batched-ILS planning, one sharded call per policy)...")
    fleet = api.sweep(job, ["burst-hads", "hads", "hads+burst",
                            "ils-ondemand"], processes=processes[:3],
                      backend="fleet",
                      mc=MCParams(n_scenarios=min(s, 128), seed=1),
                      ils=params,
                      batched_ils=BatchedILSParams(iterations=60, seed=0))
    for r in fleet:
        print(f"  {r.policy:26s} {r.process:16s} "
              f"cost={r.cost['mean']:.4f} "
              f"met={100 * r.deadline_met_frac:.0f}%")


if __name__ == "__main__":
    main()
