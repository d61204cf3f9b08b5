"""Table V/VI as *distributions*: every policy x scenario cell is a batched
Monte-Carlo estimate (mean ± 95% CI over S traces), not a one-trace
anecdote.  One ``repro.api.sweep`` call covers the grid — each policy
defaults to its own Table V scenario sweep, and all of a policy's
scenarios run as ONE fused engine call (concat-S, DESIGN.md §2.4).

  PYTHONPATH=src python examples/paper_scenarios.py [J60] [S]
"""
import sys

sys.path.insert(0, "src")

from repro import api, compile_cache
from repro.core.ils import ILSParams
from repro.sim.mc_engine import MCParams


def main() -> None:
    compile_cache.enable()
    job = sys.argv[1] if len(sys.argv) > 1 else "J60"
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    mc = MCParams(n_scenarios=n, dt=30.0, seed=3)

    print(f"{job}: {n} Monte-Carlo traces per cell (dt={mc.dt:.0f}s)\n")
    print(f"{'policy':14s}{'scenario':10s}{'cost mean±ci95':>18s}"
          f"{'makespan mean±ci95':>22s}{'met%':>6s}{'hib':>6s}")
    rows = api.sweep(job, ["burst-hads", "hads", "ils-ondemand"],
                     backend="mc-adaptive", mc=mc,
                     ils=ILSParams(max_iteration=40, max_attempt=20,
                                   seed=9))
    for r in rows:
        print(f"{r.policy:14s}{r.process:10s}"
              f"  ${r.cost['mean']:6.3f}±{r.cost['ci95']:.3f}"
              f"    {r.makespan['mean']:7.0f}s±"
              f"{r.makespan['ci95']:3.0f}s"
              f"{100 * r.deadline_met_frac:5.0f}%"
              f"{r.mean_hibernations:6.2f}")


if __name__ == "__main__":
    main()
