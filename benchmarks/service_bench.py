"""Online service-mode benchmark (DESIGN.md §2.9): a 500-arrival bursty
request stream served end-to-end through ``repro.service.Service`` —
streaming admission, rolling-horizon replanning, mid-horizon engine
re-entry — under the sc5 market process.

The stream is pressured on purpose (burst factor 8 over a ~1000s span,
900s relative deadlines) so the three-verdict admission contract is
actually exercised: the committed artifact carries a CONGESTION tail,
not a trivially-all-SUCCESS run.  The *deterministic* stream outcomes
(``admitted`` count and ``slo_met_frac``, fixed given seeds and code)
are the row's signals; its wall-clock rates (arrivals/s served, replan
p95) are CPU walls.  Chip measurements are ``bench/run.py``'s (PERF.md).
"""
from __future__ import annotations

import time

from repro.core.dynamic import ArrivalPolicy
from repro.service import Service, bursty_arrivals

#: the pressured request stream: ~1000s of on/off-modulated Poisson
#: arrivals, tight 900s relative deadlines — admission must say no
STREAM = dict(rate_per_s=0.3, burst_factor=8.0, rel_deadline_s=900.0,
              seed=0)


def run(n_arrivals: int = 500,
        policies: tuple[str, ...] = ("burst-hads", "hads"),
        process: str = "sc5", seed: int = 0) -> list[dict]:
    arrivals = bursty_arrivals(n_arrivals, **STREAM)
    rows = []
    for pol in policies:
        svc = Service(policy=pol, process=process, seed=seed,
                      arrival=ArrivalPolicy(ils_every=4))
        t0 = time.perf_counter()
        res = svc.run(arrivals)
        wall = time.perf_counter() - t0
        s = res.summary()
        rows.append({
            "table": "service", "job": f"bursty{n_arrivals}",
            "policy": pol, "process": process,
            "s": svc.mc.n_scenarios, "dt": svc.mc.dt,
            "arrivals": s["n_arrivals"],
            "admitted": s["n_admitted"], "rejected": s["n_rejected"],
            "congestion": res.verdict_counts["CONGESTION"],
            "deadline_missed": res.verdict_counts["DEADLINE_MISSED"],
            "admitted_per_s": round(s["admitted_per_s"], 4),
            "slo_met_frac": round(s["slo_met_frac"], 4),
            "replan_p95_ms": round(s["replan_p95_ms"], 1),
            "arrivals_per_wall_s": round(n_arrivals / wall, 2),
            "wall_s": round(wall, 1),
            "cost_mean": round(s["cost_mean"], 4),
            "mkp_mean_s": round(s["makespan_mean_s"], 1),
        })
    return rows


def smoke() -> list[dict]:
    """CI-sized variant: the same 500-arrival stream, one policy."""
    return run(policies=("burst-hads",))
