"""Quickstart: primary map -> one dynamic trace -> Monte-Carlo sweep.

  PYTHONPATH=src python examples/quickstart.py [J60|J80|J100|ED200]

Walks the three layers of the reproduction through the one declarative
entry point, ``repro.api``: (1) Algorithm 1 builds the Burst-HADS
primary map (ILS + burstable allocation — shown once with the low-level
core API so the pieces stay visible), (2) one exact discrete-event trace
(``backend="des"``), (3) the same scenario as a *distribution* on the
batched Monte-Carlo engine (``backend="mc-adaptive"``, hundreds of
traces in one device call).  The facade plans once and reuses the plan
across both backends.
"""
import sys

sys.path.insert(0, "src")

from repro import api, compile_cache
from repro.core import (CloudConfig, ILSParams, burst_allocation,
                        compute_dspot, evaluate, run_ils)
from repro.sim.mc_engine import MCParams
from repro.sim.workloads import make_job


def main() -> None:
    compile_cache.enable()
    job_name = sys.argv[1] if len(sys.argv) > 1 else "J60"
    cfg = CloudConfig()
    job = make_job(job_name)
    dspot = compute_dspot(job.deadline_s, job.tasks, cfg)
    print(f"job={job.name} tasks={job.n_tasks} deadline={job.deadline_s:.0f}s"
          f" D_spot={dspot:.0f}s")

    # Algorithm 1, spelled out with the core API: ILS + burstable alloc
    params = ILSParams(max_iteration=60, max_attempt=25, seed=0)
    pool = cfg.instance_pool()
    ils = run_ils(job.tasks, pool, cfg, dspot, job.deadline_s, params)
    plan = burst_allocation(ils.solution, job.tasks, cfg, dspot,
                            job.deadline_s, params.burst_rate)
    res = evaluate(plan.solution, job.tasks, cfg, ils.rd_spot,
                   job.deadline_s)
    print(f"\nprimary map: est cost=${res.cost:.3f} "
          f"est makespan={res.makespan:.0f}s "
          f"({len(res.per_vm)} VMs, {len(plan.burstable_uids)} burstable)")
    for uid, vs in sorted(res.per_vm.items()):
        print(f"  {vs.vm.name:26s} tasks={len(vs.assignments):3d} "
              f"busy until {vs.end_time:6.0f}s  ${vs.cost:.4f}")

    # One discrete-event trace under the average scenario (sc5)
    print("\none DES trace under scenario sc5 (k_h=3, k_r=2.5)...")
    exp = api.Experiment(job=job, policy="burst-hads", process="sc5",
                         cfg=cfg, ils=params, seed=1)
    r = api.run(exp, backend="des").raw
    print(f"cost=${r.cost:.3f} makespan={r.makespan:.0f}s "
          f"deadline_met={r.deadline_met} hibernations={r.n_hibernations} "
          f"migrations/steals={r.counters}")

    # The same scenario as a DISTRIBUTION: S traces in one batched call
    # (the facade reuses the DES run's cached primary plan)
    s = 256
    print(f"\nMonte-Carlo sweep: {s} sc5 scenarios in lockstep...")
    mc = api.run(exp, backend="mc-adaptive",
                 mc=MCParams(n_scenarios=s, dt=30.0, seed=1))
    print(f"cost    = ${mc.cost['mean']:.3f} ± {mc.cost['ci95']:.3f} "
          f"(p95 ${mc.cost['p95']:.3f})")
    print(f"makespan= {mc.makespan['mean']:.0f}s ± "
          f"{mc.makespan['ci95']:.0f}s (p95 {mc.makespan['p95']:.0f}s)")
    print(f"deadline met in {100 * mc.deadline_met_frac:.1f}% of runs, "
          f"{mc.mean_hibernations:.2f} hibernations/run on average")


if __name__ == "__main__":
    main()
