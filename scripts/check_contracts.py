#!/usr/bin/env python
"""Trace-contract CI driver (DESIGN.md §2.11).

Runs the three analysis passes over the repo and exits non-zero on any
violation:

* ``--lint``    AST rules (host sync, host RNG in jitted bodies,
  deprecated shims, kernel ref oracles, static-arg hygiene) plus the
  static donation audit.
* ``--schema``  pytree schema self-checks on real EventTensor /
  EngineState instances (no engine compile).
* ``--retrace`` compile-count probes of the public entry points against
  the committed ``src/repro/analysis/budgets.json`` ratchet; writes the
  measured counts to ``results/compile_counts.json``.  ``--smoke``
  shrinks the lattice sweep to its first 4 views (CI's tier-1 budget) —
  the repeat/ils/megabatch/service probes are already tiny.

No flags = all passes (full retrace).  The driver must run in a fresh
process: the budgets assume cold jit caches.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the probes are the boundary-contract test bed: schema checks stay on
os.environ["REPRO_SCHEMA_CHECKS"] = "1"

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                   "results", "compile_counts.json")


def run_lint() -> list[str]:
    from repro.analysis.lint import lint_paths
    from repro.analysis.schema import audit_donation
    problems = [str(v) for v in lint_paths(SRC)]
    problems += [str(v) for v in audit_donation(SRC)]
    return problems


def run_schema() -> list[str]:
    """Schema + carry-stability self-checks on real instances."""
    import jax
    import jax.numpy as jnp
    from repro.analysis.schema import (SchemaError, assert_carry_stable,
                                       check_engine_state,
                                       check_event_tensor)
    from repro.sim.events import SCENARIOS
    from repro.sim.market import PoissonProcess

    problems: list[str] = []
    ev = PoissonProcess.from_scenario(SCENARIOS["sc5"]).sample(
        jax.random.PRNGKey(0), s=2, n_slots=24, v=3, dt=30.0,
        deadline_s=600.0)
    try:
        dims = check_event_tensor(ev.with_index())
        if dims != {"S": 2, "N": 24, "V": 3}:
            problems.append(f"EventTensor dims bound unexpectedly: {dims}")
    except SchemaError as e:
        problems.append(f"sampled EventTensor violates its schema: {e}")

    # a state extracted from a real (tiny) engine run must conform, and
    # re-running its identity map must be carry-stable
    try:
        res = _tiny_run(stop=True)
        check_engine_state(res.state, bind={"S": 2})
        assert_carry_stable(lambda st: st, res.state)
    except SchemaError as e:
        problems.append(f"extracted EngineState violates its schema: {e}")
    return problems


# ---------------------------------------------------------------------------
# retrace probes — tiny fixtures, no ILS planning unless the entry point
# itself plans (megabatch)
# ---------------------------------------------------------------------------

def _tiny_fixture(policy=None):
    import numpy as np
    from repro.core.dynamic import BURST_HADS, PrimaryPlan
    from repro.core.types import CloudConfig, Job, Solution, TaskSpec
    cfg = CloudConfig(max_per_type_market=1)
    pool = cfg.instance_pool()
    tasks = tuple(TaskSpec(tid=i, memory_mb=100.0, base_time=300.0 + 20 * i)
                  for i in range(3))
    job = Job(name="PROBE", tasks=tasks, deadline_s=2400.0)
    sol = Solution(alloc=np.zeros(3, np.int32), modes=np.zeros(3, np.int8),
                   pool=pool, selected_uids={0})
    plan = PrimaryPlan(solution=sol, dspot=5000.0,
                       policy=policy if policy is not None else BURST_HADS)
    return job, plan, cfg


def _tensor_for(job, plan, params, s=2):
    import jax
    from repro.sim.events import SCENARIOS
    from repro.sim.market import PoissonProcess
    from repro.sim.mc_engine import n_slots_for, plan_column_uids
    return PoissonProcess.from_scenario(SCENARIOS["sc5"]).sample(
        jax.random.PRNGKey(7), s=s, n_slots=n_slots_for(job.deadline_s,
                                                        params),
        v=len(plan_column_uids(plan)), dt=params.dt,
        deadline_s=job.deadline_s)


def _tiny_run(policy=None, stop=False):
    from repro.sim.mc_engine import MCParams, run_mc_events
    job, plan, cfg = _tiny_fixture(policy)
    params = MCParams(n_scenarios=2, dt=30.0, seed=7)
    ev = _tensor_for(job, plan, params)
    kw = dict(stop_s=1800.0, return_state=True) if stop else {}
    return run_mc_events(job, plan, cfg, ev, params, label="probe", **kw)


def probe_repeat():
    """Two identical run_mc_events calls: 1 build, then a warm hit —
    any second build is an unexplained retrace by construction."""
    from repro.analysis.retrace import CompileTracker, signature_of
    from repro.sim.mc_engine import MCParams, run_mc_events
    job, plan, cfg = _tiny_fixture()
    params = MCParams(n_scenarios=2, dt=30.0, seed=7)
    ev = _tensor_for(job, plan, params)
    with CompileTracker("run_mc_events/repeat") as t:
        for _ in range(2):
            run_mc_events(job, plan, cfg, ev, params, label="probe")
            t.checkpoint(sig=signature_of(ev, plan.policy.engine_view(),
                                          params.dt, params.stepping))
    return t


def probe_lattice(max_views: int | None = None):
    """One engine call per distinct lattice engine view on one shape —
    the DESIGN.md ≤12-compiles-per-shape claim, measured."""
    from repro.analysis.retrace import CompileTracker, signature_of
    from repro.core.dynamic import POLICIES
    from repro.sim.mc_engine import MCParams, run_mc_events
    views = sorted({p.engine_view() for p in POLICIES.values()},
                   key=lambda v: v.name)
    if len(views) > 12:
        raise SystemExit(f"lattice has {len(views)} distinct engine views "
                         "(> 12) — the compile-sharing contract is broken")
    if max_views is not None:
        views = views[:max_views]
    params = MCParams(n_scenarios=2, dt=30.0, seed=7)
    with CompileTracker("run_mc_events/lattice") as t:
        for view in views:
            job, plan, cfg = _tiny_fixture(view)
            ev = _tensor_for(job, plan, params)
            run_mc_events(job, plan, cfg, ev, params, label="probe")
            t.checkpoint(sig=signature_of(ev, view, params.dt))
    return t


def probe_batched_ils():
    from repro.analysis.retrace import CompileTracker, signature_of
    from repro.core.ils_jax import BatchedILSParams, run_batched_ils
    from repro.core.types import CloudConfig, TaskSpec
    cfg = CloudConfig(max_per_type_market=1)
    pool = cfg.instance_pool()
    tasks = tuple(TaskSpec(tid=i, memory_mb=100.0, base_time=200.0)
                  for i in range(6))
    params = BatchedILSParams(population=4, iterations=3, proposals=4,
                              seed=0)
    with CompileTracker("run_batched_ils") as t:
        for _ in range(2):
            run_batched_ils(tasks, pool, cfg, 5000.0, 2400.0, params)
            t.checkpoint(sig=signature_of(len(tasks), params))
    return t


def probe_megabatch():
    from repro.analysis.retrace import CompileTracker
    from repro.core.ils import ILSParams
    from repro.core.ils_jax import BatchedILSParams
    from repro.core.types import CloudConfig
    from repro.sim.megabatch import B_MULT, SLOT_MULT, V_MULT, evaluate_grid
    from repro.sim.mc_engine import MCParams
    if (B_MULT, V_MULT, SLOT_MULT) != (16, 8, 32):
        raise SystemExit(
            f"megabatch bucket constants changed to ({B_MULT}, {V_MULT}, "
            f"{SLOT_MULT}) — re-baseline budgets.json in the same PR")
    with CompileTracker("evaluate_grid") as t:
        grid = evaluate_grid(
            ["J12"], ["burst-hads", "hads"], ["sc5"], cfg=CloudConfig(),
            params=MCParams(n_scenarios=4, dt=30.0, seed=5),
            ils_params=ILSParams(max_iteration=4, max_attempt=4, seed=3),
            plan_engine="batched",
            batched_ils=BatchedILSParams(iterations=3, population=4,
                                         proposals=4, seed=3))
        t.checkpoint()
    if t.engine_builds < grid.n_groups:
        raise SystemExit(
            f"evaluate_grid built {t.engine_builds} programs for "
            f"{grid.n_groups} fusion groups — group accounting is off")
    return t


def probe_service_replan():
    """Stream crossing one task-ledger granule boundary.  The granule is
    shrunk (64 -> 8) so the probe stays tiny; the *per-crossing* build
    count is what the budget pins (ROADMAP 1(a))."""
    from repro.analysis.retrace import CompileTracker
    import repro.service as service
    granule0 = service.TASK_GRANULE
    service.TASK_GRANULE = 8
    try:
        with CompileTracker("service_replan") as t:
            svc = service.Service("burst-hads", horizon_s=7200.0)
            # slow arrivals: the ledger crosses the (shrunken) granule
            # *between* engine advances, so the growth recompile shows
            svc.run(service.stationary_arrivals(
                12, rate_per_s=0.005, rel_deadline_s=3000.0, seed=0))
            t.checkpoint()
    finally:
        service.TASK_GRANULE = granule0
    return t


def run_retrace(smoke: bool) -> tuple[list[str], dict]:
    from repro.analysis.retrace import audit_entry_points
    trackers = {}
    for probe in (lambda: probe_lattice(4 if smoke else None),
                  probe_repeat, probe_batched_ils, probe_megabatch,
                  probe_service_replan):
        t0 = time.time()
        t = probe()
        # smoke halves the lattice: map onto the dedicated smoke budget
        if t.label == "run_mc_events/lattice" and smoke:
            t.label = "run_mc_events/lattice_smoke"
        trackers[t.label] = t
        print(f"  probe {t.label}: {t.engine_builds} engine build(s), "
              f"{t.backend_compiles} backend compile(s), "
              f"{time.time() - t0:.1f}s")
    audits = audit_entry_points(trackers)
    problems = [a.describe() for a in audits if not a.ok]
    for a in audits:
        if a.ok:
            print(" ", a.describe())
    counts = {a.name: {"engine_builds": a.engine_builds,
                       "budget": a.budget} for a in audits}
    return problems, counts


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--lint", action="store_true")
    ap.add_argument("--schema", action="store_true")
    ap.add_argument("--retrace", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced retrace probes (CI tier-1)")
    args = ap.parse_args()
    do_all = not (args.lint or args.schema or args.retrace)

    failures: list[str] = []
    if args.lint or do_all:
        print("== lint (AST rules + donation audit)")
        probs = run_lint()
        failures += probs
        print(f"   {len(probs)} violation(s)")
    if args.schema or do_all:
        print("== schema (pytree contracts)")
        probs = run_schema()
        failures += probs
        print(f"   {len(probs)} violation(s)")
    if args.retrace or args.smoke or do_all:
        print("== retrace (compile budgets)")
        probs, counts = run_retrace(smoke=args.smoke and not args.retrace)
        failures += probs
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        with open(OUT, "w") as fh:
            json.dump({"entry_points": counts}, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"   wrote {os.path.relpath(OUT)}")

    if failures:
        print("\nCONTRACT VIOLATIONS:")
        for f in failures:
            print(" ", f)
        return 1
    print("\nall trace contracts hold")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
