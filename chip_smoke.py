"""On-chip smoke run of the Burst-HADS main path.

Drives the system once through its normal entry points at the paper's
deployment sizes -- the EC2 catalog pool (7 types x 5 slots), the J100
and ED200 bags, S = 1024 interruption scenarios -- on one TPU chip, and
checks what comes out:

1. device check: a TPU, or exit nonzero (there is no CPU fallback);
2. grid: ``evaluate_grid`` over (J100, ED200) x (burst-hads, hads,
   ils-ondemand) x sc5, planned by the batched ILS; cold and warm wall
   time, and the ``tpu_custom_call`` count of the engine and ILS
   programs (the Pallas kernels are in the compiled programs);
3. kernels vs references on the device: one ED200 burst-hads cell rerun
   with ``MCParams(use_kernel=False)``, per scenario, and one ILS
   ``delta_fitness`` batch and one ``insert_tasks`` batch against the
   ``kernels/sched_fitness/ref.py`` oracles;
4. exact reference: scenario 0 of that cell replayed on the numpy DES;
5. service: the 500-arrival bursty stream of
   ``benchmarks/service_bench.py``.

``--four-chips`` runs only the grid sharded over four chips against the
same grid on one device, in the same process.

Run from the repository root::

    python chip_smoke.py
    python chip_smoke.py --four-chips

A failed phase raises and exits nonzero.  On success the last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

JOBS = ("J100", "ED200")
POLICIES = ("burst-hads", "hads", "ils-ondemand")
PROCESSES = ("sc5",)
N_SCENARIOS = 1024
#: the reference cell of phases 3 and 4
REF_JOB, REF_POLICY, REF_PROCESS = "ED200", "burst-hads", "sc5"
#: ILS shapes (``BatchedILSParams`` defaults)
ILS_P, ILS_K, ILS_N = 32, 16, 4
#: the served stream's outcome on the CPU under JAX 0.9.0 (489 and 0.908
#: under the older RNG stream); chip timings come from ``bench/run.py``
#: and PERF.md
CPU_ADMITTED, CPU_SLO_MET = 490, 0.9102
#: tolerances shared with the tests: per-scenario kernel vs jnp engine
#: (tests/test_mc_engine.py), kernel vs oracle (tests/test_kernels.py),
#: sharded vs unsharded rows (tests/test_megabatch.py), and the eventful
#: S=1 DES-vs-MC parity bounds (tests/test_termination.py)
ENGINE_RTOL = 1e-6
KERNEL_RTOL = KERNEL_ATOL = 1e-5
GRID_RTOL = 1e-6
DES_COST_RTOL = DES_MKP_RTOL = 0.50


def phase(name: str) -> None:
    print(f"== {name}", flush=True)


def device_check(want: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{devs[0].platform!r}); there is no CPU fallback")
    if len(devs) < want:
        sys.exit(f"chip_smoke: {want} chips wanted, {len(devs)} found")
    print(f"device_kind={devs[0].device_kind} count={len(devs)}",
          flush=True)
    return devs


def run_grid(cfg, params, shard: bool = True):
    from repro.sim.megabatch import evaluate_grid
    return evaluate_grid(list(JOBS), list(POLICIES), list(PROCESSES),
                         cfg=cfg, params=params, shard=shard)


def print_grid(label: str, g) -> None:
    print(f"grid {label}: wall_s={g.wall_s} plan_wall_s={g.plan_wall_s} "
          f"mc_wall_s={g.mc_wall_s} engine_calls={g.n_engine_calls} "
          f"n_devices={g.n_devices} sharded={g.sharded}", flush=True)


def check_rows(rows) -> None:
    for r in rows:
        print(f"  {r['job']:6s} {r['policy']:13s} {r['process']:4s} "
              f"s={r['s']} cost_mean={r['cost']['mean']} "
              f"deadline_met_frac={r['deadline_met_frac']} "
              f"mean_hibernations={r['mean_hibernations']} "
              f"stranded={r['stranded_tasks']}", flush=True)
        assert r["s"] == N_SCENARIOS, r
        assert np.isfinite(r["cost"]["mean"]) and r["cost"]["mean"] > 0, r
        assert np.isfinite(r["makespan"]["mean"]), r
        assert r["work_conserved"] and r["stranded_tasks"] == 0, r


def assert_rows_agree(a, b, rtol: float) -> None:
    for ra, rb in zip(a, b, strict=True):
        key = (ra["job"], ra["policy"], ra["process"])
        assert key == (rb["job"], rb["policy"], rb["process"])
        np.testing.assert_allclose(ra["cost"]["mean"], rb["cost"]["mean"],
                                   rtol=rtol, err_msg=str(key))
        np.testing.assert_allclose(ra["makespan"]["mean"],
                                   rb["makespan"]["mean"], rtol=rtol,
                                   err_msg=str(key))
        assert ra["mean_hibernations"] == rb["mean_hibernations"], key
        assert ra["deadline_met_frac"] == rb["deadline_met_frac"], key


def ref_cell(cfg, params):
    """The reference cell's job, plan (from the planner cache the grid
    filled) and event tensor (the one the grid drew)."""
    from repro import api
    from repro.core.dynamic import policy
    from repro.core.ils import ILSParams
    from repro.sim.fleet import sample_grid_events
    from repro.sim.market import as_process
    from repro.sim.workloads import make_job
    job = make_job(REF_JOB)
    plan = api._plan(job, cfg, policy(REF_POLICY),
                     ILSParams(seed=params.seed), None, engine="batched")
    ev = sample_grid_events(job, plan, [as_process(REF_PROCESS)], params)[0]
    return job, plan, ev


def count_custom_calls(cfg, params, job, plan, ev) -> None:
    """Lower and compile the grid's engine program and the batched ILS
    scan for the reference cell; the Pallas kernels must be in both."""
    import jax
    import jax.numpy as jnp
    from repro.core import ils_jax
    from repro.sim.mc_engine import (_mc_jit, _plan_arrays_cached,
                                     _scalars, engine_args, n_slots_for)
    arr, _, mem_safe = _plan_arrays_cached(job, plan, cfg, params.ovh)
    sc = _scalars(job, cfg, params, n_slots_for(job.deadline_s, params))
    engine = _mc_jit(False).lower(
        arr, sc, ev.with_index(), s=ev.n_scenarios,
        **engine_args(plan.policy.engine_view(), params, cfg, mem_safe))

    pool = cfg.instance_pool()
    e, rm, cores, mem, price, spot = ils_jax._problem_arrays(
        job.tasks, pool, cfg)
    keys = jax.random.split(jax.random.PRNGKey(0), 200)
    ils = ils_jax._ils_scan(False).lower(
        jnp.zeros((ILS_P, job.n_tasks), jnp.int32),
        jnp.zeros(ILS_P, jnp.float32), keys,
        jnp.arange(len(pool), dtype=jnp.int32), e, rm, cores, mem, price,
        spot, k=ILS_K, n=ILS_N, dspot=job.deadline_s,
        deadline=job.deadline_s, alpha=0.5, scale=1.0,
        boot_s=cfg.boot_overhead_s)
    for name, low in (("engine", engine), ("ils", ils)):
        t0 = time.perf_counter()
        compiled = low.compile()
        n_low = low.as_text().count("tpu_custom_call")
        n_comp = compiled.as_text().count("tpu_custom_call")
        print(f"{name}: tpu_custom_call lowered={n_low} compiled={n_comp} "
              f"compile_s={time.perf_counter() - t0}", flush=True)
        assert n_low > 0 and n_comp > 0, name


def engine_vs_jnp(cfg, params, job, plan, ev):
    import dataclasses
    from repro.sim.mc_engine import run_mc_events
    ker = run_mc_events(job, plan, cfg, ev, params)
    ref = run_mc_events(job, plan, cfg, ev,
                        dataclasses.replace(params, use_kernel=False))
    for name in ("cost", "makespan"):
        a, b = getattr(ker, name), getattr(ref, name)
        print(f"engine {name}: max_rel_diff="
              f"{float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))}"
              f" mean_kernel={float(np.mean(a))} mean_jnp={float(np.mean(b))}",
              flush=True)
        np.testing.assert_allclose(a, b, rtol=ENGINE_RTOL, err_msg=name)
    for name in ("n_hibernations", "unfinished"):
        np.testing.assert_array_equal(getattr(ker, name), getattr(ref, name),
                                      err_msg=name)
    print(f"engine kernel == jnp over S={len(ker.cost)} scenarios "
          f"(cost/makespan rtol {ENGINE_RTOL}, hibernations and unfinished "
          "exact)", flush=True)
    return ker


def kernels_vs_oracle(cfg, job) -> None:
    import jax.numpy as jnp
    from repro.core.ils_jax import _problem_arrays
    from repro.kernels.sched_fitness.ops import delta_fitness, insert_tasks
    from repro.kernels.sched_fitness.ref import (delta_fitness_ref,
                                                 insert_tasks_ref)
    from repro.kernels.sched_fitness.sched_fitness import population_reduce
    pool = cfg.instance_pool()
    e, rm, cores, mem, price, spot = _problem_arrays(job.tasks, pool, cfg)
    b, v = e.shape
    rng = np.random.default_rng(0)
    alloc = jnp.asarray(rng.integers(0, v, (ILS_P, b)), jnp.int32)
    t_idx = jnp.asarray(rng.integers(0, b, (ILS_P, ILS_K, ILS_N)),
                        jnp.int32)
    dest = jnp.asarray(rng.integers(0, v, (ILS_P, ILS_K)), jnp.int32)
    kw = dict(dspot=0.8 * job.deadline_s, deadline=job.deadline_s,
              alpha=0.5, cost_scale=1.0, boot_s=cfg.boot_overhead_s)
    base = population_reduce(alloc, e, rm)
    checks = {
        "delta_fitness": (
            delta_fitness(alloc, t_idx, dest, base, e, rm, cores, mem,
                          price, spot, **kw),
            delta_fitness_ref(alloc, t_idx, dest, e, rm, cores, mem, price,
                              spot, **kw)),
        "insert_tasks": (
            insert_tasks(alloc[:1], dest[:1], tuple(x[:1] for x in base),
                         e, rm, e[0], rm[0], cores, mem, price, spot, **kw),
            insert_tasks_ref(alloc[:1], dest[:1], e, rm, e[0], rm[0],
                             cores, mem, price, spot, **kw))}
    for name, (got, want) in checks.items():
        for part, g, w in zip(("fitness", "cost", "makespan"), got, want):
            g, w = np.asarray(g), np.asarray(w)
            np.testing.assert_array_equal(np.isinf(g), np.isinf(w),
                                          err_msg=f"{name} {part} inf mask")
            fin = ~np.isinf(w)
            np.testing.assert_allclose(g[fin], w[fin], rtol=KERNEL_RTOL,
                                       atol=KERNEL_ATOL,
                                       err_msg=f"{name} {part}")
        print(f"{name} [P={got[0].shape[0]}, K={got[0].shape[1]}, B={b}, "
              f"V={v}] == ref.py (rtol {KERNEL_RTOL}, atol {KERNEL_ATOL}; "
              f"{int(np.isinf(np.asarray(got[0])).sum())} infeasible)",
              flush=True)


def scenario_trace(ev, plan, cfg, params, row: int = 0):
    """Scenario ``row`` of an event tensor as an explicit-VM trace.

    Each slot's requested hibernations and resumes are resolved the way
    the engine resolves them (``mc_engine._select``: the top-k eligible
    columns by score, ties to the lower index, negative scores opt out):
    hibernation victims among the launched, booted, active spot columns,
    resume beneficiaries among the hibernated ones."""
    from repro.sim.market import TraceReplayProcess
    from repro.sim.mc_engine import _plan_arrays_cached
    from repro.sim.workloads import make_job
    arr, _, _ = _plan_arrays_cached(make_job(REF_JOB), plan, cfg, params.ovh)
    assert ev.term_k is None, "the reference process has no terminations"
    spot = np.asarray(arr["spot"], bool)
    active = np.asarray(arr["launched0"], bool).copy()
    hibernated = np.zeros_like(active)
    hib_k, res_k = np.asarray(ev.hib_k[row]), np.asarray(ev.res_k[row])
    hib_u, res_u = np.asarray(ev.hib_u[row]), np.asarray(ev.res_u[row])
    events = []

    def pick(u, elig, k):
        order = np.lexsort((np.arange(len(u)), -u))   # score desc, index asc
        return [c for c in order if elig[c] and u[c] >= 0.0][:k]

    for n in range(len(hib_k)):
        t = n * params.dt
        booted = cfg.boot_overhead_s <= t + params.dt
        for c in pick(hib_u[n], active & spot & booted, int(hib_k[n])):
            active[c], hibernated[c] = False, True
            events.append((t, "hibernate", int(c)))
        for c in pick(res_u[n], hibernated.copy(), int(res_k[n])):
            active[c], hibernated[c] = True, False
            events.append((t, "resume", int(c)))
    return TraceReplayProcess.from_events(events, name="scenario0")


def des_vs_mc(cfg, params, job, plan, ev, mc) -> None:
    from repro.sim.simulator import Simulator
    trace = scenario_trace(ev, plan, cfg, params)
    des = Simulator(job, plan, cfg, scenario=trace, seed=0).run()
    print(f"DES scenario 0: events={len(trace.times)} cost={des.cost} "
          f"makespan={des.makespan} hibernations={des.n_hibernations} "
          f"resumes={des.n_resumes} unfinished={des.unfinished}", flush=True)
    print(f"MC  scenario 0: cost={float(mc.cost[0])} "
          f"makespan={float(mc.makespan[0])} "
          f"hibernations={int(mc.n_hibernations[0])} "
          f"resumes={int(mc.n_resumes[0])} "
          f"unfinished={int(mc.unfinished[0])}", flush=True)
    assert int(mc.n_hibernations[0]) == des.n_hibernations
    assert des.unfinished == 0 and int(mc.unfinished[0]) == 0
    np.testing.assert_allclose(mc.cost[0], des.cost, rtol=DES_COST_RTOL)
    np.testing.assert_allclose(mc.makespan[0], des.makespan,
                               rtol=DES_MKP_RTOL)


def service_phase() -> None:
    from benchmarks import service_bench
    row, = service_bench.run(policies=("burst-hads",))
    print(f"service bursty500: n_admitted={row['admitted']} "
          f"slo_met_frac={row['slo_met_frac']} wall_s={row['wall_s']} "
          f"replan_p95_ms={row['replan_p95_ms']}", flush=True)
    if (row["admitted"], row["slo_met_frac"]) != (CPU_ADMITTED, CPU_SLO_MET):
        print(f"service differs from the CPU figures: n_admitted "
              f"{row['admitted']} vs {CPU_ADMITTED}, slo_met_frac "
              f"{row['slo_met_frac']} vs {CPU_SLO_MET}", flush=True)
    assert row["admitted"] > 0 and 0.0 < row["slo_met_frac"] <= 1.0, row


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the grid sharded over 4 chips vs 1 device")
    args = ap.parse_args()

    from repro import compile_cache
    cache = compile_cache.enable()
    phase("device check")
    devs = device_check(4 if args.four_chips else 1)
    print(f"compile cache: {cache} ({cache_entries(cache)} entries)",
          flush=True)

    from repro.core.types import CloudConfig
    from repro.sim.mc_engine import MCParams
    cfg = CloudConfig()
    params = MCParams(n_scenarios=N_SCENARIOS)

    if args.four_chips:
        phase("grid: 4 chips sharded vs 1 device")
        one = run_grid(cfg, params, shard=False)
        print_grid("one device", one)
        four = run_grid(cfg, params, shard=True)
        print_grid("four chips cold", four)
        four = run_grid(cfg, params, shard=True)
        print_grid("four chips warm", four)
        one = run_grid(cfg, params, shard=False)
        print_grid("one device warm", one)
        assert four.sharded and four.n_devices == 4
        check_rows(four.rows)
        assert_rows_agree(four.rows, one.rows, GRID_RTOL)
        print(f"sharded rows == one-device rows (rtol {GRID_RTOL})")
    else:
        phase("grid")
        cold = run_grid(cfg, params)
        print_grid("cold", cold)
        warm = run_grid(cfg, params)
        print_grid("warm", warm)
        print(f"grid compile_s (cold - warm engine wall)="
              f"{cold.mc_wall_s - warm.mc_wall_s} plan_s (cold, ILS compile "
              f"and search)={cold.plan_wall_s}", flush=True)
        check_rows(warm.rows)
        assert_rows_agree(cold.rows, warm.rows, 0.0)
        job, plan, ev = ref_cell(cfg, params)
        count_custom_calls(cfg, params, job, plan, ev)

        phase("kernels vs references on the device")
        mc = engine_vs_jnp(cfg, params, job, plan, ev)
        kernels_vs_oracle(cfg, job)

        phase("exact reference: DES replay of scenario 0")
        des_vs_mc(cfg, params, job, plan, ev, mc)

        phase("service")
        service_phase()

    print(f"compile cache: {cache} ({cache_entries(cache)} entries)",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
