"""Evaluation requests: one bag's plans over a draw of market scenarios.

At set-up the configuration's bag is drawn once (``pool_seed``) and
planned for each of the traffic's policies with
``repro.core.dynamic.build_primary_map`` through the batched ILS
(``engine="batched"``, the plan cell's population, proposals, moved tasks
and iterations).  For each plan a pool of ``pool`` event tensors of the
configuration's market is made (``bench.events``: S scenarios x the
engine's slots x the plan's columns, on the device); draw ``k`` shares
its event counts across the plans.  One request takes draw
``pool_member(seed, pool, i)`` and evaluates every plan on it through
``repro.sim.mc_engine.run_mc_events``, the entry point of ``run_mc``,
``repro.api`` and the service: the Monte-Carlo engine and its kernels do
the work.  A request completes one unit per plan.

Correctness: after the window, ``check.scenarios`` scenarios per policy,
drawn from the seed among the window's requests, are run again by the
plain reference of the engine's stated semantics
(``bench.reference.engine``, one scenario at a time, slot by slot, in
float64) on the event arrays those scenarios used, fetched from the very
tensors the program was given.  Every number is held at its worst over
the sample:

* ``cost_rel_error_max``: |program - reference| / reference, per scenario;
* ``makespan_rel_error_max``: the same for the makespan;
* ``billed_error_share_max``: the L1 difference of the per-column billed
  seconds over the reference's total;
* ``outcome_mismatch``: the differences in hibernations, resumes and
  unfinished tasks, and 1 where ``deadline_met`` differs, summed over the
  sample (exact: limit 0).

The cell reads the engine's loop counters (``MCResult.n_steps`` and
``MCResult.branch_entries``); a program without them fails at set-up,
naming the missing field.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

from bench import deploy, events, generator
from bench.reference import engine as ref

#: the controls: the reference's cost and makespan in bfloat16, the
#: precision below the float32 the configuration states, in place of the
#: program's; the reference with hibernated columns billing; and the
#: reference with Alg. 5 (work stealing) off
CONTROLS = ("bfloat16", "bills_hibernated", "no_steal")
#: the engine's loop counters the cell and its metrics read
COUNTERS = ("n_steps", "branch_entries")


class Cell:
    unit = "plans"

    def __init__(self, conf: dict, traffic: dict, limits: dict, seed: int):
        import jax.numpy as jnp
        from repro.core.dynamic import build_primary_map, policy
        from repro.core.ils import ILSParams
        from repro.core.ils_jax import BatchedILSParams
        from repro.sim import mc_engine
        from repro.sim.market import EventTensor
        fields = {f.name for f in dataclasses.fields(mc_engine.MCResult)}
        for name in COUNTERS:
            if name not in fields:
                raise RuntimeError(
                    f"the program's repro.sim.mc_engine.MCResult has no "
                    f"field {name!r}, which the eval cell reads")
        self.conf, self.traffic, self.limits = conf, traffic, limits
        self.seed = int(seed)
        self.records: list[dict] = []
        self.gaps: list[float] = []
        e = traffic["engine"]
        self.s = int(e["n_scenarios"])
        self.params = mc_engine.MCParams(
            n_scenarios=self.s, dt=float(e["dt"]),
            horizon_mult=float(e["horizon_mult"]), ovh=float(e["ovh"]),
            hads_margin_s=float(e["hads_margin_s"]),
            steal_rounds=int(e["steal_rounds"]),
            mig_rounds=int(e["mig_rounds"]), stepping=e["stepping"])
        self.ref_params = ref.EngineParams(
            dt=float(e["dt"]), horizon_mult=float(e["horizon_mult"]),
            ovh=float(e["ovh"]),
            checkpoint_write_s=float(e["checkpoint_write_s"]),
            hads_margin_s=float(e["hads_margin_s"]),
            steal_rounds=int(e["steal_rounds"]),
            mig_rounds=int(e["mig_rounds"]))
        d = float(conf["deadline_s"])
        pool_seed = int(traffic["pool_seed"])
        self.mem, self.base = generator.bag(
            conf["bag"], generator.request_bag_rng(pool_seed, 0))
        self.cfg = deploy.program_cloud(conf)
        self.job = deploy.program_job(conf["bag"]["name"], self.mem,
                                      self.base, d)
        n = events.n_slots(d, float(e["dt"]), float(e["horizon_mult"]))
        p = traffic["ils"]
        self.policies = list(traffic["policies"])
        self.plans, self.pools = {}, {}
        for j, name in enumerate(self.policies):
            s = generator.sub_seed(pool_seed, j)
            self.plans[name] = build_primary_map(
                self.job, self.cfg, policy(name),
                ILSParams(seed=s, alpha=float(p["alpha"]),
                          max_iteration=int(p["iterations"]),
                          burst_rate=float(p["burst_rate"])),
                engine="batched", batched_params=BatchedILSParams(
                    population=int(p["population"]),
                    iterations=int(p["iterations"]),
                    proposals=int(p["proposals"]),
                    swap_tasks=int(p["swap_tasks"]),
                    alpha=float(p["alpha"]), seed=s))
            v = len(mc_engine.plan_column_uids(self.plans[name]))
            self.pools[name] = []
            for k in range(int(traffic["pool"])):
                t = events.draw(generator.sub_seed(pool_seed, 1 << 20, k),
                                conf["market"], s=self.s, n=n, v=v,
                                dt=float(e["dt"]), deadline_s=d)
                self.pools[name].append(EventTensor(
                    jnp.asarray(t["hib_k"]), t["hib_u"],
                    jnp.asarray(t["res_k"]), t["res_u"]).with_index())
        self._cases: dict = {}
        self._refs: dict = {}

    def _evaluate(self, i: int) -> dict:
        from repro.sim.mc_engine import run_mc_events
        k = generator.pool_member(self.seed, int(self.traffic["pool"]), i)
        out = {}
        for name in self.policies:
            res = run_mc_events(self.job, self.plans[name], self.cfg,
                                self.pools[name][k], self.params,
                                label=self.conf["market"]["name"],
                                donate=False)
            out[name] = {
                "cost": res.cost, "makespan": res.makespan,
                "billed": res.billed_s, "n_hib": res.n_hibernations,
                "n_res": res.n_resumes, "unfinished": res.unfinished,
                "deadline_met": res.deadline_met, "n_steps": res.n_steps,
                "branch_entries": dict(res.branch_entries)}
        return {"i": i, "draw": k, "results": out}

    def warm_up(self) -> None:
        self._evaluate(generator.WARMUP_BASE)

    def request(self, i: int) -> int:
        self.records.append(self._evaluate(i))
        return len(self.policies)

    def shapes(self) -> dict:
        return {"s": self.s, "b": int(self.conf["bag"]["n_tasks"])}

    def n_vms(self, name: str) -> int:
        return self.pools[name][0].n_vms

    # -- correctness --------------------------------------------------------
    def _case(self, name: str) -> ref.Case:
        if name not in self._cases:
            plan = self.plans[name]
            job = deploy.reference_job(self.conf["bag"]["name"], self.mem,
                                       self.base, self.conf["deadline_s"])
            self._cases[name] = ref.Case(
                job.tasks, np.asarray(plan.solution.alloc),
                np.asarray(plan.solution.modes),
                {int(u) for u in plan.solution.selected_uids},
                deploy.reference_cloud(self.conf), job.deadline_s,
                ref.Policy(**self.traffic["policies"][name]),
                self.ref_params)
        return self._cases[name]

    def _reference(self, name: str, k: int, s: int, control=None):
        key = (name, k, s, control)
        if key not in self._refs:
            ev = self.pools[name][k]
            arrays = [np.asarray(a[s]) for a in (ev.hib_k, ev.hib_u,
                                                 ev.res_k, ev.res_u)]
            kw = {"bill_hibernated": control == "bills_hibernated"}
            if control == "no_steal":
                kw["work_stealing"] = False
            self._refs[key] = ref.run_scenario(self._case(name), *arrays,
                                               **kw)
        return self._refs[key]

    def _sample(self) -> list[tuple[str, int, int]]:
        """(policy, record, scenario) triples, drawn from the seed."""
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, 7]))
        k = int(self.traffic["check"]["scenarios"])
        out = []
        for name in self.policies:
            flat = rng.choice(len(self.records) * self.s,
                              size=min(k, len(self.records) * self.s),
                              replace=False)
            out += [(name, int(f) // self.s, int(f) % self.s)
                    for f in sorted(flat)]
        return out

    def check(self, control: str | None = None) -> dict:
        """The numbers compared, each with its limit (``CONTROLS``)."""
        if control not in (None,) + CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        sample = self._sample()
        cost = mksp = billed = 0.0 if sample else float("inf")
        mismatch = 0.0
        self.gaps = []
        for name, r, s in sample:
            rec = self.records[r]
            want = self._reference(name, rec["draw"], s)
            if control is None:
                got = rec["results"][name]
                got = {key: (got[key] if key in ("n_steps", "branch_entries")
                             else got[key][s]) for key in got}
            else:
                got = _as_result(self._reference(
                    name, rec["draw"], s,
                    None if control == "bfloat16" else control))
                if control == "bfloat16":
                    got["cost"] = _bf16(got["cost"])
                    got["makespan"] = _bf16(got["makespan"])
            c, m, b, o = _compare(got, want)
            self.gaps.append(c)
            cost, mksp = max(cost, c), max(mksp, m)
            billed, mismatch = max(billed, b), mismatch + o
        values = {"cost_rel_error_max": cost,
                  "makespan_rel_error_max": mksp,
                  "billed_error_share_max": billed,
                  "outcome_mismatch": mismatch}
        return {k: {"value": float(v), "limit": float(self.limits[k])}
                for k, v in values.items()}


def _bf16(x: float) -> float:
    return float(np.float64(x).astype(ml_dtypes.bfloat16))


def _as_result(o: ref.Outcome) -> dict:
    return {"cost": o.cost, "makespan": o.makespan, "billed": o.billed,
            "n_hib": o.n_hib, "n_res": o.n_res, "unfinished": o.unfinished,
            "deadline_met": o.deadline_met}


def _compare(got: dict, want: ref.Outcome) -> tuple[float, float, float,
                                                    float]:
    """One scenario's cost and makespan errors, billed-seconds error share
    and outcome mismatch count."""
    billed = np.asarray(got["billed"], np.float64)
    if billed.shape != want.billed.shape or not np.isfinite(
            [got["cost"], got["makespan"]]).all():
        return (float("inf"),) * 4
    cost = abs(float(got["cost"]) - want.cost) / want.cost
    mksp = abs(float(got["makespan"]) - want.makespan) / \
        max(want.makespan, 1e-9)
    share = float(np.abs(billed - want.billed).sum() / want.billed.sum())
    mismatch = (abs(int(got["n_hib"]) - want.n_hib)
                + abs(int(got["n_res"]) - want.n_res)
                + abs(int(got["unfinished"]) - want.unfinished)
                + int(bool(got["deadline_met"]) != want.deadline_met))
    return cost, mksp, share, float(mismatch)
