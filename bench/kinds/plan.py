"""Planning requests: one primary map for a bag of the traffic's pool.

One request takes a bag from a fixed pool of ``pool`` bags drawn from the
configuration's law (in an order drawn from ``--seed``) and plans it with
``repro.core.dynamic.build_primary_map`` for the traffic's policy through
the batched ILS (``engine="batched"``, the traffic's population,
proposals, moved tasks and iterations) and the burstable allocation that
follows it.  The ILS scan and its Pallas kernels do the work; the
Monte-Carlo engine is bypassed.

Correctness: after the window, a sample of the plans drawn from the seed
is planned again by the reference (``bench.reference``: the greedy seed,
the batched ILS as a float64 numpy loop on the program's stated proposal
stream, and the burstable allocation).  Three numbers are compared:

* ``search_gap_mean``: how far the fitness of the program's ILS winner
  lies from the reference search's winner, as a share of what the
  reference search gained over its best starting chain, averaged over the
  sample.  Eq. 8's LPT bound ties exactly between distinct moves (a short
  task joining a VM whose longest task sets its time changes nothing
  there), the program's float32 arithmetic breaks such ties where the
  reference takes the lowest index, and single chains then part: a plan
  reads a few thousandths either way, and the mean is steady;
* ``fitness_error_share``: how far the fitness the scan reports for its
  winner lies from the float64 Eq. 8 of that winner, as the same share,
  the worst over the sample;
* ``final_plan_mismatch``: the tasks whose VM or mode differ between the
  program's final plan and the reference's burstable allocation of the
  program's ILS winner, plus the VMs selected by one and not the other,
  summed over the sample (exact: limit 0).

``build_primary_map`` returns only the final plan, so the ILS result is
taken as the timed path produced it by wrapping
``repro.core.ils_jax.run_batched_ils``; a request fails, naming that
symbol, where the wrapper sees no call.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

from bench import deploy, generator
from bench.reference.burst import burst_allocation
from bench.reference.ils import batched_ils
from bench.reference.plans import Problem
from bench.reference.types import Market, Solution

#: the controls: the reference's fitness in bfloat16, the precision below
#: the float32 the configuration states, in place of the reported one; and
#: the program's own search cut to a quarter of its iterations
CONTROLS = ("bfloat16", "truncated")
_HOOK = "repro.core.ils_jax.run_batched_ils"


class Cell:
    unit = "plans"

    def __init__(self, conf: dict, traffic: dict, limits: dict, seed: int):
        import repro.core.ils_jax as ils_jax
        from repro.core.dynamic import policy
        self.conf, self.traffic, self.limits = conf, traffic, limits
        self.seed = int(seed)
        self.cfg = deploy.program_cloud(conf)
        self.policy = policy(traffic["policy"])
        self.ils = traffic["ils"]
        self.records: list[dict] = []
        self._results: list = []
        self._refs: dict = {}
        self.gaps: list[float] = []
        run = getattr(ils_jax, "run_batched_ils", None)
        if run is None:
            raise RuntimeError(f"the program has no {_HOOK}, which the "
                               "plan check reads the ILS result from")
        run = getattr(run, "__wrapped__", run)

        def keep(*args, **kw):
            res = run(*args, **kw)
            self._results.append(res)
            return res

        keep.__wrapped__ = run
        ils_jax.run_batched_ils = keep

    def bag(self, k: int):
        """Member ``k`` of the traffic's pool of bags."""
        return generator.bag(self.conf["bag"], generator.request_bag_rng(
            self.traffic["pool_seed"], k))

    def _plan(self, i: int, iterations: int | None = None):
        from repro.core.dynamic import build_primary_map
        from repro.core.ils import ILSParams
        from repro.core.ils_jax import BatchedILSParams
        p = self.ils
        iters = int(p["iterations"]) if iterations is None else iterations
        k = generator.pool_member(self.seed, int(self.traffic["pool"]), i)
        mem, base = self.bag(k)
        job = deploy.program_job(f"{self.conf['bag']['name']}.{k}", mem,
                                 base, self.conf["deadline_s"])
        s = generator.sub_seed(self.seed, i)
        bp = BatchedILSParams(population=int(p["population"]),
                              iterations=iters,
                              proposals=int(p["proposals"]),
                              swap_tasks=int(p["swap_tasks"]),
                              alpha=float(p["alpha"]), seed=s)
        self._results = []
        plan = build_primary_map(
            job, self.cfg, self.policy,
            ILSParams(seed=s, alpha=float(p["alpha"]), max_iteration=iters,
                      burst_rate=float(p["burst_rate"])),
            engine="batched", batched_params=bp)
        if len(self._results) != 1:
            raise RuntimeError(
                f"build_primary_map made {len(self._results)} calls to "
                f"{_HOOK} through its module attribute, where the plan "
                "check expects one")
        res = self._results[0]
        sol = plan.solution
        return {"i": i, "bag": k, "ils_seed": s,
                "alloc": np.asarray(res.solution.alloc).copy(),
                "fitness": float(res.fitness_bound),
                "final": (np.asarray(sol.alloc).copy(),
                          np.asarray(sol.modes).copy(),
                          {int(u) for u in sol.selected_uids}),
                "dspot": float(plan.dspot)}

    def warm_up(self) -> None:
        for j in range(2):
            self._plan(generator.WARMUP_BASE + j)

    def request(self, i: int) -> int:
        self.records.append(self._plan(i))
        return 1

    def shapes(self) -> dict:
        p = self.ils
        v = len(self.cfg.instance_pool())
        return {"p": int(p["population"]), "k": int(p["proposals"]),
                "n": int(p["swap_tasks"]), "b": int(self.conf["bag"]
                                                    ["n_tasks"]), "v": v}

    # -- correctness --------------------------------------------------------
    def _sample(self) -> list[dict]:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 7]))
        k = min(int(self.traffic["check"]["plans"]), len(self.records))
        picks = rng.choice(len(self.records), size=k, replace=False)
        return [self.records[int(r)] for r in sorted(picks)]

    def check(self, control: str | None = None) -> dict:
        """The numbers compared, each with its limit (``CONTROLS``)."""
        if control not in (None,) + CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        p = self.ils
        rcfg = deploy.reference_cloud(self.conf)
        pool = rcfg.instance_pool()
        market = Market(self.policy.market.value)
        sample = self._sample()
        if control == "truncated":
            sample = [self._plan(r["i"], int(p["iterations"]) // 4)
                      for r in sample]
        gaps, err, mismatch = [], 0.0 if sample else float("inf"), 0.0
        for rec in sample:
            mem, base = self.bag(rec["bag"])
            job = deploy.reference_job("bag", mem, base,
                                       self.conf["deadline_s"])
            prob = Problem(job.tasks, pool, rcfg, job.deadline_s,
                           float(p["alpha"]))
            key = (rec["bag"], rec["ils_seed"])
            if key not in self._refs:
                self._refs[key] = batched_ils(
                    prob, market, int(p["population"]), int(p["proposals"]),
                    int(p["swap_tasks"]), int(p["iterations"]),
                    rec["ils_seed"])
            ref = self._refs[key]
            try:
                g, e, m = _compare(rec, prob, ref, job, control,
                                   float(p["burst_rate"]))
            except (IndexError, ValueError):
                # a VM outside the pool, or a winner the burstable
                # allocation cannot take: the program's answer is wrong
                g = e = m = float("inf")
            gaps.append(g)
            err, mismatch = max(err, e), mismatch + m
        self.gaps = gaps
        values = {"search_gap_mean": (float(np.mean(gaps)) if gaps
                                      else float("inf")),
                  "fitness_error_share": err,
                  "final_plan_mismatch": float(mismatch)}
        return {k: {"value": v, "limit": float(self.limits[k])}
                for k, v in values.items()}


def _compare(rec: dict, prob: Problem, ref: dict, job, control,
             burst_rate: float) -> tuple[float, float, float]:
    """One plan's search gap and fitness error, as shares of the
    reference search's gain, and its final plan's mismatch count."""
    got = prob.fitness(rec["alloc"])
    reported = (prob.fitness(rec["alloc"], ml_dtypes.bfloat16)
                if control == "bfloat16" else rec["fitness"])
    gain = ref["start"] - ref["fitness"]
    if np.isfinite([got, reported, ref["fitness"]]).all() and gain > 0:
        gap, err = (abs(got - ref["fitness"]) / gain,
                    abs(reported - got) / gain)
    else:
        gap = err = float("inf")
    winner = Solution(alloc=rec["alloc"].astype(np.int32),
                      modes=np.zeros(len(job.tasks), np.int8),
                      pool=prob.pool)
    winner.selected_uids = set(winner.used_uids())
    want = burst_allocation(winner, job.tasks, prob.cfg, prob.dspot,
                            job.deadline_s, burst_rate)
    alloc, modes, selected = rec["final"]
    mismatch = int(np.sum((alloc != want.alloc) | (modes != want.modes)))
    mismatch += len(selected ^ want.selected_uids)
    mismatch += int(abs(rec["dspot"] - prob.dspot) > 1e-9 * prob.dspot)
    return gap, err, float(mismatch)
