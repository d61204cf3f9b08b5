"""Online service mode — streaming arrivals, admission control and
rolling-horizon replanning (DESIGN.md §2.9).

Everything else in this repo is one-shot: plan a fixed bag, simulate to
the end.  ``Service`` turns the reproduction into a system serving a
continuous request stream: tasks arrive over time (generator or CSV
trace) with *per-task* deadlines, an admission controller renders a
deterministic verdict per arrival in the three-verdict style of
queue-per-VM serving models —

* ``DEADLINE_MISSED`` — even an empty eligible column cannot finish the
  task by its deadline (boot + execution alone miss);
* ``CONGESTION``     — execution alone would fit somewhere, but every
  eligible column's projected backlog drain pushes the task past its
  deadline;
* ``SUCCESS``        — admitted; the ``insert_tasks`` kernel fast path
  (``kernels.sched_fitness``) scores candidate columns as single-task
  insertions into the incumbent plan without re-reducing untouched
  columns, and the winner becomes the task's placement.

Admitted arrivals are folded into the running world at rolling-horizon
boundaries (``ArrivalPolicy.replan_every_s``, quantized to the engine's
slot grid): the MC engine advances to the boundary and exits with its
``EngineState`` (mid-horizon entry — per-VM clocks, billing, credit
buckets, task progress and live hibernations are explicit state, not
implicit all-idle), the batch is admitted against that state, new tasks
are written into the state (inert pad slots keep engine shapes stable →
few compiles), and the engine re-enters bit-exactly on the slot path.
Optionally (``ArrivalPolicy.ils_every``) a warm-started batched ILS
(``core.ils_jax.run_batched_ils(initial=incumbent)``) refines the
placement of not-yet-started tasks, guarded so replanning never evicts
an already-admitted task past its deadline.

Semantics pinned by tests/test_service.py:

* the engine clock is the service clock (epoch 0); arrivals inside
  ``(t, t+replan_every_s]`` fold in at the next boundary and can never
  start before it;
* verdicts are a pure function of (state, arrival, seed) — deterministic
  and side-effect free on reject;
* billing follows the engine's contract — a column bills while work is
  pending anywhere in its scenario (warm-pool idle gaps between batches
  are not billed: billed seconds == busy-era seconds);
* with S > 1 scenarios the admission controller reads scenario 0 (the
  reference timeline); the remaining scenarios measure SLO attainment
  under market-event uncertainty.

Fault recovery (DESIGN.md §2.10): under terminating market processes a
task can be stranded when its column is killed and the engine's Alg. 4
migration finds no feasible destination.  At every rolling boundary the
service reads the engine's exported orphan ledger (``EngineState.orph``)
plus the scenario-0 terminated-column view and routes each stranded
task back through the same three-verdict pipeline as a fresh arrival —
recorded with ``REQUEUED`` provenance, re-admitting only the remaining
checkpoint-floored work, relocating in place (per-scenario progress is
preserved) and still bound by the no-eviction guard.  Rejects mutate
nothing: ``DEADLINE_MISSED`` retires the task, ``CONGESTION`` retries
at the next boundary.

First-class service metrics (``ServiceResult.summary``): sustained
tasks/s admitted, SLO-met fraction and replan-latency p95, printed by
``benchmarks/service_bench.py``.  Its chip benchmark is a future cell of
``bench/run.py`` (PERF.md §7).
"""
from __future__ import annotations

import csv
import dataclasses
import time
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dynamic import (ArrivalPolicy, PolicyConfig, PrimaryPlan,
                                policy as resolve_policy)
from repro.core.fitness import cost_scale
from repro.core.runtime import CHECKPOINT_WRITE_S
from repro.core.types import (CloudConfig, Job, Market, Solution, TaskSpec,
                              empty_solution)
from repro.ft.checkpoint import checkpoint_schedule
from repro.kernels.sched_fitness.ops import insert_tasks
from repro.kernels.sched_fitness.sched_fitness import population_reduce
from repro.sim.market import EventTensor, MarketProcess, as_process
from repro.sim.mc_engine import (BIG, EngineState, MCParams, MCResult,
                                 NOT_LAUNCHED, VM_ACTIVE, VM_TERMINATED,
                                 run_mc_events)

#: admission verdict vocabulary (one per arrival, deterministic)
VERDICT_SUCCESS = "SUCCESS"
VERDICT_CONGESTION = "CONGESTION"
VERDICT_DEADLINE_MISSED = "DEADLINE_MISSED"
VERDICTS = (VERDICT_DEADLINE_MISSED, VERDICT_CONGESTION, VERDICT_SUCCESS)

#: verdict-record provenance — a fresh arrival vs. a task stranded on a
#: terminated column routed back through admission (DESIGN.md §2.10)
PROVENANCE_ARRIVAL = "ARRIVAL"
PROVENANCE_REQUEUED = "REQUEUED"

#: engine task-axis capacity granule — admitted tasks land in inert pad
#: slots, so the jitted engine sees a new shape only every GRANULE tasks
TASK_GRANULE = 64

#: CSV trace schema (``arrivals_to_csv`` / ``arrivals_from_csv``)
ARRIVAL_CSV_FIELDS = ("time_s", "tid", "memory_mb", "base_time_s",
                      "deadline_s")


# ---------------------------------------------------------------------------
# Arrival streams
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Arrival:
    """One streaming request: a task, its arrival instant and its own
    absolute deadline (service clock, seconds)."""

    time_s: float
    task: TaskSpec
    deadline_s: float


_MEM_MB = (2.81, 13.19)     # paper synthetic band (sim.workloads)
_BASE_S = (102.0, 330.0)


def _mk_tasks(n: int, rng: np.random.Generator, mem_mb, base_s, tid0: int
              ) -> list[TaskSpec]:
    u = rng.uniform(0.0, 1.0, size=n)
    mem = mem_mb[0] + u * (mem_mb[1] - mem_mb[0])
    base = base_s[0] + u * (base_s[1] - base_s[0])
    return [TaskSpec(tid=tid0 + i, memory_mb=float(mem[i]),
                     base_time=float(base[i])) for i in range(n)]


def stationary_arrivals(n: int, *, rate_per_s: float = 0.05,
                        rel_deadline_s: float = 2700.0, seed: int = 0,
                        mem_mb=_MEM_MB, base_s=_BASE_S, tid0: int = 0
                        ) -> list[Arrival]:
    """Homogeneous-Poisson request stream: exponential inter-arrival gaps
    at ``rate_per_s``, paper-band task shapes, a fixed relative deadline
    per task.  Deterministic per seed."""
    rng = np.random.default_rng(seed)
    times = np.cumsum(rng.exponential(1.0 / rate_per_s, size=n))
    tasks = _mk_tasks(n, rng, mem_mb, base_s, tid0)
    return [Arrival(float(times[i]), tasks[i],
                    float(times[i]) + rel_deadline_s) for i in range(n)]


def bursty_arrivals(n: int, *, rate_per_s: float = 0.05,
                    burst_factor: float = 6.0, burst_len_s: float = 120.0,
                    calm_len_s: float = 600.0,
                    rel_deadline_s: float = 2700.0, seed: int = 0,
                    mem_mb=_MEM_MB, base_s=_BASE_S, tid0: int = 0
                    ) -> list[Arrival]:
    """On/off-modulated Poisson stream (the bursty request shape of
    service workload generators): alternating calm phases at
    ``rate_per_s`` and burst phases at ``rate_per_s * burst_factor``.
    Gaps are sampled at the current phase's rate; a gap that crosses the
    phase boundary is truncated there and redrawn at the new rate (the
    standard thinning-free on/off construction).  Deterministic per
    seed."""
    rng = np.random.default_rng(seed)
    times = []
    t, in_burst = 0.0, False
    phase_end = calm_len_s
    while len(times) < n:
        rate = rate_per_s * (burst_factor if in_burst else 1.0)
        gap = rng.exponential(1.0 / rate)
        if t + gap >= phase_end:
            t = phase_end
            in_burst = not in_burst
            phase_end = t + (burst_len_s if in_burst else calm_len_s)
            continue
        t += gap
        times.append(t)
    tasks = _mk_tasks(n, rng, mem_mb, base_s, tid0)
    return [Arrival(times[i], tasks[i], times[i] + rel_deadline_s)
            for i in range(n)]


def arrivals_to_csv(arrivals: Sequence[Arrival], path) -> None:
    """Persist a stream as a replayable CSV trace."""
    with open(path, "w", newline="") as fh:
        w = csv.writer(fh)
        w.writerow(ARRIVAL_CSV_FIELDS)
        for a in arrivals:
            w.writerow([a.time_s, a.task.tid, a.task.memory_mb,
                        a.task.base_time, a.deadline_s])


def arrivals_from_csv(path) -> list[Arrival]:
    """Replay a CSV trace written by ``arrivals_to_csv`` (or hand-built
    with the same header)."""
    out = []
    with open(path, newline="") as fh:
        r = csv.DictReader(fh)
        missing = set(ARRIVAL_CSV_FIELDS) - set(r.fieldnames or ())
        if missing:
            raise ValueError(f"arrival trace {path} missing columns "
                             f"{sorted(missing)}")
        for row in r:
            out.append(Arrival(
                float(row["time_s"]),
                TaskSpec(tid=int(row["tid"]),
                         memory_mb=float(row["memory_mb"]),
                         base_time=float(row["base_time_s"])),
                float(row["deadline_s"])))
    return out


# ---------------------------------------------------------------------------
# Results
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AdmissionRecord:
    """One arrival's verdict: rendered at its fold boundary, deterministic
    per (stream, seed)."""

    tid: int
    time_s: float
    verdict: str
    deadline_s: float
    eta_s: float        # best projected completion bound at admission
    column: int         # destination column (-1 on reject)
    provenance: str = PROVENANCE_ARRIVAL   # ARRIVAL | REQUEUED


@dataclasses.dataclass
class ServiceResult:
    """Service-level outcome: per-arrival verdicts + the stream metrics
    the bench artifact tracks (tasks/s admitted, SLO-met fraction,
    replan latency p95)."""

    records: list[AdmissionRecord]
    n_admitted: int
    n_rejected: int
    admitted_per_s: float
    slo_met_frac: float
    replan_ms: np.ndarray       # per-boundary planner latency
    done_at_s: np.ndarray       # f32 [S, n_admitted] absolute completion
    deadlines_s: np.ndarray     # f32 [n_admitted] absolute deadlines
    cost: np.ndarray            # f32 [S]
    makespan_s: np.ndarray      # f32 [S]
    unfinished: np.ndarray      # int [S]
    mc: MCResult | None = None  # final engine segment (counts, billing)
    n_requeued: int = 0         # stranded tasks re-admitted (§2.10)

    @property
    def replan_p95_ms(self) -> float:
        return float(np.percentile(self.replan_ms, 95)) \
            if len(self.replan_ms) else 0.0

    @property
    def verdict_counts(self) -> dict:
        out = {v: 0 for v in VERDICTS}
        for r in self.records:
            out[r.verdict] += 1
        return out

    def summary(self) -> dict:
        n_arr = sum(1 for r in self.records
                    if r.provenance == PROVENANCE_ARRIVAL)
        return {"n_arrivals": n_arr,
                "n_admitted": self.n_admitted,
                "n_rejected": self.n_rejected,
                "n_requeued": self.n_requeued,
                "verdicts": self.verdict_counts,
                "admitted_per_s": self.admitted_per_s,
                "slo_met_frac": self.slo_met_frac,
                "replan_p95_ms": self.replan_p95_ms,
                "cost_mean": float(np.mean(self.cost)),
                "makespan_mean_s": float(np.mean(self.makespan_s))}


# ---------------------------------------------------------------------------
# The service
# ---------------------------------------------------------------------------
class Service:
    """Streaming admission + rolling-horizon replanning over the MC
    engine's mid-horizon entry (module docstring; DESIGN.md §2.9).

    ``policy`` is any lattice spec (``core.dynamic.policy``) — it keeps
    governing the *dynamic* response (migration / stealing / burstables)
    while ``arrival`` governs admission and replanning.  ``process`` is
    any market process; its events are sampled once over the whole
    service horizon.  ``mc`` defaults to a single slot-path scenario —
    the deterministic ground-truth timeline; raise ``n_scenarios`` to
    measure SLO attainment under event uncertainty.
    """

    def __init__(self, policy: "str | PolicyConfig" = "burst-hads", *,
                 cfg: CloudConfig | None = None,
                 mc: MCParams | None = None,
                 arrival: ArrivalPolicy = ArrivalPolicy(),
                 process: "str | MarketProcess" = "none",
                 horizon_s: float = 8100.0, seed: int = 0):
        self.cfg = cfg or CloudConfig()
        self.policy = resolve_policy(policy)
        self.mc = mc if mc is not None else \
            MCParams(n_scenarios=1, dt=30.0, seed=seed, stepping="slot")
        self.arrival = arrival
        self.process = as_process(process)
        self.horizon_s = float(horizon_s)
        self.seed = seed
        self.n_slots = int(np.ceil(self.horizon_s / self.mc.dt))

        pool = self.cfg.instance_pool()
        self.pool = pool
        self.uids = [vm.uid for vm in pool]      # column c == pool uid c
        v = len(pool)
        gref = self.cfg.gflops_ref
        self._speed = np.array([vm.vm_type.gflops / gref for vm in pool],
                               np.float64)
        self._cores = np.array([vm.vcpus for vm in pool], np.float64)
        self._price = np.array([vm.price_per_sec for vm in pool],
                               np.float64)
        self._memv = np.array([vm.memory_mb for vm in pool], np.float64)
        self._spot = np.array([vm.is_spot for vm in pool], bool)
        self._burst = np.array([vm.is_burstable for vm in pool], bool)
        self._odm = np.array([vm.market == Market.ONDEMAND for vm in pool],
                             bool)
        # columns this policy's planner may ever target
        elig = np.ones(v, bool)
        if not self.policy.use_burstables:
            elig &= ~self._burst
        if self.policy.market == Market.ONDEMAND:
            elig &= ~self._spot
        self._elig_static = elig

        # host-side task ledger (engine order == arrival order)
        self._tasks: list[TaskSpec] = []
        self._total: list[float] = []    # checkpoint-adjusted work (ref s)
        self._cp: list[float] = []       # rollback grid
        self._deadline: list[float] = [] # absolute deadline per task
        self._assign: list[int] = []     # planned column per task
        self._records: list[AdmissionRecord] = []
        self._requeue_dead: set[int] = set()   # terminal requeue rejects
        self._replan_ms: list[float] = []
        self._state: EngineState | None = None
        self._cap = 0                    # padded engine task capacity
        self._t = 0.0                    # last fold boundary (engine s)
        self._ev: EventTensor | None = None
        self._ran = False

    # -- plumbing ----------------------------------------------------------
    @property
    def n_vms(self) -> int:
        return len(self.pool)

    def _slot_ceil(self, t_s: float) -> float:
        """Quantize a boundary up to the engine slot grid."""
        return float(np.ceil(t_s / self.mc.dt - 1e-9) * self.mc.dt)

    def _event_tensor(self) -> EventTensor:
        if self._ev is None:
            self._ev = self.process.sample(
                jax.random.PRNGKey(self.seed), s=self.mc.n_scenarios,
                n_slots=self.n_slots, v=self.n_vms, dt=self.mc.dt,
                deadline_s=self.horizon_s)
        return self._ev

    def _job_stub(self) -> Job:
        # the engine reads only deadline_s from the job on the arrays
        # path; the service has per-task deadlines, so the single engine
        # deadline is the horizon (deferred-HADS safe times key off it)
        return Job("service", (), self.horizon_s)

    def _plan_stub(self) -> PrimaryPlan:
        sol = empty_solution(len(self._tasks), self.pool)
        if self._tasks:
            sol.alloc = np.asarray(self._assign, np.int32)
        sol.selected_uids = set(int(c) for c in set(self._assign))
        return PrimaryPlan(solution=sol, dspot=self.horizon_s,
                           policy=self.policy)

    def _arrays(self) -> dict:
        """Engine plan arrays over the padded task ledger (arrival order
        — bypasses ``_plan_arrays``'s packed-start permutation)."""
        cap, b = self._cap, len(self._tasks)
        total = np.zeros(cap, np.float64)
        cp = np.ones(cap, np.float64)
        mem_t = np.zeros(cap, np.float32)
        assign0 = np.zeros(cap, np.int32)
        total[:b] = self._total
        cp[:b] = self._cp
        mem_t[:b] = [t.memory_mb for t in self._tasks]
        assign0[:b] = self._assign
        pool = self.pool
        return {
            "total": jnp.asarray(total),
            "cp": jnp.asarray(cp),
            "mem_t": jnp.asarray(mem_t),
            "assign0": jnp.asarray(assign0),
            "mode0": jnp.zeros(cap, jnp.int32),
            "price": jnp.asarray(self._price, jnp.float32),
            "cores": jnp.asarray(self._cores, jnp.float32),
            "speed": jnp.asarray(self._speed, jnp.float32),
            "bfrac": jnp.asarray([vm.vm_type.baseline_frac for vm in pool],
                                 jnp.float32),
            "memv": jnp.asarray(self._memv, jnp.float32),
            "crate": jnp.asarray(
                [vm.vm_type.credit_rate_per_hour / 3600.0 for vm in pool],
                jnp.float32),
            "cinit": jnp.asarray(
                [vm.vm_type.initial_credits for vm in pool], jnp.float32),
            "ccap": jnp.asarray(
                [vm.vm_type.credit_rate_per_hour * 24.0 for vm in pool],
                jnp.float32),
            "spot": jnp.asarray(self._spot),
            "burst": jnp.asarray(self._burst),
            "odm": jnp.asarray(self._odm),
            "burst_idx": jnp.asarray(np.flatnonzero(self._burst),
                                     jnp.int32),
            "launched0": jnp.zeros(self.n_vms, bool),
        }

    def _blank_state(self) -> EngineState:
        s, v, cap = self.mc.n_scenarios, self.n_vms, self._cap
        return EngineState(
            slot=jnp.zeros(s, jnp.int32),
            vstate=jnp.full((s, v), NOT_LAUNCHED, jnp.int32),
            boot=jnp.full((s, v), BIG, jnp.float32),
            billed=jnp.zeros((s, v), jnp.float32),
            credits=jnp.zeros((s, v), jnp.float32),
            rem=jnp.zeros((s, cap), jnp.float32),
            assign=jnp.zeros((s, cap), jnp.int32),
            mode=jnp.zeros((s, cap), jnp.int32),
            done_at=jnp.full((s, cap), BIG, jnp.float32),
            n_hib=jnp.zeros(s, jnp.int32),
            n_res=jnp.zeros(s, jnp.int32),
            n_term=jnp.zeros(s, jnp.int32))

    def _ensure_cap(self, need: int) -> None:
        if need <= self._cap:
            return
        new_cap = int(np.ceil(need / TASK_GRANULE)) * TASK_GRANULE
        if self._state is not None:
            self._state = self._state.pad_tasks(new_cap)
        self._cap = new_cap
        if self._state is None:
            self._state = self._blank_state()

    def _advance_to(self, stop_s: float | None) -> MCResult:
        """Run the engine from the current state to ``stop_s`` (or the
        horizon), swapping the frozen state back in."""
        res = run_mc_events(
            self._job_stub(), self._plan_stub(), self.cfg,
            self._event_tensor(), self.mc, label=self.process.name,
            stop_s=stop_s, state=self._state, return_state=True,
            arrays=(self._arrays(), self.uids, False))
        self._state = jax.device_get(res.state)
        return res

    # -- admission ---------------------------------------------------------
    def _column_view(self, t_b: float):
        """Deterministic numpy view of scenario 0 at boundary ``t_b``:
        per-column readiness instant (boot edge, or launch-now for
        launchable columns) and projected drain of the pending backlog.
        ``None`` entries are ineligible (hibernated/terminated, market
        excluded by the policy)."""
        st = self._state
        vstate = np.asarray(st.vstate[0])
        boot = np.asarray(st.boot[0], np.float64)
        rem = np.asarray(st.rem[0], np.float64)
        assign = np.asarray(st.assign[0])
        b = len(self._tasks)
        pending = rem[:b] > 0.0
        load = np.zeros(self.n_vms)
        np.add.at(load, assign[:b][pending], rem[:b][pending])
        drain = load / (self._cores * self._speed)
        ready = np.where(vstate == VM_ACTIVE,
                         np.maximum(boot, t_b),
                         t_b + self.cfg.boot_overhead_s)
        ok = self._elig_static & ((vstate == VM_ACTIVE) |
                                  (vstate == NOT_LAUNCHED))
        return ok, ready, drain

    def _admit(self, a: Arrival, t_b: float) -> AdmissionRecord:
        """Render one verdict against the boundary state — a pure
        function of (state, arrival); rejects leave every ledger and the
        state untouched."""
        total, cp = checkpoint_schedule(
            np.array([a.task.base_time]), self.mc.ovh,
            getattr(self.policy, "checkpoint", "periodic"),
            write_s=CHECKPOINT_WRITE_S, tids=[a.task.tid])
        work = float(total[0])
        ok, ready, drain = self._column_view(t_b)
        fits = a.task.memory_mb <= self._memv + 1e-6
        ok = ok & fits
        exec_s = work / self._speed                      # per column
        if self.arrival.admission == "always":
            eta = ready + drain + exec_s
            eta_ok = np.where(ok, eta, np.inf)
            c = int(np.argmin(eta_ok))
            if not np.isfinite(eta_ok[c]):
                c = int(np.argmin(np.where(fits, eta, np.inf)))
            return self._place(a, t_b, work, float(cp[0]), c,
                               float(eta[c]))
        empty_eta = np.where(ok, ready + exec_s, np.inf)
        if float(np.min(empty_eta)) > a.deadline_s + 1e-9:
            return AdmissionRecord(a.task.tid, a.time_s,
                                   VERDICT_DEADLINE_MISSED, a.deadline_s,
                                   float(np.min(empty_eta)), -1)
        eta = ready + self.arrival.queue_bound * drain + exec_s
        eta_ok = np.where(ok, eta, np.inf)
        if float(np.min(eta_ok)) > a.deadline_s + 1e-9:
            return AdmissionRecord(a.task.tid, a.time_s,
                                   VERDICT_CONGESTION, a.deadline_s,
                                   float(np.min(eta_ok)), -1)
        c = self._pick_column(a, t_b, work, eta_ok)
        return self._place(a, t_b, work, float(cp[0]), c, float(eta[c]))

    def _pick_column(self, a: Arrival, t_b: float, work: float,
                     eta_ok: np.ndarray) -> int:
        """Final placement among feasible columns: the ``insert_tasks``
        kernel scores the top candidates (by ETA pre-score) as Eq. 8
        single-task insertions into the incumbent; numpy ETA argmin is
        the fallback when the static view deems them all infeasible."""
        feas = np.flatnonzero(np.isfinite(eta_ok) &
                              (eta_ok <= a.deadline_s + 1e-9))
        if len(feas) == 1:
            return int(feas[0])
        k = max(8, int(np.ceil(self.arrival.insert_candidates / 8)) * 8)
        order = feas[np.argsort(eta_ok[feas], kind="stable")]
        cand = order[:min(len(order), self.arrival.insert_candidates)]
        dest = np.resize(cand, k).astype(np.int32)        # pad by cycling
        b = len(self._tasks)
        st = self._state
        rem0 = np.zeros(self._cap, np.float64)
        rem0[:b] = np.asarray(st.rem[0, :b], np.float64)
        pending = rem0 > 0.0
        alloc = np.where(pending, np.asarray(st.assign[0]),
                         self.n_vms).astype(np.int32)     # parked -> phantom
        e = (rem0[:, None] / self._speed[None]).astype(np.float32)
        rm = np.where(pending,
                      np.pad([t.memory_mb for t in self._tasks],
                             (0, self._cap - b)), 0.0).astype(np.float32)
        e_new = (work / self._speed).astype(np.float32)
        dl = max(float(a.deadline_s - t_b), self.mc.dt)
        scale = cost_scale(self._tasks, self.cfg) if self._tasks else 1.0
        base = population_reduce(alloc[None], jnp.asarray(e),
                                 jnp.asarray(rm))
        fit, _, _ = insert_tasks(
            jnp.asarray(alloc[None]), jnp.asarray(dest[None]), base,
            jnp.asarray(e), jnp.asarray(rm), jnp.asarray(e_new),
            jnp.float32(a.task.memory_mb),
            jnp.asarray(self._cores, jnp.float32),
            jnp.asarray(self._memv, jnp.float32),
            jnp.asarray(self._price, jnp.float32),
            jnp.asarray(self._spot, jnp.float32),
            dspot=dl, deadline=dl, alpha=0.5, cost_scale=scale,
            boot_s=self.cfg.boot_overhead_s)
        fit = np.asarray(fit[0])
        if np.all(np.isinf(fit)):
            return int(feas[np.argmin(eta_ok[feas])])
        return int(dest[int(np.argmin(fit))])

    def _place(self, a: Arrival, t_b: float, work: float, cp: float,
               c: int, eta: float) -> AdmissionRecord:
        """Commit an admission: ledger row + state surgery (launch the
        column if needed, write the task into a pad slot)."""
        idx = len(self._tasks)
        self._ensure_cap(idx + 1)
        self._tasks.append(a.task)
        self._total.append(work)
        self._cp.append(cp)
        self._deadline.append(a.deadline_s)
        self._assign.append(int(c))
        self._state = self._state.launch(
            np.array([c]), t_b + self.cfg.boot_overhead_s)
        self._state = jax.device_get(self._state.set_tasks(
            np.array([idx]), np.array([work], np.float32),
            np.array([c], np.int32), np.array([0], np.int32)))
        return AdmissionRecord(a.task.tid, a.time_s, VERDICT_SUCCESS,
                               a.deadline_s, eta, int(c))

    # -- fault recovery: re-admission of stranded work (§2.10) -------------
    def _requeue_stranded(self, t_b: float) -> None:
        """Route tasks stranded on terminated columns back through the
        three-verdict admission pipeline at a rolling boundary.  Scenario
        0 is the reference timeline: a task pending there whose column is
        ``VM_TERMINATED`` (or flagged in the engine's exported orphan
        ledger) gets a fresh verdict against the boundary state.  Rejects
        mutate nothing; ``DEADLINE_MISSED`` is terminal (a passed
        deadline cannot un-pass), ``CONGESTION`` re-enters at the next
        boundary once capacity frees up."""
        st = self._state
        b = len(self._tasks)
        if st is None or not b:
            return
        vstate0 = np.asarray(st.vstate[0])
        if not np.any(vstate0 == VM_TERMINATED):
            return
        rem0 = np.asarray(st.rem[0, :b], np.float64)
        assign0 = np.asarray(st.assign[0, :b])
        dead = vstate0[assign0] == VM_TERMINATED
        orph = np.asarray(st.orph[0, :b], bool) \
            if st.orph is not None else np.zeros(b, bool)
        stranded = np.flatnonzero((rem0 > 0.0) & (dead | orph))
        for j in stranded:
            if int(j) in self._requeue_dead:
                continue
            self._records.append(
                self._readmit(int(j), t_b, float(rem0[j])))

    def _readmit(self, j: int, t_b: float, work: float) -> AdmissionRecord:
        """One stranded task's fresh verdict: mirrors ``_admit`` (same
        three-verdict pipeline, same ``insert_tasks`` destination
        scoring) but re-admits the *remaining* checkpoint-floored work
        and, on success, relocates the task in place (``reassign``
        preserves per-scenario progress — unlike ``set_tasks`` it never
        resets ``rem``).  The eviction guard still binds: a placement
        that would push another admitted pending task past a deadline
        the incumbent met is refused as CONGESTION."""
        a = Arrival(t_b, self._tasks[j], self._deadline[j])
        ok, ready, drain = self._column_view(t_b)
        fits = a.task.memory_mb <= self._memv + 1e-6
        ok = ok & fits
        exec_s = work / self._speed
        if self.arrival.admission == "always":
            eta = ready + drain + exec_s
            eta_ok = np.where(ok, eta, np.inf)
            c = int(np.argmin(eta_ok))
            if not np.isfinite(eta_ok[c]):
                c = int(np.argmin(np.where(fits, eta, np.inf)))
            return self._relocate(j, t_b, c, float(eta[c]))
        empty_eta = np.where(ok, ready + exec_s, np.inf)
        if float(np.min(empty_eta)) > a.deadline_s + 1e-9:
            self._requeue_dead.add(j)
            return AdmissionRecord(a.task.tid, t_b,
                                   VERDICT_DEADLINE_MISSED, a.deadline_s,
                                   float(np.min(empty_eta)), -1,
                                   PROVENANCE_REQUEUED)
        eta = ready + self.arrival.queue_bound * drain + exec_s
        eta_ok = np.where(ok, eta, np.inf)
        if float(np.min(eta_ok)) > a.deadline_s + 1e-9:
            return AdmissionRecord(a.task.tid, t_b, VERDICT_CONGESTION,
                                   a.deadline_s, float(np.min(eta_ok)),
                                   -1, PROVENANCE_REQUEUED)
        c = self._pick_column(a, t_b, work, eta_ok)
        if not self._eviction_safe(t_b, np.array([j]), np.array([c])):
            return AdmissionRecord(a.task.tid, t_b, VERDICT_CONGESTION,
                                   a.deadline_s, float(eta[c]), -1,
                                   PROVENANCE_REQUEUED)
        return self._relocate(j, t_b, c, float(eta[c]))

    def _relocate(self, j: int, t_b: float, c: int,
                  eta: float) -> AdmissionRecord:
        """Commit a successful re-admission: launch the destination if
        needed and move the task there in every scenario, keeping each
        scenario's remaining work."""
        self._state = self._state.launch(
            np.array([c]), t_b + self.cfg.boot_overhead_s)
        self._state = jax.device_get(self._state.reassign(
            np.array([j]), np.array([c], np.int32)))
        self._assign[j] = int(c)
        return AdmissionRecord(self._tasks[j].tid, t_b, VERDICT_SUCCESS,
                               self._deadline[j], eta, int(c),
                               PROVENANCE_REQUEUED)

    # -- warm-started replanning -------------------------------------------
    def _refine(self, t_b: float) -> None:
        """Warm-started batched-ILS pass over not-yet-started tasks,
        guarded: the refinement is dropped wholesale if it would push any
        admitted pending task past its deadline that the incumbent still
        met (replanning never evicts an admitted task past its
        deadline)."""
        from repro.core.ils_jax import BatchedILSParams, run_batched_ils
        st = self._state
        b = len(self._tasks)
        rem0 = np.asarray(st.rem[0, :b], np.float64)
        not_started = np.flatnonzero(
            (rem0 > 0.0) & (np.abs(rem0 - np.asarray(self._total)) < 1e-6))
        if len(not_started) < 2:
            return
        sub_tasks = [TaskSpec(tid=i, memory_mb=self._tasks[j].memory_mb,
                              base_time=float(rem0[j]))
                     for i, j in enumerate(not_started)]
        assign = np.asarray(st.assign[0, :b])
        init = Solution(alloc=assign[not_started].astype(np.int32).copy(),
                        modes=np.zeros(len(not_started), np.int8),
                        pool=self.pool)
        init.selected_uids = set(init.used_uids())
        slack = min(self._deadline[j] for j in not_started) - t_b
        slack = max(float(slack), self.mc.dt)
        params = BatchedILSParams(
            population=8, iterations=12, proposals=16,
            swap_tasks=min(4, len(not_started)),
            seed=self.seed)
        res = run_batched_ils(sub_tasks, self.pool, self.cfg, slack, slack,
                              params, market=self.policy.market,
                              initial=init if self.arrival.warm_start
                              else None)
        cand = np.asarray(res.solution.alloc)
        cand = np.where(self._elig_static[cand], cand,
                        assign[not_started])      # column-set preserving
        if not self._eviction_safe(t_b, not_started, cand):
            return
        changed = cand != assign[not_started]
        if not np.any(changed):
            return
        idx = not_started[changed]
        tot = np.asarray(self._total, np.float32)[idx]
        self._state = self._state.launch(
            np.unique(cand[changed]),
            t_b + self.cfg.boot_overhead_s)
        self._state = jax.device_get(self._state.set_tasks(
            idx, tot, cand[changed].astype(np.int32),
            np.zeros(len(idx), np.int32)))
        for j, c in zip(idx, cand[changed]):
            self._assign[int(j)] = int(c)

    def _eviction_safe(self, t_b: float, idx: np.ndarray,
                       cand: np.ndarray) -> bool:
        """True when the candidate placement keeps every admitted pending
        task's projected finish within its deadline wherever the
        incumbent's projection already did."""
        st = self._state
        b = len(self._tasks)
        rem0 = np.asarray(st.rem[0, :b], np.float64)
        pending = np.flatnonzero(rem0 > 0.0)
        incumbent = np.asarray(st.assign[0, :b]).copy()
        proposed = incumbent.copy()
        proposed[idx] = cand

        def etas(alloc):
            load = np.zeros(self.n_vms)
            np.add.at(load, alloc[pending], rem0[pending])
            ok, ready, _ = self._column_view(t_b)
            drain = load / (self._cores * self._speed)
            cols = alloc[pending]
            return ready[cols] + drain[cols]

        dl = np.asarray(self._deadline)[pending]
        ok_inc = etas(incumbent) <= dl + 1e-9
        ok_new = etas(proposed) <= dl + 1e-9
        return bool(np.all(ok_new | ~ok_inc))

    # -- the run loop --------------------------------------------------
    def run(self, arrivals: Iterable[Arrival]) -> ServiceResult:
        """Serve a stream to completion: fold arrivals in at rolling
        boundaries, advance the engine between them, run out to the
        horizon and account per-task SLO attainment."""
        if self._ran:
            raise RuntimeError("Service.run is one-shot — build a fresh "
                               "Service per stream")
        self._ran = True
        stream = sorted(arrivals, key=lambda a: (a.time_s, a.task.tid))
        for a in stream:
            if a.time_s < 0:
                raise ValueError(f"arrival {a.task.tid} at negative time")
        per = self.arrival.replan_every_s
        folds: dict[float, list[Arrival]] = {}
        for a in stream:
            t_b = self._slot_ceil(max(per, np.ceil(a.time_s / per) * per))
            if t_b >= self.horizon_s:
                self._records.append(AdmissionRecord(
                    a.task.tid, a.time_s, VERDICT_CONGESTION,
                    a.deadline_s, np.inf, -1))
                continue
            folds.setdefault(t_b, []).append(a)

        for t_b in sorted(folds):
            if self._state is None:
                self._ensure_cap(1)
            if self._tasks and t_b > self._t:
                self._advance_to(t_b)
            t0 = time.perf_counter()
            self._state = jax.device_get(
                self._state.at_slot(int(round(t_b / self.mc.dt))))
            self._requeue_stranded(t_b)
            n_before = len(self._tasks)
            for a in folds[t_b]:
                self._records.append(self._admit(a, t_b))
            ev = self.arrival.ils_every
            if ev and (sorted(folds).index(t_b) + 1) % ev == 0 \
                    and len(self._tasks) > n_before:
                self._refine(t_b)
            self._replan_ms.append((time.perf_counter() - t0) * 1e3)
            self._t = t_b

        final = self._advance_to(None) if self._tasks else None
        return self._result(stream, final)

    def _result(self, stream: list[Arrival], final: MCResult | None
                ) -> ServiceResult:
        s = self.mc.n_scenarios
        arrivals_seen = [r for r in self._records
                         if r.provenance == PROVENANCE_ARRIVAL]
        admitted = [r for r in arrivals_seen
                    if r.verdict == VERDICT_SUCCESS]
        n_adm = len(admitted)
        n_req = sum(1 for r in self._records
                    if r.provenance == PROVENANCE_REQUEUED
                    and r.verdict == VERDICT_SUCCESS)
        if final is not None and self._state is not None:
            b = len(self._tasks)
            done = np.asarray(self._state.done_at[:, :b], np.float64)
            rem = np.asarray(self._state.rem[:, :b], np.float64)
            done = np.where(done < BIG * 0.5, done, np.inf)
            dl = np.asarray(self._deadline)
            met = (rem <= 0.0) & (done <= dl[None] + 1e-6)
            slo = float(np.mean(met)) if b else 1.0
            cost, mkp = final.cost, final.makespan
            unfin = final.unfinished
        else:
            done = np.zeros((s, 0))
            dl = np.zeros(0)
            slo = 1.0
            cost = np.zeros(s, np.float32)
            mkp = np.zeros(s, np.float32)
            unfin = np.zeros(s, int)
        span = max((a.time_s for a in stream), default=0.0)
        return ServiceResult(
            records=list(self._records), n_admitted=n_adm,
            n_rejected=len(arrivals_seen) - n_adm,
            admitted_per_s=n_adm / max(span, 1e-9),
            slo_met_frac=slo,
            replan_ms=np.asarray(self._replan_ms, np.float64),
            done_at_s=done, deadlines_s=dl,
            cost=np.asarray(cost), makespan_s=np.asarray(mkp),
            unfinished=np.asarray(unfin, int), mc=final,
            n_requeued=n_req)
