"""Dynamic Scheduling Module (§III-D) — the policy *lattice* + planning.

The paper's §IV comparison is an ablation over independent policy axes,
not three monolithic frameworks.  ``PolicyConfig`` makes the axes
first-class:

* ``planner``       — how the primary map is built: ``"ils-exact"`` (the
  paper's sequential ILS chain), ``"ils-batched"`` (the device-resident
  population search, ``core.ils_jax``) or ``"greedy"`` (Alg. 2 cost-only
  seed, the HADS baseline);
* ``market``        — market of the primary map (spot maps hibernate,
  on-demand maps do not);
* ``burstables``    — Algorithm 1 part 2 burstable allocation;
* ``hibernation``   — the response to a hibernation event:
  ``"migrate"`` (immediate Alg. 4 checkpoint-rollback migration),
  ``"defer"`` (HADS: tasks freeze in place and migration is postponed to
  the latest safe instant — the framework bets on the VM resuming), or
  ``"freeze"`` (tasks freeze in place *permanently*: the pure-optimist
  ablation point that only ever progresses again on resume);
* ``work_stealing`` — Algorithm 5 at AC boundaries / on resume.
* ``checkpoint``    — the FT-module checkpoint schedule (§2.8):
  ``"periodic"`` (default, the paper's Daly-style uniform grid — the
  historical engine behaviour), ``"off"`` (no checkpoints: no overhead
  but a preemption/termination loses all progress) or ``"random"``
  (per-task randomized intervals, arxiv 2601.14612, via
  ``ft.checkpoint.randomized_checkpoint_count``).  This axis only
  reshapes the plan *data* (total work + rollback grid); it is not part
  of the canonical registry, ``engine_view`` or the jit key.

Every lattice point is registered in ``POLICIES`` under a canonical
``planner+market+burst+hibernation+steal`` name and constructible from a
compact spec via ``policy()`` — ``policy("hads+burst")`` is HADS with
burstable allocation switched on, ``policy("hads+ckpt-off")`` the
checkpoint-free ablation.  The paper's three §IV frameworks are
registry *aliases* with byte-identical behaviour to the pre-lattice
configs (pinned by ``tests/data/des_golden.json`` and
``tests/data/mc_golden.json``):

* ``burst-hads``   = ils-exact + spot + burst + migrate + steal
* ``hads``         = greedy + spot + noburst + defer + nosteal  [1]
* ``ils-ondemand`` = ils-exact + ondemand + noburst

[1] Teylo et al., *A Bag-of-Tasks Scheduler Tolerant to Temporal
    Failures in Clouds*.
"""
from __future__ import annotations

import dataclasses
import warnings

from ..ft.checkpoint import CHECKPOINT_MODES
from ..obs import span
from .burst_alloc import burst_allocation
from .dspot import compute_dspot
from .greedy import initial_solution
from .ils import ILSParams, run_ils
from .types import CloudConfig, Job, Market, Solution

#: planner axis — ``"ils-exact"`` | ``"ils-batched"`` | ``"greedy"``
PLANNERS = ("ils-exact", "ils-batched", "greedy")
#: hibernation-response axis — ``"migrate"`` | ``"defer"`` | ``"freeze"``
HIBERNATION_MODES = ("migrate", "defer", "freeze")


class ILSKnobsDiscardedWarning(UserWarning):
    """The batched ILS engine has no equivalent for some ``ILSParams``
    knobs; raised when a caller's non-default values are dropped."""


@dataclasses.dataclass(frozen=True)
class PolicyConfig:
    """One point of the policy lattice (hashable — the MC engine's static
    jit argument is derived from it via ``engine_view``)."""

    name: str
    planner: str = "ils-exact"
    market: Market = Market.SPOT
    burstables: bool = False
    hibernation: str = "migrate"
    work_stealing: bool = False
    checkpoint: str = "periodic"

    # -- derived views consumed by the engines (the pre-lattice flags) --
    @property
    def primary(self) -> str:
        """``"ils"`` | ``"greedy"`` — the map-construction family."""
        return "greedy" if self.planner == "greedy" else "ils"

    @property
    def use_burstables(self) -> bool:
        return self.burstables

    @property
    def immediate_migration(self) -> bool:
        """Alg. 4 fires at the hibernation event itself."""
        return self.hibernation == "migrate"

    @property
    def freeze_in_place(self) -> bool:
        """Hibernation preserves task memory (EC2 hibernate semantics);
        progress is exact across the outage instead of checkpoint-floor."""
        return self.hibernation in ("defer", "freeze")

    @property
    def deferred_migration(self) -> bool:
        """Frozen bags migrate at the latest deadline-safe instant
        (HADS); under ``"freeze"`` they never migrate at all."""
        return self.hibernation == "defer"

    @property
    def hibernatable(self) -> bool:
        """Whether Table V hibernation scenarios apply: only spot primary
        maps can lose VMs to the provider."""
        return self.market == Market.SPOT

    def scenario_names(self) -> tuple[str, ...]:
        """Scenario sweep relevant to this policy (§IV): on-demand maps
        only face the event-free baseline."""
        if not self.hibernatable:
            return ("none",)
        return ("none", "sc1", "sc2", "sc3", "sc4", "sc5")

    def engine_view(self) -> "PolicyConfig":
        """The dynamic engines branch only on (burstables, hibernation,
        work_stealing) — collapse onto a canonical representative so the
        ~50 registry policies share ~12 MC-engine compilations instead of
        keying the jit cache on name/planner/market."""
        return _engine_view(self.burstables, self.hibernation,
                            self.work_stealing)


def _axes_of(p: PolicyConfig) -> tuple:
    return (p.planner, p.market, p.burstables, p.hibernation,
            p.work_stealing)


def canonical_name(planner: str, market: Market, burstables: bool,
                   hibernation: str, work_stealing: bool) -> str:
    """Canonical registry key of a lattice point, e.g.
    ``"ils-exact+spot+burst+migrate+steal"``."""
    return "+".join((planner, market.value,
                     "burst" if burstables else "noburst", hibernation,
                     "steal" if work_stealing else "nosteal"))


def make_policy(planner: str = "ils-exact", market: Market = Market.SPOT,
                burstables: bool = False, hibernation: str = "migrate",
                work_stealing: bool = False,
                checkpoint: str = "periodic",
                name: str | None = None) -> PolicyConfig:
    """Validate + canonicalize one lattice point.

    On-demand maps never hibernate, so their ``hibernation`` axis is
    degenerate — it is canonicalized to ``"migrate"`` (identical
    behaviour, one registry point instead of three).  If the resulting
    axes are already registered, the registry instance is returned (one
    object per lattice point keeps the jit cache tight); ``name`` forces
    a fresh instance under that name.  A non-default ``checkpoint`` mode
    always yields a fresh instance (named ``...+ckpt-<mode>``): the axis
    stays out of the canonical registry because it only changes plan
    data, never the engine program.
    """
    if planner not in PLANNERS:
        raise ValueError(f"unknown planner {planner!r} (one of {PLANNERS})")
    if hibernation not in HIBERNATION_MODES:
        raise ValueError(f"unknown hibernation mode {hibernation!r} "
                         f"(one of {HIBERNATION_MODES})")
    if checkpoint not in CHECKPOINT_MODES:
        raise ValueError(f"unknown checkpoint mode {checkpoint!r} "
                         f"(one of {CHECKPOINT_MODES})")
    market = Market(market)
    if market == Market.ONDEMAND:
        hibernation = "migrate"
    axes = (planner, market, burstables, hibernation, work_stealing)
    if name is None:
        if checkpoint == "periodic":
            hit = _BY_AXES.get(axes)
            if hit is not None:
                return hit
            name = canonical_name(*axes)
        else:
            name = canonical_name(*axes) + f"+ckpt-{checkpoint}"
    return PolicyConfig(name, planner=planner, market=market,
                        burstables=burstables, hibernation=hibernation,
                        work_stealing=work_stealing, checkpoint=checkpoint)


# --- the paper's three §IV frameworks, as lattice aliases ----------------
BURST_HADS = PolicyConfig("burst-hads", planner="ils-exact",
                          market=Market.SPOT, burstables=True,
                          hibernation="migrate", work_stealing=True)
HADS = PolicyConfig("hads", planner="greedy", market=Market.SPOT,
                    burstables=False, hibernation="defer",
                    work_stealing=False)
ILS_ONDEMAND = PolicyConfig("ils-ondemand", planner="ils-exact",
                            market=Market.ONDEMAND, burstables=False,
                            hibernation="migrate", work_stealing=False)

#: name -> PolicyConfig: the three aliases + every canonical lattice
#: point (spot x 3 planners x 2 burst x 3 hibernation x 2 steal, plus
#: the on-demand points with their degenerate hibernation axis).
POLICIES: dict[str, PolicyConfig] = {}
#: axes -> the single registry instance carrying them
_BY_AXES: dict[tuple, PolicyConfig] = {}

for _alias in (BURST_HADS, HADS, ILS_ONDEMAND):
    POLICIES[_alias.name] = _alias
    _BY_AXES[_axes_of(_alias)] = _alias

for _pl in PLANNERS:
    for _mk in (Market.SPOT, Market.ONDEMAND):
        for _bu in (False, True):
            for _hb in (HIBERNATION_MODES if _mk == Market.SPOT
                        else ("migrate",)):
                for _ws in (False, True):
                    _axes = (_pl, _mk, _bu, _hb, _ws)
                    _p = _BY_AXES.get(_axes) or PolicyConfig(
                        canonical_name(*_axes), planner=_pl, market=_mk,
                        burstables=_bu, hibernation=_hb, work_stealing=_ws)
                    _BY_AXES.setdefault(_axes, _p)
                    POLICIES[canonical_name(*_axes)] = _p

#: ``policy()`` modifier vocabulary: token -> (axis, value)
_TOKENS: dict[str, tuple[str, object]] = {
    "ils": ("planner", "ils-exact"),
    "ils-exact": ("planner", "ils-exact"),
    "ils-batched": ("planner", "ils-batched"),
    "greedy": ("planner", "greedy"),
    "spot": ("market", Market.SPOT),
    "ondemand": ("market", Market.ONDEMAND),
    "od": ("market", Market.ONDEMAND),
    "burst": ("burstables", True),
    "noburst": ("burstables", False),
    "migrate": ("hibernation", "migrate"),
    "defer": ("hibernation", "defer"),
    "freeze": ("hibernation", "freeze"),
    "steal": ("work_stealing", True),
    "nosteal": ("work_stealing", False),
    "ckpt-periodic": ("checkpoint", "periodic"),
    "ckpt-off": ("checkpoint", "off"),
    "ckpt-random": ("checkpoint", "random"),
}


def policy(spec: "str | PolicyConfig") -> PolicyConfig:
    """Resolve a policy spec: a ``PolicyConfig`` (returned as-is), a
    registry name (``"burst-hads"``, a canonical lattice name), or a
    ``"+"``-joined compositional spec.

    A compositional spec starts from a base and applies modifiers left to
    right: ``"hads+burst"`` is the HADS alias with burstable allocation
    on, ``"burst-hads+nosteal"`` is Burst-HADS without Alg. 5.  If the
    first token is not a registered name the defaults (ils-exact, spot,
    noburst, migrate, nosteal) are the base, so a bare axes spec like
    ``"greedy+spot+burst+freeze+steal"`` also resolves.  The result is
    always the single registry instance for those axes.
    """
    if isinstance(spec, PolicyConfig):
        return spec
    if not isinstance(spec, str):
        raise TypeError(f"cannot interpret {type(spec).__name__} as a "
                        "policy spec")
    if spec in POLICIES:
        return POLICIES[spec]
    tokens = [t.strip() for t in spec.split("+") if t.strip()]
    if not tokens:
        raise ValueError("empty policy spec")
    axes = {"planner": "ils-exact", "market": Market.SPOT,
            "burstables": False, "hibernation": "migrate",
            "work_stealing": False, "checkpoint": "periodic"}
    if tokens[0] in POLICIES:
        base = POLICIES[tokens[0]]
        axes = {"planner": base.planner, "market": base.market,
                "burstables": base.burstables,
                "hibernation": base.hibernation,
                "work_stealing": base.work_stealing,
                "checkpoint": base.checkpoint}
        tokens = tokens[1:]
    for tok in tokens:
        if tok not in _TOKENS:
            raise ValueError(
                f"unknown policy token {tok!r} in spec {spec!r}; "
                f"vocabulary: {sorted(_TOKENS)} or a registered name "
                f"from POLICIES")
        axis, value = _TOKENS[tok]
        axes[axis] = value
    return make_policy(**axes)


def _engine_view(burstables: bool, hibernation: str,
                 work_stealing: bool) -> PolicyConfig:
    return POLICIES[canonical_name("ils-exact", Market.SPOT, burstables,
                                   hibernation, work_stealing)]


@dataclasses.dataclass
class PrimaryPlan:
    solution: Solution
    dspot: float
    policy: PolicyConfig


@dataclasses.dataclass(frozen=True)
class ArrivalPolicy:
    """Arrival-aware knobs of the online service mode (``repro.service``,
    DESIGN.md §2.9) — how streaming tasks are admitted and folded into
    the incumbent plan.  Orthogonal to the ``PolicyConfig`` lattice: the
    dynamic-phase axes keep describing what happens *after* admission.

    * ``admission`` — ``"deadline"`` renders the three-verdict contract
      (DEADLINE_MISSED / CONGESTION / SUCCESS: reject when even an empty
      column cannot finish the task by its deadline, reject when only
      queue backlog kills it, admit otherwise); ``"always"`` admits
      everything (load-test mode — SLO attainment becomes the output).
    * ``replan_every_s`` — rolling-horizon cadence: arrivals inside
      ``(t, t + replan_every_s]`` are folded in together at the next
      boundary (quantized to the engine's slot grid).
    * ``queue_bound`` — CONGESTION conservativeness: a column's projected
      drain time is scaled by this factor before the deadline check.
    * ``warm_start`` — seed the batched-ILS refinement from the incumbent
      plan instead of a fresh greedy solution.
    * ``insert_candidates`` — how many columns (by projected-finish
      pre-score) the ``insert_tasks`` kernel scores per admitted task.
    * ``ils_every`` — run a warm-started batched-ILS refinement every
      k-th replan boundary (0 = never: insertion-only incremental
      planning, the cheap default).
    """

    admission: str = "deadline"
    replan_every_s: float = 300.0
    queue_bound: float = 1.0
    warm_start: bool = True
    insert_candidates: int = 8
    ils_every: int = 0

    def __post_init__(self):
        if self.admission not in ("deadline", "always"):
            raise ValueError(f"unknown admission mode {self.admission!r} "
                             "(deadline/always)")
        if self.replan_every_s <= 0:
            raise ValueError("replan_every_s must be positive")
        if self.insert_candidates < 1:
            raise ValueError("insert_candidates must be >= 1")


#: ILSParams knobs with no batched-search equivalent, checked against
#: their defaults when the hand-off has to discard them.
_BATCHED_DROPPED = ("max_attempt", "swap_rate", "max_failed", "relax_rate")


def _batched_params_from(params: ILSParams):
    """Derive ``BatchedILSParams`` from sequential-ILS knobs, warning when
    explicitly-set knobs have no batched equivalent and are discarded."""
    from .ils_jax import BatchedILSParams
    defaults = ILSParams()
    dropped = [k for k in _BATCHED_DROPPED
               if getattr(params, k) != getattr(defaults, k)]
    if dropped:
        warnings.warn(
            f"build_primary_map(engine='batched'): ILSParams knobs "
            f"{dropped} have no batched-search equivalent and are "
            f"discarded — pass batched_params=BatchedILSParams(...) to "
            f"control the population search explicitly",
            ILSKnobsDiscardedWarning, stacklevel=3)
    return BatchedILSParams(iterations=params.max_iteration,
                            alpha=params.alpha, seed=params.seed)


def build_primary_map(job: Job, cfg: CloudConfig, policy: PolicyConfig,
                      params: ILSParams = ILSParams(),
                      engine: str | None = None,
                      batched_params=None) -> PrimaryPlan:
    """Algorithm 1 end-to-end for the chosen policy.

    ``engine`` selects the ILS search backing the primary map:
    ``"exact"`` is the paper's sequential chain (``core.ils``, exact
    packer fitness); ``"batched"`` hands off to the device-resident
    population search (``core.ils_jax.run_batched_ils``) — the static
    phase the fleet pipeline (``sim.fleet``) uses so the whole
    plan→distribution flow stays on device.  ``None`` (default) follows
    ``policy.planner`` — the lattice's own axis.  Both return the same
    ``PrimaryPlan`` shape; burstable allocation and D_spot are shared.

    The two searches have different knob sets: under ``"batched"`` only
    ``max_iteration`` (→ iterations), ``alpha`` and ``seed`` carry over
    from ``params``; ``max_attempt``/``swap_rate``/``max_failed``/
    ``relax_rate`` have no batched equivalent (an
    ``ILSKnobsDiscardedWarning`` fires when non-default values are
    dropped).  Pass ``batched_params`` (a
    ``core.ils_jax.BatchedILSParams``) to control population/proposal
    sizes explicitly — it takes precedence over the derived hand-off.
    """
    if engine is None:
        engine = "batched" if policy.planner == "ils-batched" else "exact"
    with span("plan", policy=policy.name, engine=engine,
              n_tasks=len(job.tasks)):
        pool = cfg.instance_pool()
        if policy.market == Market.SPOT:
            dspot = compute_dspot(job.deadline_s, job.tasks, cfg)
        else:
            dspot = job.deadline_s  # on-demand VMs don't hibernate

        if policy.primary == "ils":
            if engine == "batched":
                from .ils_jax import run_batched_ils
                bp = batched_params if batched_params is not None \
                    else _batched_params_from(params)
                sol = run_batched_ils(job.tasks, pool, cfg, dspot,
                                      job.deadline_s, bp,
                                      market=policy.market).solution
            elif engine == "exact":
                sol = run_ils(job.tasks, pool, cfg, dspot, job.deadline_s,
                              params, market=policy.market).solution
            else:
                raise ValueError(f"unknown ILS engine {engine!r} "
                                 "(exact/batched)")
        else:
            sol = initial_solution(job.tasks, pool, cfg, dspot,
                                   market=policy.market)
            sol.selected_uids = set(sol.used_uids())

        if policy.use_burstables:
            sol = burst_allocation(sol, job.tasks, cfg, dspot, job.deadline_s,
                                   params.burst_rate).solution
        return PrimaryPlan(solution=sol, dspot=dspot, policy=policy)
