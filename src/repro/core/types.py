"""Core domain types for Burst-HADS (paper §III-A, Table I/II).

Time is discretized in seconds (the paper's ``T = {1..D}``).  Prices in the
VM catalog are quoted per hour (Table II) and converted to $/s internally,
matching EC2 per-second billing.

The scheduler is *catalog-agnostic*: the same algorithms run against the EC2
catalog reproduced from Table II and against the TPU-slice catalog in
``repro.cluster.catalog`` (see DESIGN.md §2.2).
"""
from __future__ import annotations

import dataclasses
import enum
import math
from typing import TYPE_CHECKING, Sequence

import numpy as np

if TYPE_CHECKING:
    from .greedy import GreedyCounters


class Market(enum.Enum):
    SPOT = "spot"
    ONDEMAND = "ondemand"
    BURSTABLE = "burstable"


class ExecMode(enum.Enum):
    FULL = "full"          # regular VM, or burstable in burst mode
    BASELINE = "baseline"  # burstable capped at baseline_frac of CPU


@dataclasses.dataclass(frozen=True)
class VMType:
    """A VM *type* (Table II row) available in one or more markets."""

    name: str
    vcpus: int
    memory_mb: float
    price_ondemand: float            # $/hour
    price_spot: float | None = None  # $/hour; None => not offered on spot
    burstable: bool = False
    baseline_frac: float = 1.0       # burst-mode fraction usable in baseline mode
    gflops: float = 1.0              # LINPACK estimate (Eq. 7 weight numerator)
    credit_rate_per_hour: float = 0.0   # CPU credits accrued per hour (burstable)
    initial_credits: float = 0.0

    def price(self, market: Market) -> float:
        """$/hour in the given market."""
        if market == Market.SPOT:
            if self.price_spot is None:
                raise ValueError(f"{self.name} not offered on the spot market")
            return self.price_spot
        return self.price_ondemand

    def price_per_sec(self, market: Market) -> float:
        return self.price(market) / 3600.0

    def weight(self, market: Market) -> float:
        """WRR weight, Eq. 7: Gflops / price-per-period."""
        return self.gflops / self.price(market)


@dataclasses.dataclass(frozen=True)
class VMInstance:
    """A concrete instance the scheduler may select (type x market x slot).

    ``uid`` indexes the instance in the flat candidate pool used by both the
    python and the JAX/Pallas fitness paths.
    """

    uid: int
    vm_type: VMType
    market: Market

    @property
    def name(self) -> str:
        return f"{self.vm_type.name}/{self.market.value}#{self.uid}"

    @property
    def vcpus(self) -> int:
        return self.vm_type.vcpus

    @property
    def memory_mb(self) -> float:
        return self.vm_type.memory_mb

    @property
    def price_per_sec(self) -> float:
        return self.vm_type.price_per_sec(self.market)

    @property
    def is_spot(self) -> bool:
        return self.market == Market.SPOT

    @property
    def is_burstable(self) -> bool:
        return self.market == Market.BURSTABLE


@dataclasses.dataclass(frozen=True)
class TaskSpec:
    """A BoT task: one vCPU, known memory footprint and execution time.

    ``base_time`` is the execution time in seconds on the *reference* VM type
    (``gflops_ref``) at full speed.  ``e_ij`` on other types scales inversely
    with Gflops (paper assumes e_ij known beforehand; the scaling is how we
    derive the full matrix from a single profile, mirroring LINPACK-based
    calibration).
    """

    tid: int
    memory_mb: float
    base_time: float

    def exec_time(self, vm_type: VMType, gflops_ref: float,
                  mode: ExecMode = ExecMode.FULL) -> float:
        t = self.base_time * (gflops_ref / vm_type.gflops)
        if mode == ExecMode.BASELINE:
            t /= vm_type.baseline_frac
        return t


# ---------------------------------------------------------------------------
# EC2 catalog — Table II.  Gflops are LINPACK-style estimates consistent with
# the relative generations (C4 Haswell > C3 Ivy Bridge; T3 Skylake burst).
# ---------------------------------------------------------------------------

C3_LARGE = VMType("c3.large", vcpus=2, memory_mb=3.75 * 1024,
                  price_ondemand=0.105, price_spot=0.0299, gflops=35.2)
C4_LARGE = VMType("c4.large", vcpus=2, memory_mb=3.75 * 1024,
                  price_ondemand=0.100, price_spot=0.0366, gflops=41.6)
C3_XLARGE = VMType("c3.xlarge", vcpus=4, memory_mb=7.5 * 1024,
                   price_ondemand=0.199, price_spot=0.0634, gflops=70.4)
T3_LARGE = VMType("t3.large", vcpus=2, memory_mb=8 * 1024,
                  price_ondemand=0.0832, price_spot=None,
                  burstable=True, baseline_frac=0.20, gflops=48.0,
                  credit_rate_per_hour=36.0, initial_credits=0.0)

EC2_SPOT_TYPES: tuple[VMType, ...] = (C3_LARGE, C4_LARGE, C3_XLARGE)
EC2_ONDEMAND_TYPES: tuple[VMType, ...] = (C3_LARGE, C4_LARGE, C3_XLARGE)
EC2_BURSTABLE_TYPES: tuple[VMType, ...] = (T3_LARGE,)

#: reference machine for ``TaskSpec.base_time`` (C4.large, the common case)
GFLOPS_REF = C4_LARGE.gflops

#: EC2 default limit: at most five simultaneous VMs per (type, market)
MAX_PER_TYPE_MARKET = 5

#: one CPU credit = one vCPU-minute of burst above baseline
BURST_PERIOD_S = 60.0


@dataclasses.dataclass(frozen=True)
class CloudConfig:
    """The user-provided sets M^s, M^o, M^b plus global constants."""

    spot_types: tuple[VMType, ...] = EC2_SPOT_TYPES
    ondemand_types: tuple[VMType, ...] = EC2_ONDEMAND_TYPES
    burstable_types: tuple[VMType, ...] = EC2_BURSTABLE_TYPES
    max_per_type_market: int = MAX_PER_TYPE_MARKET
    gflops_ref: float = GFLOPS_REF
    boot_overhead_s: float = 60.0        # ω — VM launch + OS boot
    checkpoint_restore_s: float = 10.0   # task state reload on migration
    allocation_cycle_s: float = 900.0    # AC (paper §IV: 900 s)
    burst_period_s: float = BURST_PERIOD_S

    def instance_pool(self) -> list[VMInstance]:
        """Flat pool of every instance the scheduler may select.

        Layout (stable, relied upon by the JAX path):
          [spot types x slots][ondemand types x slots][burstable types x slots]
        """
        pool: list[VMInstance] = []
        uid = 0
        for market, types in ((Market.SPOT, self.spot_types),
                              (Market.ONDEMAND, self.ondemand_types),
                              (Market.BURSTABLE, self.burstable_types)):
            for vt in types:
                for _ in range(self.max_per_type_market):
                    pool.append(VMInstance(uid, vt, market))
                    uid += 1
        return pool


@dataclasses.dataclass(frozen=True)
class Job:
    """A Bag-of-Tasks application with a deadline (Table III rows)."""

    name: str
    tasks: tuple[TaskSpec, ...]
    deadline_s: float

    @property
    def n_tasks(self) -> int:
        return len(self.tasks)

    def memory_stats_mb(self) -> tuple[float, float, float]:
        ms = [t.memory_mb for t in self.tasks]
        return min(ms), sum(ms) / len(ms), max(ms)


@dataclasses.dataclass
class Assignment:
    """Placement of one task inside a solution."""

    task: TaskSpec
    vm_uid: int
    mode: ExecMode = ExecMode.FULL
    start: float = 0.0   # filled by the packer
    end: float = 0.0


@dataclasses.dataclass
class Solution:
    """A scheduling map: allocation vector + the selected instances.

    Matches the paper's solution structure (§III-C): (i) a vector indexed by
    task holding the VM that executes it, (ii) the list of selected VMs.
    """

    alloc: np.ndarray                     # int32[|B|] -> VMInstance.uid, -1 = unassigned
    modes: np.ndarray                     # int8[|B|]  -> 0 FULL / 1 BASELINE
    pool: list[VMInstance]
    selected_uids: set[int] = dataclasses.field(default_factory=set)
    #: what the greedy constructor did to build this solution; None on any
    #: other solution, copies included
    greedy_counters: GreedyCounters | None = None

    def copy(self) -> "Solution":
        return Solution(self.alloc.copy(), self.modes.copy(), self.pool,
                        set(self.selected_uids))

    def tasks_on(self, uid: int) -> np.ndarray:
        return np.flatnonzero(self.alloc == uid)

    def used_uids(self) -> list[int]:
        return sorted(set(int(u) for u in self.alloc if u >= 0))

    def prune_selected(self) -> None:
        """Drop selected VMs that hold no task (idle ones cost money)."""
        used = set(self.used_uids())
        self.selected_uids &= used


def empty_solution(n_tasks: int, pool: list[VMInstance]) -> Solution:
    return Solution(alloc=np.full(n_tasks, -1, dtype=np.int32),
                    modes=np.zeros(n_tasks, dtype=np.int8),
                    pool=pool)


def exec_time_matrix(tasks: Sequence[TaskSpec], pool: Sequence[VMInstance],
                     cfg: CloudConfig) -> np.ndarray:
    """e[i, j]: full-speed execution time of task i on pool instance j."""
    e = np.empty((len(tasks), len(pool)), dtype=np.float64)
    for i, t in enumerate(tasks):
        for j, vm in enumerate(pool):
            e[i, j] = t.exec_time(vm.vm_type, cfg.gflops_ref)
    return e


def ceil_div(a: float, b: float) -> int:
    return int(math.ceil(a / b))
