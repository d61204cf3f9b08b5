"""Domain types of the reference (paper section III-A, Tables I and II).

A copy of the program's ``repro.core.types`` at the commit that added the
benchmark, with the catalog taken from a configuration file
(``cloud_from_config``) instead of module constants.  Time is in seconds;
prices are quoted per hour and billed per second.
"""
from __future__ import annotations

import dataclasses
import enum

import numpy as np


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


@dataclasses.dataclass(frozen=True)
class CloudConfig:
    """The user-provided sets M^s, M^o, M^b plus global constants."""

    spot_types: tuple[VMType, ...]
    ondemand_types: tuple[VMType, ...]
    burstable_types: tuple[VMType, ...]
    max_per_type_market: int
    gflops_ref: float
    boot_overhead_s: float               # ω — VM launch + OS boot
    checkpoint_restore_s: float          # task state reload on migration
    allocation_cycle_s: float            # AC (paper §IV: 900 s)
    burst_period_s: float                # one CPU credit = one vCPU-minute

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

    def copy(self) -> "Solution":
        return Solution(self.alloc.copy(), self.modes.copy(), self.pool,
                        set(self.selected_uids))

    def tasks_on(self, uid: int) -> np.ndarray:
        return np.flatnonzero(self.alloc == uid)

    def used_uids(self) -> list[int]:
        return sorted(set(int(u) for u in self.alloc if u >= 0))

def empty_solution(n_tasks: int, pool: list[VMInstance]) -> Solution:
    return Solution(alloc=np.full(n_tasks, -1, dtype=np.int32),
                    modes=np.zeros(n_tasks, dtype=np.int8),
                    pool=pool)


def cloud_from_config(cat: dict) -> CloudConfig:
    """The catalog section of a configuration file as a ``CloudConfig``:
    ``types`` maps a type name to its ``VMType`` fields, and ``spot``,
    ``ondemand`` and ``burstable`` list the type names of each market in
    pool order."""
    types = {name: VMType(name=name, **fields)
             for name, fields in cat["types"].items()}
    return CloudConfig(
        spot_types=tuple(types[n] for n in cat["spot"]),
        ondemand_types=tuple(types[n] for n in cat["ondemand"]),
        burstable_types=tuple(types[n] for n in cat["burstable"]),
        max_per_type_market=int(cat["max_per_type_market"]),
        gflops_ref=float(types[cat["gflops_ref_type"]].gflops),
        boot_overhead_s=float(cat["boot_overhead_s"]),
        checkpoint_restore_s=float(cat["checkpoint_restore_s"]),
        allocation_cycle_s=float(cat["allocation_cycle_s"]),
        burst_period_s=float(cat["burst_period_s"]))
