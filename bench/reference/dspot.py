"""D_spot — the hibernation-slack makespan bound (paper §III-A / [1]).

``D_spot`` is the worst-case estimated makespan that still leaves enough spare
time to migrate the tasks of *any* hibernated spot VM to other VMs and finish
them before the user deadline ``D``, no matter when the hibernation happens.
It is computed from the longest task that might need to be migrated, executed
on the slowest machine of the system, plus the VM boot overhead and the
checkpoint-restore cost.
"""
from __future__ import annotations

from typing import Sequence

from .types import CloudConfig, TaskSpec, VMType


def slowest_type(cfg: CloudConfig) -> VMType:
    types = cfg.spot_types + cfg.ondemand_types + cfg.burstable_types
    return min(types, key=lambda t: t.gflops)


def worst_case_migration_s(tasks: Sequence[TaskSpec], cfg: CloudConfig) -> float:
    """Longest task on the slowest VM + boot + restore overheads."""
    slow = slowest_type(cfg)
    longest = max(t.exec_time(slow, cfg.gflops_ref) for t in tasks)
    return longest + cfg.boot_overhead_s + cfg.checkpoint_restore_s


def compute_dspot(deadline_s: float, tasks: Sequence[TaskSpec],
                  cfg: CloudConfig) -> float:
    """D_spot = D - worst-case migration slack.  Raises if non-positive."""
    dspot = deadline_s - worst_case_migration_s(tasks, cfg)
    if dspot <= 0:
        raise ValueError(
            f"deadline {deadline_s}s leaves no room for the worst-case "
            f"migration ({worst_case_migration_s(tasks, cfg):.0f}s)")
    return dspot
