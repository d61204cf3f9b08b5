"""A configuration file as the program's inputs and as the reference's.

The same catalog and bag data build the program's ``CloudConfig`` and
``Job`` and, separately, the reference's (``bench.reference.types``), so
the two sides share numbers and no code.
"""
from __future__ import annotations

from bench.reference import types as rtypes
from bench.reference.plans import tasks_of


def program_cloud(conf: dict):
    from repro.core.types import CloudConfig, VMType
    cat = conf["catalog"]
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


def reference_cloud(conf: dict) -> rtypes.CloudConfig:
    return rtypes.cloud_from_config(conf["catalog"])


def program_job(name: str, mem, base, deadline_s: float):
    from repro.core.types import Job, TaskSpec
    tasks = tuple(TaskSpec(tid=i, memory_mb=float(m), base_time=float(b))
                  for i, (m, b) in enumerate(zip(mem, base)))
    return Job(name=name, tasks=tasks, deadline_s=float(deadline_s))


def reference_job(name: str, mem, base, deadline_s: float) -> rtypes.Job:
    return rtypes.Job(name=name, tasks=tasks_of(mem, base),
                      deadline_s=float(deadline_s))
