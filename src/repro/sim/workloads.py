"""Paper workloads (Table III) — synthetic Alves-template jobs + ED200.

Synthetic tasks execute vector operations whose times depend on vector size:
memory footprints in [2.81, 13.19] MB and base execution times in
[102, 330] s (paper §IV).  We sample sizes uniformly and map them affinely to
the time range, then jitter, reproducing the published min/avg/max bands.

ED200 (NAS GRID ED, class B): 200 embarrassingly-distributed tasks,
153.74–177.77 MB.  The paper does not publish ED task durations; base times
are calibrated (~420 s on C4.large) so the ILS-on-demand makespan lands near
Table IV's 1887 s — the constant is flagged here per DESIGN.md §5(2).
"""
from __future__ import annotations

import re
import zlib

import numpy as np

from repro.core.types import Job, TaskSpec

#: deadline for every paper job (§IV): 45 minutes
PAPER_DEADLINE_S = 2700.0

_SYN_MEM_MB = (2.81, 13.19)
_SYN_TIME_S = (102.0, 330.0)
_ED_MEM_MB = (153.74, 177.77)
_ED_TIME_S = (360.0, 480.0)   # calibrated, see module docstring


def _synthetic_tasks(n: int, rng: np.random.Generator) -> list[TaskSpec]:
    u = rng.uniform(0.0, 1.0, size=n)
    mem = _SYN_MEM_MB[0] + u * (_SYN_MEM_MB[1] - _SYN_MEM_MB[0])
    base = _SYN_TIME_S[0] + u * (_SYN_TIME_S[1] - _SYN_TIME_S[0])
    base *= rng.uniform(0.95, 1.05, size=n)   # template jitter
    return [TaskSpec(tid=i, memory_mb=float(mem[i]),
                     base_time=float(np.clip(base[i], *_SYN_TIME_S)))
            for i in range(n)]


def _ed_tasks(n: int, rng: np.random.Generator) -> list[TaskSpec]:
    mem = rng.uniform(*_ED_MEM_MB, size=n)
    base = rng.uniform(*_ED_TIME_S, size=n)
    return [TaskSpec(tid=i, memory_mb=float(mem[i]), base_time=float(base[i]))
            for i in range(n)]


def make_job(name: str, seed: int = 0,
             deadline_s: float = PAPER_DEADLINE_S) -> Job:
    # crc32, not hash(): salted str hashes would give every *process* a
    # different instance, making perf artifacts incomparable across runs
    rng = np.random.default_rng(seed + zlib.crc32(name.encode()) % (2 ** 16))
    jn = re.fullmatch(r"J(\d+)", name.upper())
    if jn:      # paper jobs are J60/J80/J100; larger J<n> scale the template
        tasks = _synthetic_tasks(int(jn.group(1)), rng)
    elif name.upper() == "ED200":
        tasks = _ed_tasks(200, rng)
    else:
        raise ValueError(f"unknown job {name!r} (J<n>/ED200)")
    return Job(name=name.upper(), tasks=tuple(tasks), deadline_s=deadline_s)


def J60(seed: int = 0) -> Job:
    return make_job("J60", seed)


def J80(seed: int = 0) -> Job:
    return make_job("J80", seed)


def J100(seed: int = 0) -> Job:
    return make_job("J100", seed)


def ED200(seed: int = 0) -> Job:
    return make_job("ED200", seed)


ALL_JOBS = ("J60", "J80", "J100", "ED200")
