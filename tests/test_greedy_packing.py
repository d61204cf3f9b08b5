"""The greedy seed answers its feasibility checks from each VM's kept LPT
packing: the plans it makes are the plans of Alg. 2 with a full
``check_schedule`` repack per candidate, bit for bit, on both markets and
where memory binds; its counters say which checks took which path."""
import json
import os
import sys

import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.core.dspot import compute_dspot
from repro.core.fitness import check_schedule
from repro.core.greedy import SmoothWRR, initial_solution
from repro.core.types import (CloudConfig, ExecMode, Market, TaskSpec,
                              empty_solution)
from repro.sim.workloads import make_job

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CFG = CloudConfig()
DEADLINE = 2700.0


def _greedy_by_repacking(tasks, pool, cfg, dspot, market):
    """Alg. 2 as written: every candidate placement is one
    ``check_schedule``, which packs the VM's tasks and the candidate from
    scratch."""
    sol = empty_solution(len(tasks), pool)
    free_by_type: dict[str, list[int]] = {}
    for vm in pool:
        if vm.market == market:
            free_by_type.setdefault(vm.vm_type.name, []).append(vm.uid)
    types = cfg.spot_types if market == Market.SPOT else cfg.ondemand_types
    wrr = SmoothWRR([t.name for t in types], [t.weight(market) for t in types])
    selected: list[int] = []
    on_vm: dict[int, list[int]] = {}
    for i in sorted(range(len(tasks)),
                    key=lambda i: (-tasks[i].memory_mb, tasks[i].tid)):
        t = tasks[i]
        for uid in sorted(selected, key=lambda u: pool[u].price_per_sec):
            cur = [tasks[k] for k in on_vm[uid]]
            if check_schedule(t, pool[uid], cur, [ExecMode.FULL] * len(cur),
                              cfg, dspot):
                sol.alloc[i] = uid
                on_vm[uid].append(i)
                break
        else:
            excluded: set[str] = set()
            while True:
                tname = wrr.next({n for n, lst in free_by_type.items()
                                  if lst and n not in excluded})
                if tname is None:
                    raise RuntimeError(f"task {t.tid} unschedulable")
                uid = free_by_type[tname].pop(0)
                if check_schedule(t, pool[uid], [], [], cfg, dspot):
                    sol.alloc[i] = uid
                    on_vm[uid] = [i]
                    selected.append(uid)
                    break
                free_by_type[tname].insert(0, uid)
                excluded.add(tname)
    sol.selected_uids = set(selected)
    return sol


def _limit(tasks, market):
    return (compute_dspot(DEADLINE, tasks, CFG) if market == Market.SPOT
            else DEADLINE)


def _both(tasks, market, cfg=CFG):
    """The program's seed and the repacking one, or the errors they raise."""
    pool, dspot = cfg.instance_pool(), _limit(tasks, market)
    out = []
    for fn in (initial_solution, _greedy_by_repacking):
        try:
            out.append(fn(tasks, pool, cfg, dspot, market))
        except RuntimeError:
            out.append(None)
    return out


def _assert_same_plan(got, want):
    assert (got is None) == (want is None)
    if got is not None:
        np.testing.assert_array_equal(got.alloc, want.alloc)
        assert got.selected_uids == want.selected_uids


def _bag(raw):
    return [TaskSpec(tid=i, memory_mb=m, base_time=t)
            for i, (m, t) in enumerate(raw)]


#: memory-binding bag: on the 3840 MB two-core types two of these tasks
#: cannot run at once, so the timeline delays starts
BINDING = [(2000.0 + 37.0 * i, 150.0 + 11.0 * i) for i in range(10)] + \
    [(40.0, 120.0 + 9.0 * i) for i in range(20)]


@st.composite
def bags(draw):
    """Light bags (J-like memory) and heavy ones whose tasks bind memory on
    the small types; base times either continuous or from a few values, so
    LPT keys tie on time and part on the task id."""
    n = draw(st.integers(1, 40))
    heavy = draw(st.sampled_from((0.0, 0.3, 0.7)))
    tied = draw(st.booleans())
    raw = []
    for _ in range(n):
        mem = (draw(st.floats(1200.0, 3800.0))
               if draw(st.floats(0.0, 1.0)) < heavy
               else draw(st.floats(2.0, 200.0)))
        base = (draw(st.sampled_from((120.0, 200.0, 300.0))) if tied
                else draw(st.floats(60.0, 330.0)))
        raw.append((mem, base))
    return _bag(raw)


@settings(max_examples=60, deadline=None)
@given(tasks=bags(), market=st.sampled_from((Market.SPOT, Market.ONDEMAND)))
@example(tasks=_bag(BINDING), market=Market.SPOT)
@example(tasks=_bag(BINDING), market=Market.ONDEMAND)
def test_seed_equals_the_repacking_greedy(tasks, market):
    got, want = _both(tasks, market)
    _assert_same_plan(got, want)
    if got is not None:
        c = got.greedy_counters
        assert c.checks == c.kept + c.fallbacks


@pytest.mark.parametrize("market", [Market.SPOT, Market.ONDEMAND])
@pytest.mark.parametrize("name", ["J60", "J80", "J100", "ED200"])
def test_paper_bag_seed_equals_the_repacking_greedy(name, market):
    """Each Table III bag on either market: the same plan as Alg. 2 with a
    full repack per candidate, every check answered from the kept
    packing (memory never binds on these bags)."""
    got, want = _both(list(make_job(name).tasks), market)
    assert got is not None
    _assert_same_plan(got, want)
    c = got.greedy_counters
    assert c.checks == c.kept + c.fallbacks
    assert c.fallbacks == 0


def _j100_bags(n):
    from bench import deploy, generator
    with open(os.path.join(ROOT, "bench", "configs", "j100-sc5.json")) as f:
        conf = json.load(f)
    cfg = deploy.program_cloud(conf)
    for k in range(n):
        mem, base = generator.bag(conf["bag"],
                                  generator.request_bag_rng(0, k))
        yield cfg, deploy.program_job(f"J100.{k}", mem, base,
                                      conf["deadline_s"])


@pytest.mark.parametrize("k", range(4))
def test_j100_checks_all_come_from_the_kept_packing(k):
    cfg, job = list(_j100_bags(k + 1))[k]
    pool = cfg.instance_pool()
    dspot = compute_dspot(job.deadline_s, job.tasks, cfg)
    sol = initial_solution(job.tasks, pool, cfg, dspot)
    c = sol.greedy_counters
    assert c.fallbacks == 0
    assert c.checks == c.kept + c.fallbacks
    assert c.checks >= len(job.tasks) and c.replayed >= c.checks
    _assert_same_plan(sol, _greedy_by_repacking(job.tasks, pool, cfg, dspot,
                                                Market.SPOT))


@pytest.mark.parametrize("market", [Market.SPOT, Market.ONDEMAND])
def test_memory_binding_bag_falls_back_to_the_full_pack(market):
    got, want = _both(_bag(BINDING), market)
    _assert_same_plan(got, want)
    c = got.greedy_counters
    assert c.fallbacks > 0 and c.kept > 0
    assert c.checks == c.kept + c.fallbacks


def test_counters_are_per_call_and_only_on_the_seed():
    """Two calls on one bag count the same work (no state outlives a
    call); a copy of the seed, or any other solution, carries none."""
    tasks = _bag(BINDING)
    dspot = _limit(tasks, Market.SPOT)
    a = initial_solution(tasks, CFG.instance_pool(), CFG, dspot)
    b = initial_solution(tasks, CFG.instance_pool(), CFG, dspot)
    assert a.greedy_counters == b.greedy_counters
    assert a.greedy_counters is not b.greedy_counters
    assert a.copy().greedy_counters is None
    assert empty_solution(3, CFG.instance_pool()).greedy_counters is None
