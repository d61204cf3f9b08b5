"""The plain reference of the Monte-Carlo engine (``bench.reference.engine``)
against the engine, scenario by scenario, at a small size on the CPU: a
bag of 40 tasks of the ED200 law (enough that Alg. 5 steals), S = 8
scenarios of which 4 are sc5 draws and 4 hand-written traces, for the
three paper policies, through the engine's Pallas kernels in interpret
mode (adaptive stepping) and through its slot walk.  Each scenario is held to the limits that decide the
``ed200-sc5.eval`` cell's ``correct`` (``bench/checks``), by the cell's own
comparison: the reference is float64 and the engine float32, and a
departure from the stated semantics moves a count or a billed slot."""
import json
import os

import numpy as np
import pytest

from bench import deploy, events, generator
from bench.kinds import eval as eval_kind
from bench.reference import engine as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
S, B = 8, 40


def _load(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


CONF = _load("bench", "configs", "ed200-sc5.json")
TRAFFIC = _load("bench", "traffic", "eval.json")
LIMITS = _load("bench", "checks", "ed200-sc5.eval.json")
E = TRAFFIC["engine"]
D = CONF["deadline_s"]
N = events.n_slots(D, E["dt"], E["horizon_mult"])


def _hand_traces(case: ref.Case):
    """Four scenarios of explicit events on the plan's busy spot columns:
    a hibernation and its resume; two hibernations in one slot over tied
    scores (the lower indices go); two later hibernations with no resume
    (under HADS the deferred migration fires); a request on unbooted
    columns, one whose every score opts out, and a resume with nothing
    hibernated."""
    v = case.n_vms
    hib_k, res_k = np.zeros((4, N), np.int32), np.zeros((4, N), np.int32)
    hib_u = np.full((4, N, v), -1.0, np.float32)
    res_u = np.full((4, N, v), -1.0, np.float32)
    busy = [c for c in range(v) if case.spot[c] and
            np.any(case.assign0 == c)] or [0]
    one = busy[0]
    hib_k[0, 5], hib_u[0, 5, one] = 1, 1.0
    res_k[0, 20], res_u[0, 20, one] = 1, 1.0
    hib_k[1, 8], hib_u[1, 8, :] = 2, 0.5
    hib_k[2, 30], hib_u[2, 30, busy[-1]] = 1, 1.0
    hib_k[2, 45], hib_u[2, 45, one] = 1, 1.0
    hib_k[3, 0], hib_u[3, 0, :] = 1, 1.0
    hib_k[3, 12] = 1
    res_k[3, 20], res_u[3, 20, :] = 1, 1.0
    return hib_k, hib_u, res_k, res_u


@pytest.fixture(scope="module")
def bag():
    import jax.numpy as jnp
    from repro.sim.market import EventTensor
    law = dict(CONF["bag"], n_tasks=B)
    mem, base = generator.bag(law, generator.request_bag_rng(5, 0))
    return mem, base, jnp, EventTensor


def _plan(name, mem, base):
    from repro.core.dynamic import build_primary_map, policy
    from repro.core.ils import ILSParams
    from repro.core.ils_jax import BatchedILSParams
    job = deploy.program_job("ED", mem, base, D)
    cfg = deploy.program_cloud(CONF)
    plan = build_primary_map(
        job, cfg, policy(name), ILSParams(seed=3, burst_rate=0.2),
        engine="batched", batched_params=BatchedILSParams(
            population=4, iterations=5, proposals=4, swap_tasks=2, seed=3))
    return job, cfg, plan


@pytest.mark.parametrize("stepping,use_kernel", [("adaptive", True),
                                                 ("slot", False)])
@pytest.mark.parametrize("name", list(TRAFFIC["policies"]))
def test_engine_agrees_with_the_reference(bag, name, stepping, use_kernel):
    from repro.sim.mc_engine import MCParams, run_mc_events
    mem, base, jnp, EventTensor = bag
    job, cfg, plan = _plan(name, mem, base)
    case = ref.Case(
        deploy.reference_job("ED", mem, base, D).tasks,
        np.asarray(plan.solution.alloc), np.asarray(plan.solution.modes),
        {int(u) for u in plan.solution.selected_uids},
        deploy.reference_cloud(CONF), D,
        ref.Policy(**TRAFFIC["policies"][name]),
        ref.EngineParams(**{k: E[k] for k in (
            "dt", "horizon_mult", "ovh", "checkpoint_write_s",
            "hads_margin_s", "steal_rounds", "mig_rounds")}))
    draw = events.draw(11, CONF["market"], s=S - 4, n=N, v=case.n_vms,
                       dt=E["dt"], deadline_s=D)
    hand = _hand_traces(case)
    hib_k = np.concatenate([draw["hib_k"], hand[0]])
    hib_u = np.concatenate([np.asarray(draw["hib_u"]), hand[1]])
    res_k = np.concatenate([draw["res_k"], hand[2]])
    res_u = np.concatenate([np.asarray(draw["res_u"]), hand[3]])
    ev = EventTensor(jnp.asarray(hib_k), jnp.asarray(hib_u),
                     jnp.asarray(res_k), jnp.asarray(res_u))
    res = run_mc_events(job, plan, cfg, ev, MCParams(
        n_scenarios=S, dt=E["dt"], stepping=stepping,
        use_kernel=use_kernel))
    assert res.vm_uids == case.uids
    hibernations, stolen = 0, 0
    for s in range(S):
        want = ref.run_scenario(case, hib_k[s], hib_u[s], res_k[s],
                                res_u[s])
        got = {"cost": res.cost[s], "makespan": res.makespan[s],
               "billed": res.billed_s[s], "n_hib": res.n_hibernations[s],
               "n_res": res.n_resumes[s], "unfinished": res.unfinished[s],
               "deadline_met": res.deadline_met[s]}
        cost, mksp, billed, mismatch = eval_kind._compare(got, want)
        assert cost <= LIMITS["cost_rel_error_max"], (s, cost)
        assert mksp <= LIMITS["makespan_rel_error_max"], (s, mksp)
        assert billed <= LIMITS["billed_error_share_max"], (s, billed)
        assert mismatch == 0, (s, got, want)
        hibernations += want.n_hib
        stolen += ref.run_scenario(case, hib_k[s], hib_u[s], res_k[s],
                                   res_u[s], work_stealing=False).cost \
            != want.cost
    if any(case.spot):
        assert hibernations > 0
    assert bool(stolen) == case.policy.work_stealing


def test_select_takes_the_top_k_eligible_ties_to_the_lower_index():
    u = np.array([0.5, 0.9, 0.5, -0.2, 0.9, 0.7])
    elig = np.array([True, True, True, True, False, True])
    assert list(np.flatnonzero(ref.select(u, elig, 1))) == [1]
    assert list(np.flatnonzero(ref.select(u, elig, 3))) == [0, 1, 5]
    # a negative score opts out whatever k asks for
    assert list(np.flatnonzero(ref.select(u, elig, 9))) == [0, 1, 2, 5]
    assert not ref.select(u, elig, 0).any()
