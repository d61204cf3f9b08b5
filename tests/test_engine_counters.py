"""The Monte-Carlo engine's loop counters, named scopes, ``mc.run`` span
and credit buckets.

The counters (``MCResult.n_steps`` and ``MCResult.branch_entries``) ride
in the loop carry and the scopes only name operations, so neither moves
an output: the goldens of ``tests/test_market.py`` and
``tests/test_stepping.py`` pin that.  Here the counts are checked on hand
traces whose branch entries can be read off the timeline, on both
steppings."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.dynamic import BURST_HADS, HADS, PrimaryPlan
from repro.core.types import CloudConfig, Job, Solution, TaskSpec
from repro.sim import mc_engine
from repro.sim.market import EventTensor

CFG = CloudConfig()
S, N = 4, 270


def _case(policy):
    """Six tasks on one booted spot column (uid 0, two cores)."""
    pool = CFG.instance_pool()
    tasks = tuple(TaskSpec(tid=i, memory_mb=160.0, base_time=400.0)
                  for i in range(6))
    job = Job(name="COUNT", tasks=tasks, deadline_s=2700.0)
    sol = Solution(alloc=np.zeros(6, np.int32), modes=np.zeros(6, np.int8),
                   pool=pool, selected_uids={0})
    return job, PrimaryPlan(solution=sol, dspot=2500.0, policy=policy)


def _tensor(plan, hibernate_at=None):
    """Every scenario alike: column 0 hibernates at slot ``hibernate_at``
    (never with None) and nothing resumes."""
    v = len(mc_engine.plan_column_uids(plan))
    hib_k = np.zeros((S, N), np.int32)
    hib_u = np.full((S, N, v), -1.0, np.float32)
    if hibernate_at is not None:
        hib_k[:, hibernate_at] = 1
        hib_u[:, hibernate_at, 0] = 1.0
    return EventTensor(jnp.asarray(hib_k), jnp.asarray(hib_u),
                       jnp.zeros((S, N), jnp.int32),
                       jnp.full((S, N, v), -1.0, jnp.float32))


def _ac_edges(exit_slot: int) -> int:
    """Allocation-Cycle boundaries a scenario that stops at ``exit_slot``
    reaches: slot i handles the edge at i + 1 = omega/dt + k AC/dt."""
    boot, ac = 60 // 30, 900 // 30
    return sum(1 for k in range(1, 20) if boot + k * ac <= exit_slot)


@pytest.mark.parametrize("stepping", ["slot", "adaptive"])
@pytest.mark.parametrize("policy,hibernate_at,want", [
    # no events: only the AC block is ever entered
    (BURST_HADS, None, {}),
    # Alg. 4 runs in the one slot whose hibernation strands tasks
    (BURST_HADS, 5, {"hibernate": 1}),
    # HADS freezes the bag and migrates it once, at its safe instant
    (HADS, 5, {"defer": 1}),
])
def test_branch_entries_on_a_hand_trace(stepping, policy, hibernate_at,
                                        want):
    job, plan = _case(policy)
    res = mc_engine.run_mc_events(
        job, plan, CFG, _tensor(plan, hibernate_at),
        mc_engine.MCParams(n_scenarios=S, stepping=stepping))
    exit_slot = int(res.exit_slots.max())
    assert (res.exit_slots == exit_slot).all()
    expect = dict.fromkeys(mc_engine.BRANCHES, 0)
    expect.update(want, ac=_ac_edges(exit_slot))
    assert res.branch_entries == expect
    assert int(res.n_hibernations.sum()) == (0 if hibernate_at is None
                                             else S)
    if stepping == "slot":
        # the slot walk takes one iteration per slot
        assert res.n_steps == exit_slot
    else:
        assert 1 <= res.n_steps < exit_slot


def test_named_scopes_label_the_while_body_phases():
    job, plan = _case(HADS)
    params = mc_engine.MCParams(n_scenarios=S)
    arr, _, mem_safe = mc_engine._plan_arrays_cached(job, plan, CFG,
                                                     params.ovh)
    lowered = mc_engine._mc_jit(False).lower(
        arr, mc_engine._scalars(job, CFG, params, N),
        _tensor(plan, 5).with_index(), None, s=S, mesh=None,
        **mc_engine.engine_args(plan.policy.engine_view(), params, CFG,
                                mem_safe))
    text = lowered.as_text(debug_info=True)
    for scope in ("stats", "span_advance", "progress_billing", "terminate",
                  "hibernate_alg4", "resume_defer", "ac_block"):
        assert f"mc.{scope}" in text, scope


def test_run_mc_events_is_one_mc_run_span(tmp_path):
    job, plan = _case(BURST_HADS)
    ev = _tensor(plan, 5)
    params = mc_engine.MCParams(n_scenarios=S)
    mc_engine.run_mc_events(job, plan, CFG, ev, params)   # compiles here
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.request"):
            mc_engine.run_mc_events(job, plan, CFG, ev, params)
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(path))
    spans = {ev.name: (int(ev.start_ns), int(ev.start_ns) +
                       int(ev.duration_ns))
             for plane in pd.planes if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events
             if ev.name in ("bench.request",) + obs.ENGINE_SPANS}
    (r0, r1), (m0, m1) = spans["bench.request"], spans["mc.run"]
    assert r0 <= m0 <= m1 <= r1
    assert "mc.run" not in obs.SPANS
    with pytest.raises(ValueError, match="unknown span"):
        obs.span("mc.nothing")


@pytest.mark.parametrize("stepping,use_kernel", [
    ("slot", False), ("adaptive", False), ("adaptive", True)])
def test_credit_buckets_accrue_per_booted_slot(stepping, use_kernel):
    """A burstable column running baseline-mode tasks only gains credits:
    rate x dt per booted slot (t3.large: 36 credits/h, 0.3 a slot), the
    other columns none.  On the TPU v5e the buckets once read through a
    traced list of burstable columns stuck at 1.3 from the first booted
    slot on; the benchmark's ``ed200-sc5.eval`` check caught it."""
    pool = CFG.instance_pool()
    uid = next(vm.uid for vm in pool if vm.is_burstable)
    tasks = tuple(TaskSpec(tid=i, memory_mb=160.0, base_time=400.0)
                  for i in range(2))
    job = Job(name="CREDITS", tasks=tasks, deadline_s=2700.0)
    sol = Solution(alloc=np.full(2, uid, np.int32),
                   modes=np.ones(2, np.int8), pool=pool,
                   selected_uids={uid})
    plan = PrimaryPlan(solution=sol, dspot=2700.0, policy=BURST_HADS)
    col = mc_engine.plan_column_uids(plan).index(uid)
    params = mc_engine.MCParams(n_scenarios=S, stepping=stepping,
                                use_kernel=use_kernel)
    for slots in (2, 3, 12, 29):
        res = mc_engine.run_mc_events(job, plan, CFG, _tensor(plan), params,
                                      stop_s=30.0 * slots)
        credits = np.asarray(res.state.credits)
        np.testing.assert_allclose(credits[:, col], 0.3 * (slots - 2),
                                   rtol=1e-5, atol=1e-6)
        assert not np.delete(credits, col, axis=1).any()
