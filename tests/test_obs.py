"""Program spans (``repro.obs``): one traced batched plan carries every
span of ``SPANS``, each nested in ``plan`` and inside the caller's own
annotation."""
import jax
import numpy as np
import pytest

from repro import obs
from repro.core.dynamic import build_primary_map, policy
from repro.core.ils import ILSParams
from repro.core.ils_jax import BatchedILSParams
from repro.core.types import CloudConfig, Job, TaskSpec

CFG = CloudConfig()


def _job(n=16, seed=0):
    rng = np.random.default_rng(seed)
    tasks = [TaskSpec(tid=i, memory_mb=float(rng.uniform(3.0, 13.0)),
                      base_time=float(rng.uniform(102.0, 330.0)))
             for i in range(n)]
    return Job(name="spans", tasks=tuple(tasks), deadline_s=2700.0)


def _plan(job):
    return build_primary_map(
        job, CFG, policy("burst-hads"), ILSParams(seed=3, burst_rate=0.2),
        engine="batched",
        batched_params=BatchedILSParams(population=4, iterations=3,
                                        proposals=4, swap_tasks=2, seed=3))


def _host_events(path):
    """(start_ns, end_ns, name) of every event on the non-device planes."""
    pd = jax.profiler.ProfileData.from_file(path)
    return [(int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
             ev.name)
            for plane in pd.planes if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events]


@pytest.fixture(scope="module")
def traced_plan(tmp_path_factory):
    job = _job()
    want = _plan(job)            # compiles outside the trace
    tdir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(tdir))
    try:
        with jax.profiler.TraceAnnotation("bench.request", i=0):
            got = _plan(job)
    finally:
        jax.profiler.stop_trace()
    paths = sorted(tdir.rglob("*.xplane.pb"))
    assert paths, "the profiler wrote no trace"
    return want, got, _host_events(str(paths[-1]))


def test_every_span_is_recorded_inside_plan_and_the_request(traced_plan):
    _, _, events = traced_plan
    by_name = {}
    for s, e, name in events:
        by_name.setdefault(name, []).append((s, e))
    assert len(by_name["bench.request"]) == 1
    assert len(by_name["plan"]) == 1
    (r0, r1), (p0, p1) = by_name["bench.request"][0], by_name["plan"][0]
    assert r0 <= p0 <= p1 <= r1
    for name in obs.SPANS[1:]:
        assert len(by_name.get(name, [])) == 1, name
        s, e = by_name[name][0]
        assert p0 <= s <= e <= p1, name


def test_spans_follow_the_plan_phases_in_order(traced_plan):
    _, _, events = traced_plan
    starts = {name: s for s, _, name in events if name in obs.SPANS}
    order = sorted(obs.SPANS[1:], key=starts.__getitem__)
    assert order == ["greedy.seed", "ils.prepare", "ils.keys", "ils.search",
                     "burst.alloc"]


def test_spans_change_no_output(traced_plan):
    want, got, _ = traced_plan
    np.testing.assert_array_equal(got.solution.alloc, want.solution.alloc)
    np.testing.assert_array_equal(got.solution.modes, want.solution.modes)
    assert got.solution.selected_uids == want.solution.selected_uids
    assert got.dspot == want.dspot


def test_an_unknown_span_name_is_refused():
    with pytest.raises(ValueError, match="unknown span"):
        obs.span("ils.nothing")
