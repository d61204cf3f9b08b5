"""The reduction of the program's spans (``bench/spans.py``): on hand-built
traces, on the small CPU trace, and on a traced window of the plan cell."""
import json
import os

import pytest

from bench import run
from bench import spans
from bench import trace_reduce as tr
from bench.tests import benchutil

TRACE = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")
#: every key ``trace_reduce.reduce`` had before the program had spans
REDUCE_KEYS = {"window_s", "busy_s", "per_module_s", "per_op_s",
               "op_durations", "idle_gaps_s", "n_requests"}


def test_the_literal_span_list_is_the_programs():
    from repro import obs
    assert spans.PROGRAM_SPANS == obs.SPANS


@pytest.fixture
def two_requests():
    """Two requests (ns): the first seeds and searches, the second
    allocates burstables; a Python-tracer frame sits inside the seed.
    Device A is the busiest; device B runs one short operation."""
    host = [(0, 100, tr.REQUEST_SPAN), (10, 90, "plan"),
            (10, 30, "greedy.seed"),
            (12, 28, "$greedy.py:38 initial_solution"),
            (40, 80, "ils.search"),
            (200, 300, tr.REQUEST_SPAN), (210, 290, "plan"),
            (250, 290, "burst.alloc")]
    ops = [("A", 50, 70, "%fusion.1", None),
           ("A", 220, 230, "%fusion.2", None), ("B", 0, 5, "%copy.1", None)]
    modules = [("A", 48, 72, "jit__ils_scan_impl"),
               ("A", 218, 232, "jit_threefry_split"),
               ("B", 0, 5, "jit_copy"), ("A", 400, 410, "jit_after")]
    return tr.Trace(ops=ops, modules=modules, host=host)


def test_self_time_leaves_out_child_spans_not_python_frames(two_requests):
    got = spans.span_self_s(two_requests)
    want = {"plan": (80 - 20 - 40) + (80 - 40), "greedy.seed": 20,
            "ils.search": 40, "burst.alloc": 40}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})


def test_idle_is_split_between_the_spans_it_crosses(two_requests):
    got = spans.idle_by_span_s(two_requests)
    # device A's gaps: [0, 50], [70, 220], [230, 300]
    want = {spans.OUTSIDE: 10 + 120 + 10, "greedy.seed": 20,
            "plan": 10 + 10 + 10 + 20, "ils.search": 10 + 10,
            "burst.alloc": 40}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx((300 - 30) * 1e-9)


def test_programs_are_counted_per_request_inside_the_window(two_requests):
    assert spans.modules_per_request(two_requests) == 1.5
    got = spans.reduce_spans(two_requests)
    assert got["n_requests"] == 2
    assert got["plan_ms_mean"] == pytest.approx(80e-6)
    assert got["self_ms_per_request"]["plan"] == pytest.approx(30e-6)
    assert got["requests_per_s"] == pytest.approx(2 / 300e-9)


def test_reporting_leaves_the_reduction_unchanged(capsys):
    trace = tr.load(TRACE)
    plain = tr.reduce(trace)
    assert set(plain) == REDUCE_KEYS
    assert spans.reporting(tr.reduce)(trace) == plain
    line = capsys.readouterr().err.strip().splitlines()[-1]
    got = json.loads(line.split("program spans: ", 1)[1])
    # the small trace has no program span: all its idle time is outside
    assert got["self_ms_per_request"] == {}
    assert list(got["idle_by_span_s"]) == [spans.OUTSIDE]
    assert got["idle_by_span_s"][spans.OUTSIDE] == pytest.approx(
        sum(plain["idle_gaps_s"].values()), rel=1e-9)


def test_a_traced_window_of_the_plan_cell(monkeypatch, capsys):
    import bench.peaks
    monkeypatch.setitem(bench.peaks.PEAKS, "cpu",
                        {"flops_per_s": 1e12, "bytes_per_s": 1e11})
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    resolve = run.resolve
    monkeypatch.setattr(run, "resolve", lambda spec, wl: benchutil.small(
        resolve(spec, wl)))
    plain = tr.reduce
    assert spans.main(["--workload", "j100-sc5.plan", "--seed", "2147483901",
                       "--seconds", "0.5"], require_tpu=False) == 0
    assert tr.reduce is plain
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] and "ils_device_ms.plan" in result["metrics"]
    line = [x for x in err.splitlines() if x.startswith("program spans: ")]
    got = json.loads(line[-1].split(": ", 1)[1])

    per_req = got["self_ms_per_request"]
    assert set(per_req) == set(spans.PROGRAM_SPANS)
    assert all(v > 0 for v in per_req.values())
    # every phase lies inside ``plan``: the self times add up to it
    assert sum(per_req.values()) == pytest.approx(got["plan_ms_mean"],
                                                  rel=1e-6)
    idle = result["device"]["window_s"] - result["device"]["busy_s"]
    assert sum(got["idle_by_span_s"].values()) == pytest.approx(idle,
                                                                rel=1e-6)
    assert got["modules_per_request"] > 0 and got["n_requests"] >= 1
