"""The trace reduction, on a small trace recorded on the CPU.

``data/cpu_trace.xplane.pb`` holds three ``bench.request`` spans, each
running a jitted ``tanh(x @ x).sum(0)`` twice around a 2 ms host sleep
annotated ``host.sleep``."""
import os

import pytest

from bench import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(tr.load(TRACE))


def test_window_and_requests(reduced):
    assert reduced["n_requests"] == 3
    # three 2 ms sleeps lie inside the window
    assert 0.006 < reduced["window_s"] < 0.05


def test_busy_is_the_union_of_operations(reduced):
    ops = sum(reduced["per_op_s"].values())
    assert 0 < reduced["busy_s"] <= ops + 1e-12
    assert reduced["busy_s"] < reduced["window_s"]
    assert set(reduced["per_op_s"]) >= {"dot_general.1", "wrapped_tanh"}


def test_programs_and_durations(reduced):
    assert list(reduced["per_module_s"]) == ["jit__lambda"]
    assert len(reduced["op_durations"]["dot_general.1"]) == 6


def test_idle_gaps_are_named_by_the_host(reduced):
    gaps = reduced["idle_gaps_s"]
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-9)
    # the sleeps are the longest gaps, named by the innermost span
    name, secs = tr.top(gaps, 1)[0]
    assert name == "$time sleep" and secs > 0.006


def test_union_and_gap_naming_on_synthetic_spans():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    host = [(0, 100, "bench.request"), (0, 100, "outer"), (10, 30, "inner"),
            (60, 61, "blip")]
    assert tr.name_gaps([(12, 20), (40, 50), (60, 64)], host) == \
        ["inner", "outer", "outer"]
    assert tr.name_gaps([(200, 210)], host) == ["host: no span"]


def test_tpu_operation_names_and_containers():
    text = ("%mc_span_reduce.8 = (f32[1024,256]{1,0}) custom-call("
            "f32[1024,1] %x), custom_call_target=\"tpu_custom_call\"")
    assert tr.op_name(text) == "%mc_span_reduce.8"
    assert tr._is_container("%while.4") and not tr._is_container("%fusion.2")
