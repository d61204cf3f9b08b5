"""``BENCHMARK.json`` keeps to the benchmark's contract, and every name in
it resolves to the files the harness reads."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def cells_of(metric):
    return metric.get("workloads", [w["name"] for w in SPEC["workloads"]])


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_entries_keys_names_and_text(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        extra = set(e) - ENTRY_KEYS[section]
        assert extra <= ({"workloads"} if section in ("end_to_end",
                                                      "per_layer") else set())
        assert ENTRY_KEYS[section] <= set(e)
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")


def test_metric_sources_and_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"


def test_layer_metrics_move_a_metric_their_cells_report():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert set(cells_of(m)) <= set(cells_of(e2e[m["moves"]])), m["name"]


def test_every_configuration_has_a_cell_and_every_cell_its_metrics():
    configs = {c["name"] for c in SPEC["configs"]}
    cells = SPEC["workloads"]
    assert configs == {w["config"] for w in cells}
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    for w in cells:
        assert w["chips"] in (1, 4)
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if w["name"] in cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert any(w["name"] in cells_of(m) for m in SPEC["per_layer"])


def test_names_resolve_to_files():
    bench = os.path.join(ROOT, "bench")
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
    for w in SPEC["workloads"]:
        for path in (("traffic", w["traffic"] + ".json"),
                     ("checks", w["name"] + ".json")):
            assert os.path.isfile(os.path.join(bench, *path)), path
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert os.path.isfile(os.path.join(bench, "metrics",
                                           m["name"] + ".py")), m["name"]


def test_layers_are_listed_in_perf_md():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in SPEC["per_layer"]}:
        assert f"| {layer} |" in perf, layer
