"""Helpers of the benchmark's tests: drive ``bench/run.py`` in this
process at a size a test run can hold, past its look for a chip."""
import contextlib
import io
import json

from bench import run

#: small sizes of each request kind (the timed path is unchanged)
SMALL = {"plan": {"iterations": 30, "plans": 4}}


def small(cell_spec: dict) -> dict:
    t = cell_spec["traffic"]
    t["ils"]["iterations"] = SMALL[t["kind"]]["iterations"]
    t["check"]["plans"] = SMALL[t["kind"]]["plans"]
    return cell_spec


def run_small(monkeypatch, workload: str, seed: int = 12345,
              seconds: float = 0.5, trace: int = 0) -> dict:
    """One run of ``workload`` at the small sizes on the CPU; returns the
    result line.  The compilation cache is left off."""
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    resolve = run.resolve
    monkeypatch.setattr(run, "resolve",
                        lambda spec, wl: small(resolve(spec, wl)))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        require_tpu=False) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def small_cell(workload: str, seed: int = 12345, warm: bool = True):
    """The cell's request kind at the small sizes, warmed up unless
    ``warm`` is false."""
    import importlib
    import os
    spec = small(run.resolve(run.load_json(os.path.join(
        run.ROOT, "BENCHMARK.json")), workload))
    kind = importlib.import_module("bench.kinds." + spec["traffic"]["kind"])
    cell = kind.Cell(spec["conf"], spec["traffic"], spec["limits"], seed)
    if warm:
        cell.warm_up()
    return kind, cell
