"""Run one benchmark cell and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are resolved by
name from ``BENCHMARK.json`` at the root of the checkout: the
configuration file it names, ``bench/traffic/<traffic>.json``, the
correctness limits ``bench/checks/<cell>.json`` and one reader
``bench/metrics/<metric>.py`` per metric.  The traffic file's ``kind``
selects the request kind in ``bench/kinds/``.

A run: keeps JAX's persistent compilation cache inside the checkout;
refuses to run without the TPU chips the cell asks for; builds its inputs
from ``--seed`` and warms up every shape its requests use (``setup_s``
covers all of that, from process start); sends requests in a closed loop
from one client for ``--seconds`` (each in a ``bench.request`` profiler
annotation), counting compilations inside the window; checks the timed
path's outputs against the plain reference; and prints the metrics.  With
``--trace 0`` the metrics are the cell's end-to-end ones; with
``--trace 1`` a shorter window runs under the profiler and the metrics are
the per-layer ones, with the trace's busy and window seconds and a
breakdown of device time and idle gaps.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: JAX's persistent compilation cache: a fixed directory inside the
#: checkout, so that only a cell's first run in a checkout compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: the profiler traces at most this many seconds of the window: enough
#: requests for the correctness check's full sample
TRACE_SECONDS = 10.0
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: object              # the request kind's Cell (records, shapes)
    setup_s: float
    window_s: float
    units: int                # work completed in the window
    trace: dict | None        # ``trace_reduce.reduce`` of the traced window
    peaks: dict | None


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(spec: dict, workload: str) -> dict:
    """The cell's entries and files, by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[workload]
    config = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def applies(m):
        return workload in m.get("workloads", [workload])
    return {
        "workload": w,
        "conf": load_json(os.path.join(ROOT, config["file"])),
        "traffic": load_json(os.path.join(BENCH, "traffic",
                                          w["traffic"] + ".json")),
        "limits": load_json(os.path.join(BENCH, "checks",
                                         workload + ".json")),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Executables built (compiled, or loaded from the persistent cache)
    and persistent-cache misses, seen by ``jax.monitoring`` listeners."""

    def __init__(self):
        import jax
        self.n = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.n += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_MISS_EVENT:
            self.misses += 1


def enable_cache() -> None:
    """Every program this process compiles goes to ``CACHE_DIR``, however
    short its compilation."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def devices_or_exit(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        print(f"bench: no TPU found (JAX platform {devs[0].platform!r}); "
              "there is no CPU fallback", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, {len(devs)} found",
              file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


def window(cell, seconds: float):
    """Closed loop, one client: the next request is sent when the last
    one has completed, until ``seconds`` have passed; the window ends
    with the request that crosses the mark."""
    import jax
    units, lat, cpu, failed, i = 0, [], [], 0, 0
    t0 = time.perf_counter()
    while True:
        ts, cs = time.perf_counter(), time.process_time()
        try:
            with jax.profiler.TraceAnnotation("bench.request", i=i):
                units += cell.request(i)
        except Exception:
            failed += 1
            if failed == 1:
                traceback.print_exc()
        te = time.perf_counter()
        lat.append(te - ts)
        cpu.append(time.process_time() - cs)
        i += 1
        if te - t0 >= seconds:
            return units, lat, cpu, i, failed, te - t0


def main(argv=None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell_spec = resolve(load_json(os.path.join(ROOT, "BENCHMARK.json")),
                        args.workload)
    import jax
    enable_cache()
    marks = [("imports", time.perf_counter())]
    devs = devices_or_exit(int(cell_spec["workload"]["chips"]), require_tpu)
    counter = CompileCounter()
    marks.append(("device", time.perf_counter()))

    kind = importlib.import_module(
        "bench.kinds." + cell_spec["traffic"]["kind"])
    cell = kind.Cell(cell_spec["conf"], cell_spec["traffic"],
                     cell_spec["limits"], args.seed)
    marks.append(("inputs", time.perf_counter()))
    cell.warm_up()
    marks.append(("warm-up", time.perf_counter()))
    setup_s = marks[-1][1] - T_START
    ends = [T_START] + [t for _, t in marks]
    print("setup seconds: " + ", ".join(
        f"{name} {t - t_prev!r}" for (name, t), t_prev in zip(marks, ends)) +
        f"; {counter.n} executables built, {counter.misses} "
        "persistent-cache misses", file=sys.stderr, flush=True)

    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else \
        args.seconds
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    reduced = None
    try:
        if tdir:
            jax.profiler.start_trace(tdir)
        c0 = counter.n
        units, lat, cpu, attempted, failed, window_s = window(cell, seconds)
        compiles = counter.n - c0
        if tdir:
            jax.profiler.stop_trace()
            from bench import trace_reduce
            reduced = trace_reduce.reduce(
                trace_reduce.load(trace_reduce.find_xplane(tdir)))
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    print(f"compiles_in_window={compiles}", flush=True)
    print(f"compiles_in_window={compiles}", file=sys.stderr, flush=True)
    print(f"request seconds: min {min(lat)!r} median "
          f"{sorted(lat)[len(lat) // 2]!r} max {max(lat)!r} over {len(lat)}",
          file=sys.stderr, flush=True)
    slow = max(range(len(lat)), key=lat.__getitem__)
    print(f"slowest request: #{slow}, {lat[slow]!r} s, of which the "
          f"process ran {cpu[slow]!r} s; window CPU {sum(cpu)!r} s of "
          f"{window_s!r} s", file=sys.stderr, flush=True)

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    t_check = time.perf_counter()
    checks = cell.check()
    print(f"reference check: {time.perf_counter() - t_check!r} s",
          file=sys.stderr, flush=True)
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())

    peaks = None
    if args.trace:
        from bench.peaks import peaks as peak_table
        peaks = peak_table(devs[0].device_kind)
    run = Run(cell=cell, setup_s=setup_s, window_s=window_s, units=units,
              trace=reduced, peaks=peaks)
    metrics = {}
    for m in cell_spec["per_layer" if args.trace else "end_to_end"]:
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        from bench.trace_reduce import top
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": top(reduced["per_op_s"]),
                               "idle_gaps": top(reduced["idle_gaps_s"])}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
