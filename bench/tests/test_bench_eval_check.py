"""``ed200-sc5.eval``'s comparison with the reference, at a small size (a
bag of 64 tasks, on whose plan Alg. 5 steals, S = 8, a pool of 2 draws,
every scenario of the window checked): sound runs pass, the three
controls fail, and each fault planted under the timed path turns
``correct`` false."""
import contextlib
import dataclasses
import importlib
import inspect
import io
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import run

CELL = "ed200-sc5.eval"


def small(spec: dict) -> dict:
    spec["conf"]["bag"]["n_tasks"] = 64
    t = spec["traffic"]
    t["engine"]["n_scenarios"] = 8
    t["pool"] = 2
    t["ils"].update(population=8, iterations=5)
    t["check"]["scenarios"] = 10 ** 6
    return spec


def run_small(monkeypatch, seed: int = 4000000007, seconds: float = 1.0,
              trace: int = 0) -> dict:
    """One run of the cell at the small size on the CPU; the result line."""
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    resolve = run.resolve
    monkeypatch.setattr(run, "resolve",
                        lambda spec, wl: small(resolve(spec, wl)))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        require_tpu=False) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture(scope="module")
def cell():
    spec = small(run.resolve(run.load_json(os.path.join(
        run.ROOT, "BENCHMARK.json")), CELL))
    kind = importlib.import_module("bench.kinds." + spec["traffic"]["kind"])
    c = kind.Cell(spec["conf"], spec["traffic"], spec["limits"], 4242)
    c.warm_up()
    for i in range(3):
        c.request(i)
    return kind, c


def test_sound_run_is_correct(monkeypatch):
    res = run_small(monkeypatch)
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"plans_per_s", "setup_s"}
    assert set(res["checks"]) == {"cost_rel_error_max",
                                  "makespan_rel_error_max",
                                  "billed_error_share_max",
                                  "outcome_mismatch"}


@pytest.mark.parametrize("control,number", [
    ("bfloat16", "cost_rel_error_max"),
    ("bfloat16", "makespan_rel_error_max"),
    ("bills_hibernated", "billed_error_share_max"),
    ("no_steal", "billed_error_share_max")])
def test_control_fails_where_the_program_passes(cell, control, number):
    _, c = cell
    sound = c.check()
    assert all(v["value"] <= v["limit"] for v in sound.values()), sound
    ctl = c.check(control=control)[number]
    assert sound[number]["value"] <= ctl["limit"] < ctl["value"]


def test_traced_run_reads_the_engine_layer(monkeypatch):
    import bench.peaks
    monkeypatch.setitem(bench.peaks.PEAKS, "cpu",
                        {"flops_per_s": 1e12, "bytes_per_s": 1e11})
    res = run_small(monkeypatch, trace=1)
    assert res["correct"]
    assert {"engine_device_ms.eval", "device_idle_pct.eval",
            "engine_steps_per_plan.eval"} <= set(res["metrics"])
    assert res["metrics"]["engine_steps_per_plan.eval"]["value"] >= 1


def _mutate_engine(monkeypatch, *edits):
    """Install a copy of the engine's loop with ``edits`` (old, new) made
    to its source, jitted afresh so that no trace of the sound engine is
    reused."""
    from repro.sim import mc_engine
    src = inspect.getsource(mc_engine._mc_run_impl)
    for old, new in edits:
        assert src.count(old) >= 1, old
        src = src.replace(old, new)
    ns = dict(vars(mc_engine))
    exec(compile(src, mc_engine.__file__, "exec"), ns)
    monkeypatch.setattr(mc_engine, "_mc_run_impl", ns["_mc_run_impl"])

    def fresh(donate):
        def impl(*args, **kw):
            return mc_engine._mc_run(*args, **kw)
        return jax.jit(impl, static_argnames=(
            "s", "mesh", "policy", "steal_rounds", "mig_rounds", "mem_safe",
            "use_kernel", "stepping", "ac_aligned", "orphan_retry",
            "dest_cascade", "return_state"))
    monkeypatch.setattr(mc_engine, "_mc_jit", fresh)


def _wrap_run(monkeypatch, wrap):
    from repro.sim import mc_engine
    real = mc_engine.run_mc_events
    monkeypatch.setattr(mc_engine, "run_mc_events",
                        lambda *a, **kw: wrap(real, *a, **kw))


def _fault(kind, monkeypatch):
    if kind == "event_shifted":
        # the engine reads each slot's events one slot late
        def shifted(real, job, plan, cfg, ev, *a, **kw):
            late = dataclasses.replace(
                ev, nxt=None, **{f: jnp.roll(getattr(ev, f), 1, axis=1)
                                 for f in ("hib_k", "hib_u", "res_k",
                                           "res_u")})
            return real(job, plan, cfg, late, *a, **kw)
        _wrap_run(monkeypatch, shifted)
    elif kind == "hibernated_column_billed":
        _mutate_engine(monkeypatch, (
            "billed + dt * live * gate[:, None]",
            "billed + dt * jnp.where(vstate == VM_HIBERNATED, 1.0, live)"
            " * gate[:, None]"), (
            "billed + mf[:, None] * dt * live01 * gate[:, None]",
            "billed + mf[:, None] * dt * jnp.where("
            "vstate == VM_HIBERNATED, 1.0, live01) * gate[:, None]"))
    elif kind == "stealing_off":
        _mutate_engine(monkeypatch, ("if policy.work_stealing:", "if False:"))
    elif kind == "one_cost_scaled":
        def scaled(real, *a, **kw):
            res = real(*a, **kw)
            cost = res.cost.copy()
            cost[0] *= 1.0 + 1e-3
            return dataclasses.replace(res, cost=cost)
        _wrap_run(monkeypatch, scaled)
    elif kind == "tensors_swapped":
        # each plan is evaluated on the draw its previous request used
        last = {}

        def swapped(real, job, plan, cfg, ev, *a, **kw):
            prev = last.get(plan.policy.name, ev)
            last[plan.policy.name] = ev
            return real(job, plan, cfg, prev, *a, **kw)
        _wrap_run(monkeypatch, swapped)


@pytest.mark.parametrize("fault", [
    "event_shifted", "hibernated_column_billed", "stealing_off",
    "one_cost_scaled", "tensors_swapped"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    _fault(fault, monkeypatch)
    res = run_small(monkeypatch)
    assert res["correct"] is False, res["checks"]


def test_program_without_the_counters_stops_at_set_up(monkeypatch):
    """The parent program's ``MCResult`` has no loop counters: the cell
    stops before any planning, naming the missing field."""
    from repro.sim import mc_engine
    fields = [(f.name, f.type, f) for f in
              dataclasses.fields(mc_engine.MCResult)
              if f.name != "branch_entries"]
    parent = dataclasses.make_dataclass("MCResult", fields)
    monkeypatch.setattr(mc_engine, "MCResult", parent)
    spec = small(run.resolve(run.load_json(os.path.join(
        run.ROOT, "BENCHMARK.json")), CELL))
    kind = importlib.import_module("bench.kinds.eval")
    with pytest.raises(RuntimeError, match="branch_entries"):
        kind.Cell(spec["conf"], spec["traffic"], spec["limits"], 1)
