"""End-to-end driver: a bag of REAL JAX training tasks on a spot cluster.

The scheduler plans and simulates a BoT of fine-tuning jobs on spot +
burstable VMs under hibernation events; the resulting execution trace then
drives *actual training* (repro.cluster.runtime.TraceExecutor): every
preemption checkpoints the real TrainState, every migration restores it —
losses must keep descending across restarts.

  PYTHONPATH=src python examples/train_bot.py [--tasks 4] [--steps 24]
"""
import argparse
import shutil
import sys
import tempfile

sys.path.insert(0, "src")

import jax
import numpy as np

from repro import compile_cache
from repro.cluster.runtime import TraceExecutor, TrainTaskPayload
from repro.configs import get_config
from repro.core.dynamic import BURST_HADS, build_primary_map
from repro.core.ils import ILSParams
from repro.core.types import CloudConfig, Job, TaskSpec
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.models.model import init_params
from repro.sim.events import SCENARIOS
from repro.sim.simulator import Simulator
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.steps import make_train_step


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tasks", type=int, default=4)
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--scenario", default="sc2")
    args = ap.parse_args()

    # 1. the bag: N fine-tune tasks (600 base-seconds each, ~1 GB footprint)
    tasks = tuple(TaskSpec(tid=i, memory_mb=1024.0, base_time=600.0)
                  for i in range(args.tasks))
    job = Job(name="train-bot", tasks=tasks, deadline_s=2700.0)
    cfg = CloudConfig()

    # 2. plan + simulate with hibernations
    plan = build_primary_map(job, cfg, BURST_HADS,
                             ILSParams(max_iteration=20, max_attempt=10))
    sim = Simulator(job, plan, cfg, SCENARIOS[args.scenario], seed=5)
    res = sim.run()
    print(f"schedule: cost=${res.cost:.3f} makespan={res.makespan:.0f}s "
          f"hibernations={res.n_hibernations} deadline={res.deadline_met}")

    # 3. replay the trace with real training payloads
    mcfg = get_config(args.arch, tiny=True)
    step_fn = jax.jit(make_train_step(mcfg))
    tmp = tempfile.mkdtemp(prefix="train_bot_")
    payloads = {}
    for t in tasks:
        pipe = TokenPipeline(DataConfig(vocab=mcfg.vocab, batch=2,
                                        seq_len=32, seed=t.tid))

        def make_state(seed=t.tid):
            params = init_params(mcfg, jax.random.PRNGKey(seed))
            return {"params": params, "opt": adamw_init(params)}

        payloads[t.tid] = TrainTaskPayload(
            name=f"ft-{t.tid}", total_steps=args.steps,
            make_state=make_state, train_step=step_fn, batch_fn=pipe.batch,
            ckpt_dir=f"{tmp}/task{t.tid}")

    ex = TraceExecutor(sim.records, payloads,
                       {t.tid: tasks[t.tid].base_time * 1.1 for t in tasks})
    out = ex.run()
    print("\nexecution log:")
    for line in ex.log:
        print(" ", line)
    print("\nresults:")
    ok = True
    for tid, o in sorted(out.items()):
        p = payloads[tid]
        head = float(np.mean(p.losses[:3]))
        tail = float(np.mean(p.losses[-3:]))
        print(f"  ft-{tid}: steps={o['steps']} restores={o['restores']} "
              f"loss {head:.3f} -> {tail:.3f}")
        ok &= o["steps"] == args.steps
    shutil.rmtree(tmp, ignore_errors=True)
    print("\nALL TASKS TRAINED TO COMPLETION ✓" if ok else "INCOMPLETE ✗")


if __name__ == "__main__":
    main()
