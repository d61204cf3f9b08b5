"""Serving-as-BoT: batched generation requests with a deadline, scheduled
by Burst-HADS across spot/burstable capacity, decoded with the real model.

Each scheduler task is one request batch; a hibernation mid-serve migrates
the batch (its decode state is re-prefills from the last token checkpoint —
represented by the task-level checkpoint machinery).

  PYTHONPATH=src python examples/serve_bot.py [--batches 6]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.configs import get_config
from repro.core.dynamic import BURST_HADS, build_primary_map
from repro.core.ils import ILSParams
from repro.core.types import CloudConfig, Job, TaskSpec
from repro.models.decode import init_cache
from repro.models.model import init_params
from repro.sim.events import SCENARIOS
from repro.sim.simulator import Simulator
from repro.train.steps import make_serve_step


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=6)
    ap.add_argument("--arch", default="rwkv6-7b")
    ap.add_argument("--gen", type=int, default=24)
    args = ap.parse_args()

    # each request batch = one task (~120 base-seconds of decode)
    tasks = tuple(TaskSpec(tid=i, memory_mb=512.0, base_time=120.0)
                  for i in range(args.batches))
    job = Job(name="serve-bot", tasks=tasks, deadline_s=900.0)
    cfg = CloudConfig()
    plan = build_primary_map(job, cfg, BURST_HADS,
                             ILSParams(max_iteration=15, max_attempt=10))
    sim = Simulator(job, plan, cfg, SCENARIOS["sc3"], seed=2)
    res = sim.run()
    print(f"schedule: cost=${res.cost:.4f} makespan={res.makespan:.0f}s "
          f"deadline_met={res.deadline_met} "
          f"hibernations={res.n_hibernations}")

    # decode the batches for real, in scheduler completion order
    mcfg = get_config(args.arch, tiny=True)
    params = init_params(mcfg, jax.random.PRNGKey(0))
    serve = jax.jit(make_serve_step(mcfg))
    order = [r["tid"] for r in sim.records if r["ev"] == "complete"]
    t0 = time.time()
    total = 0
    for tid in order:
        cache = init_cache(mcfg, 2, args.gen + 8, dtype=jnp.float32)
        tok = jnp.zeros((2,), jnp.int32) + (tid % mcfg.vocab)
        outs = []
        for _ in range(args.gen):
            logits, cache = serve(params, cache, tok)
            tok = jnp.argmax(logits, axis=-1)
            outs.append(int(tok[0]))
        total += 2 * args.gen
        print(f"  batch {tid}: {outs[:10]} ...")
    dt = time.time() - t0
    print(f"decoded {total} tokens in {dt:.1f}s "
          f"({total / dt:.0f} tok/s on CPU)")


if __name__ == "__main__":
    main()
