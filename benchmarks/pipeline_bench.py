"""Pipeline schedule microbench: step time vs n_micro for gpipe vs 1f1b
with the stage-tier stash on/off, on a toy stack over a CPU host mesh.

CPU wall-clock for regression tracking only (like benchmarks/microbench.py);
the analytic bubble-vs-stall trade lives in core/policy.plan_memory and the
paper-figure timelines in sim/simulator.simulate_pipeline.

Run: PYTHONPATH=src python benchmarks/pipeline_bench.py [--quick]
"""
from __future__ import annotations

import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import argparse
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp

Row = Tuple[str, float, str]

D = 64          # toy stack width
L_PER = 4       # layers per stage
BATCH = 32


def _toy(n_stages: int):
    key = jax.random.PRNGKey(0)
    W = jax.random.normal(key, (n_stages, L_PER, D, D), jnp.float32) * 0.3
    xb = jax.random.normal(jax.random.PRNGKey(1), (BATCH, D), jnp.float32)
    tgt = jax.random.normal(jax.random.PRNGKey(2), (BATCH, D), jnp.float32)

    def stage_fn(params, t):
        h = t["h"]
        for i in range(L_PER):
            h = jnp.tanh(h @ params[i])
        return {"h": h}

    return W, xb, tgt, stage_fn


def _time(fn, *args, reps: int = 5) -> float:
    fn(*args)[1].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        out[1].block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def pipeline_bench(quick: bool = False) -> List[Row]:
    from repro.configs.base import MemoryPlan, MeshPlan
    from repro.core.runtime import MemoryRuntime
    from repro.core.tiers import build_stage_tier
    from repro.launch.mesh import make_mesh
    from repro.parallel.pipeline import get_schedule, make_pipelined
    from repro.parallel.sharding import ShardingPlanner

    S = len(jax.devices())
    mesh = make_mesh((S,), ("pod",))
    W, xb, tgt, stage_fn = _toy(S)

    plan = MeshPlan((S,), ("pod",))
    planner = ShardingPlanner(plan)
    memory = MemoryPlan(policy="mcdla")
    runtime = MemoryRuntime(
        plan, memory, None, planner=planner,
        tier=build_stage_tier(memory, planner, None, n_stages=S))

    micros = (S,) if quick else (2, S, 2 * S, 4 * S)
    rows: List[Row] = []
    for name in ("gpipe", "1f1b"):
        for stash in (False, True) if name == "1f1b" else (False,):
            rt = runtime if stash else None
            for M in micros:
                if BATCH % M:
                    continue
                sched = get_schedule(name, runtime=rt)
                pipe = make_pipelined(mesh, stage_fn, n_micro=M,
                                      schedule=sched)

                def loss(W):
                    out = pipe(W, {"h": xb})
                    return jnp.mean((out["h"] - tgt) ** 2)

                step = jax.jit(jax.value_and_grad(loss))
                tag = f"{name}{'+stash' if stash else ''}"
                rows.append((f"pipe.{tag}.s{S}.m{M}.us",
                             round(_time(lambda w: step(w), W), 1),
                             "toy stack, CPU host mesh"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="one n_micro per schedule (CI smoke)")
    args = ap.parse_args()
    for name, value, note in pipeline_bench(quick=args.quick):
        print(f"{name},{value},{note}")


if __name__ == "__main__":
    main()
