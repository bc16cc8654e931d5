"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Runs the full config on the devices present (``launch/mesh.mesh_for_devices``:
the production mesh when the device count matches it, a local mesh
otherwise, no mesh on one device); ``--smoke`` swaps in the reduced
same-family twin.
"""
from __future__ import annotations

import argparse
import dataclasses
import logging

import jax

from repro.configs import (MemoryPlan, PipelinePlan, RunConfig,
                           SHAPES_BY_NAME, TrainConfig, get_arch)
from repro.configs.base import CheckpointPlan, MeshPlan, ShapeConfig
from repro.core.dag import build_dag
from repro.core.policy import plan_memory, summarize
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.launch.mesh import (enable_compile_cache, make_mesh,
                               mesh_for_devices)
from repro.models.model import build_model
from repro.train.chaos import ChaosMonkey, ChaosSchedule
from repro.train.elastic import ElasticController
from repro.train.fault import FaultHandler
from repro.train.loop import train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + small batch on local devices")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--policy", default="mcdla")
    ap.add_argument("--placement", default="bw_aware")
    ap.add_argument("--compress", default="none")
    ap.add_argument("--opt-bits", type=int, default=32)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--pipeline", action="store_true",
                    help="run the pod axis as a pipeline of layer stages "
                         "(parallel/pipeline.py schedule registry)")
    ap.add_argument("--pipeline-schedule", default="1f1b",
                    help="registered schedule: gpipe | 1f1b")
    ap.add_argument("--n-micro", type=int, default=0,
                    help="microbatches per step (0: planner-chosen by the "
                         "bubble-vs-stall cost model)")
    ap.add_argument("--pipeline-stages", type=int, default=0,
                    help="pipeline stages (0: all local devices)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-tier", default="",
                    help="checkpoint through a tier stack: host | mcdla | "
                         "spill (empty: legacy direct writes)")
    ap.add_argument("--ckpt-codec", default="none",
                    help="snapshot codec: none | fp8 | int8 (lossy codecs "
                         "trade restore bit-exactness for pool bytes)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint cadence in steps (0: planner-chosen "
                         "Young-Daly cadence when --ckpt-tier is set)")
    ap.add_argument("--ckpt-async", action="store_true",
                    help="double-buffered saves overlapping the next steps")
    ap.add_argument("--ckpt-shards", type=int, default=1)
    ap.add_argument("--mtbf-steps", type=int, default=10_000,
                    help="expected steps between failures (cadence planner)")
    ap.add_argument("--chaos", default="",
                    help="fault-injection schedule, e.g. "
                         "'kill@3,corrupt@5,stage_loss@7:1,preempt@9', or "
                         "'random:<seed>' for a seeded random schedule")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    enable_compile_cache()
    cfg = get_arch(args.arch)
    mesh, plan = mesh_for_devices(multi_pod=args.multi_pod)
    if args.smoke:
        cfg = cfg.reduced()
        batch = args.batch or max(4, len(jax.devices()))
        seq = args.seq or 128
    else:
        sh = SHAPES_BY_NAME[args.shape]
        batch = args.batch or sh.global_batch
        seq = args.seq or sh.seq_len

    pipeline = PipelinePlan()
    pipe_mesh = None
    if args.pipeline:
        # the pipeline owns the pod axis: stages on a dedicated 1D mesh,
        # the model itself unsharded (stage stash placement is the tier's)
        if args.multi_pod:
            raise SystemExit("--pipeline replaces pod-DP with pipeline "
                             "stages; a multi-pod pipeline+DP composition "
                             "is not implemented (see ROADMAP)")
        devs = jax.devices()
        n_stages = args.pipeline_stages or len(devs)
        if len(devs) < n_stages:
            raise SystemExit(f"--pipeline-stages {n_stages} needs that many "
                             f"devices (have {len(devs)})")
        if n_stages > 1:
            pipe_mesh = make_mesh((n_stages,), ("pod",),
                                  devices=devs[:n_stages])
        mesh = None
        plan = MeshPlan((1,), ("data",))
        pipeline = PipelinePlan(enabled=True,
                                schedule=args.pipeline_schedule,
                                n_micro=args.n_micro, n_stages=n_stages)
        pipeline.validate()

    shape = ShapeConfig("train", seq, batch, "train")
    tc = TrainConfig(total_steps=args.steps, warmup_steps=args.steps // 10,
                     learning_rate=args.lr, grad_accum=args.accum,
                     checkpoint_dir=args.ckpt_dir,
                     checkpoint_every=max(25, args.steps // 4),
                     log_every=args.log_every)
    memory = MemoryPlan(policy=args.policy, placement=args.placement,
                        compress=args.compress, opt_state_bits=args.opt_bits)
    ckpt = CheckpointPlan(enabled=bool(args.ckpt_tier),
                          tier=args.ckpt_tier or "host",
                          codec=args.ckpt_codec, every=args.ckpt_every,
                          async_saves=args.ckpt_async,
                          shards=args.ckpt_shards,
                          mtbf_steps=args.mtbf_steps)
    if ckpt.enabled:
        ckpt.validate()
    run = RunConfig(model=cfg, shape=shape, mesh=plan, memory=memory,
                    train=tc, pipeline=pipeline, ckpt=ckpt)
    model = build_model(run, mesh=mesh, pipe_mesh=pipe_mesh)
    log = logging.getLogger(__name__)
    if model.pipeline_report is not None:
        log.info("pipeline plan: %s", summarize(model.pipeline_report))
    if ckpt.enabled and ckpt.every == 0:
        # plan the save cadence (Young-Daly sweep) against the analytic
        # step time through the configured tier stack
        dag = build_dag(cfg, shape)
        opt_bytes = 4 + 2 * memory.opt_state_bits // 8
        report = plan_memory(dag, plan, memory,
                             model_state_bytes=cfg.param_count() * opt_bytes,
                             checkpoint=ckpt)
        ckpt = dataclasses.replace(ckpt, every=report.checkpoint.every)
        log.info("checkpoint plan: every=%d steps (save=%.2fs overhead="
                 "%.2fms/step lost=%.2fms/step via %s)",
                 report.checkpoint.every, report.checkpoint.save_s,
                 1e3 * report.checkpoint.overhead_s,
                 1e3 * report.checkpoint.lost_s, report.checkpoint.tier)

    chaos = None
    if args.chaos:
        if args.chaos.startswith("random:"):
            sched = ChaosSchedule.random(int(args.chaos.split(":", 1)[1]),
                                         args.steps)
        else:
            sched = ChaosSchedule.parse(args.chaos)
        chaos = ChaosMonkey(sched, seed=tc.seed)
        log.info("chaos schedule: %s", sched.spec())

    handler = FaultHandler()
    source = SyntheticLM(cfg, batch=batch, seq=seq, seed=tc.seed)
    if chaos is not None:
        # the chaos/elastic path rewinds the stream mid-run (set_state);
        # feed the loop the raw resumable source, not a prefetch queue
        # holding stale lookahead batches
        from repro.train.loop import make_manager
        mgr = make_manager(model, tc, ckpt, chaos)
        elastic = ElasticController(run, mgr, mesh=mesh, pipe_mesh=pipe_mesh)
        state, metrics = train(model, tc, source, fault_handler=handler,
                               ckpt=ckpt, chaos=chaos, elastic=elastic,
                               mgr=mgr)
        print({k: float(v) for k, v in metrics.items()})
        return
    data = Prefetcher(source)
    try:
        state, metrics = train(model, tc, iter(data), fault_handler=handler,
                               ckpt=ckpt)
        print({k: float(v) for k, v in metrics.items()})
    finally:
        data.close()


if __name__ == "__main__":
    main()
