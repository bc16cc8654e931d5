import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  This module is the multi-pod dry-run: for every
# (architecture x input shape) cell it lowers + compiles the real train /
# prefill / decode step against the production mesh with ShapeDtypeStruct
# inputs (no allocation), then extracts
#   * memory_analysis()  — bytes/device: proves the cell fits (or doesn't),
#   * cost_analysis()    — per-device HLO FLOPs / bytes for §Roofline,
#   * the collective schedule parsed from the partitioned HLO text —
#     per-type wire bytes for the §Roofline collective term.

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (ARCHS, MemoryPlan, RunConfig, SHAPES_BY_NAME,  # noqa: E402
                           TrainConfig, get_arch)
from repro.configs.registry import cells_for  # noqa: E402
from repro.launch.mesh import make_production_mesh, plan_for  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.train.loop import jit_train_step  # noqa: E402
from repro.train.train_state import abstract_state  # noqa: E402

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f8e4m3fn": 1, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "s16": 2, "u16": 2, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(tok_dtype: str, dims: str) -> float:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(tok_dtype, 4)


def parse_collectives(hlo: str) -> Dict[str, float]:
    """Per-device wire bytes by collective type (ring-schedule estimate)."""
    out: Dict[str, float] = {}
    seen_done = set()
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        if "-done(" in line:
            continue                      # paired with -start; count once
        result, kind = m.group(1), m.group(2)
        shapes = _SHAPE_RE.findall(result)
        size = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        g = _GROUPS_RE.search(line)
        n = int(g.group(2)) if g else 2
        if kind == "all-gather":
            wire = (n - 1) / n * size          # result = gathered
        elif kind == "all-reduce":
            wire = 2 * (n - 1) / n * size
        elif kind == "reduce-scatter":
            wire = (n - 1) * size              # result = scattered shard
        elif kind == "all-to-all":
            wire = (n - 1) / n * size
        else:                                  # collective-permute
            wire = size
        out[kind] = out.get(kind, 0.0) + wire
    return out


# ---------------------------------------------------------------------------
def _group_unit(cfg) -> int:
    if cfg.is_hybrid:
        return cfg.hybrid_attn_every
    if cfg.is_moe and cfg.moe_every > 1:
        return cfg.moe_every
    return 1


def probe_scaled(arch: str, shape_name: str, *, multi_pod: bool,
                 policy: str, placement: str, compress: str, opt_bits: int,
                 seq_parallel: bool, mesh, n_groups_full: int,
                 stash_aux: bool = True) -> Dict:
    """Loop-aware cost measurement.

    XLA's cost_analysis counts while-loop bodies ONCE (not x trip count),
    so FLOPs / bytes / collective bytes of the scanned layer stack are
    under-reported.  We lower the SAME step with the stack fully unrolled
    at depths k=2 and k=4 groups and extrapolate the exact linear model
    f(L) = C + L*B (every per-layer cost is linear in depth).  All numbers
    still come from compiled artifacts.
    """
    import dataclasses as _dc

    from repro.models import attention as attn_mod
    from repro.models import transformer as tfm

    cfg = get_arch(arch)
    unit = _group_unit(cfg)
    vals = {}
    tfm.SCAN_UNROLL = True
    attn_mod.UNROLL_INNER = True
    shape = SHAPES_BY_NAME[shape_name]
    # bound the unrolled online-softmax body count for long sequences
    big = max(1024, shape.seq_len // 8)
    attn_mod.Q_CHUNK, attn_mod.KV_CHUNK = big, big
    try:
        for k in (1, 2):
            over = {"num_layers": k * unit}
            if cfg.is_encoder_decoder:
                over["encoder_layers"] = k
            cfg_k = _dc.replace(cfg, **over)
            r = _lower_one(cfg_k, shape_name, multi_pod=multi_pod,
                           policy=policy, placement=placement,
                           compress=compress, opt_bits=opt_bits,
                           accum=1, seq_parallel=seq_parallel,
                           stash_aux=stash_aux, mesh=mesh)
            vals[k] = r
    finally:
        tfm.SCAN_UNROLL = False
        attn_mod.UNROLL_INNER = False
        attn_mod.Q_CHUNK = attn_mod.KV_CHUNK = 1024

    def fit(key):
        f1 = vals[1].get(key) or 0.0
        f2 = vals[2].get(key) or 0.0
        b = f2 - f1
        c = f1 - b
        return max(0.0, c + n_groups_full * b)

    coll1 = vals[1]["collectives"]
    coll2 = vals[2]["collectives"]
    coll = {}
    for kind in set(coll1) | set(coll2):
        b = coll2.get(kind, 0.0) - coll1.get(kind, 0.0)
        coll[kind] = max(0.0, coll1.get(kind, 0.0) - b + n_groups_full * b)
    return {
        "flops_per_dev": fit("flops_per_dev"),
        "bytes_accessed_per_dev": fit("bytes_accessed_per_dev"),
        "collectives": coll,
        "collective_wire_bytes_per_dev": sum(coll.values()),
    }


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               policy: str = "mcdla", placement: str = "bw_aware",
               compress: str = "none", opt_bits: int = 32,
               accum: int = 1, seq_parallel: bool = True,
               stash_aux: bool = True,
               probes: bool = True, mesh=None) -> Dict:
    """Lower + compile one cell (+ the loop-aware cost probes)."""
    cfg = get_arch(arch)
    mesh = mesh if mesh is not None else make_production_mesh(
        multi_pod=multi_pod)
    res = _lower_one(cfg, shape_name, multi_pod=multi_pod, policy=policy,
                     placement=placement, compress=compress,
                     opt_bits=opt_bits, accum=accum,
                     seq_parallel=seq_parallel, stash_aux=stash_aux,
                     mesh=mesh)
    res.update({"arch": arch, "raw_flops_per_dev": res["flops_per_dev"],
                "raw_collective_wire_bytes_per_dev":
                    res["collective_wire_bytes_per_dev"]})
    if probes:
        from repro.models.transformer import arch_group
        _, n_groups = arch_group(cfg)
        p = probe_scaled(arch, shape_name, multi_pod=multi_pod,
                         policy=policy, placement=placement,
                         compress=compress, opt_bits=opt_bits,
                         seq_parallel=seq_parallel, stash_aux=stash_aux,
                         mesh=mesh, n_groups_full=n_groups)
        # probes run accum=1 over the full batch: per-step FLOPs/bytes are
        # identical for any accum (microbatches partition the same tokens);
        # only the per-microbatch weight regathers are undercounted for
        # accum>1 (noted in EXPERIMENTS.md).
        res["flops_per_dev"] = p["flops_per_dev"]
        res["bytes_accessed_per_dev"] = p["bytes_accessed_per_dev"]
        res["collectives"] = dict(p["collectives"])
        res["collective_wire_bytes_per_dev"] = \
            p["collective_wire_bytes_per_dev"]
    return res


def _lower_one(cfg, shape_name: str, *, multi_pod: bool, policy: str,
               placement: str, compress: str, opt_bits: int, accum: int,
               seq_parallel: bool, mesh, stash_aux: bool = True) -> Dict:
    shape = SHAPES_BY_NAME[shape_name]
    plan = plan_for(multi_pod=multi_pod)
    memory = MemoryPlan(policy=policy, placement=placement,
                        compress=compress, opt_state_bits=opt_bits,
                        seq_parallel=seq_parallel, stash_aux=stash_aux)
    tc = TrainConfig(grad_accum=accum)
    run = RunConfig(model=cfg, shape=shape, mesh=plan, memory=memory,
                    train=tc)
    model = build_model(run, mesh=mesh)
    t0 = time.time()

    batch_sds = model.input_specs(shape)
    batch_sh = {k: NamedSharding(mesh, s)
                for k, s in model.batch_specs(shape).items()}

    with mesh:
        if shape.mode == "train":
            lowered = jit_train_step(model, tc, batch_sh).lower(
                abstract_state(model, tc), batch_sds)
        elif shape.mode == "prefill":
            params_sds = model.abstract_params()
            params_sh = model.param_shardings()
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cache_sh = model.cache_shardings(shape.global_batch,
                                             shape.seq_len)
            lowered = jax.jit(
                model.prefill,
                in_shardings=(params_sh, batch_sh, cache_sh),
                out_shardings=(None, cache_sh),
                donate_argnums=2).lower(params_sds, batch_sds, cache_sds)
        else:   # decode
            params_sds = model.abstract_params()
            params_sh = model.param_shardings()
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cache_sh = model.cache_shardings(shape.global_batch,
                                             shape.seq_len)
            lowered = jax.jit(
                model.decode_step,
                in_shardings=(params_sh, batch_sh["token"],
                              batch_sh["positions"], cache_sh,
                              batch_sh["index"]),
                out_shardings=(None, cache_sh),
                donate_argnums=3,
            ).lower(params_sds, batch_sds["token"], batch_sds["positions"],
                    cache_sds, batch_sds["index"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):          # older jax returns [dict]
        ca = ca[0] if ca else {}
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    # the tier traffic one run of the step moves (``per_step``: recorded
    # when the step was traced, scanned layers counted once per trip)
    traffic = model.runtime.traffic_report()
    res = {
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "policy": policy, "placement": placement, "compress": compress,
        "opt_bits": opt_bits, "accum": accum, "ok": True,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "arg_bytes_per_dev": getattr(ma, "argument_size_in_bytes", None),
        "temp_bytes_per_dev": getattr(ma, "temp_size_in_bytes", None),
        "out_bytes_per_dev": getattr(ma, "output_size_in_bytes", None),
        "flops_per_dev": ca.get("flops"),
        "bytes_accessed_per_dev": ca.get("bytes accessed"),
        "collectives": colls,
        "collective_wire_bytes_per_dev": sum(colls.values()),
        "tier": traffic["tier"],
        "traffic": traffic,
    }
    return res


def per_step_bytes(traffic: Dict) -> float:
    """Wire bytes one run of the train step moves through the tier."""
    return sum(v.get("per_step", 0.0) for v in traffic.values()
               if isinstance(v, dict))


# ---------------------------------------------------------------------------
def pipeline_cell(arch: str, shape_name: str, *, multi_pod: bool,
                  policy: str, placement: str, compress: str, opt_bits: int,
                  pipeline) -> Dict:
    """Analytic stage-tier report for one cell: the planner's joint
    n_micro x KEEP/POOL/RECOMPUTE verdict plus the per-stage act traffic
    the 1F1B schedule would push through the pipeline stage tier.  (The
    pipelined step itself is a shard_map over a dedicated stage mesh —
    the dry-run surfaces the tier contract, not a second compile.)"""
    from repro.core.dag import build_dag
    from repro.core.policy import micro_candidates, plan_memory
    from repro.core.tiers import build_stage_tier
    from repro.parallel.sharding import ShardingPlanner

    cfg = get_arch(arch)
    shape = SHAPES_BY_NAME[shape_name]
    plan = plan_for(multi_pod=multi_pod)
    memory = MemoryPlan(policy=policy, placement=placement,
                        compress=compress, opt_state_bits=opt_bits)
    planner = ShardingPlanner(plan)
    tier = build_stage_tier(memory, planner, None,
                            n_stages=pipeline.n_stages)
    report = plan_memory(
        build_dag(cfg, shape), plan, memory, tier=tier, pipeline=pipeline,
        n_micro_candidates=micro_candidates(shape.global_batch,
                                            pipeline.n_stages))
    pd = report.pipeline
    return {
        "schedule": pd.schedule, "n_stages": pd.n_stages,
        "n_micro": pd.n_micro, "bubble_s": pd.bubble_s,
        "stall_s": pd.stall_s, "act_wire_bytes": pd.act_wire_bytes,
        "act_wire_bytes_per_stage":
            pd.act_wire_bytes / max(pd.n_stages, 1),
        "tier": tier.describe(),
    }


# ---------------------------------------------------------------------------
def checkpoint_cell(arch: str, shape_name: str, *, multi_pod: bool,
                    policy: str, placement: str, compress: str,
                    opt_bits: int, ckpt) -> Dict:
    """Analytic checkpoint-traffic report for one cell: the Young-Daly
    cadence verdict through the configured CheckpointTier stack plus the
    per-snapshot wire bytes the pooled backing store absorbs.  (Pure tier
    arithmetic — no compile; the in-process metered path is
    train/checkpoint.py.)"""
    from repro.core.dag import build_dag
    from repro.core.policy import plan_memory

    cfg = get_arch(arch)
    shape = SHAPES_BY_NAME[shape_name]
    plan = plan_for(multi_pod=multi_pod)
    memory = MemoryPlan(policy=policy, placement=placement,
                        compress=compress, opt_state_bits=opt_bits)
    opt_bytes = 4 + 2 * opt_bits // 8
    report = plan_memory(build_dag(cfg, shape), plan, memory,
                         model_state_bytes=cfg.param_count() * opt_bytes,
                         checkpoint=ckpt)
    d = report.checkpoint
    return {
        "tier": d.tier, "every": d.every,
        "snapshot_bytes": d.snapshot_bytes,
        "save_s": d.save_s,
        "overhead_s_per_step": d.overhead_s,
        "lost_s_per_step": d.lost_s,
        "async": d.async_saves,
        "ckpt_wire_bytes_per_step": d.snapshot_bytes / max(d.every, 1),
    }


# ---------------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--policy", default="mcdla",
                    choices=["none", "host", "mcdla", "auto"])
    ap.add_argument("--placement", default="bw_aware",
                    choices=["bw_aware", "local"])
    ap.add_argument("--compress", default="none", choices=["none", "fp8"])
    ap.add_argument("--opt-bits", type=int, default=32, choices=[32, 8])
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--pipeline", action="store_true",
                    help="attach the analytic pipeline stage-tier report "
                         "(bubble-vs-stall verdict + per-stage traffic)")
    ap.add_argument("--pipeline-schedule", default="1f1b")
    ap.add_argument("--pipeline-stages", type=int, default=2)
    ap.add_argument("--n-micro", type=int, default=0)
    ap.add_argument("--ckpt", action="store_true",
                    help="attach the analytic checkpoint-traffic report "
                         "(Young-Daly cadence + pooled snapshot bytes)")
    ap.add_argument("--ckpt-tier", default="host")
    ap.add_argument("--ckpt-codec", default="none")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-async", action="store_true")
    ap.add_argument("--mtbf-steps", type=int, default=10_000)
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--no-probes", action="store_true",
                    help="skip the loop-aware cost probes (faster)")
    ap.add_argument("--out", default="")
    ap.add_argument("--include-skipped", action="store_true")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    archs = sorted(ARCHS) if args.arch == "all" else [args.arch]
    results = []
    for arch in archs:
        for cfg, shape, status in cells_for(get_arch(arch)):
            if args.shape != "all" and shape.name != args.shape:
                continue
            if status != "run" and not args.include_skipped:
                results.append({"arch": arch, "shape": shape.name,
                                "mesh": "2x16x16" if args.multi_pod
                                else "16x16", "ok": None, "skip": status})
                print(f"[skip] {arch} x {shape.name}: {status}")
                continue
            tag = f"{arch} x {shape.name} x " \
                  f"{'2x16x16' if args.multi_pod else '16x16'}"
            try:
                r = lower_cell(arch, shape.name, multi_pod=args.multi_pod,
                               policy=args.policy, placement=args.placement,
                               compress=args.compress, accum=args.accum,
                               seq_parallel=not args.no_seq_parallel,
                               probes=not args.no_probes,
                               opt_bits=args.opt_bits, mesh=mesh)
                if args.pipeline and shape.mode == "train":
                    from repro.configs.base import PipelinePlan
                    r["pipeline"] = pipeline_cell(
                        arch, shape.name, multi_pod=args.multi_pod,
                        policy=args.policy, placement=args.placement,
                        compress=args.compress, opt_bits=args.opt_bits,
                        pipeline=PipelinePlan(
                            enabled=True, schedule=args.pipeline_schedule,
                            n_micro=args.n_micro,
                            n_stages=args.pipeline_stages))
                if args.ckpt and shape.mode == "train":
                    from repro.configs.base import CheckpointPlan
                    r["checkpoint"] = checkpoint_cell(
                        arch, shape.name, multi_pod=args.multi_pod,
                        policy=args.policy, placement=args.placement,
                        compress=args.compress, opt_bits=args.opt_bits,
                        ckpt=CheckpointPlan(
                            enabled=True, tier=args.ckpt_tier,
                            codec=args.ckpt_codec, every=args.ckpt_every,
                            async_saves=args.ckpt_async,
                            mtbf_steps=args.mtbf_steps))
                results.append(r)
                tr = r.get("traffic", {})
                print(f"[ok]   {tag}: compile={r['compile_s']}s "
                      f"args={r['arg_bytes_per_dev']/1e9:.2f}GB "
                      f"temp={r['temp_bytes_per_dev']/1e9:.2f}GB "
                      f"flops/dev={r['flops_per_dev']:.3e} "
                      f"coll/dev={r['collective_wire_bytes_per_dev']/1e9:.3f}GB "
                      f"tier[{tr.get('tier', '?')}]="
                      f"{per_step_bytes(tr)/1e9:.3f}GB/step")
                if "pipeline" in r:
                    p = r["pipeline"]
                    print(f"       pipeline[{p['schedule']} "
                          f"S={p['n_stages']}]: n_micro={p['n_micro']} "
                          f"bubble={p['bubble_s']*1e3:.2f}ms "
                          f"stall={p['stall_s']*1e3:.2f}ms "
                          f"act/stage="
                          f"{p['act_wire_bytes_per_stage']/1e9:.3f}GB "
                          f"tier[{p['tier']}]")
                if "checkpoint" in r:
                    c = r["checkpoint"]
                    print(f"       checkpoint[{c['tier']}]: "
                          f"every={c['every']} "
                          f"snap={c['snapshot_bytes']/1e9:.3f}GB "
                          f"save={c['save_s']:.2f}s "
                          f"overhead={c['overhead_s_per_step']*1e3:.2f}ms"
                          f"/step lost={c['lost_s_per_step']*1e3:.2f}ms"
                          f"/step{' async' if c['async'] else ''}")
            except Exception as e:  # noqa: BLE001 — a failed cell is a bug
                results.append({"arch": arch, "shape": shape.name,
                                "mesh": "2x16x16" if args.multi_pod
                                else "16x16", "ok": False,
                                "error": f"{type(e).__name__}: {e}"})
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
                traceback.print_exc()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    failed = [r for r in results if r.get("ok") is False]
    print(f"\n{len([r for r in results if r.get('ok')])} ok, "
          f"{len(failed)} failed, "
          f"{len([r for r in results if r.get('ok') is None])} skipped")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
