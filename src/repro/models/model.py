"""Model facade — one object per (architecture x mesh x memory plan).

Wraps the transformer stacks with: parameter init + sharding specs, the
training loss (chunked CE + MoE aux), serving entry points (prefill /
decode), cache construction with pooled-KV sharding, and the
ShapeDtypeStruct input specs the multi-pod dry-run lowers against.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import (MemoryPlan, MeshPlan, ModelConfig,
                                PipelinePlan, RunConfig, ShapeConfig)
from repro.core.runtime import MemoryRuntime
from repro.models import frontends, transformer as tfm
from repro.models.layers import ModelContext, chunked_cross_entropy
from repro.parallel.sharding import ShardingPlanner

Params = Dict[str, Any]
AUX_WEIGHT = 0.01


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    plan: MeshPlan
    memory: MemoryPlan
    mesh: Optional[Mesh] = None
    stash_groups: Optional[int] = None     # None -> stash all (mcdla)
    pipeline: Optional[PipelinePlan] = None
    pipe_mesh: Optional[Mesh] = None       # dedicated stage-axis mesh

    def __post_init__(self):
        self.planner = ShardingPlanner(self.plan)
        self.dtype = jnp.dtype(self.cfg.dtype)
        self.runtime = MemoryRuntime(self.plan, self.memory, self.mesh,
                                     planner=self.planner)
        # pipeline runs get a second runtime whose tier is the stage tier:
        # the schedule's stash/fetch hooks meter act_stash/act_fetch there,
        # so training traffic shows up in a traffic_report like serving's.
        self.stage_runtime: Optional[MemoryRuntime] = None
        self.pipeline_report = None
        if self.pipeline is not None and self.pipeline.enabled:
            n_stages = self.pipeline.n_stages or (
                self.pipe_mesh.shape[self.pipeline.axis_name]
                if self.pipe_mesh is not None else 1)
            self.pipeline = dataclasses.replace(self.pipeline,
                                                n_stages=n_stages)
            from repro.core.tiers import build_stage_tier
            tier = build_stage_tier(self.memory, self.planner, None,
                                    n_stages=n_stages)
            self.stage_runtime = MemoryRuntime(self.plan, self.memory, None,
                                               planner=self.planner,
                                               tier=tier)

    # ------------------------------------------------------------------
    def ctx(self, mode: str) -> ModelContext:
        return ModelContext(cfg=self.cfg, planner=self.planner,
                            memory=self.memory, mesh=self.mesh, mode=mode,
                            runtime=self.runtime)

    def init(self, key) -> Params:
        return tfm.init_params(key, self.cfg, self.dtype)

    def abstract_params(self) -> Params:
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    def param_specs(self) -> Params:
        return tfm.param_specs(self.cfg, self.planner)

    def param_shardings(self) -> Params:
        assert self.mesh is not None
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.param_specs(),
                            is_leaf=lambda v: isinstance(v, P))

    # ------------------------------------------------------------------
    # training
    def loss_fn(self, params: Params, batch: Dict[str, jax.Array]
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        ctx = self.ctx("train")
        if self.pipeline is not None and self.pipeline.enabled:
            h, aux = tfm.forward_train_pipelined(
                params, ctx, batch["tokens"], batch["positions"],
                pipeline=self.pipeline, pipe_mesh=self.pipe_mesh,
                stage_runtime=self.stage_runtime)
        else:
            h, aux = tfm.forward_train(
                params, ctx, batch["tokens"], batch["positions"],
                frames=batch.get("frames"), patches=batch.get("patches"),
                stash_groups=self.stash_groups)
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        with jax.named_scope("loss"):
            # hoist the FSDP (data-axis) gather of the table out of the
            # chunk scan: vocab stays model-sharded, D gathered ONCE
            # (§Perf: was re-gathered per chunk, 8x the wire)
            table = ctx.act(table, "tensor", None)
            h = ctx.act(h, "batch", None, None)   # gather S once for the CE
            labels = batch["labels"]
            mask = (labels >= 0).astype(jnp.float32)
            loss, n_tok = chunked_cross_entropy(
                h, table, jnp.maximum(labels, 0), mask,
                constrain_logits=lambda lg: ctx.act(lg, "batch", None,
                                                    "tensor"))
        total = loss + AUX_WEIGHT * aux
        return total, {"loss": loss, "aux_loss": aux, "tokens": n_tok}

    # ------------------------------------------------------------------
    # serving
    def init_cache(self, batch: int, seq: int) -> Params:
        return tfm.init_caches(self.cfg, batch, seq, self.dtype)

    def cache_specs(self, batch: int, seq: int) -> Params:
        return tfm.cache_specs(self.cfg, self.planner, batch, seq)

    def cache_shardings(self, batch: int, seq: int) -> Params:
        assert self.mesh is not None
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.cache_specs(batch, seq),
                            is_leaf=lambda v: isinstance(v, P))

    def prefill(self, params: Params, batch: Dict[str, jax.Array],
                caches: Params) -> Tuple[jax.Array, Params]:
        """Process the prompt; returns (last-token logits (B,V), caches)."""
        ctx = self.ctx("prefill")
        h, caches = tfm.forward_serve(
            params, ctx, batch["tokens"], batch["positions"], caches,
            cache_index=jnp.zeros((), jnp.int32),
            frames=batch.get("frames"), patches=batch.get("patches"))
        logits = tfm.unembed(params, ctx, h[:, -1:, :])[:, 0, :]
        return logits, caches

    def decode_step(self, params: Params, token: jax.Array,
                    positions: jax.Array, caches: Params,
                    index: jax.Array) -> Tuple[jax.Array, Params]:
        """One decode step.  token: (B,1) int32; index: scalar int32 (number
        of tokens already in the cache); positions: (B,1) or (3,B,1)."""
        ctx = self.ctx("decode")
        h, caches = tfm.forward_serve(params, ctx, token, positions, caches,
                                      cache_index=index)
        logits = tfm.unembed(params, ctx, h[:, 0:1, :])[:, 0, :]
        return logits, caches

    # ------------------------------------------------------------------
    # dry-run input specs (ShapeDtypeStructs; no allocation)
    def input_specs(self, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32, dt = jnp.int32, self.dtype
        sd = jax.ShapeDtypeStruct
        if shape.mode in ("train", "prefill"):
            d: Dict[str, jax.ShapeDtypeStruct] = {
                "tokens": sd((B, S), i32),
                "positions": (sd((3, B, S), i32) if cfg.mrope_sections
                              else sd((B, S), i32)),
            }
            if shape.mode == "train":
                d["labels"] = sd((B, S), i32)
            if cfg.frontend == "audio_stub":
                d["frames"] = sd((B, cfg.frontend_tokens,
                                  frontends.AUDIO_FRAME_DIM), dt)
            if cfg.frontend == "vision_stub":
                d["patches"] = sd((B, cfg.frontend_tokens,
                                   frontends.VISION_PATCH_DIM), dt)
            return d
        # decode: one new token against a seq_len cache
        return {
            "token": sd((B, 1), i32),
            "positions": (sd((3, B, 1), i32) if cfg.mrope_sections
                          else sd((B, 1), i32)),
            "index": sd((), i32),
        }

    def batch_specs(self, shape: ShapeConfig) -> Dict[str, P]:
        """PartitionSpecs for input_specs entries."""
        b = self.planner.axes.batch
        specs = {}
        for name, s in self.input_specs(shape).items():
            if name == "index":
                specs[name] = P()
            elif name == "positions" and len(s.shape) == 3:
                specs[name] = self.planner.spec(s.shape, [None, b, None], name)
            else:
                specs[name] = self.planner.spec(
                    s.shape, [b] + [None] * (len(s.shape) - 1), name)
        return specs


# ---------------------------------------------------------------------------
def build_model(run: RunConfig, mesh: Optional[Mesh] = None,
                pipe_mesh: Optional[Mesh] = None) -> Model:
    """Construct the Model for a run, resolving the memory tier's stash
    split through the MemoryRuntime (cost model for non-stash-all tiers).

    Pipeline runs (``run.pipeline.enabled``) additionally resolve
    ``n_micro`` when it is 0: the planner sweeps the feasible microbatch
    counts and trades the schedule bubble against predicted stage-tier
    stalls (``core.policy.plan_memory``); the full verdict is kept on
    ``model.pipeline_report``.
    """
    cfg, memory, plan = run.model, run.memory, run.mesh
    _, n_groups = tfm.arch_group(cfg)
    pipeline = run.pipeline if run.pipeline.enabled else None
    model = Model(cfg=cfg, plan=plan, memory=memory, mesh=mesh,
                  pipeline=pipeline, pipe_mesh=pipe_mesh)
    model.stash_groups = model.runtime.resolve_stash_groups(
        cfg, run.shape, n_groups)
    if model.pipeline is not None:
        from repro.core.dag import build_dag
        from repro.core.policy import micro_candidates
        opt_bytes = 2 + (8 if memory.opt_state_bits == 32 else 2) + 4
        report = model.stage_runtime.plan_report(
            build_dag(cfg, run.shape),
            model_state_bytes=cfg.param_count() * opt_bytes,
            pipeline=model.pipeline,
            n_micro_candidates=micro_candidates(
                run.shape.global_batch, model.pipeline.n_stages))
        model.pipeline_report = report
        if model.pipeline.n_micro == 0:
            model.pipeline = dataclasses.replace(
                model.pipeline, n_micro=report.pipeline.n_micro)
    return model
