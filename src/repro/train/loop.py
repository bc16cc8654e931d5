"""Training loop: jitted train_step builder + the driver with gradient
accumulation, checkpointing, fault handling, and metrics."""
from __future__ import annotations

import contextlib
import functools
import logging
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.models.model import Model
from repro.parallel.pipeline import accumulate_microbatches
from repro.train import chaos as chaos_mod
from repro.train import checkpoint as ckpt_mod
from repro.train import fault as fault_mod
from repro.train.optimizer import apply_adamw
from repro.train.train_state import init_state, state_shardings

log = logging.getLogger(__name__)
Pytree = Any


# ---------------------------------------------------------------------------
def make_train_step(model: Model, tc: TrainConfig
                    ) -> Callable[[Pytree, Dict[str, jax.Array]],
                                  Tuple[Pytree, Dict[str, jax.Array]]]:
    """(state, batch) -> (state, metrics).

    Microbatching runs on one schedule path (parallel/pipeline.py): a
    pipeline-enabled model microbatches *inside* its pipelined forward
    (``model.pipeline.n_micro`` over the stage mesh), while gradient
    accumulation (``tc.grad_accum > 1``) is the degenerate single-stage
    schedule — microbatches scanned sequentially with gradients averaged
    and metrics accumulated across microbatches
    (:func:`repro.parallel.pipeline.accumulate_microbatches`).
    """

    def loss(params, batch):
        return model.loss_fn(params, batch)

    pipelined = (getattr(model, "pipeline", None) is not None
                 and model.pipeline.enabled)

    def grads_of(params, batch):
        if tc.grad_accum <= 1 or pipelined:
            (l, metrics), g = jax.value_and_grad(loss, has_aux=True)(
                params, batch)
            return g, l, metrics
        # microbatches are scanned: each layer's transfers run once a trip
        with model.runtime.repeat(tc.grad_accum):
            return accumulate_microbatches(loss, params, batch,
                                           tc.grad_accum)

    def train_step(state, batch):
        g, l, metrics = grads_of(state["params"], batch)
        with jax.named_scope("optimizer"):
            params, opt, opt_metrics = apply_adamw(state["params"], g,
                                                   state["opt"], tc)
        new_state = dict(state, params=params, opt=opt,
                         step=state["step"] + 1)
        metrics = dict(metrics, **opt_metrics)
        return new_state, metrics

    return train_step


class TrainStep:
    """The jitted train step, counting the memory tiers' traffic of each
    run: tracing the step records one step's transfers in each runtime
    (``per_step``), and every call adds that record to the runtime's
    executed totals.  ``lower`` traces without running, so it records
    the per-step figure and counts nothing."""

    def __init__(self, jitted, runtimes):
        self.jitted = jitted
        self.runtimes = runtimes

    def __call__(self, state, batch):
        out = self.jitted(state, batch)
        for r in self.runtimes:
            r.count_step()
        return out

    def lower(self, *args, **kwargs):
        return self.jitted.lower(*args, **kwargs)


def jit_train_step(model: Model, tc: TrainConfig, batch_shardings=None
                   ) -> TrainStep:
    step = make_train_step(model, tc)
    runtimes = [r for r in (model.runtime, model.stage_runtime)
                if r is not None]

    @functools.wraps(step)
    def recorded(state, batch):
        # runs only while jax traces the step
        with contextlib.ExitStack() as stack:
            for r in runtimes:
                stack.enter_context(r.recording_step())
            return step(state, batch)

    if model.mesh is None:
        return TrainStep(jax.jit(recorded, donate_argnums=0), runtimes)
    shardings = state_shardings(model, tc)
    return TrainStep(jax.jit(recorded,
                             in_shardings=(shardings, batch_shardings),
                             out_shardings=(shardings, None),
                             donate_argnums=0), runtimes)


# ---------------------------------------------------------------------------
def make_manager(model: Model, tc: TrainConfig, ckpt=None, chaos=None
                 ) -> ckpt_mod.CheckpointManager:
    """Build the run's CheckpointManager: tier-backed + metered when a
    :class:`~repro.configs.base.CheckpointPlan` is enabled, the legacy
    direct writer otherwise.  The chaos harness's shard corruptor rides
    on the manager's post-commit hook."""
    on_commit = chaos.after_save if chaos is not None else None
    if ckpt is None or not ckpt.enabled:
        return ckpt_mod.CheckpointManager(tc.checkpoint_dir,
                                          keep=tc.keep_checkpoints,
                                          on_commit=on_commit)
    runtime = ckpt_mod.make_ckpt_runtime(ckpt, model.plan, model.memory,
                                         planner=model.planner,
                                         mesh=model.mesh,
                                         keep=tc.keep_checkpoints)
    return ckpt_mod.CheckpointManager(tc.checkpoint_dir,
                                      keep=tc.keep_checkpoints,
                                      runtime=runtime, shards=ckpt.shards,
                                      async_saves=ckpt.async_saves,
                                      on_commit=on_commit)


def train(model: Model, tc: TrainConfig, data_iter, *,
          state: Optional[Pytree] = None,
          fault_handler=None,
          hooks: Optional[Dict[str, Callable]] = None,
          ckpt=None, chaos=None, elastic=None, mgr=None
          ) -> Tuple[Pytree, Dict[str, jax.Array]]:
    """The end-to-end driver (examples/train_*.py).

    data_iter: yields (step_idx, batch) — resumable via its own state.
    fault_handler: train.fault.FaultHandler (SIGTERM-safe checkpointing).
    ckpt: optional :class:`~repro.configs.base.CheckpointPlan` — snapshots
      then flow through the checkpoint tier (metered ``ckpt_save`` /
      ``ckpt_load``), sharded + CRC-manifested, optionally async.
    chaos: optional :class:`~repro.train.chaos.ChaosMonkey` — injects the
      scheduled kills (absorbed by ``retry_step``), preemptions (the
      SIGTERM path), shard corruptions and stage losses.
    elastic: optional :class:`~repro.train.elastic.ElasticController` —
      on a stage loss, replans the pipeline for the surviving stages and
      restores from the checkpoint tier; without it a stage loss is
      fatal.
    mgr: override the CheckpointManager (tests wiring custom runtimes).

    Returns ``(state, metrics)``: the final train state and the last
    step's metrics.  On exit it logs the memory-tier traffic summary, the
    stage tier's ``act_stash``/``act_fetch`` traffic for pipelined runs,
    and the checkpoint tier's ``ckpt_save``/``ckpt_load`` traffic.
    """
    hooks = hooks or {}
    step_fn = jit_train_step(model, tc)
    if mgr is None:
        mgr = make_manager(model, tc, ckpt, chaos)
    ckpt_every = (ckpt.every if ckpt is not None and ckpt.every > 0
                  else tc.checkpoint_every)

    start_step = 0
    if state is None:
        restored = mgr.restore_latest()
        if restored is not None:
            start_step, payload = restored
            template = jax.eval_shape(
                lambda: init_state(model, tc, jax.random.PRNGKey(tc.seed)))
            state = ckpt_mod.to_device(payload["state"], template, model, tc)
            if hasattr(data_iter, "set_state") and "data" in payload:
                data_iter.set_state(payload["data"])
            log.info("resumed from step %d", start_step)
        elif model.mesh is None:
            state = init_state(model, tc)
        else:
            # built in its sharded layout: no device ever holds the whole
            # state (an eager init lands all of it on the first device)
            state = jax.jit(lambda: init_state(model, tc),
                            out_shardings=state_shardings(model, tc))()

    times = []
    metrics = {}
    for step_idx, batch in data_iter:
        if step_idx < start_step:
            continue
        if step_idx >= tc.total_steps:
            break
        if chaos is not None:
            try:
                chaos.before_step(step_idx, fault_handler)
            except chaos_mod.StageLostError as err:
                if elastic is None:
                    raise
                model, state, start_step = elastic.recover(
                    tc, data_iter, err.stage)
                step_fn = jit_train_step(model, tc)
                continue
        t0 = time.perf_counter()
        if chaos is not None:
            state, metrics = fault_mod.retry_step(
                chaos.wrap_step(step_fn, step_idx), state, batch,
                retries=chaos.retries, backoff=chaos.backoff)
        else:
            state, metrics = step_fn(state, batch)
        # the step returns once it is enqueued: wait for the device, so the
        # time is the step's and not the dispatch's
        jax.block_until_ready(metrics)
        times.append(time.perf_counter() - t0)
        if fault_handler is not None:
            fault_handler.observe_step(times[-1])

        done = step_idx + 1
        if done % tc.log_every == 0:
            m = {k: float(v) for k, v in metrics.items()}
            log.info("step %d loss=%.4f grad_norm=%.3f lr=%.2e (%.1f ms)",
                     done, m.get("loss", -1), m.get("grad_norm", -1),
                     m.get("lr", 0), 1e3 * times[-1])
            if "on_log" in hooks:
                hooks["on_log"](done, m)
        save_now = (done % ckpt_every == 0)
        if fault_handler is not None and fault_handler.should_stop:
            save_now = True
        if save_now:
            data_state = (data_iter.get_state()
                          if hasattr(data_iter, "get_state") else None)
            mgr.save(done, {"state": state, "data": data_state})
        if fault_handler is not None and fault_handler.should_stop:
            mgr.wait()      # the preemption checkpoint must land on disk
            log.warning("preemption requested — checkpoint written, exiting")
            break
    mgr.wait()
    runtime = getattr(model, "runtime", None)
    if runtime is not None and runtime.moves_bytes:
        log.info("memory traffic: %s", runtime.traffic_summary())
    stage_runtime = getattr(model, "stage_runtime", None)
    if stage_runtime is not None and stage_runtime.offloads:
        log.info("pipeline traffic: %s", stage_runtime.traffic_summary())
    if mgr.runtime is not None:
        log.info("checkpoint traffic: %s", mgr.runtime.traffic_summary())
    if chaos is not None:
        log.info("chaos events fired: %s", chaos.summary())
    return state, metrics
