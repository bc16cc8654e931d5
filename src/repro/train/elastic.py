"""Elastic recovery: replan, reshard, and resume after losing a stage.

The restart-with-reshard primitive everything in ``train/fault.py``
reduces to, made available *mid-run*: when the chaos harness (or a real
collective failure surfaced as :class:`~repro.train.chaos.StageLostError`)
drops a pipeline stage, the :class:`ElasticController`

  1. waits out any in-flight async snapshot, then shrinks the pipeline
     plan to the surviving stages (``n_stages - 1``; a 2-stage run
     degrades to the sequential single-stage schedule),
  2. rebuilds the model through :func:`~repro.models.model.build_model`,
     which re-runs the ``plan_memory`` bubble-vs-stall sweep so
     ``n_micro`` and the per-stage KEEP/POOL/RECOMPUTE split are replanned
     for the new stage count (``n_micro=0`` → planner-chosen),
  3. restores the newest validating snapshot from the checkpoint tier —
     ``to_device`` re-shards the stored full arrays under the *new*
     model's shardings (reshard-on-load), corrupt snapshots are CRC-
     skipped — and rewinds the data iterator to the restored step,
  4. hands ``(model, state, start_step)`` back to the loop, which re-jits
     the train step and replays forward deterministically.

Steps replayed after restore recompute the same batches (the data
iterator is a pure function of ``(seed, step)``), so a same-config resume
is bit-identical; a changed stage partition replays the same *math* under
a different reduction order (loss parity within float tolerance — pinned
by tests/multidev/elastic.py).
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Optional, Tuple

import jax

log = logging.getLogger(__name__)


class ElasticController:
    """Owns the run description + checkpoint manager needed to rebuild.

    run: the full :class:`~repro.configs.base.RunConfig` of the current
    model (the controller keeps it updated as stages are lost).
    mgr: the :class:`~repro.train.checkpoint.CheckpointManager` to restore
    through (its runtime meters the ``ckpt_load`` traffic).
    """

    def __init__(self, run, mgr, mesh=None, pipe_mesh=None):
        self.run = run
        self.mgr = mgr
        self.mesh = mesh
        self.pipe_mesh = pipe_mesh
        self.recoveries = 0

    # ------------------------------------------------------------------
    def surviving_stages(self, lost_stage: int) -> int:
        pipe = self.run.pipeline
        s_old = pipe.n_stages if pipe.enabled else 1
        return max(1, s_old - 1)

    def _shrink_pipe_mesh(self, s_new: int, lost_stage: int):
        if s_new <= 1 or self.pipe_mesh is None:
            return None
        from repro.launch.mesh import make_mesh
        devs = list(self.pipe_mesh.devices.flatten())
        if 0 <= lost_stage < len(devs):
            devs.pop(lost_stage)
        axis = self.run.pipeline.axis_name
        return make_mesh((s_new,), (axis,), devices=devs[:s_new])

    def recover(self, tc, data_iter, lost_stage: int
                ) -> Tuple[object, object, int]:
        """Rebuild for the surviving stages and restore from the pool.

        Returns ``(model, state, start_step)``; the caller re-jits its
        step function against the new model.
        """
        from repro.models.model import build_model
        from repro.train.checkpoint import to_device
        from repro.train.train_state import init_state

        self.mgr.wait()
        pipe = self.run.pipeline
        s_new = self.surviving_stages(lost_stage)
        if pipe.enabled:
            # S=1 still runs the schedule's local path (microbatched),
            # so the plan stays enabled with the stage count shrunk
            new_pipe = dataclasses.replace(pipe, n_stages=s_new, n_micro=0)
            self.pipe_mesh = self._shrink_pipe_mesh(s_new, lost_stage)
            self.run = dataclasses.replace(self.run, pipeline=new_pipe)
        log.warning("elastic: lost stage %d -> replanning for %d stage(s)",
                    lost_stage, s_new)
        model = build_model(self.run, mesh=self.mesh,
                            pipe_mesh=self.pipe_mesh)
        if model.pipeline_report is not None:
            from repro.core.policy import summarize
            log.info("elastic replan: %s", summarize(model.pipeline_report))

        restored = self.mgr.restore_latest()
        if restored is None:
            log.warning("elastic: no validating checkpoint — restarting "
                        "from initialization")
            state, start_step = init_state(model, tc), 0
        else:
            start_step, payload = restored
            template = jax.eval_shape(
                lambda: init_state(model, tc, jax.random.PRNGKey(tc.seed)))
            state = to_device(payload["state"], template, model, tc)
            log.info("elastic: restored step %d from %s", start_step,
                     self.mgr.runtime.tier.describe()
                     if self.mgr.runtime else "local files")
        if hasattr(data_iter, "set_state"):
            if restored is not None and (restored[1].get("data") or None):
                data_iter.set_state(restored[1]["data"])
            elif hasattr(data_iter, "get_state"):
                ds = dict(data_iter.get_state())
                ds["step"] = start_step
                data_iter.set_state(ds)
        self.recoveries += 1
        return model, state, start_step
