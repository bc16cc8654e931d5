"""Elastic recovery under a seeded chaos schedule, S=2 pipeline stages.

The ISSUE-6 acceptance scenario: a transient step kill at step k, a
corrupted snapshot shard, and a pipeline stage loss at step m.  The run

  * absorbs the kill through ``retry_step`` (same functional step
    recomputed — the loss curve is untouched),
  * checkpoints through the CheckpointTier runtime (sharded, CRC'd,
    ``ckpt_save`` metered),
  * on the stage loss, replans for the surviving stage via the
    ``plan_memory`` sweep (n_micro=0 → planner-chosen), restores from the
    pool with reshard-on-load (``ckpt_load`` metered), rewinds the data
    stream, and continues.

Pinned against an uninterrupted 2-stage run at the same seed:

  * every step computed *before* the stage loss is bit-identical,
  * every step after recovery matches within the repo's pipeline parity
    tolerance (the surviving-stage partition changes the reduction
    order — same math, different fusion; cf. tests/multidev/pipeline.py
    which pins 2-stage vs unpipelined at rtol=1e-5),
  * ``traffic_report`` shows nonzero ckpt_save/ckpt_load wire bytes and
    the save bytes match the manifest accounting.

Run by tests/test_chaos.py::test_elastic_stage_loss via run_multidev.
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")
import glob
import json
import tempfile

import jax
import numpy as np

from repro.launch.mesh import make_mesh
from repro.configs import (ARCHS, MemoryPlan, MeshPlan, PipelinePlan,
                           RunConfig, TrainConfig)
from repro.configs.base import CheckpointPlan, ShapeConfig
from repro.data.pipeline import SyntheticLM
from repro.models.model import build_model
from repro.train.chaos import ChaosMonkey, ChaosSchedule
from repro.train.elastic import ElasticController
from repro.train.fault import FaultHandler
from repro.train.loop import make_manager, train

S = len(jax.devices())
assert S == 2, S
pipe_mesh = make_mesh((S,), ("pod",))

CFG = ARCHS["smollm-135m"].reduced(dtype="float32", num_layers=2 * S)
STEPS = 10
LOSS_STEP = 5            # stage_loss fires before step index 5


def make_run(pipeline):
    return RunConfig(model=CFG, shape=ShapeConfig("t", 32, 4, "train"),
                     mesh=MeshPlan((1,), ("data",)),
                     memory=MemoryPlan(policy="none"),
                     train=TrainConfig(), pipeline=pipeline)


def run(tag, d, chaos_spec=None):
    tc = TrainConfig(total_steps=STEPS, warmup_steps=2, learning_rate=1e-2,
                     checkpoint_every=2, log_every=1, checkpoint_dir=d,
                     seed=0)
    pipe = PipelinePlan(enabled=True, schedule="1f1b", n_stages=S, n_micro=2)
    runcfg = make_run(pipe)
    model = build_model(runcfg, mesh=None, pipe_mesh=pipe_mesh)
    data = SyntheticLM(CFG, batch=4, seq=32, seed=0)

    chaos = elastic = None
    ckpt = CheckpointPlan(enabled=True, tier="host", codec="none", shards=2)
    mgr = None
    if chaos_spec:
        chaos = ChaosMonkey(ChaosSchedule.parse(chaos_spec), seed=0,
                            retries=2, backoff=0.0)
        mgr = make_manager(model, tc, ckpt, chaos)
        elastic = ElasticController(runcfg, mgr, mesh=None,
                                    pipe_mesh=pipe_mesh)
    curve = []
    hooks = {"on_log": lambda step, m: curve.append((step, m["loss"]))}
    state, _ = train(model, tc, data,
                     fault_handler=FaultHandler(install_signals=False),
                     hooks=hooks, ckpt=ckpt, chaos=chaos, elastic=elastic,
                     mgr=mgr)
    return curve, chaos, elastic, mgr


with tempfile.TemporaryDirectory() as d_ref, \
        tempfile.TemporaryDirectory() as d_chaos:
    ref_curve, _, _, _ = run("ref", d_ref)
    spec = f"kill@2,corrupt@3,stage_loss@{LOSS_STEP}:1"
    chaos_curve, chaos, elastic, mgr = run("chaos", d_chaos, spec)

    # every scheduled event actually delivered
    fired = ",".join(chaos.fired)
    assert "kill@2" in fired and "corrupt@" in fired \
        and f"stage_loss@{LOSS_STEP}" in fired, fired
    assert elastic.recoveries == 1
    assert elastic.run.pipeline.n_stages == S - 1

    # ckpt traffic metered on both directions; save bytes == manifest truth
    tr = mgr.runtime.traffic_report()
    assert tr["ckpt_save"]["wire_bytes"] > 0, tr
    assert tr["ckpt_load"]["wire_bytes"] > 0, tr
    manifests = sorted(glob.glob(os.path.join(d_chaos, "step_*",
                                              "manifest.json")))
    meta = json.load(open(manifests[0]))
    # state size is constant, so total metered save bytes must equal the
    # per-commit manifest accounting times the number of commits
    n_commits = tr["ckpt_save"]["calls"] // len(meta["keys"])
    assert tr["ckpt_save"]["wire_bytes"] == meta["bytes"]["wire"] * n_commits, \
        (tr["ckpt_save"], meta["bytes"], n_commits)

    ref = dict(ref_curve)
    # prefix (before the stage loss): bit-identical to the uninterrupted run
    first = {}
    for s, l in chaos_curve:
        first.setdefault(s, l)
    for s in range(1, LOSS_STEP + 1):
        assert first[s] == ref[s], (s, first[s], ref[s])
    # post-recovery (replayed + new steps on the surviving stage): parity
    # within the repo's pipeline tolerance
    final = dict(chaos_curve)
    for s in range(LOSS_STEP, STEPS + 1):
        np.testing.assert_allclose(final[s], ref[s], rtol=1e-4,
                                   err_msg=f"step {s}")
    print("prefix bit-identical:", [round(first[s], 6)
                                    for s in range(1, LOSS_STEP + 1)])
    print("post-recovery parity:", [(round(final[s], 6), round(ref[s], 6))
                                    for s in range(LOSS_STEP, STEPS + 1)])
print("elastic stage-loss recovery OK")
