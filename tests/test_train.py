"""Training substrate: convergence, resume, 8-bit Adam, grad accumulation."""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, MemoryPlan, MeshPlan, RunConfig, TrainConfig
from repro.configs.base import ShapeConfig
from repro.data.pipeline import SyntheticLM
from repro.models.model import build_model
from repro.train.fault import FaultHandler
from repro.train.loop import make_train_step, train
from repro.train.optimizer import (apply_adamw, init_opt_state, lr_schedule,
                                   opt_state_specs)
from repro.train.train_state import init_state

CFG = ARCHS["smollm-135m"].reduced()
PLAN1 = MeshPlan((1,), ("data",))


def _run(tc, memory=None, steps=None):
    run = RunConfig(model=CFG, shape=ShapeConfig("t", 64, 4, "train"),
                    mesh=PLAN1, memory=memory or MemoryPlan(policy="none"),
                    train=tc)
    m = build_model(run)
    data = SyntheticLM(CFG, batch=4, seq=64, seed=0)
    return train(m, tc, iter(data),
                 fault_handler=FaultHandler(install_signals=False))


def test_loss_decreases():
    with tempfile.TemporaryDirectory() as d:
        tc = TrainConfig(total_steps=30, warmup_steps=5, learning_rate=1e-2,
                         checkpoint_every=100, log_every=100,
                         checkpoint_dir=d)
        _, metrics = _run(tc)
        assert float(metrics["loss"]) < 6.0        # from ~6.3 at init


def test_resume_from_checkpoint_continues():
    with tempfile.TemporaryDirectory() as d:
        tc = TrainConfig(total_steps=10, warmup_steps=2, learning_rate=1e-2,
                         checkpoint_every=10, log_every=100,
                         checkpoint_dir=d)
        _, m1 = _run(tc)
        tc2 = dataclasses.replace(tc, total_steps=20)
        _, m2 = _run(tc2)
        assert float(m2["loss"]) < float(m1["loss"]) + 0.05


def test_8bit_adam_tracks_fp32():
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        tc = TrainConfig(total_steps=25, warmup_steps=5, learning_rate=1e-2,
                         checkpoint_every=100, log_every=100,
                         checkpoint_dir=d1)
        _, m32 = _run(tc)
        tc8 = dataclasses.replace(tc, checkpoint_dir=d2)
        _, m8 = _run(tc8, memory=MemoryPlan(policy="none", opt_state_bits=8))
        assert abs(float(m8["loss"]) - float(m32["loss"])) < 0.15


def test_grad_accum_equivalence():
    """accum=2 over batch 8 must match accum=1 over the same batch (mean of
    microbatch grads == full-batch grad when token counts are equal)."""
    cfg = dataclasses.replace(CFG, dtype="float32")
    run = RunConfig(model=cfg, shape=ShapeConfig("t", 32, 8, "train"),
                    mesh=PLAN1, memory=MemoryPlan(policy="none"),
                    train=TrainConfig())
    m = build_model(run)
    tc1 = TrainConfig(grad_accum=1, grad_clip=0.0)
    tc2 = TrainConfig(grad_accum=2, grad_clip=0.0)
    B, S = 8, 32
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(0), (B, S), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                     cfg.vocab_size),
        "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S)),
    }
    s1 = init_state(m, tc1)
    s2 = jax.tree.map(lambda x: x, s1)
    out1, _ = jax.jit(make_train_step(m, tc1))(s1, batch)
    out2, _ = jax.jit(make_train_step(m, tc2))(s2, batch)
    for a, b in zip(jax.tree.leaves(out1["params"]),
                    jax.tree.leaves(out2["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_grad_accum_metrics_accumulated():
    """Regression: the accumulated path used to hardcode aux_loss=0 and
    tokens=0, discarding per-microbatch metrics — it must now report the
    same token count and aux loss as the unaccumulated step."""
    cfg = dataclasses.replace(CFG, dtype="float32")
    run = RunConfig(model=cfg, shape=ShapeConfig("t", 32, 8, "train"),
                    mesh=PLAN1, memory=MemoryPlan(policy="none"),
                    train=TrainConfig())
    m = build_model(run)
    B, S = 8, 32
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(0), (B, S), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                     cfg.vocab_size),
        "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S)),
    }
    tc1 = TrainConfig(grad_accum=1, grad_clip=0.0)
    tc2 = TrainConfig(grad_accum=2, grad_clip=0.0)
    _, m1 = jax.jit(make_train_step(m, tc1))(init_state(m, tc1), batch)
    _, m2 = jax.jit(make_train_step(m, tc2))(init_state(m, tc2), batch)
    assert float(m2["tokens"]) == float(m1["tokens"]) == B * S
    assert float(m2["aux_loss"]) == pytest.approx(float(m1["aux_loss"]),
                                                  abs=1e-5)
    assert float(m2["loss"]) == pytest.approx(float(m1["loss"]), rel=1e-4)


def test_lr_schedule_shape():
    tc = TrainConfig(total_steps=100, warmup_steps=10, learning_rate=1e-3)
    lrs = [float(lr_schedule(tc, jnp.int32(s))) for s in (1, 5, 10, 50, 100)]
    assert lrs[0] < lrs[1] < lrs[2]                 # warmup
    assert lrs[2] > lrs[3] > lrs[4]                 # cosine decay
    assert lrs[4] >= 0.09 * 1e-3                    # floor ~10%


def test_opt_state_specs_structure():
    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}
    st = init_opt_state(params, bits=8)
    from jax.sharding import PartitionSpec as P
    specs = opt_state_specs({"w": P("data", "model"), "b": P(None)}, bits=8)
    assert set(specs["m"]["w"]) == {"q", "scale"}
    assert set(specs["v"]["w"]) == {"q", "lo", "hi"}
    assert jax.tree.structure(st["m"], is_leaf=lambda x: hasattr(x, "shape")) \
        is not None


def test_weight_decay_skips_scalars_and_clip():
    params = {"w": jnp.ones((4, 4)), "scale": jnp.ones(())}
    grads = {"w": jnp.full((4, 4), 100.0), "scale": jnp.zeros(())}
    st = init_opt_state(params)
    tc = TrainConfig(grad_clip=1.0, learning_rate=1e-2)
    new_p, new_st, metrics = apply_adamw(params, grads, st, tc)
    assert float(metrics["grad_norm"]) == pytest.approx(400.0)
    assert bool(jnp.all(jnp.isfinite(new_p["w"])))
    assert float(new_p["scale"]) == pytest.approx(1.0)   # zero grad, no decay


def test_launch_mesh_rule_and_auto_axes():
    """Both launchers share one mesh rule: no mesh on one device; every
    mesh the repo builds has Auto axes (the sharding constraints and the
    FSDP embedding gather rely on GSPMD propagation)."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_mesh, mesh_for_devices

    if len(jax.devices()) == 1:
        assert mesh_for_devices() == (None, PLAN1)
    mesh = make_mesh((1,), ("data",))
    assert mesh.axis_types == (AxisType.Auto,)
    pinned = make_mesh((1,), ("pod",), devices=jax.devices()[:1])
    assert pinned.axis_types == (AxisType.Auto,)


def test_step_time_waits_for_the_device(monkeypatch, tmp_path):
    """The step time the loop logs (and the straggler monitor sees) ends
    in block_until_ready on the step's outputs, not at the enqueue."""
    waited = []
    block = jax.block_until_ready

    def spy(x):
        waited.append(sorted(x))
        return block(x)

    monkeypatch.setattr(jax, "block_until_ready", spy)
    tc = TrainConfig(total_steps=2, warmup_steps=1, log_every=1,
                     checkpoint_every=100,
                     checkpoint_dir=str(tmp_path))
    run = RunConfig(model=CFG, shape=ShapeConfig("t", 16, 2, "train"),
                    mesh=PLAN1, memory=MemoryPlan(policy="none"), train=tc)
    train(build_model(run), tc, iter(SyntheticLM(CFG, 2, 16)))
    assert len(waited) == 2 and all("loss" in keys for keys in waited)
