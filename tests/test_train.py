"""Training substrate: convergence, resume, 8-bit Adam, grad accumulation,
the step's named scopes and the tier's per-step byte counter."""
import collections
import dataclasses
import re
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
from repro.train.loop import jit_train_step, make_train_step, train
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


# ---------------------------------------------------------------------------
# the step's named scopes and the memory tier's per-step byte counter
SCOPES = ("attention_core", "mlp", "tier.recompute", "loss", "optimizer",
          "layers")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _host_step(B=2, S=16, grad_accum=1):
    tc = TrainConfig(total_steps=4, warmup_steps=0, grad_accum=grad_accum)
    run = RunConfig(model=CFG, shape=ShapeConfig("t", S, B, "train"),
                    mesh=PLAN1, memory=MemoryPlan(policy="host"), train=tc)
    model = build_model(run)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                CFG.vocab_size)
    batch = {"tokens": tokens, "labels": tokens,
             "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S))}
    return model, tc, jit_train_step(model, tc), batch


def _scopes(op_name):
    """Scope names on an op_name path, transforms (``jvp(...)``) taken
    off."""
    return [re.sub(r"^(?:\w+\()+|\)+$", "", seg)
            for seg in op_name.split("/")]


def _pre_fusion_ops(hlo, root="jit(train_step)"):
    """(opcode, op_name) of every instruction of a lowered module.  XLA
    keeps a called computation's op_names relative to its caller: each is
    made whole by putting the caller's path in front."""
    instr = re.compile(r"^\s+(?:ROOT )?%?[\w.-]+ = .*? ([a-z][\w-]*)\(")
    callee = re.compile(r"(?:to_apply|body|condition|calls)=%?([\w.-]+)")
    comps, caller, comp = {}, {}, None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[line.startswith("ENTRY")].lstrip("%")
            comps[comp] = []
            continue
        m = instr.match(line)
        if m is None or comp is None:
            continue
        op = OP_NAME.search(line)
        name = op.group(1) if op else ""
        comps[comp].append((m.group(1), name))
        for c in callee.findall(line):
            caller[c] = (comp, name)

    def whole(c, name):
        if name.startswith(root) or c not in caller:
            return name
        prefix = whole(*caller[c])
        return f"{prefix}/{name}" if name else prefix

    return [(opc, whole(c, n)) for c in comps for opc, n in comps[c]]


@pytest.fixture(scope="module")
def host_step_hlo():
    model, tc, step, batch = _host_step()
    lowered = step.lower(jax.eval_shape(lambda: init_state(model, tc)),
                         batch)
    return (lowered.as_text(dialect="hlo", debug_info=True),
            lowered.compile().as_text())


@pytest.mark.parametrize("scope", SCOPES)
def test_step_ops_carry_their_scope(host_step_hlo, scope):
    pre, post = host_step_hlo
    assert any(scope in _scopes(n) for n in OP_NAME.findall(post))
    if scope != "tier.recompute":
        return
    ops = _pre_fusion_ops(pre)
    under = [n for _, n in ops if "tier.recompute" in _scopes(n)]
    # the re-run forward only: the backward's own ops stay outside it
    assert under and not any(
        "transpose(" in n.split("tier.recompute", 1)[1] for n in under)

    def einsums(names):
        return collections.Counter(_scopes(n)[-2] for n in names)

    dots = [n for opc, n in ops if opc == "dot"]
    recompute = einsums(n for n in dots if "tier.recompute" in _scopes(n))
    forward = einsums(n for n in dots if "jvp(layers)" in n.split("/")
                      and "tier.recompute" not in _scopes(n))
    # one forward of the layer body, less the MLP's down projection: its
    # output is the layer's, which the backward does not need
    assert recompute == forward - collections.Counter({"bsf,fd->bsd": 1})


@pytest.mark.parametrize("runs,grad_accum", [(0, 1), (1, 1), (3, 1),
                                             (1, 2)])
def test_tier_counter_counts_executed_steps(runs, grad_accum):
    B, S = 2, 16
    model, tc, step, batch = _host_step(B, S, grad_accum)
    rt = model.runtime
    state = init_state(model, tc)
    step.lower(state, batch)               # traced, not run
    jax.clear_caches()
    step.lower(state, batch)               # a second trace replaces the
    assert rt.step_traces == 2             # record, never adds to it
    per = CFG.num_layers * B * S * CFG.d_model * jnp.dtype(CFG.dtype).itemsize
    rep = rt.traffic_report()
    for d in ("stash", "fetch"):
        assert rep[d]["per_step"] == per
        assert rep[d]["calls"] == 0 and rep[d]["wire_bytes"] == 0
    for _ in range(runs):
        state, _ = step(state, batch)
    rep = rt.traffic_report()
    for d in ("stash", "fetch"):
        assert rep[d]["per_step"] == per
        assert rep[d]["wire_bytes"] == rep[d]["raw_bytes"] == runs * per
        assert rep[d]["calls"] == runs * CFG.num_layers * grad_accum


# ---------------------------------------------------------------------------
# which attention the training step takes
def _loss_grads_paths(B=2, S=16):
    from repro.kernels import ops
    model, _, _, batch = _host_step(B, S)
    params = model.init(jax.random.PRNGKey(0))
    before = ops.attention_paths()
    (loss, _), grads = jax.jit(jax.value_and_grad(model.loss_fn,
                                                  has_aux=True))(params,
                                                                 batch)
    after = ops.attention_paths()
    return loss, grads, {k: after[k] - before[k] for k in after}


def test_train_attention_path_follows_the_backend(monkeypatch):
    """On the CPU every traced training attention call takes the XLA
    blockwise twin.  With the backend check reading TPU (the kernels run
    interpreted) every one takes the Pallas pair instead, the host tier's
    recompute included, and loss and gradients equal the twin's."""
    from repro.kernels import ops
    loss_x, grads_x, paths_x = _loss_grads_paths()
    assert paths_x["pallas"] == 0 and paths_x["xla"] > 0
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    loss_p, grads_p, paths_p = _loss_grads_paths()
    assert paths_p == {"pallas": paths_x["xla"], "xla": 0}
    np.testing.assert_allclose(float(loss_p), float(loss_x), rtol=1e-3)
    for a, b in zip(jax.tree.leaves(grads_p), jax.tree.leaves(grads_x)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.max(np.abs(a - b)) <= 2e-2 * max(np.abs(b).max(), 1e-6)
