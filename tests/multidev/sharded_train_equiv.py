"""Distributed train step (mcdla policy, 8 devices) must match the
single-device oracle bitwise-ish (fp32 tolerance)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.configs import ARCHS, MemoryPlan, MeshPlan, RunConfig, TrainConfig
from repro.configs.base import ShapeConfig
from repro.models.model import build_model
from repro.train.loop import make_train_step
from repro.train.train_state import init_state, state_shardings

cfg = dataclasses.replace(ARCHS["smollm-135m"].reduced(), dtype="float32",
                          num_heads=4, num_kv_heads=2, d_model=128)
tc = TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=10)
B, S = 8, 32
shape = ShapeConfig("t", S, B, "train")
batch = {
    "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size),
    "labels": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size),
    "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S)),
}

# single-device oracle
run1 = RunConfig(model=cfg, shape=shape, mesh=MeshPlan((1,), ("data",)),
                 memory=MemoryPlan(policy="none"), train=tc)
m1 = build_model(run1)
s1 = init_state(m1, tc)
step1 = make_train_step(m1, tc)
s1b, metrics1 = jax.jit(step1)(s1, batch)

# 8-device mcdla
mesh = make_mesh((4, 2), ("data", "model"))
run8 = RunConfig(model=cfg, shape=shape, mesh=MeshPlan((4, 2), ("data", "model")),
                 memory=MemoryPlan(policy="mcdla", placement="bw_aware"), train=tc)
m8 = build_model(run8, mesh=mesh)
s8 = init_state(m8, tc)     # same seed -> identical init
sh = state_shardings(m8, tc)
with mesh:
    s8 = jax.tree.map(lambda x, s: jax.device_put(x, s), s8, sh)
    bsh = {k: NamedSharding(mesh, m8.batch_specs(shape)[k]) for k in batch}
    batch8 = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
    step8 = make_train_step(m8, tc)
    s8b, metrics8 = jax.jit(step8, in_shardings=(sh, bsh), out_shardings=(sh, None))(s8, batch8)

np.testing.assert_allclose(float(metrics1["loss"]), float(metrics8["loss"]), rtol=1e-5)
for a, b in zip(jax.tree.leaves(s1b["params"]), jax.tree.leaves(s8b["params"])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-4)
print("sharded mcdla train step == single-device oracle OK")

# the training driver builds the state in its sharded layout, so the jitted
# step sees the shardings it was compiled for and traces once (the runtime
# records one step's transfers per trace and counts them per run)
import tempfile
from repro.data.pipeline import SyntheticLM
from repro.train.loop import train
m8t = build_model(run8, mesh=mesh)
with tempfile.TemporaryDirectory() as ckpt_dir:
    tc2 = dataclasses.replace(tc, total_steps=2, checkpoint_every=100,
                              checkpoint_dir=ckpt_dir)
    state, _ = train(m8t, tc2, iter(SyntheticLM(cfg, batch=B, seq=S)))
rep = m8t.runtime.traffic_report()
assert rep["step_traces"] == 1, rep
assert rep["stash"]["calls"] == tc2.total_steps * cfg.num_layers, rep
for leaf, want in zip(jax.tree.leaves(state), jax.tree.leaves(sh)):
    assert leaf.sharding.is_equivalent_to(want, leaf.ndim), (leaf.sharding, want)
print("sharded train() traces its step once OK")
