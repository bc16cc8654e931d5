"""Pipeline schedule parity on a toy stack, S = number of host devices.

Pins, for gpipe and 1f1b at several (including uneven) microbatch counts:
  * forward parity: pipelined == sequential through all stages,
  * loss/grad parity: bit-identical loss and near-exact grads vs the
    sequential per-microbatch reference,
  * a 3-step SGD loss curve identical to the sequential baseline,
  * nonzero act_stash/act_fetch traffic attributed to the stage tier
    (1f1b routes stage inputs through PipelineStageTier hooks),
  * the real-model path: smollm-smoke loss via forward_train_pipelined ==
    the unpipelined baseline (run under 2 devices; needs n_groups % S == 0).

Respects an XLA_FLAGS set by the runner (tests/conftest.py run_multidev
launches this with 2 and with 4 devices).
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.configs.base import MemoryPlan, MeshPlan
from repro.core.runtime import MemoryRuntime
from repro.core.tiers import build_stage_tier
from repro.parallel.pipeline import get_schedule, make_pipelined
from repro.parallel.sharding import ShardingPlanner

S = len(jax.devices())
mesh = make_mesh((S,), ("pod",))
L_per = 2
key = jax.random.PRNGKey(0)
W = jax.random.normal(key, (S, L_per, 8, 8)) * 0.3


def stage_fn(params, x):
    for i in range(L_per):
        x = jnp.tanh(x @ params[i])
    return x


# --- 1. legacy API forward parity (gpipe default) --------------------------
x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
pipe = make_pipelined(mesh, stage_fn, n_micro=4, axis_name="pod",
                      stage_param_spec=P("pod"))
with mesh:
    y = jax.jit(pipe)(W, x)
ref = x
for s in range(S):
    ref = stage_fn(W[s], ref)
np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                           rtol=1e-5, atol=1e-5)
print("pipeline == sequential OK")

# --- 2. schedule loss/grad parity (tree inputs, uneven M) ------------------
plan = MeshPlan((S,), ("pod",))
planner = ShardingPlanner(plan)
memory = MemoryPlan(policy="mcdla")
rt = MemoryRuntime(plan, memory, None, planner=planner,
                   tier=build_stage_tier(memory, planner, None, n_stages=S))

B = 24
xb = jax.random.normal(jax.random.PRNGKey(2), (B, 8))
pos = jnp.arange(B, dtype=jnp.int32)
tgt = jax.random.normal(jax.random.PRNGKey(3), (B, 8))


def stage_tree_fn(params, t):
    return {"h": stage_fn(params, t["h"]), "pos": t["pos"]}


def ref_loss(W, xb, M):
    mb = B // M
    hs = []
    for m in range(M):                       # sequential per-microbatch ref
        h = xb[m * mb:(m + 1) * mb]
        for s in range(S):
            h = stage_fn(W[s], h)
        hs.append(h)
    return jnp.mean((jnp.concatenate(hs) - tgt) ** 2)


for name in ("gpipe", "1f1b"):
    for M in (2, 3, 4, 6):                   # includes M < S and M % S != 0
        sched = get_schedule(name, runtime=rt)
        pipe = make_pipelined(mesh, stage_tree_fn, n_micro=M, schedule=sched)

        def loss(W):
            out = pipe(W, {"h": xb, "pos": pos})
            return jnp.mean((out["h"] - tgt) ** 2)

        l, g = jax.jit(jax.value_and_grad(loss))(W)
        lr, gr = jax.jit(jax.value_and_grad(
            lambda W: ref_loss(W, xb, M)))(W)
        assert float(l) == float(lr), (name, M, float(l), float(lr))
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=1e-5, atol=1e-7)
print("schedule loss parity OK")

# --- 3. loss curves: 3 SGD steps, pipelined vs sequential ------------------
for name in ("gpipe", "1f1b"):
    M = S
    sched = get_schedule(name, runtime=rt)
    pipe = make_pipelined(mesh, stage_tree_fn, n_micro=M, schedule=sched)

    def loss_p(W):
        return jnp.mean((pipe(W, {"h": xb, "pos": pos})["h"] - tgt) ** 2)

    step_p = jax.jit(lambda W: (loss_p(W), W - 0.1 * jax.grad(loss_p)(W)))
    step_r = jax.jit(lambda W: (ref_loss(W, xb, M),
                                W - 0.1 * jax.grad(
                                    lambda w: ref_loss(w, xb, M))(W)))
    Wp = Wr = W
    for _ in range(3):
        lp, Wp = step_p(Wp)
        lr, Wr = step_r(Wr)
        assert float(lp) == float(lr), (name, float(lp), float(lr))
print("loss curve parity OK")

# --- 4. stage-tier traffic metered (1f1b hooks) ----------------------------
rep = rt.traffic_report()
assert "pipeline_stage" in rep["tier"], rep["tier"]
assert rep["act_stash"]["calls"] > 0, rep
assert rep["act_fetch"]["calls"] > 0, rep
assert rep["act_stash"]["wire_bytes"] > 0, rep
print("stage tier traffic OK")

# --- 5. real model: pipelined smollm == unpipelined baseline ---------------
from repro.configs import ARCHS, PipelinePlan, RunConfig, TrainConfig
from repro.configs.base import ShapeConfig
from repro.models.model import build_model

cfg = ARCHS["smollm-135m"].reduced(dtype="float32", num_layers=2 * S)
plan1 = MeshPlan((1,), ("data",))
shape = ShapeConfig("t", 32, 4, "train")
tc = TrainConfig()
batch = {
    "tokens": jax.random.randint(jax.random.PRNGKey(4), (4, 32), 0,
                                 cfg.vocab_size),
    "labels": jax.random.randint(jax.random.PRNGKey(5), (4, 32), 0,
                                 cfg.vocab_size),
    "positions": jnp.broadcast_to(jnp.arange(32)[None], (4, 32)),
}
base = build_model(RunConfig(model=cfg, shape=shape, mesh=plan1,
                             memory=memory, train=tc))
params = base.init(jax.random.PRNGKey(0))
l_base, _ = jax.jit(base.loss_fn)(params, batch)
for name in ("gpipe", "1f1b"):
    m = build_model(
        RunConfig(model=cfg, shape=shape, mesh=plan1, memory=memory,
                  train=tc,
                  pipeline=PipelinePlan(enabled=True, schedule=name,
                                        n_micro=2, n_stages=S)),
        mesh=None, pipe_mesh=mesh)
    l_pipe, _ = jax.jit(m.loss_fn)(params, batch)
    np.testing.assert_allclose(float(l_pipe), float(l_base), rtol=1e-5)
print("model pipeline parity OK")
