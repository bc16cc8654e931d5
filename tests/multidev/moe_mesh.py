import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.configs.base import MeshPlan, ModelConfig, MemoryPlan
from repro.parallel.sharding import ShardingPlanner
from repro.models.moe import moe_init, moe_specs, moe_block, _moe_local, use_ep
from repro.models.layers import ModelContext

cfg = ModelConfig(name="t", family="moe", num_layers=1, d_model=32, num_heads=4,
                  num_kv_heads=2, d_ff=64, vocab_size=128, num_experts=4, top_k=2,
                  shared_experts=1, capacity_factor=2.0)
key = jax.random.PRNGKey(0)
params = moe_init(key, cfg, jnp.float32)
B, S, D = 8, 16, 32
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D)) * 0.5

# dense reference: loop over experts, full capacity (cf high enough -> no drops)
def dense_ref(params, x):
    x2d = x.reshape(-1, D)
    logits = x2d @ params["router"]
    probs = jax.nn.softmax(logits, -1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    out = jnp.zeros_like(x2d)
    for e in range(cfg.num_experts):
        h = jax.nn.silu(x2d @ params["w1"][e]) * (x2d @ params["w3"][e])
        ye = h @ params["w2"][e]
        w_e = jnp.where(top_i == e, top_p, 0.0).sum(-1)
        out = out + ye * w_e[:, None]
    h = jax.nn.silu(x2d @ params["shared_w1"]) * (x2d @ params["shared_w3"])
    out = out + h @ params["shared_w2"]
    return out.reshape(x.shape)

ref = dense_ref(params, x)

# 1) local path (no mesh)
plan1 = MeshPlan((1,), ("data",))
ctx1 = ModelContext(cfg=cfg, planner=ShardingPlanner(plan1), memory=MemoryPlan(), mesh=None)
out1, aux1 = moe_block(params, ctx1, x)
np.testing.assert_allclose(np.asarray(out1), np.asarray(ref), rtol=1e-4, atol=1e-5)
print("local MoE == dense ref OK, aux:", float(aux1))

# 2) mesh path, EP (E=4 % tp=4... use mesh (2,4): E%4==0 -> EP)
mesh = make_mesh((2, 4), ("data", "model"))
plan = MeshPlan((2, 4), ("data", "model"))
planner = ShardingPlanner(plan)
print("use_ep:", use_ep(cfg, planner))
ctx = ModelContext(cfg=cfg, planner=planner, memory=MemoryPlan(), mesh=mesh)
pspecs = moe_specs(cfg, planner)
params_sharded = jax.tree.map(lambda w, s: jax.device_put(w, NamedSharding(mesh, s)), params, pspecs)
xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
with mesh:
    out2, aux2 = jax.jit(lambda p, x: moe_block(p, ctx, x))(params_sharded, xs)
np.testing.assert_allclose(np.asarray(out2), np.asarray(ref), rtol=1e-4, atol=1e-5)
print("EP shard_map MoE == dense ref OK, aux:", float(aux2))

# 3) TP-in-expert: experts=3 not divisible by 4
cfg3 = ModelConfig(name="t3", family="moe", num_layers=1, d_model=32, num_heads=4,
                   num_kv_heads=2, d_ff=64, vocab_size=128, num_experts=3, top_k=2,
                   shared_experts=0, capacity_factor=2.0)
params3 = moe_init(jax.random.PRNGKey(2), cfg3, jnp.float32)
def dense_ref3(params, x):
    x2d = x.reshape(-1, D)
    probs = jax.nn.softmax(x2d @ params["router"], -1)
    top_p, top_i = jax.lax.top_k(probs, cfg3.top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    out = jnp.zeros_like(x2d)
    for e in range(3):
        h = jax.nn.silu(x2d @ params["w1"][e]) * (x2d @ params["w3"][e])
        out = out + (h @ params["w2"][e]) * jnp.where(top_i == e, top_p, 0.0).sum(-1)[:, None]
    return out.reshape(x.shape)
ref3 = dense_ref3(params3, x)
ctx3 = ModelContext(cfg=cfg3, planner=planner, memory=MemoryPlan(), mesh=mesh)
ps3 = jax.tree.map(lambda w, s: jax.device_put(w, NamedSharding(mesh, s)), params3, moe_specs(cfg3, planner))
with mesh:
    out3, aux3 = jax.jit(lambda p, x: moe_block(p, ctx3, x))(ps3, xs)
np.testing.assert_allclose(np.asarray(out3), np.asarray(ref3), rtol=1e-4, atol=1e-5)
print("TP-in-expert MoE == dense ref OK")

# 4) gradients flow
def loss(p, x):
    o, aux = moe_block(p, ctx, x)
    return jnp.sum(o**2) + 0.01 * aux
with mesh:
    g = jax.jit(jax.grad(loss))(params_sharded, xs)
gref = jax.grad(lambda p, x: jnp.sum(dense_ref(p, x)**2) + 0.01*0)(params, x)  # aux grad small, test router separately
for k in ["w1","w2","w3","shared_w1"]:
    a, b = np.asarray(g[k]), np.asarray(jax.grad(lambda p,x: jnp.sum(dense_ref(p,x)**2))(params, x)[k])
    np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)
print("MoE gradients == dense ref OK")
