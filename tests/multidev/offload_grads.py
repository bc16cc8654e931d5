import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.configs.base import MeshPlan, MemoryPlan
from repro.parallel.sharding import ShardingPlanner
from repro.core.offload import maybe_offload

mesh = make_mesh((4, 2), ("data", "model"))
plan = MeshPlan((4, 2), ("data", "model"))
planner = ShardingPlanner(plan)

def layer(params, x, pos):
    h = jnp.einsum("bsd,df->bsf", x, params["w1"])
    h = jax.nn.silu(h) + pos.astype(h.dtype)[None, :, None] * 0.0
    return x + jnp.einsum("bsf,fd->bsd", h, params["w2"])

key = jax.random.PRNGKey(0)
B, S, D, F = 8, 16, 32, 64
params = {"w1": jax.random.normal(key, (D, F)) * 0.1,
          "w2": jax.random.normal(key, (F, D)) * 0.1}
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D))
pos = jnp.arange(S, dtype=jnp.int32)
cs = P("data", None, None)

for policy, compress in [("none","none"), ("mcdla","none"), ("mcdla","fp8"), ("auto","none"), ("host","none")]:
    for placement in (["bw_aware","local"] if policy=="mcdla" else ["bw_aware"]):
        mem = MemoryPlan(policy=policy, placement=placement, compress=compress)
        f = maybe_offload(layer, planner, mesh, mem, compute_spec=cs)
        def loss(p, x):
            return jnp.sum(f(p, x, pos) ** 2)
        with jax.set_mesh(mesh) if hasattr(jax, 'set_mesh') else mesh:
            lj = jax.jit(loss, in_shardings=(NamedSharding(mesh, P()), NamedSharding(mesh, cs)))
            v = lj(params, x)
            g = jax.jit(jax.grad(loss, argnums=(0,1)), in_shardings=(NamedSharding(mesh, P()), NamedSharding(mesh, cs)))(params, x)
        # reference
        vref = jnp.sum(layer(params, x, pos) ** 2)
        gref = jax.grad(lambda p, x: jnp.sum(layer(p, x, pos)**2), argnums=(0,1))(params, x)
        tol = 2e-1 if compress == "fp8" else 1e-5
        if compress == "fp8":
            continue  # fp8 grads validated against the dequantized oracle in offload_fp8.py
        np.testing.assert_allclose(v, vref, rtol=1e-5)
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol, atol=tol)
        print(f"OK policy={policy} placement={placement} compress={compress} loss={float(v):.4f}")
