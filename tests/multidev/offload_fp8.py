import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.configs.base import MeshPlan, MemoryPlan
from repro.parallel.sharding import ShardingPlanner
from repro.core.offload import maybe_offload
from repro.core.compress import fp8_compress, fp8_decompress

mesh = make_mesh((4, 2), ("data", "model"))
planner = ShardingPlanner(MeshPlan((4, 2), ("data", "model")))

def layer(params, x, pos):
    h = jnp.einsum("bsd,df->bsf", x, params["w1"])
    h = jax.nn.silu(h)
    return x + jnp.einsum("bsf,fd->bsd", h, params["w2"])

key = jax.random.PRNGKey(0)
B, S, D, F = 8, 16, 32, 64
params = {"w1": jax.random.normal(key, (D, F)) * 0.1,
          "w2": jax.random.normal(key, (F, D)) * 0.1}
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D))
pos = jnp.arange(S, dtype=jnp.int32)
cs = P("data", None, None)
mem = MemoryPlan(policy="mcdla", compress="fp8")
f = maybe_offload(layer, planner, mesh, mem, compute_spec=cs)

def loss(p, x): return jnp.sum(f(p, x, pos) ** 2)
with mesh:
    g = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x)

# oracle: same layer but backward built from dequantized x, forward exact
q, sc = fp8_compress(x)
x_deq = fp8_decompress(q, sc, x.dtype)
y_exact = layer(params, x, pos)
_, vjp = jax.vjp(lambda p, xx: layer(p, xx, pos), params, x_deq)
gref = vjp(2.0 * y_exact)
for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gref)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)
# cosine vs exact grads
gexact = jax.grad(lambda p, x: jnp.sum(layer(p, x, pos)**2), argnums=(0,1))(params, x)
for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gexact)):
    a, b = np.asarray(a).ravel(), np.asarray(b).ravel()
    cos = (a @ b) / (np.linalg.norm(a) * np.linalg.norm(b))
    print("cos:", cos)
    assert cos > 0.99
print("fp8 oracle test OK")
