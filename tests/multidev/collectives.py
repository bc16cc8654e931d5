import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.parallel.collectives import ring_all_reduce, compressed_all_reduce

mesh = make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 32))

def f(xl):
    return ring_all_reduce(xl[0], "data")
out = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False)(x)
np.testing.assert_allclose(np.asarray(out), np.asarray(x.sum(0)), rtol=1e-5, atol=1e-5)
print("ring_all_reduce == sum OK")

def g(xl, el):
    m, e = compressed_all_reduce(xl[0], el[0], "data")
    return m, e
err = jnp.zeros((8, 16, 32))
m, e = jax.shard_map(g, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=(P(), P("data")), check_vma=False)(x, err)
ref = x.mean(0)
rel = float(jnp.linalg.norm(m - ref) / jnp.linalg.norm(ref))
print(f"compressed_all_reduce rel err: {rel:.4f}")
assert rel < 0.02
# error feedback: the residual equals corrected - sent
print("compressed OK")
