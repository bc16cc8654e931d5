"""Kernel microbenchmarks (CPU wall-clock for regression tracking; the TPU
roofline terms come from launch/roofline.py, not from these timings)."""
from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp

Row = Tuple[str, float, str]


def _time(fn, *args, reps: int = 3) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def kernel_microbench() -> List[Row]:
    from repro.kernels import ops, ref
    rows: List[Row] = []
    key = jax.random.PRNGKey(0)

    x = jax.random.normal(key, (256, 512), jnp.float32)
    w = jax.random.normal(key, (512, 256), jnp.float32)
    rows.append(("micro.gemm_os_256x512x256.us",
                 round(_time(lambda a, b: ops.gemm(a, b, bm=128, bn=128,
                                                   bk=128), x, w), 1),
                 "interpret mode (CPU)"))
    rows.append(("micro.gemm_xla_ref.us",
                 round(_time(jax.jit(ref.gemm_ref), x, w), 1), ""))

    q = jax.random.normal(key, (1, 256, 4, 64))
    k = jax.random.normal(key, (1, 256, 2, 64))
    v = jax.random.normal(key, (1, 256, 2, 64))
    rows.append(("micro.flash_fwd_256.us",
                 round(_time(lambda *a: ops.flash_attention(*a, True, 0),
                             q, k, v), 1), "interpret mode"))

    xx = jax.random.normal(key, (2, 128, 32)) * 0.5
    a = -jax.nn.softplus(jax.random.normal(key, (2, 128)))
    B = jax.random.normal(key, (2, 128, 16)) * 0.3
    C = jax.random.normal(key, (2, 128, 16)) * 0.3
    rows.append(("micro.ssd_scan_128.us",
                 round(_time(lambda *t: ops.ssd(*t, chunk=32),
                             xx, a, B, C), 1), "interpret mode"))

    big = jax.random.normal(key, (1024, 256))
    rows.append(("micro.fp8_pack_1024x256.us",
                 round(_time(lambda t: ops.fp8_pack(t, block_rows=128)[0],
                             big), 1), "interpret mode"))
    return rows


def tier_microbench() -> List[Row]:
    """stash/fetch round-trip through each registered memory tier
    (single-device CPU wall-clock; the constraint collectives are no-ops
    off-mesh, so this times the data path: codec + copies)."""
    from repro.configs.base import MemoryPlan, MeshPlan
    from repro.core.runtime import MemoryRuntime
    from repro.core.tiers import HostTier, TransferHints

    plan = MeshPlan((1,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 1024), jnp.float32)
    hints = TransferHints(dtype=jnp.float32)
    rows: List[Row] = []
    for policy, compress in (("none", "none"), ("mcdla", "none"),
                             ("mcdla", "fp8"), ("host", "none"),
                             ("host", "fp8")):
        memory = MemoryPlan(policy=policy, compress=compress)
        runtime = MemoryRuntime(plan, memory)
        tier = runtime.tier

        @jax.jit
        def roundtrip(t, _tier=tier):
            return _tier.fetch(_tier.stash(t, hints), hints)

        note = "stash+fetch round-trip (CPU)"
        inner = tier
        while hasattr(inner, "inner"):
            inner = inner.inner
        if isinstance(inner, HostTier) and not HostTier.places_host_memory():
            # don't let a no-op masquerade as a transfer in regression CSVs
            note = "no-op: host arrays stay in place here (codec only)"
        rows.append((f"micro.tier_{tier.describe()}_256x1024.us",
                     round(_time(roundtrip, x), 1), note))
    return rows
