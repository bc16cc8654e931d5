"""Ahead-of-time compiles for a described TPU v5e chip: the Pallas kernels
of the serving and training paths at real widths, and a small host-tier
train step.

Nothing here runs: the TPU compiler only has to accept each kernel, which
is what interpret mode cannot show (block-shape tiling rules, scalar
stores to VMEM, VMEM limits), and the step's compiled text shows where
the named scopes land.  The topology is described inside a module
fixture, never at import time, so that every xdist worker collects the
same tests and only the worker given this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import offload_pack as op
from repro.kernels import paged_attention as pa

PAGE = 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of these tests
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sd(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# smollm-135m (K=3, G=3, hd=64) and h2o-danube-1.8b (K=8, G=4, hd=80)
@pytest.mark.parametrize("kv_heads,group,hd", [(3, 3, 64), (8, 4, 80)],
                         ids=["smollm", "danube"])
@pytest.mark.parametrize("compressed", [False, True],
                         ids=["raw", "side_pool"])
def test_paged_decode_compiles(one_chip, kv_heads, group, hd, compressed):
    B, pp, P, C = 8, 16, 97, 24
    H = kv_heads * group
    args = [_sd(one_chip, (B, 1, H, hd), jnp.bfloat16),
            _sd(one_chip, (P, PAGE, kv_heads, hd), jnp.bfloat16),
            _sd(one_chip, (P, PAGE, kv_heads, hd), jnp.bfloat16),
            _sd(one_chip, (B, pp), jnp.int32),
            _sd(one_chip, (), jnp.int32)]
    if compressed:
        args += [_sd(one_chip, (C, PAGE, kv_heads, hd), jnp.int8),
                 _sd(one_chip, (C, PAGE, kv_heads, hd), jnp.int8),
                 _sd(one_chip, (C, 1), jnp.float32),
                 _sd(one_chip, (C, 1), jnp.float32)]

        def fn(q, k, v, pm, ix, kq, vq, ks, vs):
            return pa.paged_decode_attention(q, k, v, pm, ix, kq_pool=kq,
                                             vq_pool=vq, k_scale=ks,
                                             v_scale=vs)
    else:
        def fn(q, k, v, pm, ix):
            return pa.paged_decode_attention(q, k, v, pm, ix)
    _compile(fn, *args)


# one page of smollm K/V as the spill path packs it (rows = page * K,
# cols = hd, one block), and a multi-block activation shape
@pytest.mark.parametrize("rows,cols,block_rows",
                         [(PAGE * 3, 64, PAGE * 3), (1024, 576, 128)],
                         ids=["one_page", "multi_block"])
@pytest.mark.parametrize("codec", ["int8", "fp8", "blocksparse"])
def test_pack_unpack_compiles(one_chip, codec, rows, cols, block_rows):
    pack = {"int8": op.int8_pack, "fp8": op.fp8_pack,
            "blocksparse": op.blocksparse_pack}[codec]
    unpack = {"int8": op.int8_unpack, "fp8": op.fp8_unpack,
              "blocksparse": op.blocksparse_unpack}[codec]
    qdtype = jnp.float8_e4m3fn if codec == "fp8" else jnp.int8
    nb = rows // block_rows
    _compile(lambda x: pack(x, block_rows=block_rows),
             _sd(one_chip, (rows, cols), jnp.bfloat16))
    _compile(lambda q, s: unpack(q, s, block_rows=block_rows),
             _sd(one_chip, (rows, cols), qdtype),
             _sd(one_chip, (nb,), jnp.float32))


# smollm-135m (9 query heads over 3 kv heads, hd 64) and h2o-danube-1.8b
# (32 over 8, hd 80) at a 2k context: the training attention's shapes
FLASH_SHAPES = pytest.mark.parametrize("kv_heads,group,hd",
                                       [(3, 3, 64), (8, 4, 80)],
                                       ids=["smollm", "danube"])


def _flash_args(sharding, kv_heads, group, hd, B=2, S=2048):
    H = kv_heads * group
    q = _sd(sharding, (B, H, S, hd), jnp.bfloat16)
    kv = _sd(sharding, (B, kv_heads, S, hd), jnp.bfloat16)
    lse = _sd(sharding, (B, H, 1, S), jnp.float32)
    return q, kv, lse


@FLASH_SHAPES
def test_flash_attention_fwd_compiles(one_chip, kv_heads, group, hd):
    q, kv, _ = _flash_args(one_chip, kv_heads, group, hd)
    _compile(lambda q, k, v: fa.flash_attention_fwd(q, k, v, causal=True,
                                                    save_lse=True),
             q, kv, kv)


@FLASH_SHAPES
def test_flash_attention_bwd_compiles(one_chip, kv_heads, group, hd):
    q, kv, lse = _flash_args(one_chip, kv_heads, group, hd)
    compiled = _compile(
        lambda q, k, v, o, l, do: fa.flash_attention_bwd(q, k, v, o, l, do,
                                                         causal=True),
        q, kv, kv, q, lse, q)
    assert compiled.as_text().count("custom_call_target=\"tpu_custom_call") \
        == 2                                    # the dK/dV and the dQ kernel


def test_host_tier_train_step_scopes(one_chip, monkeypatch):
    """The host tier's transfers come out of the layer scan's residual
    stacking, so they carry the ``layers`` scope (none inside the body);
    the recompute keeps its own scope through fusion; the attention's
    Pallas kernels carry ``attention_core``."""
    from repro.configs import ARCHS, MemoryPlan, MeshPlan, RunConfig, \
        TrainConfig
    from repro.configs.base import ShapeConfig
    from repro.core.tiers import HostTier
    from repro.kernels import ops
    from repro.models.model import build_model
    from repro.train.loop import jit_train_step
    from repro.train.train_state import init_state

    # the tier and the attention decide by the default backend, which is
    # the CPU here: steer both to what they take on one chip
    monkeypatch.setattr(HostTier, "places_host_memory",
                        staticmethod(lambda: True))
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    B, S = 2, 16
    tc = TrainConfig(total_steps=4, warmup_steps=0)
    model = build_model(RunConfig(
        model=ARCHS["smollm-135m"].reduced(),
        shape=ShapeConfig("t", S, B, "train"),
        mesh=MeshPlan((1,), ("data",)), memory=MemoryPlan(policy="host"),
        train=tc))
    state = jax.tree.map(lambda x: _sd(one_chip, x.shape, x.dtype),
                         jax.eval_shape(lambda: init_state(model, tc)))
    batch = {k: _sd(one_chip, (B, S), jnp.int32)
             for k in ("tokens", "labels", "positions")}
    text = jit_train_step(model, tc).lower(state, batch).compile().as_text()
    named = {}
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if "S(5)" in line and " = " in line and m:
            name = line.split(" = ", 1)[0].split()[-1].lstrip("%")
            named[name] = m.group(1)
    # tuples and their elements carry no op_name and take no device time
    starts = [n for n in named if n.startswith(("dynamic-slice-start",
                                                "dynamic-update-slice-"))]
    assert starts, named
    assert all("layers" in re.split(r"[/()]", op) for op in named.values()), \
        named
    assert "/tier.recompute/" in text
    # the attention runs as Pallas kernels in the forward, the recompute
    # and the backward, each call inside attention_core
    kernels = [m.group(1) for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               for m in [re.search(r'op_name="([^"]*)"', line)] if m]
    assert kernels and all("attention_core" in op for op in kernels), kernels
    assert any("tier.recompute" in op for op in kernels), kernels
    assert any("tier.recompute" not in op for op in kernels), kernels
