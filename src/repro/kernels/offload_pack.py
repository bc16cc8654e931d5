"""Fused fp8 quantize+pack for the stash path — the memory-node's
"compression ASIC" (paper §III-A, Fig. 6) realised as a Pallas kernel.

Quantizes a (rows, cols) activation to float8_e4m3fn with a per-row-block
absmax scale in a single VMEM pass, halving the bytes that cross the ICI
into the pool.  Blockwise scales (vs core.compress's per-tensor scale)
bound the quantization error per block — a strictly better trade at zero
extra traffic (one f32 per block).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

FP8_MAX = 448.0
INT8_MAX = 127.0
BLOCKSPARSE_TAU = 32.0   # prune |x| < block_absmax / TAU to exact zero


#: lanes of one scale row: a block's scale is written (and read) as a
#: lane-wide (1, 1, LANES) row, since a TPU kernel cannot store a scalar
#: to VMEM and a (1, 1) block over (nb, 1) breaks the (8, 128) tiling rule
LANES = 128


def _store_scale(s_ref, scale):
    s_ref[...] = jnp.full(s_ref.shape, scale, jnp.float32)


def _pack_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(absmax / FP8_MAX, 1e-12)
    q_ref[...] = (x / scale).astype(q_ref.dtype)
    _store_scale(s_ref, scale)


def _int8_pack_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(absmax / INT8_MAX, 1e-30)
    q_ref[...] = jnp.clip(jnp.round(x / scale),
                          -INT8_MAX, INT8_MAX).astype(q_ref.dtype)
    _store_scale(s_ref, scale)


def _blocksparse_pack_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(absmax / INT8_MAX, 1e-30)
    q = jnp.clip(jnp.round(x / scale), -INT8_MAX, INT8_MAX)
    keep = jnp.abs(x) >= absmax / BLOCKSPARSE_TAU
    q_ref[...] = jnp.where(keep, q, 0.0).astype(q_ref.dtype)
    _store_scale(s_ref, scale)


def _unpack_kernel(q_ref, s_ref, o_ref):
    scale = s_ref[0][:, :1]                           # (1, 1)
    o_ref[...] = (q_ref[...].astype(jnp.float32) * scale).astype(o_ref.dtype)


def _pack(kernel, x: jax.Array, block_rows: int, qdtype, interpret: bool
          ) -> Tuple[jax.Array, jax.Array]:
    """Run a per-row-block pack kernel: x (R, C) -> (q (R, C), scales
    (R // block_rows,))."""
    R, C = x.shape
    assert R % block_rows == 0, (R, block_rows)
    nb = R // block_rows
    q, s = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), qdtype),
            jax.ShapeDtypeStruct((nb, 1, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q, s[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fp8_pack(x: jax.Array, *, block_rows: int = 128,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (R, C) -> (q: fp8 (R, C), scales: f32 (R//block_rows,))."""
    return _pack(_pack_kernel, x, block_rows, jnp.float8_e4m3fn, interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "dtype",
                                             "interpret"))
def fp8_unpack(q: jax.Array, scales: jax.Array, *, block_rows: int = 128,
               dtype=jnp.bfloat16, interpret: bool = False) -> jax.Array:
    R, C = q.shape
    nb = R // block_rows
    rows = jnp.broadcast_to(scales.astype(jnp.float32)[:, None, None],
                            (nb, 1, LANES))
    return pl.pallas_call(
        _unpack_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), dtype),
        interpret=interpret,
    )(q, rows)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def int8_pack(x: jax.Array, *, block_rows: int = 128,
              interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (R, C) -> (q: int8 (R, C), scales: f32 (R//block_rows,)).

    The int8 codec twin of :func:`fp8_pack` — same per-row-block absmax
    scaling, round-and-clip instead of fp8 cast (int8 has no subnormals,
    so the round is explicit)."""
    return _pack(_int8_pack_kernel, x, block_rows, jnp.int8, interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def blocksparse_pack(x: jax.Array, *, block_rows: int = 128,
                     interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array]:
    """x: (R, C) -> (q: int8 (R, C) with small entries pruned, scales f32).

    The block-sparse codec twin: per-row-block int8 quantization (as
    :func:`int8_pack`) plus in-block magnitude pruning — entries below
    ``absmax / BLOCKSPARSE_TAU`` become *exact* zeros, so a run-length /
    entropy stage on the wire (the memory node's compression ASIC,
    §III-A) sees dense zero runs.  Decode needs no sparsity metadata: the
    zeros dequantize to zero through the shared unpack twin.
    """
    return _pack(_blocksparse_pack_kernel, x, block_rows, jnp.int8,
                 interpret)


#: dequantize-by-scale has no dtype-specific logic — the int8 and
#: blocksparse unpack twins ARE the fp8 one (kernels/ref.py delegates
#: identically; pruned zeros dequantize to zero by construction)
int8_unpack = fp8_unpack
blocksparse_unpack = fp8_unpack
