"""Explicit collectives: ring all-reduce + int8 error-feedback compression.

XLA already maps ``jax.lax`` collectives to near-optimal ICI ring schedules,
so the *models* use plain psum/all_gather (DESIGN.md §2: do not emulate
NCCL).  This module exists for the two places explicit control is the
feature, not a detail:

* ``ring_all_reduce`` — a reduce-scatter + all-gather ring written with
  ``ppermute``, the textbook schedule the paper's NCCL-based systems use
  (Fig. 4/5).  It is bit-identical to psum and is used by the tests and the
  ring-latency benchmark (paper Fig. 9) to validate the simulator's latency
  model against an executable implementation.

* ``compressed_all_reduce`` — int8 wire traffic with fp32 accumulation and
  error feedback (the 'compressing DMA engine' the paper cites as [56]):
  each hop quantizes its outgoing chunk; the quantization residual is
  carried to the next step by the caller (``CompressionState``).
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.compress import INT8_MAX


def _ring_perm(n: int, shift: int = 1):
    return [(i, (i + shift) % n) for i in range(n)]


def ring_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Reduce-scatter + all-gather ring all-reduce over ``axis_name``.

    Call inside shard_map.  x: identical shape on every member; the leading
    dim must be divisible by the axis size.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    assert x.shape[0] % n == 0, (x.shape, n)
    chunks = x.reshape((n, x.shape[0] // n) + x.shape[1:])
    me = jax.lax.axis_index(axis_name)
    perm = _ring_perm(n)

    # reduce-scatter: at step k node i sends its partial of chunk (i-k) and
    # accumulates the received partial of chunk (i-k-1).  After n-1 steps
    # node i owns the complete sum of chunk (i+1) mod n.
    acc = chunks
    for k in range(n - 1):
        buf = acc[(me - k) % n]
        buf = jax.lax.ppermute(buf, axis_name, perm)
        acc = acc.at[(me - k - 1) % n].add(buf)

    # all-gather: circulate the complete chunks around the ring.
    mine_idx = (me + 1) % n
    buf = acc[mine_idx]
    out = jnp.zeros_like(chunks)
    out = out.at[mine_idx].set(buf)
    for k in range(n - 1):
        buf = jax.lax.ppermute(buf, axis_name, perm)
        out = out.at[(me - k) % n].set(buf)
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
def _quant(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)) / INT8_MAX, 1e-30)
    q = jnp.clip(jnp.round(x / scale), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scale


def compressed_all_reduce(x: jax.Array, err: jax.Array, axis_name: str
                          ) -> Tuple[jax.Array, jax.Array]:
    """Error-feedback int8 all-reduce (inside shard_map).

    Wire bytes: int8 payload + one fp32 scale per hop (4x less traffic than
    fp32).  Accumulation stays fp32 on-chip.  Returns (mean-reduced value,
    new local error).  Convergence: the residual err is added before
    quantization next call (EF-SGD).
    """
    n = jax.lax.axis_size(axis_name)
    corrected = x.astype(jnp.float32) + err
    q, scale = _quant(corrected)
    sent = q.astype(jnp.float32) * scale
    new_err = corrected - sent

    if n == 1:
        return sent, new_err

    acc = sent
    buf_q, buf_s = q, scale
    for _ in range(n - 1):
        buf_q = jax.lax.ppermute(buf_q, axis_name, _ring_perm(n))
        buf_s = jax.lax.ppermute(buf_s, axis_name, _ring_perm(n))
        acc = acc + buf_q.astype(jnp.float32) * buf_s
    return acc / n, new_err


def compressed_tree_all_reduce(grads, errs, axis_name: str = "data"):
    """Pytree version of compressed_all_reduce (call inside shard_map):
    per-device local grad tree + error tree -> (mean grads, new errors)."""
    flat_g, tree = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(errs)
    out = [compressed_all_reduce(g, e, axis_name)
           for g, e in zip(flat_g, flat_e)]
    return (jax.tree.unflatten(tree, [o[0] for o in out]),
            jax.tree.unflatten(tree, [o[1] for o in out]))
