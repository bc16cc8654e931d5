"""Flash attention — Pallas TPU kernels for the forward and the backward.

Online-softmax attention with (bq x bk) score tiles living in VMEM; the
running max / denominator / output accumulator persist in VMEM scratch
across the kv-block grid dimension.  GQA is handled in the index_map (query
head h reads kv head h // group) — no k/v repeat is materialized.

Block skipping: with causal masking, kv blocks strictly above the diagonal
(and, for sliding-window, strictly below the window band) contribute
nothing.  Their compute is guarded out with ``pl.when``, and the k/v (or,
in the dK/dV kernel, q) index map clamps to the nearest live block, so a
skipped grid step repeats the previous block index and its DMA is elided.
Only blocks that cross the diagonal, the window edge or the padded tail
build a mask.

The backward is FA2-style and recomputes each probability tile from the
forward's per-row logsumexp (the only residuals are q, k, v, o, lse):

* ``_dkv_kernel`` walks every query block of each kv block, for each of
  the kv head's ``group`` query heads, and sums dK/dV over them in VMEM;
* ``_dq_kernel`` walks the kv blocks of each query block.

Both take ``D = rowsum(dO * O)``.  Precision follows the XLA blockwise
twin (models/attention.blockwise_attention): bf16 MXU operands with f32
accumulation, f32 max / sum / logsumexp, ``p`` cast to the value dtype
before P·V, dS formed in f32 and cast only as an MXU operand.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
MAX_BLOCK = 1024


def block_sizes(S: int, T: int) -> Tuple[int, int]:
    """(bq, bk) for S queries over T keys: the fewest lane-aligned tiles
    of at most MAX_BLOCK rows, split evenly so the padded tail stays under
    a lane group.  On v5e at the smollm training shapes (B 16, H 9, S
    2,048, hd 64) 1,024-row tiles ran the forward 1.5x faster than 512-row
    ones; every head dim up to 256 compiles at that size."""
    def one(n: int) -> int:
        n_blocks = -(-n // MAX_BLOCK)
        return -(-n // (n_blocks * LANES)) * LANES
    return one(S), one(T)


# ---------------------------------------------------------------------------
# which (query block, kv block) pairs hold an unmasked entry
def _kv_range(qi, *, bq: int, bk: int, n_kv: int, causal: bool,
              window: int):
    """[lo, hi] live kv blocks of query block ``qi``."""
    hi = n_kv - 1
    lo = 0
    if causal:
        hi = jnp.minimum(hi, (qi * bq + bq - 1) // bk)
        if window > 0:
            lo = jnp.maximum(0, (qi * bq - window + 1) // bk)
    return lo, hi


def _q_range(ki, *, bq: int, bk: int, n_q: int, causal: bool, window: int):
    """[lo, hi] query blocks that see kv block ``ki``."""
    lo = 0
    hi = n_q - 1
    if causal:
        lo = (ki * bk) // bq
        if window > 0:
            hi = jnp.minimum(hi, (ki * bk + bk - 1 + window - 1) // bq)
    return lo, hi


def _needs_mask(q0, k0, *, bq: int, bk: int, causal: bool, window: int,
                kv_len: int, padded: bool):
    """Whether a live (q0, k0) tile holds any masked entry."""
    need = jnp.asarray(False)
    if causal:
        need |= k0 + bk - 1 > q0                    # crosses the diagonal
        if window > 0:
            need |= q0 + bq - 1 - k0 >= window      # crosses the window edge
    if padded:
        need |= k0 + bk > kv_len
    return need


def _mask(q0, k0, shape, *, transposed: bool, causal: bool, window: int,
          kv_len: int):
    """Visibility of a (bq, bk) tile, or of its (bk, bq) transpose."""
    qa, ka = (1, 0) if transposed else (0, 1)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, qa)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, ka)
    mask = k_pos < kv_len
    if causal:
        mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
    return mask


def _when_live(compute, live, need_mask):
    """Run ``compute(masked)`` on a live tile, with the static flag the
    tile needs: interior tiles build no mask."""
    @pl.when(live & need_mask)
    def _():
        compute(True)

    @pl.when(live & jnp.logical_not(need_mask))
    def _():
        compute(False)


_NT = (((1,), (1,)), ((), ()))                       # a @ b.T


# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float,
                causal: bool, window: int, bq: int, bk: int, n_kv: int,
                kv_len: int, padded: bool, save_lse: bool):
    if save_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q0, k0 = qi * bq, ki * bk
    lo, hi = _kv_range(qi, bq=bq, bk=bk, n_kv=n_kv, causal=causal,
                       window=window)
    opts = dict(causal=causal, window=window, kv_len=kv_len)

    def compute(masked: bool):
        q = q_ref[0, 0]                              # (bq, d)
        k = k_ref[0, 0]                              # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_mask(q0, k0, s.shape, transposed=False, **opts),
                          s, NEG_INF)
        m_prev = m_ref[...]                          # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv

    _when_live(compute, (ki >= lo) & (ki <= hi),
               _needs_mask(q0, k0, bq=bq, bk=bk, padded=padded, **opts))

    @pl.when(ki == n_kv - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if save_lse:
            lse = jnp.broadcast_to(m_ref[...] + jnp.log(l), (bq, LANES))
            lse_ref[0, 0] = jnp.transpose(lse)[:1]  # (1, bq), lane-dense


def _tiling(S: int, T: int, bq: int, bk: int):
    """(bq, bk, padded S, padded T): the given tiles, else the shapes'."""
    auto_q, auto_k = block_sizes(S, T)
    bq, bk = bq or auto_q, bk or auto_k
    return bq, bk, -(-S // bq) * bq, -(-T // bk) * bk


def _pad_to(x, axis: int, n: int):
    pad = n - x.shape[axis]
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "save_lse", "interpret"))
def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        bq: int = 0, bk: int = 0, save_lse: bool = False,
                        interpret: bool = False):
    """q: (B, H, S, d); k/v: (B, Hkv, T, d) -> o (B, H, S, d), and with
    ``save_lse`` the per-row logsumexp (B, H, 1, S) in f32."""
    B, H, S, d = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    group = H // Hkv
    bq, bk, Sq, Tk = _tiling(S, T, bq, bk)
    q = _pad_to(q, 2, Sq)
    k, v = _pad_to(k, 2, Tk), _pad_to(v, 2, Tk)
    n_q, n_kv = Sq // bq, Tk // bk
    rng = functools.partial(_kv_range, bq=bq, bk=bk, n_kv=n_kv,
                            causal=causal, window=window)

    def kv_map(b, h, qi, ki):
        lo, hi = rng(qi)
        return b, h // group, jnp.clip(ki, lo, hi), 0

    def q_map(b, h, qi, ki):
        return b, h, qi, 0

    out_shape = [jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, bq, d), q_map)]
    if save_lse:
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, 1, bq),
                                      lambda b, h, qi, ki: (b, h, 0, qi)))
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d),
                          causal=causal, window=window, bq=bq, bk=bk,
                          n_kv=n_kv, kv_len=T, padded=Tk > T,
                          save_lse=save_lse),
        grid=(B, H, n_q, n_kv),
        in_specs=[pl.BlockSpec((1, 1, bq, d), q_map),
                  pl.BlockSpec((1, 1, bk, d), kv_map),
                  pl.BlockSpec((1, 1, bk, d), kv_map)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    o = outs[0][:, :, :S]
    if save_lse:
        return o, outs[1][..., :S]
    return o


# ---------------------------------------------------------------------------
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale: float, causal: bool, window: int,
                bq: int, bk: int, n_q: int, group: int, kv_len: int,
                padded: bool):
    ki = pl.program_id(2)
    g = pl.program_id(3)
    qi = pl.program_id(4)

    @pl.when((g == 0) & (qi == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q0, k0 = qi * bq, ki * bk
    lo, hi = _q_range(ki, bq=bq, bk=bk, n_q=n_q, causal=causal,
                      window=window)
    opts = dict(causal=causal, window=window, kv_len=kv_len)

    def compute(masked: bool):
        q = q_ref[0, 0]                              # (bq, d)
        k = k_ref[0, 0]                              # (bk, d)
        v = v_ref[0, 0]
        do = do_ref[0, 0]                            # (bq, d)
        # transposed tiles: the per-row lse and D broadcast as rows
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_mask(q0, k0, s.shape, transposed=True, **opts),
                          s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])               # (bk, bq)
        dv_acc[...] += jax.lax.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0, 0])
        dk_acc[...] += jax.lax.dot(ds.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

    _when_live(compute, (qi >= lo) & (qi <= hi),
               _needs_mask(q0, k0, bq=bq, bk=bk, padded=padded, **opts))

    @pl.when((g == group - 1) & (qi == n_q - 1))
    def _():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_acc,
               *, scale: float, causal: bool, window: int, bq: int, bk: int,
               n_kv: int, kv_len: int, padded: bool):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q0, k0 = qi * bq, ki * bk
    lo, hi = _kv_range(qi, bq=bq, bk=bk, n_kv=n_kv, causal=causal,
                       window=window)
    opts = dict(causal=causal, window=window, kv_len=kv_len)

    def compute(masked: bool):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_mask(q0, k0, s.shape, transposed=False, **opts),
                          s, NEG_INF)
        lse = jnp.expand_dims(lse_ref[0, 0, 0], -1)  # (bq, 1)
        di = jnp.expand_dims(di_ref[0, 0, 0], -1)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di)
        dq_acc[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)

    _when_live(compute, (ki >= lo) & (ki <= hi),
               _needs_mask(q0, k0, bq=bq, bk=bk, padded=padded, **opts))

    @pl.when(ki == n_kv - 1)
    def _():
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool = True,
                        window: int = 0, bq: int = 0, bk: int = 0,
                        interpret: bool = False):
    """Gradients of :func:`flash_attention_fwd` from its residuals.

    q, o, do: (B, H, S, d); k/v: (B, Hkv, T, d); lse: (B, H, 1, S) f32.
    Returns (dq, dk, dv) in the dtypes of q, k, v."""
    B, H, S, d = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    group = H // Hkv
    bq, bk, Sq, Tk = _tiling(S, T, bq, bk)
    n_q, n_kv = Sq // bq, Tk // bk
    scale = 1.0 / math.sqrt(d)
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    di = _pad_to(di[:, :, None, :], 3, Sq)
    lse = _pad_to(lse, 3, Sq)
    # padded query rows carry dO = 0 and so add nothing to dK/dV
    q, do = _pad_to(q, 2, Sq), _pad_to(do, 2, Sq)
    k, v = _pad_to(k, 2, Tk), _pad_to(v, 2, Tk)
    row = (1, 1, 1, bq)
    tile_q = (1, 1, bq, d)
    tile_k = (1, 1, bk, d)
    common = dict(scale=scale, causal=causal, window=window, bq=bq, bk=bk,
                  kv_len=T, padded=Tk > T)

    # dK/dV: grid (b, kv head, kv block, group member, query block)
    q_rng = functools.partial(_q_range, bq=bq, bk=bk, n_q=n_q,
                              causal=causal, window=window)

    def q_map(b, kh, ki, g, qi):
        lo, hi = q_rng(ki)
        return b, kh * group + g, jnp.clip(qi, lo, hi), 0

    def row_map(b, kh, ki, g, qi):
        lo, hi = q_rng(ki)
        return b, kh * group + g, 0, jnp.clip(qi, lo, hi)

    def k_map(b, kh, ki, g, qi):
        return b, kh, ki, 0

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q=n_q, group=group, **common),
        grid=(B, Hkv, n_kv, group, n_q),
        in_specs=[pl.BlockSpec(tile_q, q_map), pl.BlockSpec(tile_k, k_map),
                  pl.BlockSpec(tile_k, k_map), pl.BlockSpec(tile_q, q_map),
                  pl.BlockSpec(row, row_map), pl.BlockSpec(row, row_map)],
        out_specs=[pl.BlockSpec(tile_k, k_map), pl.BlockSpec(tile_k, k_map)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, di)

    # dQ: grid (b, query head, query block, kv block)
    kv_rng = functools.partial(_kv_range, bq=bq, bk=bk, n_kv=n_kv,
                               causal=causal, window=window)

    def kv_map(b, h, qi, ki):
        lo, hi = kv_rng(qi)
        return b, h // group, jnp.clip(ki, lo, hi), 0

    def dq_map(b, h, qi, ki):
        return b, h, qi, 0

    def dq_row_map(b, h, qi, ki):
        return b, h, 0, qi

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_kv=n_kv, **common),
        grid=(B, H, n_q, n_kv),
        in_specs=[pl.BlockSpec(tile_q, dq_map), pl.BlockSpec(tile_k, kv_map),
                  pl.BlockSpec(tile_k, kv_map), pl.BlockSpec(tile_q, dq_map),
                  pl.BlockSpec(row, dq_row_map),
                  pl.BlockSpec(row, dq_row_map)],
        out_specs=pl.BlockSpec(tile_q, dq_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, di)
    return dq[:, :, :S], dk[:, :, :T], dv[:, :, :T]
