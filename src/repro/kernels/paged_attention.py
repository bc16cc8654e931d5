"""Paged-attention decode — Pallas TPU kernel with in-kernel page lookup.

The decode-path expression of the paper's memory-centric argument: instead
of gathering a session's pages into a contiguous view before attention can
run (bytes shipped to the compute), the block table rides into the kernel
as a scalar-prefetch operand and the BlockSpec index_maps dereference it —
each grid step DMAs exactly one page frame of the pool, in place.  Pages a
query cannot see (beyond ``cache_index``, or below the sliding-window band)
are skipped with ``pl.when``, so the FLOPs scale with the rows a session
actually holds, never with the pool size.

Fused codec decode: page-map ids ``>= num_frames`` address a *compressed*
side pool (int8/fp8 payload + one per-page scale, the ``core/compress.py``
per-page spill encoding).  The K/V load dequantizes those pages inline —
``q.astype(f32) * scale`` cast back to the pool dtype, bit-identical to
``decode_tensor`` — so cold pages resumed in compressed form are attended
without a separate inflate pass (Buddy-Compression-style transparent
capacity carried through the kernel boundary).

Online softmax follows the flash-attention blocking idiom
(kernels/flash_attention.py): running max / denominator / accumulator live
in VMEM scratch across the page grid dimension, masking uses a finite
``NEG_INF`` so a fully-masked (inactive) slot yields a finite discarded
row.  GQA is layout-native: q arrives as (B, K, G, hd) and each grid step
holds one page of every kv head, each serving its G query heads — no k/v
repeat.  Per-page codec scales sit whole in SMEM.

The pure-XLA twin is :func:`repro.kernels.ref.paged_decode_attention_ref`
(gather-then-``decode_attention``, the exact math of the legacy path);
``tests/test_kernels.py`` pins kernel == ref across page sizes, windows,
softcap, GQA group counts, and every registered codec.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(pm_ref, idx_ref, q_ref, k_ref, v_ref, kq_ref, vq_ref,
                  ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, window: int, softcap: float,
                  page: int, pp: int, n_raw: int, n_comp: int, kv_heads: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[0]
    pid = pm_ref[b * pp + j]
    base = j * page
    # page visibility: any row <= idx (and, with a sliding window, any row
    # inside the band).  Dead pages — unowned tail entries routed to the
    # scratch frame included — cost no FLOPs.
    live = base <= idx
    if window > 0:
        live &= (base + page - 1) > idx - window

    @pl.when(live)
    def _():
        is_comp = pid >= n_raw
        ci = jnp.clip(pid - n_raw, 0, n_comp - 1)
        k_s, v_s = ks_ref[ci], vs_ref[ci]             # per-page scales (SMEM)
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        mask = pos <= idx
        if window > 0:
            mask &= pos > idx - window
        # one grid step holds the page of every kv head; each head serves
        # its G query heads (GQA without a k/v repeat)
        for h in range(kv_heads):
            kr = k_ref[0, :, h, :]                    # (page, hd) raw
            vr = v_ref[0, :, h, :]
            # fused codec decode: the per-page scale+unpack of the
            # registered spill codecs (int8 / blocksparse / fp8 all decode
            # as q*scale), cast to the pool dtype so the math equals
            # inflate-then-attend
            kd = (kq_ref[0, :, h, :].astype(jnp.float32)
                  * k_s).astype(kr.dtype)
            vd = (vq_ref[0, :, h, :].astype(jnp.float32)
                  * v_s).astype(vr.dtype)
            k = jnp.where(is_comp, kd, kr)
            v = jnp.where(is_comp, vd, vr)
            q = q_ref[0, h]                           # (G, hd)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (G, page)
            if softcap > 0:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h]                         # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * corr + pv

    @pl.when(j == pp - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "softcap",
                                             "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_map: jax.Array,
                           cache_index: jax.Array, *,
                           window: int = 0, softcap: float = 0.0,
                           kq_pool: Optional[jax.Array] = None,
                           vq_pool: Optional[jax.Array] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           interpret: bool = False) -> jax.Array:
    """Single-token decode attention straight over the page pool.

    q: (B, 1, H, hd); pools: (P, page, K, hd) — ``P`` frames including the
    trailing scratch frame; page_map: (B, pages_per_slot) int32 frame ids
    in logical page order (unowned entries -> scratch); cache_index:
    scalar int32, the new token attends to rows [0, cache_index].

    ``kq_pool``/``vq_pool`` (C, page, K, hd) + ``k_scale``/``v_scale``
    (C, 1): compressed side pool; page-map ids ``>= P`` address frame
    ``id - P`` there and decode in-kernel.  Semantics (window / softcap /
    GQA / masking) match ``models/attention.decode_attention`` over the
    gathered equivalent view.
    """
    B, one, H, hd = q.shape
    assert one == 1, f"decode kernel takes a single query row, got {one}"
    P, page, K, _ = k_pool.shape
    G = H // K
    pp = page_map.shape[1]
    scale = 1.0 / math.sqrt(hd)
    qq = q.reshape(B, K, G, hd)

    if kq_pool is None:
        # no compressed frames: a 1-frame dummy side pool keeps the kernel
        # signature static; ids never reach it (is_comp is always false)
        kq_pool = jnp.zeros((1, page, K, hd), jnp.int8)
        vq_pool = jnp.zeros((1, page, K, hd), jnp.int8)
        k_scale = jnp.zeros((1, 1), jnp.float32)
        v_scale = jnp.zeros((1, 1), jnp.float32)
    C = kq_pool.shape[0]

    flat_map = page_map.reshape(-1).astype(jnp.int32)
    idx = jnp.asarray(cache_index, jnp.int32).reshape(1)

    # scalar-prefetched block table: the page map (and cache_index) land
    # in SMEM before the grid runs, so the index_maps below dereference
    # them to pick each step's page frame — the block-tabled K/V lookup.
    # A block spans all kv heads of a page: its last two dims (K, hd) are
    # the pool's own, which the TPU's (8, 128) tiling rule admits.
    def qmap(b, j, pm, ix):
        return (b, 0, 0, 0)

    def rawmap(b, j, pm, ix):
        return (jnp.clip(pm[b * pp + j], 0, P - 1), 0, 0, 0)

    def compmap(b, j, pm, ix):
        return (jnp.clip(pm[b * pp + j] - P, 0, C - 1), 0, 0, 0)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)      # whole scale vectors
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pp),
        in_specs=[
            pl.BlockSpec((1, K, G, hd), qmap),
            pl.BlockSpec((1, page, K, hd), rawmap),
            pl.BlockSpec((1, page, K, hd), rawmap),
            pl.BlockSpec((1, page, K, hd), compmap),
            pl.BlockSpec((1, page, K, hd), compmap),
            smem,
            smem,
        ],
        out_specs=pl.BlockSpec((1, K, G, hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, window=window,
                          softcap=softcap, page=page, pp=pp, n_raw=P,
                          n_comp=C, kv_heads=K),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
    )(flat_map, idx, qq, k_pool, v_pool, kq_pool, vq_pool,
      k_scale.reshape(-1).astype(jnp.float32),
      v_scale.reshape(-1).astype(jnp.float32))
    return out.reshape(B, 1, H, hd)
