"""Attention: GQA/MHA/SWA with a blockwise (flash-style) XLA implementation.

Three execution paths:

* training (``attention_block`` without a cache) goes through
  ``kernels/ops.train_attention``: the Pallas flash kernels, forward and
  backward, on one TPU chip; ``blockwise_attention`` on other backends,
  under a multi-device mesh, or with a logit soft-cap.

* ``blockwise_attention`` — the XLA twin of those kernels, and the prefill
  path.  Unrolled python loop over query chunks gives each chunk a *static*
  causal / sliding-window KV span (no wasted FLOPs on fully-masked blocks),
  and an inner ``lax.scan`` with an online softmax keeps the score tensor
  at (chunk × chunk) instead of S×S.  Both are validated against
  kernels/ref.py.

* ``decode_attention`` — single-token decode against a KV cache.  The cache
  is sharded over the sequence axis (the paper's pooled memory applied to
  inference: KV lives striped across the mesh's HBM pool) and the softmax
  reductions run distributed over that axis.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import ModelContext, apply_rope, dense_init

Cache = Dict[str, jax.Array]

# Dry-run probe switches (launch/dryrun.py): the online-softmax kv scan is a
# while loop, which XLA cost_analysis counts once — probes unroll it (and
# use bigger chunks to bound the unrolled body count).
UNROLL_INNER = False
Q_CHUNK = 1024
KV_CHUNK = 1024


def _unroll():
    return True if UNROLL_INNER else 1


# ---------------------------------------------------------------------------
def attn_init(key, cfg: ModelConfig, dtype) -> dict:
    H, K, hd, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim, cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], D, H * hd, dtype),
        "wk": dense_init(ks[1], D, K * hd, dtype),
        "wv": dense_init(ks[2], D, K * hd, dtype),
        "wo": dense_init(ks[3], H * hd, D, dtype),
    }
    if cfg.use_qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    return p


def attn_specs(cfg: ModelConfig, planner) -> dict:
    H, K, hd, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim, cfg.d_model
    fs, tp = planner.axes.fsdp, planner.axes.tensor
    s = {
        "wq": planner.spec((D, H * hd), [fs, tp], "wq"),
        "wk": planner.spec((D, K * hd), [fs, tp], "wk"),
        "wv": planner.spec((D, K * hd), [fs, tp], "wv"),
        "wo": planner.spec((H * hd, D), [tp, fs], "wo"),
    }
    if cfg.use_qkv_bias:
        s["bq"] = planner.spec((H * hd,), [tp], "bq")
        s["bk"] = planner.spec((K * hd,), [tp], "bk")
        s["bv"] = planner.spec((K * hd,), [tp], "bv")
    return s


# ---------------------------------------------------------------------------
def _span_for_chunk(qi: int, q_chunk: int, kv_len: int, causal: bool,
                    window: int, kv_chunk: int) -> Tuple[int, int]:
    """Static [start, end) KV span a query chunk may attend to."""
    q_end = (qi + 1) * q_chunk
    end = min(kv_len, q_end) if causal else kv_len
    start = 0
    if causal and window > 0:
        start = max(0, qi * q_chunk - window)
    start = (start // kv_chunk) * kv_chunk           # align to kv chunks
    return start, end


def _online_softmax_span(q, k_span, v_span, *, scale, q0, k0, causal, window,
                         kv_chunk, softcap):
    """q: (B, Cq, K, G, hd); span: (B, T, K, hd).  Online softmax over kv
    chunks.  Returns (B, Cq, K, G, hd)."""
    B, Cq, K, G, hd = q.shape
    T = k_span.shape[1]
    n_kv = -(-T // kv_chunk)
    pad = n_kv * kv_chunk - T
    if pad:
        k_span = jnp.pad(k_span, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_span = jnp.pad(v_span, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k_span.reshape(B, n_kv, kv_chunk, K, hd).swapaxes(0, 1)
    vc = v_span.reshape(B, n_kv, kv_chunk, K, hd).swapaxes(0, 1)
    kidx = jnp.arange(n_kv)

    q_pos = q0 + jnp.arange(Cq)

    def step(carry, xs):
        m, l, acc = carry
        kk, vv, ki = xs
        s = jnp.einsum("bqkgd,btkd->bkgqt", q, kk,
                       preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = k0 + ki * kv_chunk + jnp.arange(kv_chunk)
        mask = jnp.ones((Cq, kv_chunk), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
            if window > 0:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
        if pad:
            mask &= (k_pos < k0 + T)[None, :]
        s = jnp.where(mask, s, -1e30)    # finite NEG: a fully-masked
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))   # chunk must not NaN
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqt,btkd->bkgqd", p.astype(vv.dtype), vv,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, K, G, Cq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, K, G, Cq), jnp.float32)
    a0 = jnp.zeros((B, K, G, Cq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, kidx),
                                  unroll=_unroll())
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)   # (B, Cq, K, G, hd)


def blockwise_attention(q, k, v, *, causal: bool, window: int = 0,
                        q_chunk: int = 0, kv_chunk: int = 0,
                        softcap: float = 0.0) -> jax.Array:
    """q: (B, S, H, hd); k/v: (B, T, K, hd) with H = K*G (GQA).

    Unrolled query chunks -> exact causal/window FLOPs with static shapes.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    q = q.reshape(B, S, K, G, hd)
    q_chunk = q_chunk or Q_CHUNK
    kv_chunk = kv_chunk or KV_CHUNK
    q_chunk = min(q_chunk, S)
    n_q = -(-S // q_chunk)
    pad_q = n_q * q_chunk - S
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    outs = []
    for qi in range(n_q):
        qc = jax.lax.slice_in_dim(q, qi * q_chunk, (qi + 1) * q_chunk, axis=1)
        start, end = _span_for_chunk(qi, q_chunk, T, causal, window, kv_chunk)
        ks = jax.lax.slice_in_dim(k, start, end, axis=1)
        vs = jax.lax.slice_in_dim(v, start, end, axis=1)
        outs.append(_online_softmax_span(
            qc, ks, vs, scale=scale, q0=qi * q_chunk, k0=start, causal=causal,
            window=window, kv_chunk=min(kv_chunk, end - start), softcap=softcap))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    if pad_q:
        out = jax.lax.slice_in_dim(out, 0, S, axis=1)
    return out.reshape(B, S, H, hd)


def decode_attention(q, k_cache, v_cache, cache_index, *, window: int = 0,
                     softcap: float = 0.0) -> jax.Array:
    """Single-token attention over a (possibly mesh-pooled) KV cache.

    q: (B, 1, H, hd); caches: (B, S, K, hd); cache_index: scalar int32 —
    number of valid cache positions (the new token attends to [0, index]).
    """
    B, _, H, hd = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    qq = q.reshape(B, K, G, hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qq, k_cache,
                   preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    pos = jnp.arange(S)
    mask = pos <= cache_index
    if window > 0:
        mask &= pos > cache_index - window
    # finite NEG, not -inf: an inactive slot (cache_index < 0, mask all
    # false) must yield a finite (discarded) row, not NaN-poison the
    # batched einsum — same contract as _online_softmax_span.  For any
    # row with >=1 valid position the result is bit-identical (exp of
    # -1e30 - m underflows to exactly 0).
    s = jnp.where(mask[None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def prefix_prefill_attention(q, k_cache, v_cache, positions, *,
                             window: int = 0,
                             softcap: float = 0.0) -> jax.Array:
    """Multi-token attention over a cache holding a reused prefix.

    The suffix-prefill twin of :func:`decode_attention`: ``q`` holds the
    S2 suffix tokens of a prompt whose first rows were grafted from the
    prefix cache (prefix-sharing admission), the K/V caches hold the
    grafted rows plus the just-written suffix rows, and each query at
    absolute position ``positions[b, i]`` attends causally to cache rows
    ``[0, positions[b, i]]`` — rows beyond are masked (stale frames).

    q: (B, S2, H, hd); caches: (B, T, K, hd); positions: (B, S2) int32.
    """
    B, S2, H, hd = q.shape
    T, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    qq = q.reshape(B, S2, K, G, hd)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qq, k_cache,
                   preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    t = jnp.arange(T)
    # (B, 1, 1, S2, T): row t visible to query at absolute position p
    # iff t <= p (and within the sliding window when one is set)
    mask = t[None, None, None, None, :] <= \
        positions[:, None, None, :, None]
    if window > 0:
        mask &= t[None, None, None, None, :] > \
            positions[:, None, None, :, None] - window
    # finite NEG (see decode_attention): a padded query row whose
    # position masks every cache row must not softmax over all -inf
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqt,btkd->bkgqd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S2, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
def attention_block(params: dict, ctx: ModelContext, x: jax.Array,
                    positions: jax.Array, *, causal: bool = True,
                    cache: Optional[Cache] = None,
                    cache_index: Optional[jax.Array] = None,
                    kv_x: Optional[jax.Array] = None,
                    use_rope: bool = True,
                    prefix_attend: bool = False,
                    paged: Optional[dict] = None
                    ) -> Tuple[jax.Array, Optional[Cache]]:
    """Full attention sub-block: projections + rope + attend + output proj.

    kv_x: source of K/V for cross-attention (encoder states); when given with
    a cache, the cache holds the projected cross K/V and is reused as-is.
    """
    cfg = ctx.cfg
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B, S, D = x.shape
    window = cfg.window if cfg.attention == "swa" else 0

    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    q = q.reshape(B, S, H, hd)

    src = kv_x if kv_x is not None else x
    k = jnp.einsum("bsd,dh->bsh", src, params["wk"])
    v = jnp.einsum("bsd,dh->bsh", src, params["wv"])
    if "bk" in params:
        k, v = k + params["bk"], v + params["bv"]
    Tkv = src.shape[1]
    k = k.reshape(B, Tkv, K, hd)
    v = v.reshape(B, Tkv, K, hd)

    if use_rope and kv_x is None:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)

    # heads over 'model' when divisible; else sequence-parallel (smollm 9H,
    # starcoder2 36H) so the score buffers still split across the mesh
    tp = ctx.planner.axes.size(ctx.planner.axes.tensor)
    if tp > 1 and H % tp != 0 and S > 1:
        q = ctx.act(q, "batch", "seq", None, None)
        k = ctx.act(k, "batch", None, None, None)
        v = ctx.act(v, "batch", None, None, None)
    else:
        q = ctx.act(q, "batch", None, "heads", None)
        # pinning K/V to the batch shard prevents GSPMD's full-batch K/V
        # gather when K < tp and the (K,G) reshape defeats head sharding
        # (§Perf H3: measured 2x9.7 GB/dev/layer on command-r; an explicit
        # repeat-to-MHA variant was tried and REFUTED — it added wire on
        # danube/mixtral where no pathology existed)
        k = ctx.act(k, "batch", None, None, None)
        v = ctx.act(v, "batch", None, None, None)

    new_cache = cache
    if cache is not None and paged is not None:
        # in-place paged decode: the cache leaves ARE the page pool
        # (P, page, K, hd) — no batch dim, no gathered view.  The step's
        # K/V row lands directly in its page frame (write_pid routes
        # masked slots to the scratch frame) and attention dereferences
        # the block table inside the kernel, touching only the pages each
        # session holds.  Compressed side-pool leaves (kq/vq/ks/vs) ride
        # along read-only; new_cache returns only the mutated raw pool.
        assert S == 1, "paged decode is single-token"
        from repro.kernels import ops as kops
        kc, vc = cache["k"], cache["v"]
        row = paged["row_off"]
        kc = kc.at[paged["write_pid"], row].set(k[:, 0].astype(kc.dtype))
        vc = vc.at[paged["write_pid"], row].set(v[:, 0].astype(vc.dtype))
        o = kops.paged_attention(
            q, kc, vc, paged["page_map"], cache_index, window=window,
            softcap=cfg.logit_softcap, kq_pool=cache.get("kq"),
            vq_pool=cache.get("vq"), k_scale=cache.get("ks"),
            v_scale=cache.get("vs"))
        new_cache = {"k": kc, "v": vc}
    elif cache is not None:
        # self-attention with cache: decode (S==1) writes one slot; prefill
        # writes the whole prefix at 0 — except a prefix-sharing suffix
        # prefill (prefix_attend), which writes the S suffix rows at
        # cache_index and attends over the cache (grafted prefix rows
        # included) instead of only the in-flight tokens.
        kc, vc = cache["k"], cache["v"]
        idx = cache_index if (cache_index is not None
                              and (S == 1 or prefix_attend)) else 0
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), idx, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), idx, 1)
        kc = ctx.act(kc, "batch", "seq", None, None)   # pooled KV (MC-DLA)
        vc = ctx.act(vc, "batch", "seq", None, None)
        new_cache = dict(cache, k=kc, v=vc)
        if S == 1:
            o = decode_attention(q, kc, vc, cache_index, window=window,
                                 softcap=cfg.logit_softcap)
        elif prefix_attend:
            o = prefix_prefill_attention(q, kc, vc, positions, window=window,
                                         softcap=cfg.logit_softcap)
        else:
            o = blockwise_attention(q, k, v, causal=causal, window=window,
                                    softcap=cfg.logit_softcap)
    else:
        # scores, softmax and values: the flash kernel pair on one TPU chip,
        # the blockwise twin elsewhere (kernels/ops.train_attention)
        from repro.kernels import ops as kops
        meshed = ctx.mesh is not None and ctx.mesh.size > 1
        with jax.named_scope("attention_core"):
            o = kops.train_attention(q, k, v, causal=causal, window=window,
                                     softcap=cfg.logit_softcap,
                                     meshed=meshed)

    if tp > 1 and H % tp != 0 and S > 1:
        o = ctx.act(o, "batch", "seq", None, None)
    else:
        o = ctx.act(o, "batch", None, "heads", None)
    out = jnp.einsum("bsh,hd->bsd", o.reshape(B, S, H * hd), params["wo"])
    return out, new_cache


def cross_attention_block(params: dict, ctx: ModelContext, x: jax.Array,
                          *, enc_kv: Cache) -> jax.Array:
    """Cross-attention against precomputed encoder K/V (whisper decoder).

    enc_kv: {"k": (B, T_enc, K, hd), "v": ...} — projected once at prefill
    (see transformer.encode_cross_kv) and reused for every decode step.
    """
    cfg = ctx.cfg
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B, S, D = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    q = q.reshape(B, S, H, hd)
    q = ctx.act(q, "batch", None, "heads", None)
    kc, vc = enc_kv["k"], enc_kv["v"]
    if S == 1:
        o = decode_attention(q, kc, vc, jnp.int32(kc.shape[1] - 1),
                             softcap=cfg.logit_softcap)
    else:
        o = blockwise_attention(q, kc, vc, causal=False,
                                softcap=cfg.logit_softcap)
    o = ctx.act(o, "batch", None, "heads", None)
    return jnp.einsum("bsh,hd->bsd", o.reshape(B, S, H * hd), params["wo"])


def encode_cross_kv(params: dict, cfg: ModelConfig, enc_out: jax.Array) -> Cache:
    """Project encoder states to cross K/V once (reused across decode steps)."""
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    B, T, _ = enc_out.shape
    k = jnp.einsum("bsd,dh->bsh", enc_out, params["wk"])
    v = jnp.einsum("bsd,dh->bsh", enc_out, params["wv"])
    if "bk" in params:
        k, v = k + params["bk"], v + params["bv"]
    return {"k": k.reshape(B, T, K, hd), "v": v.reshape(B, T, K, hd)}


def init_kv_cache(cfg: ModelConfig, batch: int, seq: int, dtype) -> Cache:
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {"k": jnp.zeros((batch, seq, K, hd), dtype),
            "v": jnp.zeros((batch, seq, K, hd), dtype)}
