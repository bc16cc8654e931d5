"""Public jit'd wrappers for the Pallas kernels.

Each op auto-selects interpret mode off-TPU (the kernels are written for
TPU BlockSpec/VMEM semantics; interpret=True executes the same kernel body
on CPU for correctness).  ``flash_attention`` pairs the Pallas forward
with the Pallas backward in one custom_vjp; ``train_attention`` picks it or
the XLA blockwise twin from what the call can observe.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import (flash_attention as fa, gemm_os as gos,
                           offload_pack as op, paged_attention as pa,
                           ref as kref, ssd_scan as ss)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def gemm(x: jax.Array, w: jax.Array, **kw) -> jax.Array:
    return gos.gemm_os(x, w, interpret=_interpret(), **kw)


def ssd(x, a, B, C, *, chunk: int = 128) -> jax.Array:
    return ss.ssd_scan(x, a, B, C, chunk=chunk, interpret=_interpret())


def fp8_pack(x, *, block_rows: int = 128):
    return op.fp8_pack(x, block_rows=block_rows, interpret=_interpret())


def fp8_unpack(q, scales, *, block_rows: int = 128, dtype=jnp.bfloat16):
    return op.fp8_unpack(q, scales, block_rows=block_rows, dtype=dtype,
                         interpret=_interpret())


def int8_pack(x, *, block_rows: int = 128):
    return op.int8_pack(x, block_rows=block_rows, interpret=_interpret())


def int8_unpack(q, scales, *, block_rows: int = 128, dtype=jnp.bfloat16):
    return op.int8_unpack(q, scales, block_rows=block_rows, dtype=dtype,
                          interpret=_interpret())


# ---------------------------------------------------------------------------
# paged decode attention: in-place block-tabled K/V lookup with fused codec
# decode.  A registry flag (not a bool) so backends stay pluggable: the
# serving stack routes through ``paged_attention`` and the active impl can
# be swapped (tests pin pallas == xla) without touching the call sites.
PAGED_IMPLS = ("pallas", "xla")
_PAGED_IMPL = {"default": "pallas"}


def set_paged_impl(name: str) -> None:
    """Select the paged-attention backend ('pallas' kernel / 'xla' ref)."""
    if name not in PAGED_IMPLS:
        raise ValueError(f"unknown paged-attention impl {name!r}; "
                         f"registered: {PAGED_IMPLS}")
    _PAGED_IMPL["default"] = name


def paged_attention(q, k_pool, v_pool, page_map, cache_index, *,
                    window: int = 0, softcap: float = 0.0,
                    kq_pool=None, vq_pool=None, k_scale=None, v_scale=None,
                    impl: Optional[str] = None):
    """q: (B, 1, H, d) over a (P, page, K, d) pool via a (B, pp) page map.

    Ids >= P address the compressed side pool (decoded in the K/V load).
    Semantics match ``models/attention.decode_attention`` on the gathered
    view — the ref twin IS that path."""
    name = impl or _PAGED_IMPL["default"]
    if name == "pallas":
        return pa.paged_decode_attention(
            q, k_pool, v_pool, page_map, cache_index, window=window,
            softcap=softcap, kq_pool=kq_pool, vq_pool=vq_pool,
            k_scale=k_scale, v_scale=v_scale, interpret=_interpret())
    if name == "xla":
        return kref.paged_decode_attention_ref(
            q, k_pool, v_pool, page_map, cache_index, window=window,
            softcap=softcap, kq_pool=kq_pool, vq_pool=vq_pool,
            k_scale=k_scale, v_scale=v_scale)
    raise ValueError(f"unknown paged-attention impl {name!r}")


# ---------------------------------------------------------------------------
# training attention: the flash kernel pair where it can run, else the XLA
# blockwise twin.  The counter records, at trace time, which path each call
# took (a scanned layer stack traces its body once per trace).
_ATTENTION_CALLS = {"pallas": 0, "xla": 0}


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention_paths() -> Dict[str, int]:
    """Traced training-attention calls so far, by path."""
    return dict(_ATTENTION_CALLS)


def train_attention(q, k, v, *, causal: bool, window: int = 0,
                    softcap: float = 0.0, meshed: bool = False) -> jax.Array:
    """q: (B, S, H, hd); k/v: (B, T, K, hd) -> (B, S, H, hd).

    The Pallas kernel pair on a TPU without a multi-device mesh (a Pallas
    call has no GSPMD partitioning rule) and without a logit soft-cap;
    ``models/attention.blockwise_attention`` otherwise."""
    path = "pallas" if _on_tpu() and not meshed and softcap == 0 else "xla"
    _ATTENTION_CALLS[path] += 1
    if path == "pallas":
        return flash_attention(q, k, v, causal, window)
    from repro.models.attention import blockwise_attention
    return blockwise_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap)


def _heads_major(x):
    return x.swapaxes(1, 2)                 # (B, S, H, d) <-> (B, H, S, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, window: int = 0):
    """q: (B, S, H, hd); k/v: (B, T, K, hd) with H = K * G.  Pallas
    forward and Pallas backward (kernels/flash_attention.py); the only
    residuals are q, k, v, the output and its per-row logsumexp."""
    o = fa.flash_attention_fwd(_heads_major(q), _heads_major(k),
                               _heads_major(v), causal=causal, window=window,
                               interpret=_interpret())
    return _heads_major(o)


def _fa_fwd(q, k, v, causal, window):
    qh, kh, vh = _heads_major(q), _heads_major(k), _heads_major(v)
    o, lse = fa.flash_attention_fwd(qh, kh, vh, causal=causal, window=window,
                                    save_lse=True, interpret=_interpret())
    return _heads_major(o), (qh, kh, vh, o, lse)


def _fa_bwd(causal, window, res, g):
    qh, kh, vh, o, lse = res
    grads = fa.flash_attention_bwd(qh, kh, vh, o, lse, _heads_major(g),
                                   causal=causal, window=window,
                                   interpret=_interpret())
    return tuple(_heads_major(x) for x in grads)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
