"""Stash compression — the memory-node's "optional compression ASIC" (§III-A).

The paper's memory-node architecture (Fig. 6) reserves a slot for an ASIC
"that handles encryption or compression".  On TPU the analogue is a fused
quantize-and-pack executed *before* the stash collective, halving (fp8) the
bytes that cross the ICI and that occupy the pool.

This module owns the **codec registry**: every stash codec is a
:class:`Codec` carrying four twins of the same transform —

  ``compress``/``decompress``   pure-jnp per-tensor scale (the default data
                                path and the kernel oracle)
  ``pack``/``unpack``           blockwise Pallas kernel twins
                                (``kernels/offload_pack.py``), plus their
                                pure-jnp references ``pack_ref``/``unpack_ref``
                                (``kernels/ref.py``)

so a consumer (``CompressedTier``, the paged KV spill path, tests) can pick
the granularity/backend it needs and the test suite can assert kernel ≡ ref
for *every* registered codec without naming them.  New codecs are one
:func:`register_codec` call; ``core.tiers`` re-exports the registry for
back-compat.

Also provides int8 error-feedback quantization for compressed gradient
all-reduce (beyond-paper distributed-optimization trick; cf. the paper's
§V-B citation of the Compressing-DMA-Engine work [56] as a traffic
reduction technique).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

FP8_MAX = 448.0                 # float8_e4m3fn dynamic range
INT8_MAX = 127.0


# ---------------------------------------------------------------------------
# fp8 stash compression (per-tensor scale; kernels/offload_pack fuses this)
def fp8_compress(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x -> (fp8 payload, fp32 scale).  Halves stash bytes vs bf16."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.maximum(absmax / FP8_MAX, 1e-12)
    q = (x.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
    return q, scale


def fp8_decompress(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# int8 stash compression (per-tensor scale; kernels/offload_pack has the
# blockwise Pallas twin) — registered as a stash codec below
def int8_compress(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x -> (int8 payload, fp32 scale).  Halves stash bytes vs bf16."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.maximum(absmax / INT8_MAX, 1e-30)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                 -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scale


def int8_decompress(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# block-sparse stash compression: int8 quantization + magnitude pruning.
# Entries below absmax/BLOCKSPARSE_TAU become EXACT zeros, so the payload
# is dense-shaped but zero-run-rich — what a wire-side run-length/entropy
# stage (the memory node's compression ASIC slot, §III-A) feeds on.
# Decode needs no sparsity metadata: zeros dequantize to zero.
# Must equal kernels/offload_pack.BLOCKSPARSE_TAU (mirrored here, like
# FP8_MAX/INT8_MAX, to keep pallas out of core's import path; the codec
# tests pin the two constants together).
BLOCKSPARSE_TAU = 32.0


def blocksparse_compress(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x -> (magnitude-pruned int8 payload, fp32 scale)."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf))
    scale = jnp.maximum(absmax / INT8_MAX, 1e-30)
    q = jnp.clip(jnp.round(xf / scale), -INT8_MAX, INT8_MAX)
    keep = jnp.abs(xf) >= absmax / BLOCKSPARSE_TAU
    return jnp.where(keep, q, 0.0).astype(jnp.int8), scale


#: pruned zeros dequantize to zero — decode IS the int8 decode
blocksparse_decompress = int8_decompress


# ---------------------------------------------------------------------------
# int8 error-feedback gradient compression
def int8_ef_quantize(g: jax.Array, err: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Quantize gradient+carried error to int8 with a per-tensor scale.

    Returns (int8 payload, scale, new_error).  The residual (quantization
    error) is fed back into the next step — guarantees convergence of the
    compressed all-reduce (error-feedback SGD).
    """
    corrected = g.astype(jnp.float32) + err
    absmax = jnp.max(jnp.abs(corrected))
    scale = jnp.maximum(absmax / INT8_MAX, 1e-30)
    q = jnp.clip(jnp.round(corrected / scale), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    new_err = corrected - q.astype(jnp.float32) * scale
    return q, scale, new_err


def int8_dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# codec registry — the memory-node's "optional compression ASIC" (§III-A)
@dataclasses.dataclass(frozen=True)
class Codec:
    """One stash codec: the ref transform plus its optional kernel twins.

    ``pack``/``unpack`` take ``(x_2d, *, block_rows, interpret)`` /
    ``(q_2d, scales, *, block_rows, dtype, interpret)`` — the
    kernels/offload_pack signature; ``pack_ref``/``unpack_ref`` are the
    pure-jnp blockwise twins the tests assert against.  Codecs without a
    kernel twin leave them ``None``.
    """

    name: str
    ratio: float                                   # stashed bytes per raw byte
    compress: Callable[[jax.Array], Tuple[jax.Array, jax.Array]]
    decompress: Callable[..., jax.Array]           # (q, scale, dtype) -> x
    pack: Optional[Callable[..., Tuple[jax.Array, jax.Array]]] = None
    unpack: Optional[Callable[..., jax.Array]] = None
    pack_ref: Optional[Callable[..., Tuple[jax.Array, jax.Array]]] = None
    unpack_ref: Optional[Callable[..., jax.Array]] = None

    def applies_to(self, x: jax.Array) -> bool:
        return jnp.issubdtype(x.dtype, jnp.floating)

    @property
    def has_kernel(self) -> bool:
        return self.pack is not None and self.unpack is not None


_CODECS: Dict[str, Codec] = {}


def register_codec(codec: Codec) -> None:
    _CODECS[codec.name] = codec


def get_codec(name: str) -> Codec:
    if name not in _CODECS:
        raise KeyError(f"unknown stash codec {name!r}; "
                       f"registered: {sorted(_CODECS)}")
    return _CODECS[name]


def registered_codecs() -> Tuple[str, ...]:
    return tuple(sorted(_CODECS))


def _register_builtin_codecs() -> None:
    # runs at import time; the function only keeps the module namespace
    # clean.  Pulling in repro.kernels here is free of new dependencies —
    # pallas ships inside jax — and pallas code is only *executed* when a
    # kernel twin is actually called.
    from repro.kernels import offload_pack as kp
    from repro.kernels import ref as kref
    register_codec(Codec("fp8", 0.5, fp8_compress, fp8_decompress,
                         pack=kp.fp8_pack, unpack=kp.fp8_unpack,
                         pack_ref=kref.fp8_pack_ref,
                         unpack_ref=kref.fp8_unpack_ref))
    register_codec(Codec("int8", 0.5, int8_compress, int8_decompress,
                         pack=kp.int8_pack, unpack=kp.int8_unpack,
                         pack_ref=kref.int8_pack_ref,
                         unpack_ref=kref.int8_unpack_ref))
    register_codec(Codec("blocksparse", 0.5,
                         blocksparse_compress, blocksparse_decompress,
                         pack=kp.blocksparse_pack,
                         unpack=kp.blocksparse_unpack,
                         pack_ref=kref.blocksparse_pack_ref,
                         unpack_ref=kref.blocksparse_unpack_ref))


_register_builtin_codecs()


# ---------------------------------------------------------------------------
# whole-tensor encode/decode through a codec — the per-page spill path.
# kernel=True routes through the Pallas twin as ONE block (page-granular
# scale, bit-identical to the ref per-tensor path by construction); the
# kernel runs compiled on a TPU and interpreted elsewhere
# (``kernels.ops._interpret``, the one place that chooses).
def encode_tensor(codec: Codec, x: jax.Array, *, kernel: bool = False
                  ) -> Tuple[jax.Array, jax.Array]:
    """Quantize ``x`` (any shape) with one per-tensor scale.

    Returns ``(q, scale)`` with ``q.shape == x.shape``.  ``kernel=True``
    uses the codec's Pallas pack twin on the flattened 2D view.
    """
    if kernel and codec.has_kernel:
        from repro.kernels.ops import _interpret
        x2 = x.reshape(-1, x.shape[-1])
        q2, scales = codec.pack(x2, block_rows=x2.shape[0],
                                interpret=_interpret())
        return q2.reshape(x.shape), scales[0]
    return codec.compress(x)


def decode_tensor(codec: Codec, q: jax.Array, scale: jax.Array,
                  dtype=jnp.bfloat16, *, kernel: bool = False) -> jax.Array:
    if kernel and codec.has_kernel:
        from repro.kernels.ops import _interpret
        q2 = q.reshape(-1, q.shape[-1])
        x2 = codec.unpack(q2, scale.reshape(1), block_rows=q2.shape[0],
                          dtype=dtype, interpret=_interpret())
        return x2.reshape(q.shape)
    return codec.decompress(q, scale, dtype)


def compress_ratio(kind: str) -> float:
    """Bytes multiplier vs bf16 (used by the cost model and the simulator)."""
    if kind == "none":
        return 1.0
    return get_codec(kind).ratio
