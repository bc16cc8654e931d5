"""Shared layer primitives: norms, activations, RoPE/M-RoPE, embeddings,
chunked cross-entropy.  Everything is a pure function over explicit param
dicts — no framework magic, fully pjit/shard_map compatible."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import MemoryPlan, ModelConfig
from repro.parallel.sharding import ShardingPlanner, constrain


@dataclasses.dataclass
class ModelContext:
    """Everything the pure model functions need besides params/inputs."""

    cfg: ModelConfig
    planner: ShardingPlanner
    memory: MemoryPlan
    mesh: Optional[Mesh] = None
    mode: str = "train"                  # train | prefill | decode
    runtime: Optional["MemoryRuntime"] = None

    def __post_init__(self):
        if self.runtime is None:
            from repro.core.runtime import MemoryRuntime
            self.runtime = MemoryRuntime(self.planner.plan, self.memory,
                                         self.mesh, planner=self.planner)

    def constrain(self, x: jax.Array, assignment) -> jax.Array:
        if self.mesh is None or self.mesh.size == 1:
            return x
        spec = self.planner.spec(x.shape, assignment, name="act")
        return constrain(x, self.mesh, spec)

    def act(self, x: jax.Array, *roles: Optional[str]) -> jax.Array:
        """Constrain an activation by logical dim roles.

        Roles: "batch", "seq" (sequence-parallel over the model axis),
        "heads", "tensor", None.
        """
        ax = self.planner.axes
        table = {None: None, "batch": ax.batch, "seq": ax.tensor,
                 "heads": ax.tensor, "tensor": ax.tensor,
                 "pool": ("data", "model")}
        return self.constrain(x, [table[r] for r in roles])

    def resid(self, x: jax.Array) -> jax.Array:
        """Residual-stream layout between layers: sequence-parallel over the
        'model' axis (Megatron-SP) when enabled — a (B,S,D) copy costs
        1/tp per device; layer-internal einsums gather/reduce-scatter S as
        part of their collectives."""
        if self.memory.seq_parallel:
            return self.act(x, "batch", "seq", None)
        return self.act(x, "batch", None, None)

    def wrap(self, name: str, fn):
        """vDNN-wrap a sub-layer for training (MemoryRuntime.wrap_layer):
        the layer's input feature map is stashed to the configured memory
        tier, intermediates are recomputed in backward.  No-op for serving
        and for a tier whose stash stays in this device's HBM (no
        offloading, or a pool of one device)."""
        if self.mode != "train" or not self.runtime.moves_bytes:
            return fn
        return self.runtime.wrap_layer(fn, batch_dim=0, name=name)


# ---------------------------------------------------------------------------
# dtype helpers
def param_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def activation_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# norms
def rmsnorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype=jnp.float32)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(dt)


def layernorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(dt)


def norm_init(cfg: ModelConfig, d: int) -> dict:
    return layernorm_init(d, None) if cfg.norm == "layernorm" else rmsnorm_init(d, None)


def apply_norm(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    return layernorm(params, x) if cfg.norm == "layernorm" else rmsnorm(params, x)


# ---------------------------------------------------------------------------
# dense / embedding init
def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE (+ M-RoPE for qwen2-vl)
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections: Tuple[int, ...] = ()) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) or (3, B, S) for M-RoPE."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    if mrope_sections:
        assert positions.ndim == 3, "M-RoPE needs (3, B, S) positions"
        secs = list(mrope_sections)
        assert sum(secs) == hd // 2, (secs, hd)
        pos_parts = []
        start = 0
        for axis_i, sec in enumerate(secs):
            pos_parts.append(jnp.broadcast_to(
                positions[axis_i][..., None], positions.shape[1:] + (sec,)))
            start += sec
        pos = jnp.concatenate(pos_parts, axis=-1)       # (B, S, hd/2)
        ang = pos.astype(jnp.float32) * freqs           # (B, S, hd/2)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(seq: int, d: int, offset=0) -> jax.Array:
    """Sinusoidal positional encoding (whisper enc/dec; any length)."""
    pos = jnp.arange(seq, dtype=jnp.float32) + offset
    half = d // 2
    freq = jnp.exp(-jnp.log(10_000.0) * jnp.arange(half, dtype=jnp.float32)
                   / max(half - 1, 1))
    ang = pos[:, None] * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# chunked cross-entropy (keeps (B,S,V) logits out of live memory)
def chunked_cross_entropy(h: jax.Array, embed: jax.Array, labels: jax.Array,
                          mask: Optional[jax.Array] = None,
                          chunk: int = 512,
                          constrain_logits=None) -> Tuple[jax.Array, jax.Array]:
    """h: (B, S, D); embed: (V, D) (tied head) — returns (mean loss, n_tokens).

    Scans over S in chunks so the full logits tensor is never resident.
    constrain_logits: optional fn applied to each (B, chunk, V) logits block
    — vocab-parallel sharding (V over 'model') keeps the block at V/tp per
    device; the logsumexp reductions become cheap psums.
    """
    B, S, D = h.shape
    V = embed.shape[0]
    chunk = max(chunk, -(-S // 8))     # <=8 chunks (the scan is unrolled)
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad))) if mask is not None else \
            jnp.pad(jnp.ones((B, S), jnp.float32), ((0, 0), (0, pad)))
    elif mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    n_chunks = h.shape[1] // chunk
    hc = h.reshape(B, n_chunks, chunk, D).swapaxes(0, 1)
    lc = labels.reshape(B, n_chunks, chunk).swapaxes(0, 1)
    mc = mask.reshape(B, n_chunks, chunk).swapaxes(0, 1)

    @jax.checkpoint          # recompute the chunk logits in backward — the
    def body(carry, xs):     # scan must NOT save (B,chunk,V) per step
        tot, cnt = carry
        hh, ll, mm = xs
        logits = jnp.einsum("bsd,vd->bsv", hh, embed).astype(jnp.float32)
        if constrain_logits is not None:
            logits = constrain_logits(logits)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        nll = (lse - picked) * mm
        return (tot + jnp.sum(nll), cnt + jnp.sum(mm)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                                 (hc, lc, mc), unroll=True)
    return tot / jnp.maximum(cnt, 1.0), cnt
