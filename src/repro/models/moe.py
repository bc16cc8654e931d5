"""Mixture-of-Experts with expert parallelism over the 'model' mesh axis.

Two sharding regimes, selected by divisibility (DESIGN.md §5):

* **EP** (``E % model_size == 0``, e.g. llama4's 128 experts on 16-way TP):
  each model-column owns E/model_size experts; tokens are replicated over
  the model axis (they already are, under DP+TP), each column gathers only
  the tokens routed to *its* experts, and one ``psum`` over 'model' combines
  the expert outputs — no all-to-all required.

* **TP-in-expert** (``E % model_size != 0``, e.g. mixtral's 8 experts on a
  16-way axis): every column processes all experts with the FFN hidden dim
  sharded, and the same ``psum`` completes the row-parallel matmul.

Routing is capacity-based top-k (sort by expert id -> position-in-expert ->
drop overflow), the standard dense-shardable formulation.  The whole layer
runs under ``shard_map`` so the collective schedule is explicit and
deterministic; gradients flow through ``psum``/gather/scatter natively.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import ModelContext, dense_init

def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
def moe_init(key, cfg: ModelConfig, dtype) -> dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], D, E, jnp.float32),
        "w1": (jax.random.normal(ks[1], (E, D, F), jnp.float32)
               * (D ** -0.5)).astype(dtype),
        "w3": (jax.random.normal(ks[2], (E, D, F), jnp.float32)
               * (D ** -0.5)).astype(dtype),
        "w2": (jax.random.normal(ks[3], (E, F, D), jnp.float32)
               * (F ** -0.5)).astype(dtype),
    }
    if cfg.shared_experts:
        Fs = cfg.d_ff * cfg.shared_experts
        kk = jax.random.split(ks[4], 3)
        p["shared_w1"] = dense_init(kk[0], D, Fs, dtype)
        p["shared_w3"] = dense_init(kk[1], D, Fs, dtype)
        p["shared_w2"] = dense_init(kk[2], Fs, D, dtype)
    return p


def use_ep(cfg: ModelConfig, planner) -> bool:
    tp = planner.axes.size(planner.axes.tensor)
    return cfg.num_experts % max(tp, 1) == 0 and tp > 1


def moe_specs(cfg: ModelConfig, planner) -> dict:
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    fs, tp = planner.axes.fsdp, planner.axes.tensor
    s = planner.spec
    if use_ep(cfg, planner):
        sp = {
            "router": s((D, E), [None, None], "router"),
            "w1": s((E, D, F), [tp, fs, None], "moe_w1"),
            "w3": s((E, D, F), [tp, fs, None], "moe_w3"),
            "w2": s((E, F, D), [tp, None, fs], "moe_w2"),
        }
    else:
        sp = {
            "router": s((D, E), [None, None], "router"),
            "w1": s((E, D, F), [None, fs, tp], "moe_w1"),
            "w3": s((E, D, F), [None, fs, tp], "moe_w3"),
            "w2": s((E, F, D), [None, tp, fs], "moe_w2"),
        }
    if cfg.shared_experts:
        Fs = F * cfg.shared_experts
        sp["shared_w1"] = s((D, Fs), [fs, tp], "shared_w1")
        sp["shared_w3"] = s((D, Fs), [fs, tp], "shared_w3")
        sp["shared_w2"] = s((Fs, D), [tp, fs], "shared_w2")
    return sp


# ---------------------------------------------------------------------------
def _route(x2d: jax.Array, router: jax.Array, top_k: int, capacity: int,
           num_experts: int):
    """Capacity-based top-k routing.

    x2d: (T, D).  Returns (gather_idx (E, C) into [0, T] with T = dropped
    sentinel, combine_w (E, C), router_probs (T, E) for the aux loss).
    """
    T = x2d.shape[0]
    logits = x2d.astype(jnp.float32) @ router              # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)             # (T, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    flat_e = top_i.reshape(-1)                             # (T*k,)
    flat_w = top_p.reshape(-1)
    tok = jnp.arange(T * top_k, dtype=jnp.int32) // top_k
    order = jnp.argsort(flat_e)                            # stable
    e_sorted = flat_e[order]
    t_sorted = tok[order]
    w_sorted = flat_w[order]
    # rank within each expert group
    first = jnp.searchsorted(e_sorted, e_sorted, side="left")
    rank = jnp.arange(T * top_k, dtype=jnp.int32) - first.astype(jnp.int32)
    valid = rank < capacity
    gather_idx = jnp.full((num_experts, capacity), T, jnp.int32)
    combine_w = jnp.zeros((num_experts, capacity), jnp.float32)
    e_dst = jnp.where(valid, e_sorted, num_experts)        # overflow -> drop
    gather_idx = gather_idx.at[e_dst, rank].set(t_sorted, mode="drop")
    combine_w = combine_w.at[e_dst, rank].set(w_sorted, mode="drop")
    return gather_idx, combine_w, probs


def _expert_ffn(xe: jax.Array, w1, w3, w2) -> jax.Array:
    """xe: (e, C, D) -> (e, C, D), gated-SiLU experts."""
    h = jnp.einsum("ecd,edf->ecf", xe, w1)
    g = jnp.einsum("ecd,edf->ecf", xe, w3)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * g, w2)


def _moe_local(params: dict, x2d: jax.Array, cfg: ModelConfig,
               capacity: int, ep: bool, e_local: int,
               axis_name: Optional[str]) -> Tuple[jax.Array, jax.Array]:
    """Per-device MoE body.  x2d: (T, D) local tokens (replicated over the
    model axis).  Returns (out (T, D), aux load-balance loss)."""
    T, D = x2d.shape
    E, k = cfg.num_experts, cfg.top_k
    gather_idx, combine_w, probs = _route(
        x2d, params["router"], k, capacity, E)

    if ep and axis_name is not None:
        col = jax.lax.axis_index(axis_name)
        e0 = col * e_local
        gi = jax.lax.dynamic_slice_in_dim(gather_idx, e0, e_local, axis=0)
        cw = jax.lax.dynamic_slice_in_dim(combine_w, e0, e_local, axis=0)
    else:
        gi, cw = gather_idx, combine_w

    x_pad = jnp.concatenate([x2d, jnp.zeros((1, D), x2d.dtype)], axis=0)
    xe = x_pad[gi]                                         # (e, C, D)
    ye = _expert_ffn(xe, params["w1"], params["w3"], params["w2"])
    ye = ye * cw[..., None].astype(ye.dtype)
    out = jnp.zeros((T + 1, D), ye.dtype).at[gi].add(
        ye, mode="drop")[:T]

    if cfg.shared_experts:
        h = jax.nn.silu(x2d @ params["shared_w1"]) * (x2d @ params["shared_w3"])
        out = out + h @ params["shared_w2"]

    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)

    # Switch-style load-balance loss: E * sum_e f_e * p_e
    top1 = jnp.argmax(probs, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * p)
    return out, aux


# ---------------------------------------------------------------------------
def moe_block(params: dict, ctx: ModelContext, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,D) -> ((B,S,D), aux loss).  Dispatches to shard_map on a real
    mesh, plain local computation otherwise (smoke tests)."""
    cfg, planner, mesh = ctx.cfg, ctx.planner, ctx.mesh
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    ep = use_ep(cfg, planner) and mesh is not None and mesh.size > 1

    if mesh is None or mesh.size <= 1:
        x2d = x.reshape(-1, D)
        cap = _round_up(int(cfg.capacity_factor * x2d.shape[0] * k / E) or 1, 8)
        out, aux = _moe_local(params, x2d, cfg, cap, False, E, None)
        return out.reshape(B, S, D).astype(x.dtype), aux

    batch_axes = planner.axes.batch
    fsdp_axes = planner.axes.fsdp
    tp_axes = planner.axes.tensor
    tp_name = tp_axes[0] if tp_axes else None
    tp_size = planner.axes.size(tp_axes)
    dp_size = planner.axes.size(batch_axes)
    dp_eff = dp_size if B % max(dp_size, 1) == 0 else 1
    t_local = (B // dp_eff) * S
    cap = _round_up(int(cfg.capacity_factor * t_local * k / E) or 1, 8)
    e_local = E // tp_size if ep else E

    pspecs = moe_specs(cfg, planner)
    x_spec = planner.spec((B, S, D), [batch_axes, None, None], "moe_x")

    def body(params, xb):
        # ZeRO-3: transiently all-gather the FSDP ('data') shard of each
        # expert weight; the pooled copy stays resident.
        p = dict(params)
        if fsdp_axes:
            def ag(w, spec):
                for dim, part in enumerate(spec):
                    if part and set(_as_tuple(part)) & set(fsdp_axes):
                        return jax.lax.all_gather(w, fsdp_axes, axis=dim,
                                                  tiled=True)
                return w
            for key in p:
                p[key] = ag(p[key], pspecs[key])
        x2d = xb.reshape(-1, D)
        out, aux = _moe_local(p, x2d, cfg, cap, ep, e_local, tp_name)
        aux = jax.lax.pmean(aux, batch_axes) if batch_axes else aux
        return out.reshape(xb.shape).astype(xb.dtype), aux

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(params, x)
    return out, aux


def _as_tuple(part):
    return (part,) if isinstance(part, str) else tuple(part)
