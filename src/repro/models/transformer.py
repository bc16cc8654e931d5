"""Layer stacks for all 10 assigned architectures.

One scan-over-layers code path serves every family.  Each architecture is
described by its *group*: the repeating unit the scan iterates over.

  dense LM            group = ("dense",)                x L
  mixtral             group = ("moe",)                  x L
  llama4 (moe_every=2) group = ("dense","moe")          x L/2
  mamba2              group = ("ssm",)                  x L
  zamba2              group = ("ssm",)*6 + ("shared",)  x L/6   (shared-weight
                      attention block: params unstacked, one copy reused)
  whisper             encoder stack + decoder stack (self + cross attention)

Training wraps every sub-layer in the vDNN offload unit (core.offload): the
layer input is the stash unit, intermediates are recomputed — paper §III-B +
footnote 4.  Serving runs the raw sub-layers against (possibly pooled) KV /
SSM caches.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import frontends, moe as moe_mod, ssm as ssm_mod
from repro.models.attention import (attn_init, attn_specs, attention_block,
                                    cross_attention_block, encode_cross_kv,
                                    init_kv_cache)
from repro.models.layers import (ModelContext, activation_fn, apply_norm,
                                 dense_init, embed_init, norm_init,
                                 sinusoidal_pos)

Params = Dict[str, Any]

# Full-unroll switch for the dry-run FLOPs probes: XLA's cost_analysis
# counts while-loop bodies ONCE (not x trip count), so the roofline probes
# lower small unrolled stacks and extrapolate (launch/dryrun.py).
SCAN_UNROLL = False


def _unroll():
    return True if SCAN_UNROLL else 1


# ---------------------------------------------------------------------------
def arch_group(cfg: ModelConfig) -> Tuple[Tuple[str, ...], int]:
    """(group kinds, n_groups)."""
    if cfg.is_hybrid:
        k = cfg.hybrid_attn_every
        assert cfg.num_layers % k == 0
        return ("ssm",) * k + ("shared",), cfg.num_layers // k
    if cfg.is_ssm:
        return ("ssm",), cfg.num_layers
    if cfg.is_moe:
        if cfg.moe_every > 1:
            assert cfg.num_layers % cfg.moe_every == 0
            return ("dense",) * (cfg.moe_every - 1) + ("moe",), \
                cfg.num_layers // cfg.moe_every
        return ("moe",), cfg.num_layers
    return ("dense",), cfg.num_layers


# ---------------------------------------------------------------------------
# MLP
def mlp_init(key, cfg: ModelConfig, dtype) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    gated = cfg.act == "silu"
    ks = jax.random.split(key, 3)
    p = {"w1": dense_init(ks[0], D, F, dtype),
         "w2": dense_init(ks[1], F, D, dtype)}
    if gated:
        p["w3"] = dense_init(ks[2], D, F, dtype)
    return p


def mlp_specs(cfg: ModelConfig, planner) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    fs, tp = planner.axes.fsdp, planner.axes.tensor
    s = {"w1": planner.spec((D, F), [fs, tp], "w1"),
         "w2": planner.spec((F, D), [tp, fs], "w2")}
    if cfg.act == "silu":
        s["w3"] = planner.spec((D, F), [fs, tp], "w3")
    return s


@jax.named_scope("mlp")
def mlp_block(params: dict, ctx: ModelContext, x: jax.Array) -> jax.Array:
    act = activation_fn(ctx.cfg.act)
    h = jnp.einsum("bsd,df->bsf", x, params["w1"])
    h = ctx.act(h, "batch", None, "tensor")
    h = act(h)
    if "w3" in params:
        h = h * jnp.einsum("bsd,df->bsf", x, params["w3"])
    return jnp.einsum("bsf,fd->bsd", h, params["w2"])


# ---------------------------------------------------------------------------
# sub-layer init / specs
def sublayer_init(key, cfg: ModelConfig, dtype, kind: str) -> dict:
    ks = jax.random.split(key, 4)
    if kind == "ssm":
        return {"ln1": norm_init(cfg, cfg.d_model),
                "ssm": ssm_mod.mamba_init(ks[0], cfg, dtype)}
    if kind == "dec":    # whisper decoder layer (self + cross + mlp)
        return {"ln1": norm_init(cfg, cfg.d_model),
                "attn": attn_init(ks[0], cfg, dtype),
                "ln_x": norm_init(cfg, cfg.d_model),
                "cross": attn_init(ks[1], cfg, dtype),
                "ln2": norm_init(cfg, cfg.d_model),
                "mlp": mlp_init(ks[2], cfg, dtype)}
    p = {"ln1": norm_init(cfg, cfg.d_model),
         "attn": attn_init(ks[0], cfg, dtype)}
    if kind == "moe":
        p["ln2"] = norm_init(cfg, cfg.d_model)
        p["moe"] = moe_mod.moe_init(ks[1], cfg, dtype)
    else:                # dense / shared / enc
        if not cfg.parallel_block:
            p["ln2"] = norm_init(cfg, cfg.d_model)
        p["mlp"] = mlp_init(ks[1], cfg, dtype)
    return p


def sublayer_specs(cfg: ModelConfig, planner, kind: str) -> dict:
    def nspec(_):
        return {"scale": P()} if cfg.norm == "rmsnorm" else \
            {"scale": P(), "bias": P()}
    if kind == "ssm":
        return {"ln1": nspec(0), "ssm": ssm_mod.mamba_specs(cfg, planner)}
    if kind == "dec":
        return {"ln1": nspec(0), "attn": attn_specs(cfg, planner),
                "ln_x": nspec(0), "cross": attn_specs(cfg, planner),
                "ln2": nspec(0), "mlp": mlp_specs(cfg, planner)}
    s = {"ln1": nspec(0), "attn": attn_specs(cfg, planner)}
    if kind == "moe":
        s["ln2"] = nspec(0)
        s["moe"] = moe_mod.moe_specs(cfg, planner)
    else:
        if not cfg.parallel_block:
            s["ln2"] = nspec(0)
        s["mlp"] = mlp_specs(cfg, planner)
    return s


# ---------------------------------------------------------------------------
# sub-layer forward (train path; cache handled in serve path below)
def run_sublayer(kind: str, params: dict, ctx: ModelContext, x: jax.Array,
                 positions: jax.Array, enc_out: Optional[jax.Array] = None,
                 cache: Optional[dict] = None,
                 cache_index: Optional[jax.Array] = None,
                 causal: bool = True, use_rope: bool = True,
                 prefix_attend: bool = False,
                 paged: Optional[dict] = None
                 ) -> Tuple[jax.Array, jax.Array, Optional[dict]]:
    """Returns (x_out, aux_loss, new_cache)."""
    cfg = ctx.cfg
    zero = jnp.zeros((), jnp.float32)
    if kind == "ssm":
        h = apply_norm(cfg, params["ln1"], x)
        y, new_cache = ssm_mod.mamba_block(params["ssm"], ctx, h, cache)
        if ctx.mode == "train":
            y = ctx.resid(y)
        return x + y, zero, new_cache
    if kind == "dec":
        h = apply_norm(cfg, params["ln1"], x)
        a, new_cache = attention_block(
            params["attn"], ctx, h, positions, causal=True, cache=cache,
            cache_index=cache_index, use_rope=False)
        x = x + a
        h = apply_norm(cfg, params["ln_x"], x)
        if cache is not None and "ck" in cache:
            kv = {"k": cache["ck"], "v": cache["cv"]}
        else:
            kv = encode_cross_kv(params["cross"], cfg, enc_out)
        c = cross_attention_block(params["cross"], ctx, h, enc_kv=kv)
        x = x + c
        h = apply_norm(cfg, params["ln2"], x)
        x = x + mlp_block(params["mlp"], ctx, h)
        if new_cache is not None:
            new_cache = dict(new_cache, ck=kv["k"], cv=kv["v"])
        return x, zero, new_cache
    # dense / moe / shared / enc
    sp = ctx.resid if ctx.mode == "train" else (lambda t: t)
    h = apply_norm(cfg, params["ln1"], x)
    a, new_cache = attention_block(
        params["attn"], ctx, h, positions, causal=causal, cache=cache,
        cache_index=cache_index, use_rope=use_rope,
        prefix_attend=prefix_attend, paged=paged)
    # constrain TP-contraction outputs to the sequence-parallel layout at
    # the point of production: GSPMD then emits reduce-scatter (+ the
    # all-gather already inside the next layer's projections) instead of a
    # full all-reduce — half the wire bytes per sub-layer (§Perf).
    a = sp(a)
    if kind == "moe":
        x = x + a
        h = apply_norm(cfg, params["ln2"], x)
        m, aux = moe_mod.moe_block(params["moe"], ctx, h)
        return x + sp(m), aux, new_cache
    if cfg.parallel_block and kind in ("dense", "shared"):
        m = sp(mlp_block(params["mlp"], ctx, h))  # same ln1 input (cohere)
        return x + a + m, zero, new_cache
    x = x + a
    h = apply_norm(cfg, params["ln2"], x)
    x = x + sp(mlp_block(params["mlp"], ctx, h))
    return x, zero, new_cache


# ---------------------------------------------------------------------------
# parameter tree
def init_params(key, cfg: ModelConfig, dtype) -> Params:
    group, n_groups = arch_group(cfg)
    ks = jax.random.split(key, 8)
    p: Params = {"embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model,
                                     dtype),
                 "final_norm": norm_init(cfg, cfg.d_model)}
    if not cfg.tie_embeddings:
        p["unembed"] = embed_init(ks[1], cfg.padded_vocab, cfg.d_model, dtype)
    if cfg.frontend != "none":
        p["frontend"] = frontends.frontend_init(ks[2], cfg, dtype)

    def stack_init(subkey, kind, n):
        keys = jax.random.split(subkey, n)
        return jax.vmap(lambda k: sublayer_init(k, cfg, dtype, kind))(keys)

    groups: Params = {}
    gk = jax.random.split(ks[3], len(group))
    for j, kind in enumerate(group):
        if kind == "shared":
            continue
        groups[f"sub_{j}"] = stack_init(gk[j], kind, n_groups)
    p["groups"] = groups
    if "shared" in group:
        p["shared"] = sublayer_init(ks[4], cfg, dtype, "shared")
    if cfg.is_encoder_decoder:
        enc_keys = jax.random.split(ks[5], cfg.encoder_layers)
        p["encoder"] = {
            "layers": jax.vmap(
                lambda k: sublayer_init(k, cfg, dtype, "enc"))(enc_keys),
            "final_norm": norm_init(cfg, cfg.d_model),
        }
        # decoder layers are the scanned groups but of kind "dec"
        p["groups"] = {"sub_0": stack_init(gk[0], "dec", cfg.num_layers)}
    return p


def param_specs(cfg: ModelConfig, planner) -> Params:
    group, n_groups = arch_group(cfg)
    fs, tp = planner.axes.fsdp, planner.axes.tensor
    V, D = cfg.padded_vocab, cfg.d_model
    nspec = {"scale": P()} if cfg.norm == "rmsnorm" else \
        {"scale": P(), "bias": P()}
    s: Params = {"embed": planner.spec((V, D), [tp, fs], "embed"),
                 "final_norm": dict(nspec)}
    if not cfg.tie_embeddings:
        s["unembed"] = planner.spec((V, D), [tp, fs], "unembed")
    if cfg.frontend != "none":
        s["frontend"] = frontends.frontend_specs(cfg, planner)

    def stacked(spec_tree):
        return jax.tree.map(lambda sp: P(*((None,) + tuple(sp))), spec_tree,
                            is_leaf=lambda v: isinstance(v, P))

    groups: Params = {}
    for j, kind in enumerate(group):
        if kind == "shared":
            continue
        groups[f"sub_{j}"] = stacked(sublayer_specs(cfg, planner, kind))
    s["groups"] = groups
    if "shared" in group:
        s["shared"] = sublayer_specs(cfg, planner, "shared")
    if cfg.is_encoder_decoder:
        s["encoder"] = {
            "layers": stacked(sublayer_specs(cfg, planner, "enc")),
            "final_norm": dict(nspec),
        }
        s["groups"] = {"sub_0": stacked(sublayer_specs(cfg, planner, "dec"))}
    return s


# ---------------------------------------------------------------------------
# embedding / head
def embed_tokens(params: Params, ctx: ModelContext, tokens: jax.Array,
                 frames: Optional[jax.Array] = None,
                 patches: Optional[jax.Array] = None) -> jax.Array:
    cfg = ctx.cfg
    x = params["embed"][tokens]
    if cfg.frontend == "vision_stub" and patches is not None:
        x = frontends.merge_patches(params["frontend"], cfg, x, patches)
    if cfg.is_encoder_decoder:
        x = x + sinusoidal_pos(x.shape[1], cfg.d_model).astype(x.dtype)[None]
    if ctx.mode == "train":
        return ctx.resid(x)
    return ctx.act(x, "batch", None, None)


def unembed(params: Params, ctx: ModelContext, h: jax.Array) -> jax.Array:
    table = params["embed"] if ctx.cfg.tie_embeddings else params["unembed"]
    return jnp.einsum("bsd,vd->bsv", h, table)


# ---------------------------------------------------------------------------
# encoder (whisper)
def encode(params: Params, ctx: ModelContext, frames: jax.Array) -> jax.Array:
    cfg = ctx.cfg
    x = frontends.embed_frames(params["frontend"], cfg, frames)
    x = ctx.act(x, "batch", None, None)
    enc = params["encoder"]
    wrapped = ctx.wrap("enc_layer", functools.partial(_enc_layer, ctx))

    def body(carry, lp):
        return wrapped(lp, carry, jnp.zeros((), jnp.int32)), None

    x, _ = jax.lax.scan(body, x, enc["layers"], unroll=_unroll())
    return apply_norm(cfg, enc["final_norm"], x)


def _enc_layer(ctx, lp, x, _pos):
    y, _, _ = run_sublayer("enc", lp, ctx, x,
                           positions=jnp.zeros((x.shape[0], x.shape[1]),
                                               jnp.int32),
                           causal=False, use_rope=False)
    return y


# ---------------------------------------------------------------------------
# train forward
def forward_train(params: Params, ctx: ModelContext, tokens: jax.Array,
                  positions: jax.Array,
                  frames: Optional[jax.Array] = None,
                  patches: Optional[jax.Array] = None,
                  stash_groups: Optional[int] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Returns (hidden (B,S,D), aux_loss)."""
    cfg = ctx.cfg
    group, n_groups = arch_group(cfg)
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, ctx, frames)
        group = ("dec",)

    x = embed_tokens(params, ctx, tokens, frames, patches)
    use_rope = not cfg.is_encoder_decoder

    wrapped = {k: ctx.wrap(f"{k}_layer",
                           functools.partial(_train_sublayer, ctx, k,
                                             use_rope))
               for k in set(group)}

    def make_body(wrap: bool):
        def body(carry, gp):
            x, aux = carry
            for j, kind in enumerate(group):
                p = params["shared"] if kind == "shared" else gp[f"sub_{j}"]
                fn = wrapped[kind] if wrap else \
                    functools.partial(_train_sublayer, ctx, kind, use_rope)
                if cfg.is_encoder_decoder:
                    y, a = fn(p, x, positions, enc_out)
                else:
                    y, a = fn(p, x, positions)
                x, aux = ctx.resid(y), aux + a
            return (x, aux), None
        return body

    stacked = params["groups"]
    if stash_groups is None:
        stash_groups = n_groups
    g1 = max(0, min(n_groups, stash_groups))
    aux = jnp.zeros((), jnp.float32)
    # "layers" also names the host tier's stash and fetch: XLA folds them
    # into the scan's residual stacking, out of any scope inside the body
    with jax.named_scope("layers"):
        if g1 > 0:
            p1 = jax.tree.map(lambda l: l[:g1], stacked)
            with ctx.runtime.repeat(g1):
                (x, aux), _ = jax.lax.scan(make_body(True), (x, aux), p1,
                                           unroll=_unroll())
        if g1 < n_groups:
            p2 = jax.tree.map(lambda l: l[g1:], stacked)
            (x, aux), _ = jax.lax.scan(make_body(False), (x, aux), p2,
                                       unroll=_unroll())
    x = apply_norm(cfg, params["final_norm"], x)
    return x, aux


def _train_sublayer(ctx, kind, use_rope, p, x, positions, enc_out=None):
    y, aux, _ = run_sublayer(kind, p, ctx, x, positions, enc_out=enc_out,
                             use_rope=use_rope)
    return y, aux


# ---------------------------------------------------------------------------
# pipelined train forward: the scanned decoder stack split into contiguous
# stages over a dedicated pipe mesh axis (parallel/pipeline.py schedules).
def forward_train_pipelined(params: Params, ctx: ModelContext,
                            tokens: jax.Array, positions: jax.Array,
                            pipeline, pipe_mesh,
                            stage_runtime=None
                            ) -> Tuple[jax.Array, jax.Array]:
    """Returns (hidden (B,S,D), aux_loss).

    Each pipe-mesh member owns ``n_groups / n_stages`` contiguous layer
    groups; embed / final-norm / CE run replicated on every member (the
    pipeline output is broadcast).  The stage's saved activations are
    placed by the schedule: 1F1B routes them through ``stage_runtime``'s
    :class:`~repro.core.tiers.PipelineStageTier` (metered as
    ``act_stash``/``act_fetch``), GPipe keeps them implicitly live.  MoE
    aux losses are computed per microbatch (like gradient accumulation).
    """
    from repro.parallel.pipeline import get_schedule, make_pipelined

    cfg = ctx.cfg
    group, n_groups = arch_group(cfg)
    if cfg.is_encoder_decoder or cfg.frontend != "none" or \
            cfg.mrope_sections:
        raise ValueError("pipeline schedules support decoder-only stacks "
                         f"with batch-leading positions (got {cfg.name})")
    S = pipeline.n_stages or (pipe_mesh.shape[pipeline.axis_name]
                              if pipe_mesh is not None else 1)
    if n_groups % max(S, 1) != 0:
        raise ValueError(f"{n_groups} layer groups do not split into "
                         f"{S} stages")
    M = max(1, pipeline.n_micro)
    B = tokens.shape[0]
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by n_micro {M}")

    x = embed_tokens(params, ctx, tokens)

    def stage_fn(gp, tree):
        h, pos = tree["h"], tree["positions"]

        def body(carry, g1):
            h, aux = carry
            for j, kind in enumerate(group):
                p = params["shared"] if kind == "shared" else g1[f"sub_{j}"]
                y, a = _train_sublayer(ctx, kind, True, p, h, pos)
                # spread the scalar aux over the GLOBAL batch rows so it
                # rides the pipeline as ordinary activation data and the
                # final sum is the microbatch MEAN (matching grad-accum
                # semantics; a load-balance aux is batch-size-invariant,
                # so summing raw per-microbatch auxes would inflate it M x)
                h, aux = y, aux + a / B
            return (h, aux), None

        (h, aux), _ = jax.lax.scan(body, (h, tree["aux"]), gp,
                                   unroll=_unroll())
        return {"h": h, "positions": pos, "aux": aux}

    tree = {"h": x, "positions": positions,
            "aux": jnp.zeros((B,), jnp.float32)}
    schedule = get_schedule(pipeline.schedule, runtime=stage_runtime)
    if S <= 1 or pipe_mesh is None:
        out = schedule.run_local(stage_fn, params["groups"], tree, M)
    else:
        stage_params = jax.tree.map(
            lambda l: l.reshape((S, n_groups // S) + l.shape[1:]),
            params["groups"])
        pipe = make_pipelined(pipe_mesh, stage_fn, n_micro=M,
                              axis_name=pipeline.axis_name,
                              schedule=schedule)
        out = pipe(stage_params, tree)
    h = apply_norm(cfg, params["final_norm"], out["h"])
    return h, jnp.sum(out["aux"])


# ---------------------------------------------------------------------------
# serve forward (prefill S>1 / decode S==1) against stacked caches
def forward_serve(params: Params, ctx: ModelContext, tokens: jax.Array,
                  positions: jax.Array, caches: Params,
                  cache_index: jax.Array,
                  frames: Optional[jax.Array] = None,
                  patches: Optional[jax.Array] = None,
                  enc_out: Optional[jax.Array] = None,
                  prefix_attend: bool = False,
                  paged: Optional[dict] = None
                  ) -> Tuple[jax.Array, Params]:
    """``prefix_attend=True`` (static) runs the prefix-sharing *suffix*
    prefill: the S>1 tokens are the prompt's tail, written into the cache
    at ``cache_index`` with attention over the cache rows (the grafted
    shared-prefix pages included) instead of only the in-flight tokens —
    see attention.prefix_prefill_attention."""
    cfg = ctx.cfg
    group, n_groups = arch_group(cfg)
    if cfg.is_encoder_decoder:
        group = ("dec",)
        if enc_out is None and frames is not None:
            enc_out = encode_infer(params, ctx, frames)

    x = embed_tokens(params, ctx, tokens, frames, patches)
    if cfg.is_encoder_decoder and tokens.shape[1] == 1:
        # decode: positional encoding at the current index
        x = (params["embed"][tokens] +
             sinusoidal_pos(1, cfg.d_model, offset=cache_index
                            ).astype(x.dtype)[None])
        x = ctx.act(x, "batch", None, None)
    use_rope = not cfg.is_encoder_decoder

    def body(x, xs):
        gp, cache_g = xs
        new_g = {}
        for j, kind in enumerate(group):
            p = params["shared"] if kind == "shared" else gp[f"sub_{j}"]
            c = cache_g.get(f"sub_{j}")
            x, _, nc = run_sublayer(kind, p, ctx, x, positions,
                                    enc_out=enc_out, cache=c,
                                    cache_index=cache_index,
                                    use_rope=use_rope,
                                    prefix_attend=prefix_attend,
                                    paged=paged)
            if nc is not None:
                new_g[f"sub_{j}"] = nc
        return x, new_g

    x, new_caches = jax.lax.scan(body, x, (params["groups"], caches),
                                 unroll=_unroll())
    x = apply_norm(cfg, params["final_norm"], x)
    return x, new_caches


def encode_infer(params: Params, ctx: ModelContext, frames: jax.Array
                 ) -> jax.Array:
    cfg = ctx.cfg
    x = frontends.embed_frames(params["frontend"], cfg, frames)
    x = ctx.act(x, "batch", None, None)
    enc = params["encoder"]

    def body(carry, lp):
        y = _enc_layer(ctx, lp, carry, None)
        return y, None

    x, _ = jax.lax.scan(body, x, enc["layers"], unroll=_unroll())
    return apply_norm(cfg, enc["final_norm"], x)


# ---------------------------------------------------------------------------
# caches
def init_caches(cfg: ModelConfig, batch: int, seq: int, dtype) -> Params:
    """Stacked (n_groups, ...) caches matching forward_serve's scan."""
    group, n_groups = arch_group(cfg)
    if cfg.is_encoder_decoder:
        group = ("dec",)

    def one(kind):
        if kind == "ssm":
            return ssm_mod.init_ssm_cache(cfg, batch, dtype)
        c = init_kv_cache(cfg, batch, seq, dtype)
        if kind == "dec":
            K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            c["ck"] = jnp.zeros((batch, cfg.frontend_tokens, K, hd), dtype)
            c["cv"] = jnp.zeros((batch, cfg.frontend_tokens, K, hd), dtype)
        return c

    def stack(tree):
        return jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (n_groups,) + l.shape), tree)

    return {f"sub_{j}": stack(one(kind))
            for j, kind in enumerate(group) if kind != "none"}


def cache_specs(cfg: ModelConfig, planner, batch: int, seq: int) -> Params:
    """Pooled-KV sharding for serve caches: batch over data, sequence over
    'model' (the paper's technique applied to inference: the KV cache lives
    striped across the pooled HBM)."""
    group, n_groups = arch_group(cfg)
    if cfg.is_encoder_decoder:
        group = ("dec",)
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    b, tp = planner.axes.batch, planner.axes.tensor

    def one(kind):
        if kind == "ssm":
            conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
            return {
                "conv": planner.spec(
                    (n_groups, batch, cfg.ssm_conv_width - 1, conv_dim),
                    [None, b, None, tp], "conv_cache"),
                "ssm": planner.spec(
                    (n_groups, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                     cfg.ssm_state), [None, b, tp, None, None], "ssm_cache"),
            }
        kv = planner.spec((n_groups, batch, seq, K, hd),
                          [None, b, tp, None, None], "kv_cache")
        c = {"k": kv, "v": kv}
        if kind == "dec":
            ckv = planner.spec((n_groups, batch, cfg.frontend_tokens, K, hd),
                               [None, b, None, None, None], "cross_cache")
            c["ck"] = ckv
            c["cv"] = ckv
        return c

    return {f"sub_{j}": one(kind)
            for j, kind in enumerate(group) if kind != "none"}


def slot_cache(caches: Params, slot) -> Params:
    """Extract one batch slot of the stacked caches (batch dim kept at 1).

    Cache leaves are (n_groups, B, ...): batch is dim 1.  ``slot`` may be a
    Python int or a traced scalar — the serving KVCacheManager jits this for
    per-slot prefill and cold-slot spill."""
    return jax.tree.map(
        lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1), caches)


def merge_slot_cache(caches: Params, one_cache: Params, slot) -> Params:
    """Insert a single-slot cache (from :func:`slot_cache` or a spill-tier
    fetch) back into batch position ``slot`` of the stacked caches."""
    return jax.tree.map(
        lambda c, n: jax.lax.dynamic_update_slice_in_dim(
            c, n.astype(c.dtype), slot, axis=1),
        caches, one_cache)


# ---------------------------------------------------------------------------
# paged KV: the contiguous per-slot sequence axis becomes a pool of
# fixed-size pages — the paper's unit of pool placement applied to serving.
# Self-attention K/V leaves (batch dim 1, sequence dim 2) are paged; SSM
# state and cross-attention caches have no growing sequence axis and stay
# slot-shaped (one row per decode slot, spilled whole on preemption).
PAGED_KEYS = ("k", "v")


def split_paged(caches: Params) -> Tuple[Params, Params]:
    """Split a stacked cache tree into (paged kv leaves, slot-shaped rest).

    Both halves keep the ``sub_j`` group structure so
    :func:`gather_pages` can zip them back into the exact tree
    ``forward_serve`` scans over."""
    paged = {g: {k: v for k, v in sub.items() if k in PAGED_KEYS}
             for g, sub in caches.items()}
    rest = {g: {k: v for k, v in sub.items() if k not in PAGED_KEYS}
            for g, sub in caches.items()}
    return paged, rest


def paged_pool(caches: Params, page_size: int) -> Tuple[Params, Params]:
    """Rebuild the kv leaves of a freshly-initialised stacked cache as a
    page pool.

    Each (n_groups, B, S, K, hd) kv leaf becomes
    (n_groups, B*S/page_size + 1, page_size, K, hd): ``B * pages_per_slot``
    real pages plus ONE trailing scratch page (id ``num_pages``) that
    absorbs writes routed away by the slot mask — unowned page-map entries
    point there, so a scatter never needs dynamic shapes.

    Returns ``(pool_tree, slot_tree)``; raises ``ValueError`` when the
    architecture has no pageable KV (pure-SSM caches are O(1)/session and
    gain nothing from paging).
    """
    paged, rest = split_paged(caches)
    leaves = jax.tree_util.tree_leaves(paged)
    if not leaves:
        raise ValueError("paged KV needs attention k/v caches; this "
                         "architecture's cache has none (pure SSM?)")
    S = leaves[0].shape[2]
    if page_size < 1 or S % page_size != 0:
        raise ValueError(f"page_size {page_size} must divide max_len {S}")

    def to_pool(c):
        G, B, S_, K, hd = c.shape
        pages = c.reshape(G, B * (S_ // page_size), page_size, K, hd)
        scratch = jnp.zeros((G, 1, page_size, K, hd), c.dtype)
        return jnp.concatenate([pages, scratch], axis=1)

    return jax.tree.map(to_pool, paged), rest


def gather_pages(pool: Params, slot_tree: Params,
                 page_map: jax.Array) -> Params:
    """Materialise the contiguous decode view from the page pool.

    ``page_map``: (B, pages_per_slot) int32 page ids, logical page order
    per slot; unowned positions point at the scratch page (their rows are
    garbage, masked out of attention by ``cache_index``).  The result
    merges back with the slot-shaped leaves into the stacked tree shape
    ``forward_serve`` expects.
    """
    B, pp = page_map.shape
    flat = page_map.reshape(-1)

    def one(c):
        g = jnp.take(c, flat, axis=1)            # (G, B*pp, page, K, hd)
        G, _, page, K, hd = g.shape
        return g.reshape(G, B, pp * page, K, hd)

    gathered = jax.tree.map(one, pool)
    return {g: {**slot_tree.get(g, {}), **gathered.get(g, {})}
            for g in set(pool) | set(slot_tree)}


def scatter_pages(pool: Params, caches: Params,
                  page_map: jax.Array) -> Params:
    """Write a decode view's kv rows back into the pool.

    The caller routes every non-writable position of ``page_map`` (unowned
    pages, slots outside the current decode group) to the scratch page id —
    duplicate scratch indices overwrite each other, which is exactly the
    masked-dummy-write semantics of the unpaged merge."""
    paged, _ = split_paged(caches)
    B, pp = page_map.shape
    flat = page_map.reshape(-1)

    def one(p, c):
        G, B_, S, K, hd = c.shape
        pages = c.reshape(G, B_ * pp, S // pp, K, hd).astype(p.dtype)
        return p.at[:, flat].set(pages)

    return jax.tree.map(one, pool, paged)


def scatter_one_page(pool: Params, caches: Params, target: jax.Array,
                     row_start, page_size: int) -> Params:
    """Write back only the page a decode step touched.

    A decode step writes exactly one cache row (at ``cache_index``), so
    per slot only the page containing it changes: ``target`` is its (B,)
    pool ids (scratch for slots outside the decode group) and
    ``row_start`` the page-aligned row offset — shared by the whole
    length group.  A pages_per_slot-times smaller writeback than
    :func:`scatter_pages` (which prefill still uses: it fills many pages).
    """
    paged, _ = split_paged(caches)

    def one(p, c):
        w = jax.lax.dynamic_slice_in_dim(c, row_start, page_size, axis=2)
        return p.at[:, target].set(w.astype(p.dtype))

    return jax.tree.map(one, pool, paged)


def slot_pages(one_cache: Params, page_size: int, num_pages: int
               ) -> Tuple[List[Params], Params]:
    """Chop a single-slot cache into page-shaped KV chunks (prefill handoff).

    A prefill-only worker (serve/disagg.py) computes a prompt's KV in a
    plain contiguous slot — no pool, no page table — and ships the result
    to a decode runtime that *is* paged.  This helper is the boundary: the
    slot's paged leaves (``(G, 1, S, K, hd)``) become ``num_pages`` page
    trees shaped exactly like :func:`page_slice` output (``(G, page, K,
    hd)``), so the decode side lands them with :func:`page_insert`
    unchanged.  Returns ``(pages, rest)`` where ``rest`` holds the
    slot-shaped leaves (SSM / cross-attention state) that ship whole.
    """
    if page_size < 1 or num_pages < 1:
        raise ValueError(f"bad page chunking: {num_pages}x{page_size}")
    paged, rest = split_paged(one_cache)
    leaves = jax.tree_util.tree_leaves(paged)
    if leaves and num_pages * page_size > leaves[0].shape[2]:
        raise ValueError(f"{num_pages} pages of {page_size} rows exceed the "
                         f"slot's {leaves[0].shape[2]} cache rows")
    pages = [
        jax.tree.map(
            lambda c: jax.lax.dynamic_slice_in_dim(
                c, p * page_size, page_size, axis=2)[:, 0],
            paged)
        for p in range(num_pages)
    ]
    return pages, rest


def page_slice(pool: Params, pid) -> Params:
    """Extract one page (all groups) from the pool — the spill unit."""
    return jax.tree.map(
        lambda c: jax.lax.dynamic_slice_in_dim(c, pid, 1, axis=1)[:, 0],
        pool)


def page_insert(pool: Params, page: Params, pid) -> Params:
    """Write a fetched page back into pool position ``pid``."""
    return jax.tree.map(
        lambda c, n: jax.lax.dynamic_update_slice_in_dim(
            c, n.astype(c.dtype)[:, None], pid, axis=1),
        pool, page)
