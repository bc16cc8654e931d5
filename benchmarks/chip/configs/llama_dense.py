"""Plain reference of a dense decoder in float32 ``jax.numpy``.

RMSNorm, rotary attention with grouped KV heads and an optional sliding
window, a SwiGLU MLP, a tied or untied vocabulary head; no kernels, no
cache, no batching tricks.  It serves the configurations whose file names
``"reference": "llama_dense"``.  It imports nothing of the program under
test and takes nothing it made: it builds its own weights from the seed,
by the published initialisation the program documents (normal draws,
``d_in ** -0.5`` for projections, 0.02 for embeddings, ones for norm
scales, keys split in the same order), rounded to the configuration's
``torch_dtype`` because those are the weights the configuration serves.

Matmuls run at ``precision="highest"`` (a TPU would otherwise run float32
matmuls in bfloat16).  ``precision="fp8"`` is the control: every matmul
operand rounded to float8 e4m3 with a per-tensor scale, the step below
the configuration's bfloat16 that would tempt a later change.  In
training the backward's matmuls are fp8 too, as in fp8 training: they
take the rounded forward operands (straight-through) and the incoming
gradient rounded to float8 e5m2 under a per-tensor scale.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
E5M2_MAX = 57344.0
Q_BLOCK = 1024
PAD = 512


def dims(cfg: Dict[str, Any]):
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    K = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // H
    return D, H, K, hd, cfg["intermediate_size"], cfg["num_hidden_layers"], \
        cfg["vocab_size"], cfg.get("sliding_window") or 0


# ---------------------------------------------------------------------------
def init_params(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    """The configuration's weights from ``key``, as float32 arrays (one
    compiled program on the device)."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    return _init(items, key)


@partial(jax.jit, static_argnums=0)
def _init(items, key):
    cfg = dict(items)
    D, H, K, hd, F, L, V, _ = dims(cfg)
    dt = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    def dense(k, din, dout):
        return normal(k, (din, dout), din ** -0.5)

    def layer(k):
        ks = jax.random.split(k, 4)
        ka = jax.random.split(ks[0], 4)
        km = jax.random.split(ks[1], 3)
        return {"ln1": {"scale": jnp.ones((D,), jnp.float32)},
                "attn": {"wq": dense(ka[0], D, H * hd),
                         "wk": dense(ka[1], D, K * hd),
                         "wv": dense(ka[2], D, K * hd),
                         "wo": dense(ka[3], H * hd, D)},
                "ln2": {"scale": jnp.ones((D,), jnp.float32)},
                "mlp": {"w1": dense(km[0], D, F), "w2": dense(km[1], F, D),
                        "w3": dense(km[2], D, F)}}

    ks = jax.random.split(key, 8)
    p = {"embed": normal(ks[0], (V, D), 0.02),
         "final_norm": {"scale": jnp.ones((D,), jnp.float32)}}
    if not cfg["tie_word_embeddings"]:
        p["unembed"] = normal(ks[1], (V, D), 0.02)
    gk = jax.random.split(ks[3], 1)
    p["groups"] = {"sub_0": jax.vmap(layer)(jax.random.split(gk[0], L))}
    return jax.tree.map(lambda x: x.astype(jnp.float32), p)


# ---------------------------------------------------------------------------
def _quant(x, dtype, top):
    """``x`` rounded to the 8-bit float ``dtype`` under a per-tensor scale
    that maps its largest magnitude to ``top``."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _fp8(x):
    """Round to float8 e4m3 under a per-tensor scale, straight-through."""
    q = _quant(jax.lax.stop_gradient(x), jnp.float8_e4m3fn, F8_MAX)
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _fp8_cotangent(y):
    """The identity; its backward rounds the incoming gradient to float8
    e5m2 under a per-tensor scale, as an fp8 matmul's backward takes it."""
    return y


_fp8_cotangent.defvjp(lambda y: (y, None),
                      lambda _, g: (_quant(g, jnp.float8_e5m2, E5M2_MAX),))


def matmul(precision: str):
    if precision == "f32":
        return partial(jnp.einsum, precision=HIGHEST)
    if precision == "fp8":
        return lambda eq, a, b: _fp8_cotangent(
            jnp.einsum(eq, _fp8(a), _fp8(b), precision=HIGHEST))
    raise ValueError(f"unknown precision {precision!r}")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, pos, window, mm):
    """Causal (windowed) attention, one block of query rows at a time."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    q = q.reshape(B, S, K, H // K, hd) / math.sqrt(hd)
    outs = []
    for a in range(0, S, Q_BLOCK):
        qb = q[:, a:a + Q_BLOCK]
        s = mm("bqkgd,btkd->bkgqt", qb, k)
        dq = pos[:, a:a + Q_BLOCK, None] - pos[:, None, :]     # (B, q, t)
        vis = dq >= 0
        if window > 0:
            vis &= dq < window
        s = jnp.where(vis[:, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(mm("bkgqt,btkd->bqkgd", p, v))
    return jnp.concatenate(outs, axis=1).reshape(B, S, H * hd)


def hidden(params, cfg, tokens, pos, precision="f32"):
    """Final normed hidden states (B, S, D)."""
    D, H, K, hd, F, L, V, W = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mm = matmul(precision)
    x = params["embed"][tokens]
    B, S, _ = x.shape

    @jax.checkpoint
    def layer(x, lp):
        h = rmsnorm(x, lp["ln1"]["scale"], eps)
        a = lp["attn"]
        q = rope(mm("bsd,dh->bsh", h, a["wq"]).reshape(B, S, H, hd), pos,
                 theta)
        k = rope(mm("bsd,dh->bsh", h, a["wk"]).reshape(B, S, K, hd), pos,
                 theta)
        v = mm("bsd,dh->bsh", h, a["wv"]).reshape(B, S, K, hd)
        x = x + mm("bsh,hd->bsd", attention(q, k, v, pos, W, mm), a["wo"])
        h = rmsnorm(x, lp["ln2"]["scale"], eps)
        m = lp["mlp"]
        g = jax.nn.silu(mm("bsd,df->bsf", h, m["w1"])) \
            * mm("bsd,df->bsf", h, m["w3"])
        return x + mm("bsf,fd->bsd", g, m["w2"]), None

    x, _ = jax.lax.scan(layer, x, params["groups"]["sub_0"])
    return rmsnorm(x, params["final_norm"]["scale"], eps)


def head(params, cfg):
    return params["embed"] if cfg["tie_word_embeddings"] else \
        params["unembed"]


def nll_sum(params, cfg, tokens, labels, pos, precision="f32"):
    h = hidden(params, cfg, tokens, pos, precision)
    logits = matmul(precision)("bsd,vd->bsv", h, head(params, cfg))
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked)


# ---------------------------------------------------------------------------
def _leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(jnp.square(x))))
            for p, x in flat}


def lr_at(hp: Dict[str, Any], count: int) -> float:
    """Linear warm-up, then cosine decay to a tenth of the peak."""
    warm = min(count / max(hp["warmup_steps"], 1), 1.0)
    prog = min(max((count - hp["warmup_steps"])
                   / max(hp["total_steps"] - hp["warmup_steps"], 1), 0.0),
               1.0)
    return hp["learning_rate"] * warm * (0.1 + 0.45 * (1 + math.cos(
        math.pi * prog)))


def train_readings(cfg: Dict[str, Any], key, batches: Sequence[Dict],
                   hp: Dict[str, Any], precision: str = "f32",
                   rows_per_block: int = 2, keep_rows: str = "all"
                   ) -> Dict[str, Any]:
    """Loss of each step, the first clipped gradient's norm per leaf, and
    each leaf's change after ``len(batches)`` AdamW steps.

    ``keep_rows="half"`` is a planted fault: the loss and gradient of each
    step are the mean over the first half of the batch only."""
    params = init_params(cfg, key)
    p0 = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def block(params, tokens, labels, pos):
        return jax.value_and_grad(nll_sum)(params, cfg, tokens, labels, pos,
                                           precision)

    @jax.jit
    def update(params, m, v, grads, lr, count):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        clip = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        b1, b2, eps = hp["beta1"], hp["beta2"], hp["eps"]
        bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
        g = jax.tree.map(lambda x: x * clip, grads)
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        params = jax.tree.map(
            lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + eps)
                                      + hp["weight_decay"] * p),
            params, m, v)
        return params, m, v, g

    losses: List[float] = []
    out: Dict[str, Any] = {}
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, start=1):
            rows = batch["tokens"].shape[0]
            if keep_rows == "half":
                rows //= 2
            tot, grads = 0.0, None
            for a in range(0, rows, rows_per_block):
                sl = slice(a, min(a + rows_per_block, rows))
                l, g = block(params, jnp.asarray(batch["tokens"][sl]),
                             jnp.asarray(batch["labels"][sl]),
                             jnp.asarray(batch["positions"][sl]))
                tot += float(l)
                grads = g if grads is None else jax.tree.map(jnp.add, grads,
                                                             g)
            n = rows * batch["tokens"].shape[1]
            grads = jax.tree.map(lambda x: x / n, grads)
            losses.append(tot / n)
            params, m, v, clipped = update(params, m, v, grads,
                                           lr_at(hp, t), float(t))
            if t == 1:
                out["grad_norms"] = _leaf_norms(clipped)
                out["raw_grad_norms"] = _leaf_norms(grads)
        out["update_norms"] = _leaf_norms(
            jax.tree.map(jnp.subtract, params, p0))
    out["losses"] = losses
    return out


# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnums=(1, 4))
def _served(params, cfg_items, toks, pos, precision):
    """Logits at every position of one sequence."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        h = hidden(params, cfg, toks, pos, precision)[0]
        return matmul(precision)("sd,vd->sv", h, head(params, cfg))


def serve_gaps(cfg: Dict[str, Any], key, sessions: Sequence[Tuple],
               control: bool = False) -> Dict[str, float]:
    """For each (prompt, served tokens): how far each served token's
    reference logit lies below the reference's best at its position.
    With ``control``, also the same gap of the token that the fp8 control
    puts first at each position."""
    params = init_params(cfg, key)
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    worst, worst_ctl, n = 0.0, 0.0, 0
    for prompt, served in sessions:
        served = np.asarray(served, np.int64)
        seq = np.concatenate([np.asarray(prompt, np.int64), served])[:-1]
        # padded at the end to a multiple of PAD (causal: the padding
        # changes no earlier row), so that few lengths compile
        toks = np.zeros(-(-len(seq) // PAD) * PAD, np.int32)
        toks[:len(seq)] = seq
        toks = jnp.asarray(toks)[None]
        pos = jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
        rows = slice(len(prompt) - 1, len(seq))
        ref = np.asarray(_served(params, items, toks, pos, "f32"),
                         np.float64)[rows]
        best = ref.max(axis=1)
        gap = best - ref[np.arange(len(served)), served]
        worst = max(worst, float(gap.max()))
        n += len(served)
        if control:
            ctl = np.asarray(_served(params, items, toks, pos, "fp8"))[rows]
            pick = ctl.argmax(axis=1)
            worst_ctl = max(worst_ctl, float(
                (best - ref[np.arange(len(served)), pick]).max()))
    out = {"served_logit_gap": worst, "tokens": n}
    if control:
        out["control_logit_gap"] = worst_ctl
    return out
