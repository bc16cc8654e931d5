"""Operations and bytes the algorithm needs, from shapes alone.

The model is a dense decoder (RMSNorm, rotary GQA attention with an
optional sliding window, SwiGLU MLP).  "Needed" counts each matmul once,
as 2 x multiply-adds, and attention over the (query, visible key) pairs:
no recomputation, no padding, no work for slots that carry no session.
"""
from __future__ import annotations

from typing import Any, Dict


def _dims(cfg: Dict[str, Any]):
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    K = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // H
    F = cfg["intermediate_size"]
    L = cfg["num_hidden_layers"]
    V = cfg["vocab_size"]
    W = cfg.get("sliding_window") or 0
    return D, H, K, hd, F, L, V, W


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    D, H, K, hd, F, _, _, _ = _dims(cfg)
    return D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F


def visible_pairs(length: int, window: int) -> int:
    """(query, key) pairs of causal self-attention over ``length`` tokens."""
    if window <= 0 or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def forward_flops(cfg: Dict[str, Any], tokens: int, pairs: int,
                  head_rows: int) -> float:
    """Forward FLOPs of ``tokens`` positions through every layer, with
    ``pairs`` attention (query, key) pairs per layer and ``head_rows``
    positions through the vocabulary projection."""
    D, H, K, hd, F, L, V, W = _dims(cfg)
    return (2.0 * L * layer_matmul_params(cfg) * tokens
            + 4.0 * L * H * hd * pairs
            + 2.0 * V * D * head_rows)


def train_step_flops(cfg: Dict[str, Any], batch: int, seq: int) -> float:
    """Forward and backward (3x forward) of one step; recompute excluded."""
    W = _dims(cfg)[7]
    return 3.0 * forward_flops(cfg, batch * seq,
                               batch * visible_pairs(seq, W), batch * seq)


def prefill_flops(cfg: Dict[str, Any], prompt: int) -> float:
    """A prompt's prefill: every layer over the prompt, the head over the
    last position only (that is all the engine samples from)."""
    W = _dims(cfg)[7]
    return forward_flops(cfg, prompt, visible_pairs(prompt, W), 1)


def decode_flops(cfg: Dict[str, Any], length: int) -> float:
    """One decode token written at row ``length`` (attends rows 0..length,
    inside the window)."""
    W = _dims(cfg)[7]
    rows = length + 1 if W <= 0 else min(length + 1, W)
    return forward_flops(cfg, 1, rows, 1)


def paged_decode_need(cfg: Dict[str, Any], length: int, n_active: int,
                      page: int, itemsize: int = 2) -> Dict[str, float]:
    """What one layer's paged-attention call needs for ``n_active``
    sessions at ``length`` cache rows: the K and V pages holding the rows
    it can see (rows 0..length, inside the window), the query and the
    output; and the attention FLOPs over those rows."""
    D, H, K, hd, F, L, V, W = _dims(cfg)
    lo = 0 if W <= 0 else max(0, length - W + 1) // page
    pages = -(-(length + 1) // page) - lo
    rows = length + 1 if W <= 0 else min(length + 1, W)
    kv = 2.0 * pages * page * K * hd * itemsize
    qo = 2.0 * H * hd * itemsize
    return {"bytes": n_active * (kv + qo),
            "flops": n_active * 4.0 * H * hd * rows}
