"""What every runner shares: the program's configuration built from the
cell's file, device facts, compile counting and the traced window."""
from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

from harness import trace as tr


def model_config(cfg: Dict[str, Any]):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("only SwiGLU (silu) MLPs are described here")
    window = cfg.get("sliding_window") or 0
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or 0, d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        attention="swa" if window else "full", window=window or 4096,
        rope_theta=float(cfg["rope_theta"]), act="silu", norm="rmsnorm",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg.get("torch_dtype", "bfloat16"))


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileCounter:
    """Counts traces and XLA backend compiles while ``on``: inside the
    measured window both should stay at 0."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.traces = 0
        self.compiles = 0
        self.on = False
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._hear)

    def _hear(self, event: str, secs: float, **_: Any) -> None:
        if self.on:
            self.traces += event == self.TRACE
            self.compiles += event == self.COMPILE


@dataclasses.dataclass
class Traced:
    """A profiled stretch of the window and its reduction."""

    trace: Optional[Dict[str, Any]] = None
    lo: float = 0.0
    hi: float = 0.0

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self) -> float:
        return tr.busy_s(self.trace, self.lo, self.hi)

    def breakdown(self) -> Dict[str, List]:
        return {"device_ops": tr.top_ops(self.trace, self.lo, self.hi),
                "idle_gaps": tr.idle_gaps(self.trace, self.lo, self.hi)}


class Profiler:
    """Starts the JAX profiler into a scratch directory under TMPDIR and
    reduces what it wrote once stopped."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.result = Traced()

    @contextlib.contextmanager
    def window(self):
        import jax
        jax.profiler.start_trace(self.dir)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()
        self.result.trace = tr.load(tr.find_xplane(self.dir))
        self.result.lo, self.result.hi = tr.window(self.result.trace)
        shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A host span on the profiler's clock (no cost when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def now() -> float:
    return time.perf_counter()


def device_facts(devices) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
