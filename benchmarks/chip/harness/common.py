"""What every runner shares: the program's configuration and mesh built
from the cell's files, device facts, compile counting and the traced
window."""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

from harness import spec
from harness import trace as tr


def _published(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The ModelConfig fields that the published keys of ``cfg`` state,
    each from the key that means the same thing."""
    out: Dict[str, Any] = {"name": cfg["name"]}
    for field, key, kind in (
            ("num_layers", "num_hidden_layers", int),
            ("d_model", "hidden_size", int),
            ("num_heads", "num_attention_heads", int),
            ("num_kv_heads", "num_key_value_heads", int),
            ("d_ff", "intermediate_size", int),
            ("vocab_size", "vocab_size", int),
            ("rope_theta", "rope_theta", float),
            ("tie_embeddings", "tie_word_embeddings", bool)):
        if key in cfg:
            out[field] = kind(cfg[key])
    if cfg.get("head_dim"):
        out["head_dim"] = int(cfg["head_dim"])
    if cfg.get("sliding_window"):
        out["attention"] = "swa"
        out["window"] = int(cfg["sliding_window"])
    if "torch_dtype" in cfg:
        out["dtype"] = cfg["torch_dtype"]
    return out


def model_config(cfg: Dict[str, Any]):
    """The program's ModelConfig for a configuration file: a dense
    decoder (RMSNorm, rotary attention, SwiGLU) from the published keys,
    with the fields of the file's ``program`` object over it.  A
    ``program`` field that is not a ModelConfig field, or that differs
    from the published key meaning the same thing, is refused: the file
    states the value that is run (a change goes under ``changed``)."""
    from repro.configs.base import ModelConfig
    program = dict(cfg.get("program", {}))
    fields = {f.name: f for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(program) - set(fields))
    if unknown:
        raise spec.SpecError(f"{cfg['name']}: program keys {unknown} are "
                             f"not fields of the program's ModelConfig")
    published = _published(cfg)
    clash = {k: (published[k], v) for k, v in program.items()
             if k in published and v != published[k]}
    if clash:
        raise spec.SpecError(f"{cfg['name']}: program values contradict "
                             f"the published keys (published, program): "
                             f"{clash}")
    if "act" not in program and cfg.get("hidden_act", "silu") != "silu":
        raise spec.SpecError(f"{cfg['name']}: hidden_act "
                             f"{cfg['hidden_act']!r} needs the program's "
                             f"act in the file's program object")
    args = dict(family="dense", head_dim=0, attention="full", window=4096,
                act="silu", norm="rmsnorm", dtype="bfloat16")
    args.update(published)
    args.update({k: tuple(v) if isinstance(v, list) else v
                 for k, v in program.items()})
    missing = sorted(k for k, f in fields.items() if k not in args
                     and f.default is dataclasses.MISSING)
    if missing:
        raise spec.SpecError(f"{cfg['name']}: neither the published keys "
                             f"nor the program object state {missing}")
    return ModelConfig(**args)


def mesh(cell: Dict[str, Any], devices):
    """The program's mesh over the cell's devices and its MeshPlan: on
    one chip no mesh, as the program runs unsharded there."""
    from repro.configs.base import MeshPlan
    shape, axes = spec.mesh(cell)
    if len(devices) == 1:
        return None, MeshPlan(shape, axes)
    from repro.launch.mesh import make_mesh
    return make_mesh(shape, axes, devices=devices), MeshPlan(shape, axes)


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
