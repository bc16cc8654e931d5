"""Find a cell's pieces by the names BENCHMARK.json gives them.

Everything that belongs to one configuration, traffic mix, per-layer
metric or cell lives in a file of its own, so that a later change adds a
configuration, a mix or a cell by adding files and entries, and edits
none of the harness:

* ``configs/<config>.json``  -- the sizes, as run, under the published
  keys.  An optional ``program`` object holds the fields of the program's
  ``ModelConfig`` that the published keys do not say (``family``, ``act``,
  ``num_experts``, ``top_k``, ``ssm_state``, ``hybrid_attn_every``, ...),
  by their field names: a hybrid, SSM or MoE model is described there
  (``common.model_config``).  ``reference`` names the plain reference
  module beside it (``configs/<reference>.py``);
* the reference module -- ``train_readings`` and ``serve_gaps``, what
  ``correct`` compares against, and optionally the configuration's own
  counts of operations and bytes: ``train_step_flops``,
  ``prefill_flops``, ``decode_flops``, ``paged_decode_need`` and
  ``attention_layers``, with the signatures of ``harness/flops.py``.
  That counts the dense SwiGLU decoder of the published keys and stands
  in for any count the module leaves out, but only in a file with no
  ``program`` object, whose module must define every count (``count``);
* ``traffic/<traffic>.json`` -- the mix's parameters (``traffic.py`` reads
  it); ``mesh`` (``{"shape": [...], "axes": [...]}``) lays the cell's
  chips out as the program's mesh, ``[chips]`` over ``data`` where absent
  (``mesh``);
* ``metrics/<metric>.py``    -- a reader with ``read(m) -> float | None``;
* ``limits/<workload>.json`` -- the limits of the numbers ``correct`` compares.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
from typing import Any, Callable, Dict, List, Tuple

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


class SpecError(ValueError):
    pass


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise SpecError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(name: str, base: str = HERE) -> Dict[str, Any]:
    path = os.path.join(base, "configs", f"{name}.json")
    if not os.path.exists(path):
        raise SpecError(f"no configuration file {path}")
    return _load_json(path)


def traffic(name: str, base: str = HERE) -> Dict[str, Any]:
    path = os.path.join(base, "traffic", f"{name}.json")
    if not os.path.exists(path):
        raise SpecError(f"no traffic file {path}")
    return _load_json(path)


def limits(workload: str, base: str = HERE) -> Dict[str, float]:
    path = os.path.join(base, "limits", f"{workload}.json")
    if not os.path.exists(path):
        raise SpecError(f"no limits file {path}")
    return {k: float(v["limit"]) for k, v in _load_json(path).items()}


def reference(cfg: Dict[str, Any], base: str = HERE):
    name = cfg["reference"]
    return load_module(os.path.join(base, "configs", f"{name}.py"),
                       f"chipbench_ref_{name}")


# the counts a reference module may define in place of harness/flops.py's
COUNTS = ("train_step_flops", "prefill_flops", "decode_flops",
          "paged_decode_need", "attention_layers")


def count(cfg: Dict[str, Any], name: str, base: str = HERE) -> Callable:
    """The configuration's count ``name``: its reference module's where
    that defines one, else ``harness/flops.py``'s.  That counts the dense
    SwiGLU decoder of the published keys alone, so a file with a
    ``program`` object, which may change what is built, brings its own."""
    if name not in COUNTS:
        raise SpecError(f"unknown count {name!r}; known: {COUNTS}")
    own = getattr(reference(cfg, base), name, None)
    if own is not None:
        return own
    if "program" in cfg:
        raise SpecError(f"configuration {cfg['name']!r} has a program "
                        f"object: its reference module must define {name} "
                        f"(harness/flops.py counts the dense SwiGLU decoder "
                        f"of the published keys)")
    from harness import flops
    return getattr(flops, name)


def mesh(cell: Dict[str, Any]) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """The shape and axis names the cell lays over its chips: the mix's
    ``mesh`` where it gives one, else all chips on ``data``."""
    chips = int(cell["chips"])
    m = cell["traffic_spec"].get("mesh")
    if m is None:
        return (chips,), ("data",)
    shape, axes = tuple(int(n) for n in m["shape"]), tuple(m["axes"])
    if len(shape) != len(axes) or len(set(axes)) != len(axes):
        raise SpecError(f"mesh {m} needs one distinct axis name per "
                        f"dimension")
    size = math.prod(shape)
    if size != chips:
        raise SpecError(f"mesh {m} holds {size} chips, the cell "
                        f"{cell.get('name')!r} asks for {chips}")
    return shape, axes


def metric_reader(name: str, base: str = HERE) -> Callable:
    path = os.path.join(base, "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"no reader {path} for metric {name}")
    return load_module(path, "chipbench_metric_" +
                       name.replace(".", "_").replace("-", "_")).read


def cell(workload: str, bench: Dict[str, Any]) -> Dict[str, Any]:
    """The workload entry with its configuration and traffic loaded."""
    for w in bench["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"known: {[w['name'] for w in bench['workloads']]}")
    out = dict(w)
    out["config_spec"] = config(w["config"])
    out["traffic_spec"] = traffic(w["traffic"])
    mesh(out)
    return out


def metrics_for(workload: str, entries: List[Dict[str, Any]]
                ) -> List[Dict[str, Any]]:
    """The metrics of ``entries`` that this cell reports."""
    return [m for m in entries
            if "workloads" not in m or workload in m["workloads"]]
