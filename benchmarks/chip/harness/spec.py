"""Find a cell's pieces by the names BENCHMARK.json gives them.

Everything that belongs to one configuration, traffic mix, per-layer
metric or cell lives in a file of its own, so that a later change adds a
cell by adding files:

* ``configs/<config>.json``  -- the sizes, as run; ``reference`` names the
  plain reference module beside it (``configs/<reference>.py``);
* ``traffic/<traffic>.json`` -- the mix's parameters (``traffic.py`` reads it);
* ``metrics/<metric>.py``    -- a reader with ``read(m) -> float | None``;
* ``limits/<workload>.json`` -- the limits of the numbers ``correct`` compares.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List

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
    return out


def metrics_for(workload: str, entries: List[Dict[str, Any]]
                ) -> List[Dict[str, Any]]:
    """The metrics of ``entries`` that this cell reports."""
    return [m for m in entries
            if "workloads" not in m or workload in m["workloads"]]
