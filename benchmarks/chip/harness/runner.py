"""Run one cell once and print its result line.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``: each number ``correct`` compared,
beside its limit.  The checks are also the last lines of standard error.
A machine without the chips the cell asks for gets no result line and a
non-zero exit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

from harness import spec

CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")


class NoChip(RuntimeError):
    pass


def peaks() -> Dict[str, Dict[str, Any]]:
    with open(os.path.join(spec.HERE, "harness", "peaks.json")) as f:
        return json.load(f)


def check_devices(chips: int, require_tpu: bool = True):
    """The devices a cell runs on; the peak entry of their kind."""
    import jax
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    table = peaks()
    kind = devs[0].device_kind
    if require_tpu and kind not in table:
        raise NoChip(f"no peak figures for device kind {kind!r}; known: "
                     f"{sorted(table)}")
    return devs[:chips], table.get(kind, table["TPU v5 lite"])


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, cell: Optional[Dict[str, Any]] = None,
        limits: Optional[Dict[str, float]] = None,
        bench: Optional[Dict[str, Any]] = None,
        control: bool = False) -> Dict[str, Any]:
    """The result object of one run (``cell``/``limits``/``bench`` stand
    in for the files, for tests at small sizes).  ``control`` adds to the
    log what the limits' upper readings come from (``control.py``)."""
    bench = bench or spec.load_benchmark()
    cell = cell or spec.cell(workload, bench)
    devices, chip = check_devices(int(cell["chips"]), require_tpu)
    if require_tpu:
        enable_compile_cache()
    src = os.path.join(spec.ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    limits = limits if limits is not None else spec.limits(workload)
    kind = cell["traffic_spec"]["kind"]
    if kind == "train":
        from harness import train_cell as runner
    elif kind == "serve":
        from harness import serve_cell as runner
    else:
        raise spec.SpecError(f"unknown traffic kind {kind!r}")
    res = runner.run(cell, seed, seconds, trace, devices, limits, control)
    return assemble(workload, bench, res, devices, chip, trace)


def assemble(workload: str, bench: Dict[str, Any], res: Dict[str, Any],
             devices, chip: Dict[str, Any], trace: bool) -> Dict[str, Any]:
    from harness import common
    checks = res["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    metrics: Dict[str, Dict[str, Any]] = {}
    out: Dict[str, Any] = {"correct": correct,
                           "attempted": res["attempted"],
                           "failed": res["failed"]}
    device = dict(common.device_facts(devices),
                  memory_peak_bytes=res["memory_peak_bytes"])
    if not trace:
        for m in spec.metrics_for(workload, bench["end_to_end"]):
            metrics[m["name"]] = {"value": res["e2e"][m["name"]],
                                  "unit": m["unit"]}
    else:
        tr = res["traced"]
        m_in = Measurement(res["counters"], tr, chip)
        for m in spec.metrics_for(workload, bench["per_layer"]):
            v = spec.metric_reader(m["name"])(m_in)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        out["breakdown"] = tr.breakdown()
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = checks
    out["log"] = dict(res.get("log", {}), end_to_end=res["e2e"])
    return out


class Measurement:
    """What a per-layer reader reads: the runner's counters for the traced
    window, the reduced trace, and the chip's peaks."""

    def __init__(self, counters: Dict[str, Any], traced, chip):
        self.counters = counters
        self.traced = traced
        self.chip = chip

    @property
    def window_s(self) -> float:
        return self.traced.window_s

    def busy_s(self) -> float:
        return self.traced.busy_s()

    def op_seconds(self, match) -> float:
        from harness import trace as tr
        t = self.traced
        return sum(tr.op_seconds(t.trace, t.lo, t.hi, match).values())

    def exposed_s(self, where) -> float:
        from harness import trace as tr
        t = self.traced
        return tr.exposed_s(t.trace, t.lo, t.hi, where)


def emit(out: Dict[str, Any]) -> None:
    """Checks last on stderr; the result line last on stdout."""
    log = out.pop("log", {})
    print(json.dumps({"log": log}, default=float), file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json once, on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 3
    emit(out)
    return 0
