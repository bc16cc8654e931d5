"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` the JAX profiler writes into plain
events: per device, the operations of its "XLA Ops" line; on the host,
the benchmark's own ``bench.*`` spans.  Everything else here works on
those lists, so the tests check it on a small recorded trace.

Times are nanoseconds on the profiler's clock, which it shares between
host and device planes.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench."
HOST_SPACE = "S(5)"          # pinned host memory in a TPU buffer's layout
COLLECTIVE = re.compile(r"all-to-all|all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|send|recv")


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _transfer_tag(hlo: str) -> str:
    if HOST_SPACE in hlo:
        return "host"
    if COLLECTIVE.search(short_name(hlo)):
        return "collective"
    return "device"


def _nest(events: List[Event]) -> List[list]:
    """[name, start, dur, self_ns, container] for one line's events, which
    nest (a ``while`` spans the operations of its body)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [[n, s, d, d, 0] for n, s, d in evs]
    stack: List[int] = []
    for i, (_, s, d) in enumerate(evs):
        while stack and out[stack[-1]][1] + out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= d
            out[stack[-1]][4] = 1
        stack.append(i)
    return out


def load(path: str) -> Dict[str, object]:
    """From an xplane file: per device, its operations (``ops``: name,
    start, duration, self time, whether it contains others) and its
    asynchronous transfers (``xfer``: name, start, duration, and where the
    bytes go: ``host``, ``collective`` or ``device``); on the host, the
    benchmark's ``bench.*`` spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(m.group(1), {"ops": [], "xfer": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = _nest([(short_name(e.name),
                                         float(e.start_ns),
                                         float(e.duration_ns))
                                        for e in line.events])
                elif line.name == ASYNC_LINE:
                    dev["xfer"] = [[short_name(e.name), float(e.start_ns),
                                    float(e.duration_ns),
                                    _transfer_tag(e.name)]
                                   for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


def is_wait(name: str) -> bool:
    """An operation that only waits for an asynchronous one to finish."""
    return re.sub(r"[.\d]+$", "", name).endswith("-done")


# ---------------------------------------------------------------------------
def _ev(rows) -> List[Event]:
    return [(r[0], r[1], r[2]) for r in rows]


def _clip(events: Iterable[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def length(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Parts of the (merged) intervals ``a`` not covered by merged ``b``."""
    out: List[Tuple[float, float]] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window(trace: Dict[str, object], span: str = "bench.window"
           ) -> Tuple[float, float]:
    """[start, end) of the host span that marks the traced window."""
    hits = [(s, s + d) for n, s, d in trace["host"] if n == span]
    if not hits:
        raise ValueError(f"the trace holds no {span!r} span")
    return hits[0]


def busy_s(trace: Dict[str, object], lo: float, hi: float) -> float:
    """Seconds in [lo, hi) in which some operation ran, averaged over the
    devices of the trace."""
    devs = trace["devices"]
    if not devs:
        return 0.0
    return sum(length(union(_clip(_ev(d["ops"]), lo, hi)))
               for d in devs.values()) / len(devs) / 1e9


def op_seconds(trace: Dict[str, object], lo: float, hi: float,
               match: Optional[Callable[[str], bool]] = None
               ) -> Dict[str, float]:
    """Device self seconds per operation name (the part of its time no
    nested operation covers) for operations that start in [lo, hi),
    divided by the number of devices."""
    devs = trace["devices"]
    out: Dict[str, float] = {}
    for d in devs.values():
        for name, s, _, self_ns, _ in d["ops"]:
            if lo <= s < hi and (match is None or match(name)):
                out[name] = out.get(name, 0.0) + self_ns / 1e9
    n = max(len(devs), 1)
    return {k: v / n for k, v in out.items()}


def exposed_s(trace: Dict[str, object], lo: float, hi: float,
              where: Sequence[str] = ("host", "collective")) -> float:
    """Seconds in [lo, hi) in which a transfer of the given kinds is in
    flight and the device computes nothing (no operation runs but ones
    that wait for a transfer), averaged over devices."""
    devs = trace["devices"]
    if not devs:
        return 0.0
    tot = 0.0
    for d in devs.values():
        xfer = union(_clip([(n, s, du) for n, s, du, w in d["xfer"]
                            if w in where], lo, hi))
        comp = union(_clip([(n, s, du) for n, s, du, _, box in d["ops"]
                            if not box and not is_wait(n)], lo, hi))
        tot += length(subtract(xfer, comp))
    return tot / len(devs) / 1e9


def idle_gaps(trace: Dict[str, object], lo: float, hi: float,
              top: int = 10) -> List[List[object]]:
    """Device idle time in [lo, hi) by what the host was doing: each gap
    between busy intervals (of the first device) is charged to the
    innermost ``bench.*`` span that holds its midpoint (``host.other``
    where none does).  The ``top`` names by total idle seconds."""
    devs = trace["devices"]
    if not devs:
        return []
    first = devs[sorted(devs)[0]]
    busy = union(_clip(_ev(first["ops"]), lo, hi))
    gaps = subtract([(lo, hi)], busy)
    spans = sorted(((s, s + d, n) for n, s, d in trace["host"]
                    if n != "bench.window"), key=lambda x: x[1] - x[0])
    out: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        name = next((n for s, e, n in spans if s <= mid < e), "host.other")
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])
            ][:top]


def top_ops(trace: Dict[str, object], lo: float, hi: float, top: int = 10
            ) -> List[List[object]]:
    ops = op_seconds(trace, lo, hi)
    return [[k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])
            ][:top]
