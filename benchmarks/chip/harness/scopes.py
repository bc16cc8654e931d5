"""The program's named scopes in a reduced trace.

The training step names its work with ``jax.named_scope`` (``layers``,
``attention_core``, ``mlp``, ``tier.recompute``, ``loss``,
``optimizer``): HLO metadata, the ``op_name`` of each instruction.  A v5e
profile's operation events carry no ``op_name`` (their names are the
instructions' text without metadata), so the paths come from the
compiled program's text (``hlo_op_names``), matched by instruction name.

A trace as ``trace.load`` reduces it gains a per-device ``scope`` map
(instruction name -> ``op_name``; ``attach``); the functions here read
it, and read nothing where a device has none.  Times are nanoseconds on
the profiler's clock, seconds out, as in ``trace``.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Sequence

from harness.trace import short_name

OP_NAME = re.compile(r'op_name="([^"]*)"')
# the program's named scopes, in the order the step's partition takes them
SCOPES = ("tier.recompute", "attention_core", "mlp", "loss", "optimizer",
          "layers")
UNSCOPED = "unscoped"


def hlo_op_names(hlo: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` from a compiled module's text.  An
    instruction without one (a fusion, often) takes its called
    computation's root's, or else the first one that computation holds."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    comp_root: Dict[str, str] = {}
    comp_first: Dict[str, str] = {}
    comp = ""
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.-]+) .*\{$", line)
        if head and not line.startswith(" "):
            comp = head.group(1)
            continue
        body = line.strip()
        if " = " not in body:
            continue
        name = short_name(body.removeprefix("ROOT "))
        op = OP_NAME.search(body)
        if op:
            own[name] = op.group(1)
            comp_first.setdefault(comp, op.group(1))
            if body.startswith("ROOT "):
                comp_root[comp] = op.group(1)
        called = re.search(r"calls=%?([\w.-]+)", body)
        if called:
            calls[name] = called.group(1)
    out = dict(own)
    for name, c in calls.items():
        if name not in out:
            op = comp_root.get(c) or comp_first.get(c)
            if op:
                out[name] = op
    return out


def attach(trace: Dict[str, object], op_names: Dict[str, str]) -> None:
    """Give each device of a reduced trace the ``op_name`` of each of its
    operations that ``op_names`` knows (``scope``: name -> ``op_name``)."""
    for d in trace["devices"].values():
        d["scope"] = {r[0]: op_names[r[0]] for r in d["ops"]
                      if r[0] in op_names}


def scoped(trace: Dict[str, object]) -> bool:
    """Whether any device of the trace knows an operation's scope path."""
    return any(d.get("scope") for d in trace["devices"].values())


def scopes_of(op_name: str) -> List[str]:
    """The scope names on an ``op_name`` path, transforms taken off:
    ``jit(step)/transpose(jvp(layers))/while/body/tier.recompute/...``
    -> ``["step", "layers", "while", "body", "tier.recompute", ...]``."""
    return [re.sub(r"^(?:[\w]+\()+|\)+$", "", seg)
            for seg in op_name.split("/")]


def forward_scopes_of(op_name: str) -> List[str]:
    """The scope names on an ``op_name`` path that no ``transpose`` wraps.
    The backward that JAX derives from a scope's forward carries
    ``transpose(<scope>)``, so this leaves it out:
    ``.../body/transpose(tier.recompute)/jvp(attention_core)/...`` ->
    ``[..., "body", "attention_core", ...]``."""
    return [name for seg, name in zip(op_name.split("/"),
                                      scopes_of(op_name))
            if "transpose(" not in seg]


def scope_seconds(trace: Dict[str, object], lo: float, hi: float,
                  match: Callable[[List[str]], bool],
                  names: Callable[[str], List[str]] = scopes_of) -> float:
    """Device self seconds of the operations that start in [lo, hi) and
    whose scope names (``names`` of their op_name) ``match`` accepts,
    divided by the number of devices."""
    devs = trace["devices"]
    tot = 0.0
    for d in devs.values():
        scope = d.get("scope", {})
        for name, s, _, self_ns, _ in d["ops"]:
            if lo <= s < hi and match(names(scope.get(name, ""))):
                tot += self_ns
    return tot / max(len(devs), 1) / 1e9


def scope_split(trace: Dict[str, object], lo: float, hi: float,
                order: Sequence[str] = SCOPES) -> Dict[str, float]:
    """The device self seconds of operations that start in [lo, hi), each
    given to the first scope of ``order`` on its path (``unscoped`` where
    none is), divided by the number of devices."""
    devs = trace["devices"]
    out = {k: 0.0 for k in tuple(order) + (UNSCOPED,)}
    for d in devs.values():
        scope = d.get("scope", {})
        for name, s, _, self_ns, _ in d["ops"]:
            if lo <= s < hi:
                on = set(scopes_of(scope.get(name, "")))
                key = next((k for k in order if k in on), UNSCOPED)
                out[key] += self_ns / 1e9
    n = max(len(devs), 1)
    return {k: v / n for k, v in out.items()}
