"""Share of the traced window the device spends re-running layer forwards
for the backward (the memory tier's recompute, footnote 4 of the paper):
the self time of operations under the ``tier.recompute`` scope.  Read
from the device trace and its scope map (``harness/scopes.py``); silent
where the trace carries no scope map or the program names no such
scope."""
from harness import scopes


def read(m):
    t = m.traced
    if m.counters.get("kind") != "train" or not scopes.scoped(t.trace):
        return None
    s = scopes.scope_seconds(t.trace, t.lo, t.hi,
                             lambda names: "tier.recompute" in names)
    if s <= 0:
        return None
    return 100.0 * s / m.window_s
