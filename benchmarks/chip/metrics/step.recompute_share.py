"""Share of the traced window the device spends re-running layer forwards
for the backward (the memory tier's recompute, footnote 4 of the paper):
the self time of operations under a ``tier.recompute`` scope that no
``transpose`` wraps.  The backward of the re-run forward, the attention's
backward kernels among it, carries ``transpose(tier.recompute)`` and is
not counted (``scopes.forward_scopes_of``).  Read from the device trace
and its scope map (``harness/scopes.py``); silent where the trace carries
no scope map or the program names no such scope."""
from harness import scopes


def read(m):
    t = m.traced
    if m.counters.get("kind") != "train" or not scopes.scoped(t.trace):
        return None
    s = scopes.scope_seconds(t.trace, t.lo, t.hi,
                             lambda names: "tier.recompute" in names,
                             names=scopes.forward_scopes_of)
    if s <= 0:
        return None
    return 100.0 * s / m.window_s
