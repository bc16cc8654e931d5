"""Share of the traced window the device spends in the attention core
(scores, softmax and values: the part a flash kernel would replace): the
self time of operations with an ``attention_core`` scope on their path,
in the forward, the tier's recompute and the backward alike.  Read from
the device trace and its scope map (``harness/scopes.py``); silent where
the trace carries no scope map or the program names no such scope."""
from harness import scopes


def read(m):
    t = m.traced
    if m.counters.get("kind") != "train" or not scopes.scoped(t.trace):
        return None
    s = scopes.scope_seconds(t.trace, t.lo, t.hi,
                             lambda names: "attention_core" in names)
    if s <= 0:
        return None
    return 100.0 * s / m.window_s
