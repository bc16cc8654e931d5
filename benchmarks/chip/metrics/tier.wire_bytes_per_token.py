"""Bytes the memory tier moved to and from its backing store (the
runtime's executed ``stash`` + ``fetch`` wire-byte counters) in the traced
steps, per token trained, from the runner's counters ``tier_wire_bytes``
and ``tokens``.  Silent where the runner gives no such counters or
nothing moved."""


def read(m):
    c = m.counters
    if c.get("kind") != "train" or not c.get("tier_wire_bytes") \
            or not c.get("tokens"):
        return None
    return c["tier_wire_bytes"] / c["tokens"]
