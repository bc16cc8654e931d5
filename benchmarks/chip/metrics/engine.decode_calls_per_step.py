"""Decode calls per engine step over the window: the cache manager's own
decode counter (one per length group the engine decodes) over the number
of ``Engine.step`` calls.  1 means every resident session shares one
batched decode; more means the step splits by cache length."""


def read(m):
    c = m.counters
    if c.get("kind") != "serve" or not c.get("engine_steps"):
        return None
    return c["decode_calls"] / c["engine_steps"]
