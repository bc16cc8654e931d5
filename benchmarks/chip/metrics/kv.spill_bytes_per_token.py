"""Bytes the KV cache manager moved to and from its spill tier (its own
``kv_stash`` + ``kv_fetch`` wire-byte counters, counted per page moved)
per output token, over the window.  Silent where nothing spilled."""


def read(m):
    c = m.counters
    if c.get("kind") != "serve" or not c.get("spill_bytes") \
            or not c.get("tokens"):
        return None
    return c["spill_bytes"] / c["tokens"]
