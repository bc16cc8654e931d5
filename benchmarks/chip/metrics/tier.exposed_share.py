"""Share of the traced window in which a memory-tier transfer is in flight
on the device and no computation runs (only operations that wait for a
transfer, or none): the host tier's copies to and from pinned host memory
on one chip, the pooled tier's collectives on several.  Read from the
device trace (asynchronous operations whose buffers live in host memory,
or collectives)."""


def read(m):
    c = m.counters
    if c.get("kind") != "train" or c.get("policy") == "none":
        return None
    return 100.0 * m.exposed_s(("host", "collective")) / m.window_s
