"""Device idle share of a training window: 1 - (union of the intervals in
which an operation ran) / window, averaged over the chips used."""


def read(m):
    if m.counters.get("kind") != "train":
        return None
    return 100.0 * (1.0 - m.busy_s() / m.window_s)
