"""Model FLOP/s utilisation of training: the forward and backward FLOPs the
model needs (3x forward, recomputation not counted) of the traced steps,
over chips x peak x the traced window."""


def read(m):
    c = m.counters
    if c.get("kind") != "train" or not c.get("steps"):
        return None
    peak = m.chip["bf16_flops_per_s"] * c["chips"]
    return 100.0 * c["model_flops"] / (peak * m.window_s)
