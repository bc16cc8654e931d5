"""Model FLOP/s utilisation of serving: the forward FLOPs of the prompt
tokens prefilled and the tokens decoded in the traced window (sessions
only, not idle slots), over chips x peak x the window."""


def read(m):
    c = m.counters
    if c.get("kind") != "serve" or not c.get("model_flops"):
        return None
    peak = m.chip["bf16_flops_per_s"] * c["chips"]
    return 100.0 * c["model_flops"] / (peak * m.window_s)
