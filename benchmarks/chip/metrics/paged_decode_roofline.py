"""The paged-attention decode kernel's share of its roofline.

For each decode call in the traced window, every layer's kernel needs the
K and V pages holding the rows its sessions can see (up to their length,
inside the sliding window), the query and the output, and the attention
FLOPs over those rows (``harness/flops.paged_decode_need``).  The least
time the chip could take is the larger of bytes over peak bandwidth and
FLOPs over peak FLOP/s, summed over calls and layers; the share is that
over the kernel's device time in the trace.  The bound is bandwidth at
every decode shape here (a few FLOPs per byte)."""
import re

KERNEL = re.compile(r"paged", re.IGNORECASE)


def read(m):
    c = m.counters
    if c.get("kind") != "serve" or not c.get("decode_kernel"):
        return None
    need = c.get("paged_need") or []
    if not need:
        return None
    t = m.op_seconds(lambda n: bool(KERNEL.search(n)))
    if t <= 0:
        return None
    bw, peak = m.chip["hbm_bytes_per_s"], m.chip["bf16_flops_per_s"]
    least = c["layers"] * sum(max(x["bytes"] / bw, x["flops"] / peak)
                              for x in need)
    return 100.0 * least / t
