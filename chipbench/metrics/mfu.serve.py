"""The whole serve step's share of the chip's roofline: for each step of
the traced window the least time its work requires (the larger of FLOPs
over peak and bytes over bandwidth: weights once per decode, keys and
values of the positions in use, prefill of admitted prompts, one read and
write of the weights per fold; ``windows/serve.cost``), summed, over the
window's time."""
from chipbench import costs


def read(m):
    rec, steps = m["rec"], m["cost"]["steps"]
    if not steps:
        return None
    least = sum(costs.least_seconds(f, b, m["peak"]) for f, b in steps)
    return 100.0 * least / (rec["elapsed_s"] * m["ctx"].chips)
