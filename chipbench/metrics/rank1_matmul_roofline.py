"""Share of the roofline the fused rank-1 matmuls reach in the train step:
the least time of every ``rank1_matmul`` and ``rank1_matmul_t`` call of the
traced window (the larger of FLOPs over peak and bytes over bandwidth,
``costs.rank1_cost``) over their summed device time in the trace."""
from chipbench import costs, trace


def read(m):
    rec, c, t = m["rec"], m["cost"], m["trace"]
    dev = t.op_seconds(lambda o: trace.kernel_of(o) in ("rank1_matmul",
                                                        "rank1_matmul_t"))
    if not rec["steps"] or dev <= 0:
        return None
    least = sum(costs.least_seconds(f, b, m["peak"]) for _, f, b in c["rank1"])
    return 100.0 * least * c["rank1_per_step"] * rec["steps"] / dev
