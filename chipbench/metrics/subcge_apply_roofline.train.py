"""Share of the roofline the fold kernel reaches in the train step: the
least time of ``W + U A Vᵀ`` over every matrix leaf (one read and one write
of each weight, ``costs.subcge_apply_cost``) per step, over the kernel's
device time in the trace."""
from chipbench import costs, trace


def read(m):
    rec, c, t = m["rec"], m["cost"], m["trace"]
    dev = t.op_seconds(lambda o: trace.kernel_of(o) == "subcge_apply")
    if not rec["steps"] or dev <= 0:
        return None
    return 100.0 * costs.least_seconds(*c["subcge_apply"], m["peak"]) \
        * rec["steps"] / dev
