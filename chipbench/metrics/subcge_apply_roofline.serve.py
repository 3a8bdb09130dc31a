"""Share of the roofline the fold kernel reaches in the live fold: the
least time of ``W + U A Vᵀ`` over every matrix leaf per fold
(``costs.subcge_apply_cost``) over the kernel's device time in the fold
programs of the trace."""
from chipbench import costs, trace


def read(m):
    c, t = m["cost"], m["trace"]
    dev = t.op_seconds(lambda o: trace.in_fold(o)
                       and trace.kernel_of(o) == "subcge_apply")
    if not c["folds"] or dev <= 0:
        return None
    return 100.0 * costs.least_seconds(*c["fold"], m["peak"]) * c["folds"] \
        / dev
