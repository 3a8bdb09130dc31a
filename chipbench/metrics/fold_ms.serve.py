"""Device time of the live fold program (the bridge's jitted fold) per
fold in the traced window."""
from chipbench import trace


def read(m):
    n = m["cost"]["folds"]
    dev = m["trace"].op_seconds(trace.in_fold)
    if not n or dev <= 0:
        return None
    return 1000.0 * dev / n
