"""Comparisons that decide ``correct``.

A gap of norms is taken leaf by leaf: the distance between the program's
norm and the reference's, over the reference's norm of that leaf or of the
median leaf, whichever is larger (some leaves barely move).  Leaves whose
reference update is under a thousandth of the median leaf's are left out:
they move by round-off alone.
"""
from __future__ import annotations

import statistics

#: a leaf whose reference update is under this share of the median leaf's
#: moves by round-off alone and is not compared
NEGLIGIBLE = 1e-3


def compared_leaves(update_ref: dict) -> list[str]:
    med = statistics.median(update_ref.values())
    return [k for k, v in update_ref.items() if v >= NEGLIGIBLE * med]


def worst_leaf_gap(prog: dict, ref: dict, update_ref: dict) -> float:
    """max over compared leaves of |‖prog‖ − ‖ref‖| / max(‖ref‖, median)."""
    keys = compared_leaves(update_ref)
    med = statistics.median(ref[k] for k in keys)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def worst_leaf_share(diff: dict, ref: dict) -> float:
    """max over compared leaves of ‖prog − ref‖ / max(‖Δref‖, median):
    the distance of two results, leaf by leaf, against the reference's
    change ``ref``."""
    keys = compared_leaves(ref)
    med = statistics.median(ref[k] for k in keys)
    return max(diff[k] / max(ref[k], med) for k in keys)


def widest_logit_gap(ref_logits, served) -> float:
    """How far the served tokens' reference logits lie below the
    reference's best: max over rows of max(logits) − logits[served]."""
    import numpy as np
    ref_logits = np.asarray(ref_logits, np.float32)
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, np.asarray(served)[:, None], -1)[:, 0]
    return float((best - got).max()) if len(best) else 0.0
