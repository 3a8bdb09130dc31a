#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 chipbench/calibrate.py --cell <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 [--seconds 20] [--out calib.json] \\
        [--config <configuration, for a cell BENCHMARK.json does not list>]

In one process: the program's readings on every seed of ``--seeds`` (the
lower readings: the cell's own set-up, and for a serve cell its window at
the cell's load, each compared with the float32 reference as a run does),
and on ``--control-seeds`` the controls' readings (the upper ones): the
reference put in the program's place at the precision below the
configuration's (fp8 for bfloat16) and, for a train cell, the reference
with half of each client's batch left out.  A state left unchanged reads 1
on the norm gaps by construction and needs no run.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import compare, run  # noqa: E402

CONTROL_PREC = {"bfloat16": "fp8", "float16": "fp8", "float32": "bf16"}


def train_rows(window, ctx, control: bool) -> list[dict]:
    st = window.setup(ctx)
    prog = st.readings
    del st
    ref = window.reference_readings(ctx)
    rows = [{"seed": ctx.seed, "who": "program",
             **window.readings_gap(prog, ref)}]
    if control:
        low = CONTROL_PREC[ctx.config["dtype"]]
        rows.append({"seed": ctx.seed, "who": f"control_{low}",
                     **window.readings_gap(
                         window.reference_readings(ctx, low), ref)})
        rows.append({"seed": ctx.seed, "who": "fault_half_batch",
                     **window.readings_gap(window.reference_readings(
                         ctx, "f32", "half_batch"), ref)})
    return rows


def serve_rows(window, ctx, control: bool) -> list[dict]:
    import jax
    import numpy as np
    from chipbench import weights
    st = window.setup(ctx)
    rec = window.measure(st, ctx)
    prog_final = jax.device_get(st.srv.params)
    window.finish(st, ctx, rec)
    del st
    readings = ctx.program_readings
    low = CONTROL_PREC[ctx.config["dtype"]]
    precs = ("f32", low) if control else ("f32",)
    prog_gap, low_gap, final = 0.0, 0.0, None
    for k, active, served, lg in window.replay(ctx, readings, precs):
        if k < 0:
            final = lg["final_params"]
            continue
        ref_logits = np.asarray(lg["f32"])[active]
        prog_gap = max(prog_gap, compare.widest_logit_gap(ref_logits,
                                                          served[active]))
        if control:
            top = np.asarray(lg[low])[active].argmax(-1)
            low_gap = max(low_gap, compare.widest_logit_gap(ref_logits, top))
    norms = weights.leaf_norms_fn()
    p0 = weights.make(window.abstract_params(ctx), ctx.seed, ctx.dtype)

    def host(tree):
        return {k: float(v) for k, v in tree.items()}
    ref_change = host(norms(final, p0))

    def fold_numbers(params) -> dict:
        return {"fold_change_gap": compare.worst_leaf_gap(
                    host(norms(params, p0)), ref_change, ref_change),
                "fold_diff": compare.worst_leaf_share(
                    host(norms(params, final)), ref_change)}
    rows = [{"seed": ctx.seed, "who": "program", "logit_gap": prog_gap,
             **fold_numbers(jax.device_put(prog_final)),
             "steps": rec["steps"], "tokens": rec["tokens"],
             "folds": sum(1 for f in readings["folds"] if f)}]
    del prog_final
    if control:
        rows.append({"seed": ctx.seed, "who": f"control_{low}",
                     "logit_gap": low_gap,
                     **fold_numbers(window.folded_weights(ctx, readings,
                                                          low))})
    return rows


def summary(rows: list[dict]) -> dict:
    out = {}
    for who in sorted({r["who"] for r in rows}):
        mine = [r for r in rows if r["who"] == who]
        keys = [k for k in mine[0] if k not in ("seed", "who")]
        pick = max if who == "program" else min
        out[who] = {k: pick(r[k] for r in mine if k in r and
                            math.isfinite(r[k])) for k in keys}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cell", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--out", default="")
    p.add_argument("--config", default="",
                   help="the configuration of a cell that BENCHMARK.json "
                        "does not list yet (its traffic file has its name)")
    args = p.parse_args(argv)
    ov = ({"entry": {"name": args.cell, "config": args.config,
                     "traffic": args.cell, "chips": 1}}
          if args.config else None)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in seeds + sorted(controls - set(seeds)):
        ctx, window, _, _ = run.make_ctx(args.cell, seed, args.seconds,
                                         overrides=ov)
        fn = train_rows if ctx.workload["kind"] == "train" else serve_rows
        for row in fn(window, ctx, seed in controls):
            if row["who"] != "program" or seed in seeds:
                rows.append(row)
                print(json.dumps(row), flush=True)
    result = {"cell": args.cell, "rows": rows, "summary": summary(rows)}
    print(json.dumps(result["summary"]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
