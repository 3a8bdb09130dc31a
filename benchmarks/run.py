# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig5,...] \
        [--json results.json]

Default (fast) mode keeps every benchmark CPU-tractable; --full uses the
paper-scale settings where feasible.  Dry-run roofline rows are included
when results/dryrun/*.json exist (produced by repro.launch.dryrun_all).
``--json`` additionally dumps every CSV row plus every full RunResult
(via RunResult.to_json, so numpy/JAX scalars never break serialization).
"""
import argparse
import json
import time


def main(argv=None) -> int:
    """Run the tables; a failing table is reported as an ERROR row and the
    rest still run, but the exit code is then 1."""
    from benchmarks import paper_tables, roofline_table

    p = argparse.ArgumentParser()
    p.add_argument("--full", action="store_true")
    p.add_argument("--only", default="")
    p.add_argument("--skip-roofline", action="store_true")
    p.add_argument("--json", default="",
                   help="also write rows + RunResult dumps to this file")
    args = p.parse_args(argv)

    paper_tables.RUN_LOG.clear()   # per-invocation, not per-process

    names = list(paper_tables.ALL)
    if args.only:
        names = [n for n in names
                 if any(tok in n for tok in args.only.split(","))]

    all_rows = []
    failed = []

    def emit(tag, val, derived):
        all_rows.append({"name": tag, "value": val, "derived": derived})
        print(f"{tag},{val},{derived}", flush=True)

    print("name,us_per_call,derived")
    for name in names:
        fn = paper_tables.ALL[name]
        t0 = time.time()
        try:
            rows = fn(fast=not args.full)
        except Exception as e:  # keep the harness running, fail at the end
            emit(name, "ERROR", f"{type(e).__name__}: {e}")
            failed.append(name)
            continue
        for tag, val, derived in rows:
            emit(tag, val, derived)
        emit(f"{name}/_wall", f"{(time.time()-t0)*1e6:.0f}",
             "benchmark wall time")

    if not args.skip_roofline:
        try:
            recs = roofline_table.load()
            for tag, val, derived in roofline_table.csv_rows(recs):
                emit(tag, val, derived)
        except Exception as e:
            emit("roofline", "ERROR", str(e))
            failed.append("roofline")

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": all_rows, "runs": paper_tables.RUN_LOG}, f,
                      indent=2)
        print(f"wrote {args.json} ({len(paper_tables.RUN_LOG)} runs)")
    if failed:
        print(f"FAILED: {', '.join(failed)}")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
