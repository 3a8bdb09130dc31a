"""The control: the reference put in the program's place one precision
below the configuration's (fp8 for bfloat16) reads not correct, where the
program passes; so does the planted half-batch fault.  Reduced widths,
bfloat16, on the CPU — the chip's readings are in PERF.md."""
import pytest

from chipbench import calibrate, run
from chipbench.tests import cells


@pytest.mark.parametrize("cell", ["train.opt-1.3b.c16",
                                  "serve.opt-1.3b.live"])
def test_control_is_not_correct(cell):
    ov = cells.overrides(cell, backend="jnp", dtype="bfloat16")
    rows = []
    for seed in (1, 2):
        ctx, window, _, _ = run.make_ctx(cell, seed, 2.0, require_chip=False,
                                         overrides=ov)
        rows_fn = (calibrate.train_rows if cell.startswith("train.")
                   else calibrate.serve_rows)
        rows += rows_fn(window, ctx, True)
    limits = {k: v for k, v in cells.BF16_LIMITS.items()
              if k in rows[0]}
    for r in rows:
        verdict = all(c["ok"] for c in run.judge(r, limits).values()
                      if c["value"] is not None)
        assert verdict == (r["who"] == "program"), r
