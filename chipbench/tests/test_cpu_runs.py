"""Whole runs on the CPU at reduced widths, kernels in Pallas interpret
mode: the look for a chip is skipped, the rest of a run is driven.  A sound
run is correct; a run whose timed path is broken underneath is not."""
import pytest

from chipbench import run
from chipbench.tests import cells


def cpu_run(cell, seconds=1.0, **kw):
    return run.run_cell(cell, 2**31 + 5, seconds, False, require_chip=False,
                        overrides=cells.overrides(cell, **kw))


@pytest.mark.parametrize("cell", ["train.opt-1.3b.c16",
                                  "train.qwen1.5-0.5b.c16",
                                  "serve.opt-1.3b.live"])
def test_sound_run_is_correct(cell):
    r = cpu_run(cell, seconds=2.0)
    assert r["correct"], r["check"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "check"
    assert "setup_s" in r["metrics"]


def _unchanged_step(monkeypatch):
    from repro.launch import train as trainlib
    orig = trainlib.compile_step

    def broken(*a, **k):
        step, in_sh, s = orig(*a, **k)

        def same(params, batch, t):
            return params, step(params, batch, t)[1]
        return same, in_sh, s
    monkeypatch.setattr(trainlib, "compile_step", broken)


def _half_batch(monkeypatch):
    from repro.models import transformer as tf
    orig = tf.lm_loss

    def half(cfg, params, batch, **kw):
        tok = batch["tokens"]
        return orig(cfg, params, {"tokens": tok[: tok.shape[0] // 2]}, **kw)
    monkeypatch.setattr(tf, "lm_loss", half)


def _token_altered(monkeypatch):
    from repro.serve import server
    orig = server.DecodeServer._sample

    def altered(self, row, rid, pos):
        tok = orig(self, row, rid, pos)
        return (tok + 1) % self.cfg.vocab if pos % 5 == 0 else tok
    monkeypatch.setattr(server.DecodeServer, "_sample", altered)


def _fold_unchanged(monkeypatch):
    from repro.serve import bridge
    orig = bridge.LiveUpdateBridge.fold

    def dropped(self, params):
        orig(self, params)
        return params
    monkeypatch.setattr(bridge.LiveUpdateBridge, "fold", dropped)


@pytest.mark.parametrize("cell,fault", [
    ("train.opt-1.3b.c16", _unchanged_step),
    ("train.opt-1.3b.c16", _half_batch),
    ("serve.opt-1.3b.live", _token_altered),
    ("serve.opt-1.3b.live", _fold_unchanged),
])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    r = cpu_run(cell)
    assert not r["correct"], r["check"]


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  run.benchmark_spec()["workloads"]])
def test_traced_run_reads_its_per_layer_metrics(cell, monkeypatch):
    """The CPU has no device trace: a recorded one stands in, so that the
    traced path from the profiler to the readers and the line runs."""
    from chipbench import trace
    from chipbench.tests.test_trace import recorded
    monkeypatch.setattr(trace, "reduce_dir",
                        lambda d: trace.reduce_planes(recorded()))
    r = run.run_cell(cell, 11, 1.0, True, require_chip=False,
                     overrides=cells.overrides(cell))
    bench = run.benchmark_spec()
    want = {e["name"] for e in run.cell_metrics(bench, cell, "per_layer")}
    assert set(r["metrics"]) <= want
    assert any(k.startswith("idle_share") for k in r["metrics"])
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(r)[-1] == "check"
