"""The phase readers (ge_ms, ma_ms, head_ms, mlp_ms) on a small recorded
trace of a program registered with ``repro.obs``: a phase's ops summed per
step, nested phases counted in both, loops left out, nothing read where
nothing maps."""
import sys
from types import SimpleNamespace as NS

import pytest

from chipbench import run, trace
from chipbench.tests.test_trace import ev

HLO = "\n".join([
    "HloModule jit_train_step, entry_computation_layout={(f32[8])->f32[8]}",
    "",
    "%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {",
    "  %t = (s32[], f32[8]{0}) parameter(0)",
    "  %copy.3 = f32[8]{0} copy(%t)",
    '  %rank1_matmul.4 = f32[8]{0} custom-call(%copy.3), metadata={op_name='
    '"jit(train_step)/seedflood.ge/vmap()/while/body/seedflood.mlp/'
    'pallas_call"}',
    '  %rank1_matmul.5 = f32[8]{0} custom-call(%copy.3), metadata={op_name='
    '"jit(train_step)/seedflood.ge/vmap()/while/body/pallas_call"}',
    "  ROOT %tuple.6 = (s32[], f32[8]{0}) tuple(%t, %rank1_matmul.4)",
    "}",
    "",
    "ENTRY %main (x: f32[8]) -> f32[8] {",
    '  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, metadata={op_name='
    '"jit(train_step)/seedflood.subspace/erf_inv"}',
    '  %while.2 = (s32[], f32[8]{0}) while(%fusion.1), body=%body, '
    'metadata={op_name="jit(train_step)/seedflood.ge/vmap()/while"}',
    '  %rank1_matmul_t.7 = f32[8]{0} custom-call(%while.2), metadata={'
    'op_name="jit(train_step)/seedflood.ge/vmap(seedflood.head)/'
    'pallas_call"}',
    '  %subcge_apply.8 = f32[8]{0} custom-call(%rank1_matmul_t.7), '
    'metadata={op_name="jit(train_step)/seedflood.ma/pallas_call"}',
    "  ROOT %copy.9 = f32[8]{0} copy(%subcge_apply.8)",
    "}",
])


class Program:
    """What ``obs.register`` reads of a compiled program."""

    def as_text(self):
        return HLO

    def runtime_executable(self):
        return NS(hlo_modules=lambda: [NS(name="jit_train_step")])


@pytest.fixture
def registered(monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "_programs", {})
    monkeypatch.setattr(obs, "_phases", {})
    return obs.register(Program())


def planes(module="jit_train_step"):
    # a window [0, 2000] of two steps; ns durations chosen so that each
    # phase's sum is distinct
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 2000)])])
    ops = []
    for t0 in (0, 1000):
        ops += [ev("%fusion.1 = f32[8] fusion(%x)", t0, 10),
                ev("%while.2 = (s32[], f32[8]) while(%fusion.1)", t0 + 10,
                   500),
                ev("%copy.3 = f32[8] copy(%t)", t0 + 10, 20),
                ev("%rank1_matmul.4 = f32[8] custom-call(%copy.3)", t0 + 30,
                   300),
                ev("%rank1_matmul.5 = f32[8] custom-call(%copy.3)", t0 + 330,
                   100),
                ev("%rank1_matmul_t.7 = f32[8] custom-call(%while.2)",
                   t0 + 510, 40),
                ev("%subcge_apply.8 = f32[8] custom-call(%x)", t0 + 550, 8),
                ev("%copy.9 = f32[8] copy(%subcge_apply.8)", t0 + 558, 2)]
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=ops),
        NS(name="XLA Modules", events=[ev(f"{module}(123)", 0, 600),
                                       ev(f"{module}(123)", 1000, 600)])])
    return [host, dev]


def read(name, pl, steps=2):
    return run.load_metric(name)({"trace": trace.reduce_planes(pl),
                                  "rec": {"steps": steps}})


@pytest.mark.parametrize("name,ns_per_step", [
    # copy.3 inherits the loop's ge; the while itself is left out
    ("ge_ms.train", 20 + 300 + 100 + 40),
    ("ma_ms.train", 8),
    ("head_ms.train", 40),
    ("mlp_ms.train", 300),
])
def test_phase_readers_sum_their_ops_per_step(registered, name,
                                              ns_per_step):
    assert read(name, planes()) == pytest.approx(ns_per_step * 1e-6)
    assert read(name, planes(), steps=1) == pytest.approx(
        2 * ns_per_step * 1e-6)


@pytest.mark.parametrize("name", ["ge_ms.train", "ma_ms.train",
                                  "head_ms.train", "mlp_ms.train"])
def test_phase_readers_read_nothing_where_nothing_maps(registered, name):
    assert read(name, planes(module="jit_other")) is None
    assert read(name, planes(), steps=0) is None


def test_phase_readers_read_nothing_without_a_registered_program(
        monkeypatch):
    import repro
    from repro import obs
    monkeypatch.setattr(obs, "_programs", {})
    monkeypatch.setattr(obs, "_phases", {})
    assert read("ge_ms.train", planes()) is None
    # a program that has no repro.obs at all
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read("ge_ms.train", planes()) is None
