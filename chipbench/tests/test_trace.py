"""trace.py on a small recorded trace: busy is the union of op intervals,
gaps are attributed to the innermost benchmark span open in them."""
from types import SimpleNamespace as NS

import pytest

from chipbench import trace


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=stats)


def recorded():
    # host: a window [100, 1100] holding two steps and an ingest
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 100, 1000),
        ev("bench.step", 100, 500),
        ev("bench.ingest", 600, 100),
        ev("server.step", 700, 400),
        ev("unrelated", 0, 2000),
    ])])
    # device: overlapping ops (union, not sum), one op straddling the
    # window's start, a gap [400, 650) inside step then ingest, idle tail
    # (ops are named by their HLO text, as a TPU trace names them; a loop
    # encloses its body's ops on the same line)
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            ev("%while.7 = (s32[]) while(%tuple.1), body=%region_0", 50, 350),
            ev("%fusion.1 = bf16[8,2048] fusion(bf16[8,2048] "
               "%rank1_matmul.5), kind=kLoop", 50, 150),
            ev("%rank1_matmul.5 = bf16[512,2048] custom-call(bf16[512,2048] "
               "%x)", 150, 200),
            ev("%vmap_jit_rank1_matmul_t__ = bf16[16,512,50272] "
               "custom-call(%y)", 250, 150),
            ev("%subcge_apply.8 = bf16[1,4096,2048] custom-call(%w)", 650,
               250),
        ]),
        NS(name="XLA Modules", events=[
            ev("jit_train_step(6463510849197169833)", 50, 400),
            ev("jit_fold(5186521979061897628)", 640, 270)]),
    ])
    return [host, dev]


def test_busy_is_the_union_inside_the_window():
    r = trace.reduce_planes(recorded())
    assert r.window == (100, 1100)
    assert r.window_s == pytest.approx(1000e-9)
    # [100, 400) ∪ [650, 900) = 300 + 250 ns
    assert r.busy_s() == pytest.approx(550e-9)


def test_gaps_are_attributed_by_span():
    r = trace.reduce_planes(recorded())
    gaps = r.idle_gaps()
    # [400, 650): midpoint 525 lies in bench.step; [900, 1100): midpoint
    # 1000 in server.step
    assert gaps[0] == ["bench.step", pytest.approx(250e-9)]
    assert gaps[1] == ["server.step", pytest.approx(200e-9)]


def test_kernels_and_fold_are_told_apart():
    r = trace.reduce_planes(recorded())
    kinds = {o.name: trace.kernel_of(o) for o in r.ops}
    # a fusion that reads a kernel's output is not that kernel
    assert kinds == {"while.7": None, "fusion.1": None,
                     "rank1_matmul.5": "rank1_matmul",
                     "vmap_jit_rank1_matmul_t__": "rank1_matmul_t",
                     "subcge_apply.8": "subcge_apply"}
    assert {o.name: o.module for o in r.ops}["subcge_apply.8"] == "jit_fold"
    assert r.op_seconds(trace.in_fold) == pytest.approx(250e-9)
    top = dict(r.top_ops())
    assert top["jit_fold:subcge_apply"] == pytest.approx(250e-9)
    assert not any(k.endswith(":while") for k in top)   # loops not summed


def test_a_trace_without_window_or_device_is_refused():
    host, dev = recorded()
    with pytest.raises(ValueError):
        trace.reduce_planes([dev])
    with pytest.raises(ValueError):
        trace.reduce_planes([host])
