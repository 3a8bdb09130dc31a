"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

* device ops: the events of the ``XLA Ops`` line of every ``/device:TPU:n``
  plane.  An event is named by its HLO text (``%rank1_matmul.3 = ...``):
  the op's own name is what precedes `` = ``, and a Pallas kernel's is the
  name of the jitted function that called it (``rank1_matmul``,
  ``vmap_jit_rank1_matmul_t__``, ``subcge_apply``).  Its program is the
  ``XLA Modules`` event (``jit_fold(…)``) that encloses it in time.  Loops
  (``while``) enclose their body's ops on the same line and are left out
  of per-op sums;
* the window: the host span ``bench.window`` that the harness opens around
  the measured loop (all planes share one clock);
* busy: the union of a chip's op intervals inside the window, averaged over
  the chips; the idle share is 1 − busy / window;
* gaps: the stretches of the window in which no op runs on chip 0, each
  named after the innermost benchmark span (``bench.*``, ``server.step``)
  open at its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("bench.", "server.")
OP_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
#: ops that enclose other ops' events on the same line
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    chip: int
    start: int          # ns, the profile's clock
    end: int
    name: str           # the HLO instruction
    module: str         # the HLO module (jitted program)
    label: str          # the op's name without its numeric suffix


@dataclasses.dataclass
class Span:
    start: int
    end: int
    name: str


@dataclasses.dataclass
class Reduced:
    window: tuple[int, int]
    ops: list[Op]                     # clipped to the window
    spans: list[Span]
    n_chips: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an op runs, averaged over the chips."""
        tot = sum(union_ns([(o.start, o.end) for o in self.ops
                            if o.chip == c]) for c in range(self.n_chips))
        return tot * 1e-9 / self.n_chips

    def op_seconds(self, pred) -> float:
        """Summed device time of the ops ``pred`` accepts, per chip."""
        return sum(o.end - o.start for o in self.ops if pred(o)) * 1e-9 \
            / self.n_chips

    def top_ops(self, k=10) -> list[list]:
        tot: dict[str, int] = {}
        for o in self.ops:
            if o.label in CONTAINERS:
                continue
            key = f"{o.module}:{o.label}"
            tot[key] = tot.get(key, 0) + (o.end - o.start)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9 / self.n_chips] for name, ns in top]

    def idle_gaps(self, k=10) -> list[list]:
        t0, t1 = self.window
        ivs = sorted((o.start, o.end) for o in self.ops if o.chip == 0)
        out, cur = [], t0
        for s, e in ivs:
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if t1 > cur:
            out.append((cur, t1))
        out.sort(key=lambda g: g[0] - g[1])
        return [[self.span_at((s + e) // 2), (e - s) * 1e-9]
                for s, e in out[:k]]

    def span_at(self, t: int) -> str:
        best = None
        for sp in self.spans:
            if sp.name != WINDOW_SPAN and sp.start <= t <= sp.end:
                if best is None or sp.end - sp.start < best.end - best.start:
                    best = sp
        return best.name if best else "host outside any span"


#: Pallas kernels by the jitted wrapper whose name the op's framework path
#: carries; longer names first (``rank1_matmul_t`` holds ``rank1_matmul``)
KERNELS = ("rank1_matmul_expert", "rank1_matmul_t", "rank1_matmul",
           "subcge_apply")
#: the live fold's program: ``LiveUpdateBridge`` jits a function ``fold``
FOLD_MODULE = "fold"


def kernel_of(op: Op) -> str | None:
    return next((k for k in KERNELS if k in op.label), None)


def in_fold(op: Op) -> bool:
    return FOLD_MODULE in op.module


def union_ns(ivs) -> int:
    tot, cur_s, cur_e = 0, None, None
    for s, e in sorted(ivs):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                tot += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        tot += cur_e - cur_s
    return tot


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def own_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def op_label(name: str) -> str:
    """The op's name without XLA's numeric suffix: ``fusion.12`` ->
    ``fusion``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def module_name(text: str) -> str:
    """``jit_fold(5186521979061897628)`` -> ``jit_fold``."""
    return text.split("(", 1)[0]


def reduce_planes(planes) -> Reduced:
    """``planes``: iterable of objects with ``name`` and ``lines`` (each with
    ``name`` and ``events`` carrying ``name``, ``start_ns`` and
    ``duration_ns``), as ``jax.profiler.ProfileData`` gives them."""
    spans, raw_ops, chips, modules = [], [], set(), []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            chip = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        modules.append((chip, s, s + int(ev.duration_ns),
                                        module_name(ev.name)))
                if line.name != OP_LINE:
                    continue
                chips.add(chip)
                for ev in line.events:
                    name = own_name(ev.name)
                    s = int(ev.start_ns)
                    raw_ops.append(Op(chip, s, s + int(ev.duration_ns), name,
                                      "", op_label(name)))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        s = int(ev.start_ns)
                        spans.append(Span(s, s + int(ev.duration_ns),
                                          ev.name))
    _assign_modules(raw_ops, modules)
    windows = [sp for sp in spans if sp.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    if not chips:
        raise ValueError("trace holds no device ops")
    w = max(windows, key=lambda sp: sp.end - sp.start)
    ops = [dataclasses.replace(o, start=max(o.start, w.start),
                               end=min(o.end, w.end))
           for o in raw_ops if o.end > w.start and o.start < w.end]
    chip_ix = {c: i for i, c in enumerate(sorted(chips))}
    for o in ops:
        o.chip = chip_ix[o.chip]
    return Reduced((w.start, w.end), ops, spans, len(chips))


def _assign_modules(ops: list[Op], modules: list[tuple]) -> None:
    """Give each op the program whose execution encloses it."""
    by_chip: dict[int, list[tuple]] = {}
    for chip, s, e, name in sorted(modules):
        by_chip.setdefault(chip, []).append((s, e, name))
    starts = {c: [m[0] for m in ms] for c, ms in by_chip.items()}
    for o in ops:
        if o.chip not in by_chip:
            continue
        i = bisect.bisect_right(starts[o.chip], o.start) - 1
        if i >= 0:
            s, e, name = by_chip[o.chip][i]
            if o.end <= e:
                o.module = name


def reduce_dir(trace_dir: str) -> Reduced:
    import jax
    pd = jax.profiler.ProfileData.from_file(find_xplane(trace_dir))
    return reduce_planes(pd.planes)
