#!/usr/bin/env python3
"""On-chip benchmark of SeedFlood: one cell, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of BENCHMARK.json's ``workloads``: a configuration
(``chipbench/configs/<config>.json``) under a traffic mix
(``chipbench/workloads/<traffic>.json``), whose ``kind`` names the window
that drives it (``chipbench/windows/<kind>.py``).  The configuration names
the program's architecture, which must be the one its ``model`` block
states layer by layer, and its plain reference
(``chipbench/references/<reference>.py``), which owns its family's
equations and its count of operations and bytes.  The run builds the
program's entry points and the inputs from ``--seed``, warms every shape
the cell uses (the set-up, ``setup_s``), measures for ``--seconds``, reads
the device's peak memory, frees the program's state, and decides
``correct`` by comparing what the timed path produced with that reference.
With ``--trace 1`` the window runs under the profiler and the line carries
the per-layer metrics that ``chipbench/metrics/<metric>.py`` read from the
trace.

The last line of standard output is one JSON object; the numbers compared
for ``correct`` close standard error and the line (key ``check``).  Without
a TPU, with fewer chips than the cell asks for, on a device missing from
``chipbench/peaks.json``, or without the program's ``src/`` beside it, the
run exits 1 and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


class BenchError(Exception):
    """The run cannot produce a result (exit 1, no result line)."""


def load_json(*parts) -> dict:
    path = os.path.join(HERE, *parts)
    if not os.path.exists(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError("missing BENCHMARK.json")
    with open(path) as f:
        return json.load(f)


def load_metric(name: str):
    """``chipbench/metrics/<name>.py``'s ``read``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no reader chipbench/metrics/{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [e for e in bench[kind]
            if "workloads" not in e or cell in e["workloads"]]


@dataclasses.dataclass
class Ctx:
    """What a window gets: the cell, its configuration, the seed, the
    program's architecture object (checked against the configuration) and
    the configuration's reference module."""
    name: str
    workload: dict
    config: dict
    seed: int
    seconds: float
    arch: object
    peak: dict
    chips: int
    ref: object = None

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def dtype(self):
        import jax.numpy as jnp
        return jnp.dtype(self.config["dtype"])


#: what a ``model`` block states of the whole architecture
ARCH_KEYS = ("d_model", "vocab", "norm", "pos", "act", "gated_mlp",
             "tie_embeddings")
#: what a run of ``layers`` states of a layer, by the part that has it
ATTN_FIELDS = ("n_heads", "n_kv_heads", "head_dim", "qkv_bias", "window",
               "q_lora", "kv_lora", "rope_head_dim", "v_head_dim")
MAMBA_FIELDS = ("d_inner", "d_state", "d_conv", "dt_rank")
MOE_FIELDS = ("n_experts", "top_k", "d_ff_expert", "n_shared")
LAYER_FIELDS = ("mixer", *ATTN_FIELDS, *MAMBA_FIELDS, "ffn", "d_ff",
                *MOE_FIELDS)
#: the keys of a block without ``layers`` that state its one dense layer
DENSE_KEYS = ("n_heads", "n_kv_heads", "head_dim", "qkv_bias", "d_ff")


def layer_fields(slot) -> dict:
    """What a configuration states of one of the program's layers (a
    ``LayerCfg``): its mixer and ffn, and the fields of the parts it runs."""
    out = {"mixer": slot.mixer}
    if slot.mixer == "attn":
        out.update((k, getattr(slot.attn, k)) for k in ATTN_FIELDS)
    elif slot.mixer == "mamba":
        out.update((k, getattr(slot.mamba, k)) for k in MAMBA_FIELDS)
    out["ffn"] = slot.ffn
    if slot.ffn == "dense":
        out["d_ff"] = slot.d_ff
    elif slot.ffn == "moe":
        out.update((k, getattr(slot.moe, k)) for k in MOE_FIELDS)
    return out


def program_runs(arch) -> list[tuple[int, dict]]:
    """``arch.layer_cfgs()`` run-length encoded: (count, layer fields)."""
    runs: list[tuple[int, dict]] = []
    for slot in arch.layer_cfgs():
        f = layer_fields(slot)
        if runs and runs[-1][1] == f:
            runs[-1] = (runs[-1][0] + 1, f)
        else:
            runs.append((1, f))
    return runs


def stated_runs(name: str, m: dict) -> list[dict]:
    """The runs of layers a ``model`` block states.  A block without
    ``layers`` states ``n_layers`` dense layers of global attention."""
    if "layers" not in m:
        return [{"count": m["n_layers"], "mixer": "attn",
                 "n_heads": m["n_heads"], "n_kv_heads": m["n_kv_heads"],
                 "head_dim": m["head_dim"], "qkv_bias": m["qkv_bias"],
                 "window": None, "q_lora": 0, "kv_lora": 0,
                 "rope_head_dim": 0, "v_head_dim": 0, "ffn": "dense",
                 "d_ff": m["d_ff"]}]
    stray = [k for k in DENSE_KEYS if k in m]
    if stray:
        raise BenchError(f"{name}: a model block with layers states "
                         f"{', '.join(stray)} in its runs, not beside them")
    return m["layers"]


def check_layers(name: str, stated: list[dict], runs: list) -> None:
    """Refuse unless the stated runs are the program's, run by run, naming
    the first layer and field that differ."""
    start = 0
    for r, want in enumerate(stated):
        count, have = runs[r] if r < len(runs) else (0, {})
        where = f"{name}: layer {start} (run {r})"
        if have:
            keys = [k for k in LAYER_FIELDS if k in have or k in want]
            keys += sorted(set(want) - set(LAYER_FIELDS) - {"count"})
            for k in keys:
                if have.get(k, "(none)") != want.get(k, "(none)"):
                    raise BenchError(
                        f"{where}: program has {k}={have.get(k, '(none)')!r}"
                        f", configuration states {want.get(k, '(none)')!r}")
        if want.get("count") != count:
            raise BenchError(f"{where}: program has count={count!r}, "
                             f"configuration states {want.get('count')!r}")
        start += count
    if len(runs) > len(stated):
        raise BenchError(f"{name}: layer {start} (run {len(stated)}): "
                         f"program has count={runs[len(stated)][0]!r}, "
                         f"configuration states none")


def program_arch(config: dict):
    """The program's architecture for ``config["arch"]``, refused unless it
    is the one the configuration's ``model`` block states: the whole-model
    keys, and every layer, as runs of equal layers (``layers``, or one run
    of dense layers for a block without it)."""
    from repro.configs import archs
    name = config["arch"]
    arch = archs.get(name)
    if config.get("arch_reduced"):
        arch = archs.reduced(arch, **config["arch_reduced"])
    m = config["model"]
    for key in ARCH_KEYS + (("n_layers",) if "n_layers" in m else ()):
        if getattr(arch, key) != m[key]:
            raise BenchError(f"{name}: program has {key}="
                             f"{getattr(arch, key)!r}, configuration states "
                             f"{m[key]!r}")
    if m["pos"] == "rope" and float(arch.rope_theta) != float(m["rope_theta"]):
        raise BenchError(f"{name}: rope_theta differs")
    check_layers(name, stated_runs(name, m), program_runs(arch))
    return arch


def load_reference(config: dict):
    """The module ``chipbench/references/<config["reference"]>.py``: the
    configuration's family's equations (``zo_step``, ``forward``,
    ``apply_messages``) and its count of operations and bytes
    (``train_cost``)."""
    name = config.get("reference")
    if not (isinstance(name, str) and name.isidentifier() and os.path.exists(
            os.path.join(HERE, "references", name + ".py"))):
        raise BenchError(f"{config.get('name')}: no reference "
                         f"chipbench/references/{name}.py")
    return importlib.import_module("chipbench.references." + name)


def find_device(chips: int, require_chip: bool):
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu":
            raise BenchError(f"no TPU (JAX found {dev.platform})")
        if len(devices) < chips:
            raise BenchError(f"cell needs {chips} chips, JAX found "
                             f"{len(devices)}")
    return devices[:chips]


def peak_for(kind: str) -> dict:
    table = load_json("peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def enable_cache() -> str:
    """The program's compile cache (``$JAX_COMPILATION_CACHE_DIR`` or the
    checkout's ``.jax_cache``), holding every program however fast it
    compiled, so that only a cell's first run in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts backend compilations while ``active``."""

    def __init__(self):
        import jax
        self.active, self.n = False, 0

        def on_event(event, duration, **kw):
            if self.active and event.endswith("backend_compile_duration"):
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)


def fmt_check(check: dict) -> list[str]:
    return [f"check {k} {v['value']!r} limit {v['limit']!r} "
            f"({'ok' if v['ok'] else 'FAIL'})" for k, v in check.items()]


def judge(readings: dict, limits: dict) -> dict:
    """Each reading beside its limit; a reading passes when it is a finite
    number at or under its limit."""
    out = {}
    for name, limit in limits.items():
        v = readings.get(name)
        ok = v is not None and math.isfinite(v) and v <= limit
        out[name] = {"value": v, "limit": limit, "ok": ok}
    return out


def make_ctx(cell: str, seed: int, seconds: float, *,
             require_chip: bool = True, overrides: dict | None = None,
             log=print):
    """Everything a run of ``cell`` needs before its set-up: (ctx, window
    module, devices, BENCHMARK.json).  ``cell`` is an entry of
    BENCHMARK.json's ``workloads``, which names its configuration and
    traffic.  ``overrides`` (tests) replaces traffic or configuration keys,
    or gives the entry of a cell that BENCHMARK.json does not list yet:
    ``{"workload": {...}, "config": {...}, "entry": {...}}``."""
    overrides = overrides or {}
    bench = benchmark_spec()
    entry = overrides.get("entry") or next(
        (w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise BenchError(f"BENCHMARK.json has no cell {cell!r}")
    wl = {**load_json("workloads", entry["traffic"] + ".json"),
          **overrides.get("workload", {}), "chips": entry["chips"]}
    conf = {**load_json("configs", entry["config"] + ".json"),
            **overrides.get("config", {})}
    ref = load_reference(conf)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise BenchError(f"the program's src/ is not beside chipbench/: {e}")
    window = importlib.import_module(f"chipbench.windows.{wl['kind']}")
    devices = find_device(wl["chips"], require_chip)
    dev = devices[0]
    # off the chip (tests) the numbers are computed against a v5e's peaks
    peak = peak_for(dev.device_kind if require_chip else "TPU v5 lite")
    cache_dir = enable_cache()
    log(f"# {cell}: {dev.platform} {dev.device_kind!r} x{len(devices)}, "
        f"compile cache {cache_dir}", file=sys.stderr)
    ctx = Ctx(cell, wl, conf, int(seed), float(seconds),
              program_arch(conf), peak, wl["chips"], ref)
    return ctx, window, devices, bench


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, overrides: dict | None = None,
             log=print) -> dict:
    """One run of ``cell``; returns the result object (see
    :func:`make_ctx` for ``overrides``)."""
    import jax
    ctx, window, devices, bench = make_ctx(
        cell, seed, seconds, require_chip=require_chip, overrides=overrides,
        log=log)
    dev = devices[0]
    state = window.setup(ctx)
    setup_s = time.perf_counter() - T_START
    log(f"# set-up {setup_s:.3f} s", file=sys.stderr)

    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    counter.active = True
    try:
        if trace:
            with jax.profiler.trace(trace_dir):
                rec = window.measure(state, ctx)
        else:
            rec = window.measure(state, ctx)
    finally:
        counter.active = False
    e2e = window.end_to_end(state, ctx, rec)
    e2e["setup_s"] = (setup_s, "s")
    mem = [d.memory_stats() or {} for d in devices]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in mem)

    reduced = None
    if trace:
        from chipbench import trace as tracelib
        try:
            reduced = tracelib.reduce_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    cost = window.cost(state, ctx, rec)
    window.finish(state, ctx, rec)        # program readings, then free it
    del state
    readings = window.check(ctx, rec)
    check = judge(readings, ctx.workload["limits"])

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    if trace:
        metrics = {}
        mctx = dict(trace=reduced, rec=rec, cost=cost, peak=ctx.peak,
                    ctx=ctx)
        for entry in cell_metrics(bench, cell, "per_layer"):
            v = load_metric(entry["name"])(mctx)
            if v is None:
                log(f"# per-layer metric {entry['name']} found nothing to "
                    f"read in this trace", file=sys.stderr)
            else:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s
    else:
        metrics = {e["name"]: {"value": e2e[e["name"]][0],
                               "unit": e2e[e["name"]][1]}
                   for e in cell_metrics(bench, cell, "end_to_end")
                   if e["name"] in e2e}
    result = {"correct": all(c["ok"] for c in check.values())
              and rec["failed"] == 0,
              "attempted": rec["attempted"], "failed": rec["failed"],
              "metrics": metrics, "device": device,
              "window_compiles": counter.n}
    if trace:
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.idle_gaps(10)}
    result["check"] = check
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    for line in fmt_check(result["check"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
