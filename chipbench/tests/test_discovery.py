"""A configuration, its reference module, a traffic mix and a per-layer
metric added as new files (and entries of BENCHMARK.json) are found with no
edit to an existing file; a checkout without the program, or a host without
a TPU, gets no result."""
import json
import os
import shutil
import subprocess
import sys

from chipbench.tests import cells

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)

NEW_METRIC = '''"""Steps the window ran (a new reader, found by its file name)."""


def read(m):
    return float(m["rec"]["steps"])
'''

NEW_REFERENCE = '''"""The dense decoder's equations and count for a model block that states
its one layer as a run of ``layers`` (a new family, found by the
configuration's ``reference``)."""
from chipbench.references import decoder

calls = []


def dense(m):
    (run,) = m["layers"]
    return {**m, "n_layers": run["count"],
            **{k: run[k] for k in ("n_heads", "n_kv_heads", "head_dim",
                                   "qkv_bias", "d_ff")}}


def zo_step(m, *args, **kw):
    calls.append("zo_step")
    return decoder.zo_step(dense(m), *args, **kw)


def train_cost(m, wl):
    calls.append("train_cost")
    return {**decoder.train_cost(dense(m), wl), "layer_runs": len(m["layers"])}
'''

DRIVE = '''
import json, sys
sys.path.insert(0, {root!r})
from chipbench import run
r = run.run_cell("train.tiny.c2", 9, 1.0, False, require_chip=False)
bench = run.benchmark_spec()
names = [e["name"] for e in run.cell_metrics(bench, "train.tiny.c2",
                                             "per_layer")]
read = run.load_metric("steps_seen.train")
ref = sys.modules["chipbench.references.layered"]
print(json.dumps({{"correct": r["correct"], "metrics": sorted(r["metrics"]),
                  "per_layer": names, "reference_calls": sorted(set(ref.calls)),
                  "steps": read({{"rec": {{"steps": 3}}}})}}))
'''


def copy_benchmark(dst):
    shutil.copytree(HERE, os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)


def test_new_files_are_found(tmp_path):
    copy_benchmark(tmp_path)
    cb = tmp_path / "chipbench"
    conf = json.load(open(cb / "configs" / "opt-1.3b.json"))
    conf.update(name="opt-tiny", arch="opt-125m",
                arch_reduced={"d_model": 64}, model=cells.RED_OPT_LAYERS,
                dtype="float32", reference="layered")
    (cb / "configs" / "opt-tiny.json").write_text(json.dumps(conf))
    (cb / "references" / "layered.py").write_text(NEW_REFERENCE)
    wl = json.load(open(cb / "workloads" / "train.opt-1.3b.c16.json"))
    wl.update(cells.overrides("train.opt-1.3b.c16", "jnp")["workload"])
    (cb / "workloads" / "train.tiny.c2.json").write_text(json.dumps(wl))
    (cb / "metrics" / "steps_seen.train.py").write_text(NEW_METRIC)
    bench = json.load(open(tmp_path / "BENCHMARK.json"))
    bench["configs"].append({"name": "opt-tiny", "source": "test",
                             "file": "chipbench/configs/opt-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "train.tiny.c2", "config": "opt-tiny",
                               "traffic": "train.tiny.c2", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("train.tiny.c2")
    bench["per_layer"].append({"name": "steps_seen.train", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "pod train step",
                               "moves": "train_tokens_per_s",
                               "workloads": ["train.tiny.c2"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.join(ROOT, "src")}
    out = subprocess.run([sys.executable, "-c", DRIVE.format(
        root=str(tmp_path))], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["correct"]
    assert got["metrics"] == ["setup_s", "train_tokens_per_s"]
    assert "steps_seen.train" in got["per_layer"]
    assert got["steps"] == 3.0
    assert got["reference_calls"] == ["train_cost", "zo_step"]


def run_cli(cwd, env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "train.opt-1.3b.c16", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def test_no_result_without_program_or_chip(tmp_path):
    copy_benchmark(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    bare = run_cli(tmp_path, env)                  # BENCHMARK.json + paths
    assert bare.returncode != 0 and bare.stdout.strip() == ""
    assert "src/" in bare.stderr
    no_chip = run_cli(ROOT, env)                   # the program, no TPU
    assert no_chip.returncode != 0 and no_chip.stdout.strip() == ""
    assert "no TPU" in no_chip.stderr
