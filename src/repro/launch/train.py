"""Pod training driver: runs the sharded SeedFlood train_step in a loop.

On a real TPU pod this is the production entry point (one process per host;
jax.distributed.initialize() handles the rest).  On CPU it runs the same
program on a host mesh at reduced scale — the step function is identical to
the one the dry-runs lower for 256/512 chips.

    PYTHONPATH=src python -m repro.launch.train \
        --arch tinyllama-1.1b --reduced --steps 20 --batch 8 --seq 64

Checkpoints (params + step + seed — ZO has no optimizer state) land in
--ckpt-dir every --ckpt-every steps.  ``setup``/``client_batch``/``train``
are the pieces ``main`` runs, for callers that drive the loop themselves.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import ckpt
from repro.configs import archs
from repro.configs.base import ArchConfig, InputShape
from repro.data import synthetic
from repro.launch import steps as steplib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import params as plib
from repro.models import transformer as tf


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="tinyllama-1.1b",
                   choices=sorted(archs.REGISTRY))
    p.add_argument("--reduced", action="store_true",
                   help="reduced config (CPU-scale)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=8, help="global batch")
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--n-clients", type=int, default=2)
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--rank", type=int, default=16)
    p.add_argument("--production-mesh", action="store_true",
                   help="use the 16x16 pod mesh (requires 256 devices)")
    p.add_argument("--ckpt-dir", default="/tmp/seedflood_pod")
    p.add_argument("--ckpt-every", type=int, default=0)
    return p.parse_args(argv)


@dataclasses.dataclass
class PodRun:
    """Everything one training run holds: the compiled step, its inputs'
    shardings, the placed parameters and the client-partitioned corpus."""
    cfg: ArchConfig
    shape: InputShape
    mesh: Any
    pod: steplib.PodConfig
    step: Any                      # compiled train step
    in_sh: Any
    params: Any
    train: synthetic.Dataset
    test: synthetic.Dataset
    parts: list
    compile_s: float


def compile_step(cfg: ArchConfig, shape: InputShape, mesh,
                 pod: steplib.PodConfig):
    """Lower and compile the SeedFlood train step; returns (compiled
    step, its input shardings, compile seconds).  The compiled step is
    registered with :mod:`repro.obs`, so a profile of it can be read by
    phase."""
    fn, example, in_sh, out_sh = steplib.build_seedflood_train_step(
        cfg, shape, mesh, pod)
    t0 = time.perf_counter()   # set-up timing report only
    compiled = jax.jit(fn, in_shardings=in_sh,
                       out_shardings=out_sh).lower(*example).compile()
    obs.register(compiled)
    return compiled, in_sh, time.perf_counter() - t0


def setup(args: argparse.Namespace, mesh=None, **pod_kw) -> PodRun:
    """Build and compile the run ``args`` describe (``pod_kw`` overrides
    :class:`~repro.launch.steps.PodConfig` fields)."""
    cfg = archs.get(args.arch)
    if args.reduced:
        cfg = archs.reduced(cfg)
    shape = InputShape("cli", args.seq, args.batch, "train")
    if mesh is None:
        mesh = (make_production_mesh() if args.production_mesh
                else make_host_mesh(1, len(jax.devices())))
    pod = steplib.PodConfig(lr=args.lr, rank=args.rank,
                            n_clients=args.n_clients,
                            param_dtype=jnp.float32 if args.reduced
                            else jnp.bfloat16, **pod_kw)
    step, in_sh, compile_s = compile_step(cfg, shape, mesh, pod)

    # synthetic corpus (sequences of --seq tokens), partitioned across the
    # logical clients
    task = synthetic.TaskConfig(vocab=cfg.vocab, seq_len=args.seq - 1,
                                n_train=max(256, args.batch * 8))
    train, _, test = synthetic.make_splits(task)
    parts = synthetic.partition(train, args.n_clients)
    params = jax.device_put(
        plib.init_params(tf.arch_spec(cfg), 0, pod.param_dtype), in_sh[0])
    return PodRun(cfg, shape, mesh, pod, step, in_sh, params, train, test,
                  parts, compile_s)


def client_batch(run: PodRun, step: int) -> dict:
    """Step ``step``'s minibatch of every client, placed for the step."""
    n = run.pod.n_clients
    per_client = run.shape.global_batch // n
    toks = np.stack([
        np.asarray(synthetic.client_batch(run.train, run.parts[i], i, step,
                                          per_client)["tokens"])
        for i in range(n)])
    return jax.device_put({"tokens": toks}, run.in_sh[1])


def train(run: PodRun, steps: int, *, log_every: int = 0, ckpt_dir: str = "",
          ckpt_every: int = 0):
    """Run ``steps`` steps from ``run.params``; returns (params, history)
    with one {"step", "loss", "alpha_rms", "wall_s"} record per step."""
    params, history = run.params, []
    for step in range(steps):
        # throughput timing only: data + perturbations key off (base_seed,
        # client, step) so a re-run is bit-identical — never clock-seed here
        t0 = time.perf_counter()
        params, metrics = run.step(params, client_batch(run, step),
                                   jnp.int32(step))
        rec = {"step": step, "loss": float(metrics["loss"]),
               "alpha_rms": float(metrics["alpha_rms"]),
               "wall_s": time.perf_counter() - t0}
        history.append(rec)
        if log_every and step % log_every == 0:
            print(f"step {step:>5}  loss {rec['loss']:.4f}  "
                  f"alpha_rms {rec['alpha_rms']:.4f}", flush=True)
        if ckpt_every and (step + 1) % ckpt_every == 0:
            path = os.path.join(ckpt_dir, f"step{step + 1}.npz")
            ckpt.save(path, params, {"step": step + 1, "arch": run.cfg.name})
            print(f"  saved {path}")
    return params, history


def main(argv=None) -> int:
    args = parse_args(argv)
    enable_compile_cache()
    run = setup(args)
    t0 = time.time()
    params, _ = train(run, args.steps, log_every=max(1, args.steps // 10),
                      ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    dt = time.time() - t0

    acc = synthetic.accuracy(run.cfg, params, run.test, forward_fn=tf.forward)
    print(f"\n{args.steps} steps in {dt:.1f}s "
          f"({args.steps / dt:.2f} steps/s); test accuracy {acc:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
