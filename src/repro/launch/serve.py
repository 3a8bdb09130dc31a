"""Pod serving driver: continuous-batching decode over a paged KV cache
(repro.serve).  Requests admit and evict per step, prefill scatters into
reserved pages, and decode runs one bucketed dispatch per step — the same
programs the serve swarm simulator drives under churn.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch tinyllama-1.1b --reduced --batch 4 --prompt-len 32 --new 16 \
        --sampling greedy

``--sampling temperature --temperature 0.8`` switches to temperature
sampling (keyed per (request, position), so a run is deterministic).  On a
real pod drop --reduced and add --production-mesh.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import archs
from repro.launch import steps as steplib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import params as plib
from repro.models import transformer as tf
from repro.serve import SAMPLING_KINDS, DecodeServer, Request, ServeConfig


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="tinyllama-1.1b",
                   choices=sorted(archs.REGISTRY))
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--requests", type=int, default=None,
                   help="total requests to serve (default: --batch)")
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--new", type=int, default=16)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--production-mesh", action="store_true")
    p.add_argument("--sampling", choices=SAMPLING_KINDS, default="greedy")
    p.add_argument("--temperature", type=float, default=0.8)
    args = p.parse_args(argv)
    enable_compile_cache()

    cfg = archs.get(args.arch)
    if args.reduced:
        cfg = archs.reduced(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(1, len(jax.devices())))
    pod = steplib.PodConfig(param_dtype=jnp.float32 if args.reduced
                            else jnp.bfloat16)

    n_req = args.requests if args.requests is not None else args.batch
    page = min(args.page_size, args.prompt_len + args.new)
    ppr = -(-(args.prompt_len + args.new) // page)
    serve = ServeConfig(max_batch=args.batch, page_size=page,
                        n_pages=args.batch * ppr, max_seq=ppr * page,
                        sampling=args.sampling,
                        temperature=args.temperature,
                        param_dtype=pod.param_dtype)

    params = plib.init_params(tf.arch_spec(cfg), 0, pod.param_dtype)
    prompts = jax.random.randint(jax.random.PRNGKey(0),
                                 (n_req, args.prompt_len), 0, cfg.vocab)

    srv = DecodeServer(cfg, params, serve, mesh=mesh, pod=pod)
    for b in range(n_req):
        srv.submit(Request(rid=b, prompt=np.asarray(prompts[b], np.int32),
                           max_new=args.new))
    t0 = time.perf_counter()
    results = srv.run()
    dt = time.perf_counter() - t0

    emitted = sum(len(v) for v in results.values())
    print(f"{cfg.name}: {n_req} requests x {args.new} new tokens "
          f"({args.sampling}); {emitted / dt:.1f} tok/s; "
          f"stats={srv.stats()}")
    for b in range(n_req):
        print(f"  req{b}: {results[b]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
