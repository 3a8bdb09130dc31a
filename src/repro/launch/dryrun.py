import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"   # a CPU lowering tool: never take a chip
"""Multi-pod dry-run: prove every (arch × shape × mesh) lowers + compiles.

MUST be the process entry point (the environment lines above run before any
jax import, including transitively through repro).  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch qwen2-72b --shape train_4k [--multipod] [--kind train] \
        [--out out.json] [--hlo-out out.hlo]

Emits a JSON record: memory_analysis, cost_analysis flops/bytes, parsed
collective stats, roofline terms — consumed by benchmarks/ and
EXPERIMENTS.md §Dry-run/§Roofline.
"""
import argparse
import json
import sys
import time

import jax

from repro.configs import archs
from repro.configs.base import INPUT_SHAPES
from repro.launch import steps as steplib
from repro.launch.mesh import make_production_mesh, mesh_size
from repro.models import transformer as tf
from repro.roofline import analysis as ra
from repro.roofline import cost_model


def active_params(cfg) -> int:
    """Approximate activated parameter count (MoE: top-k+shared experts)."""
    import dataclasses
    from repro.configs.base import Group, MoECfg
    total = 0
    from repro.models import params as plib
    spec = tf.arch_spec(cfg)
    flat = plib.flatten_paths(spec)
    import math
    for path, leaf in flat.items():
        n = math.prod(leaf.shape)
        # expert-stacked leaves: scale by active fraction
        if "experts" in leaf.axes[: leaf.n_batch_dims]:
            e_dim = leaf.shape[leaf.axes.index("experts")]
            # find the owning MoE cfg: use top_k from any moe slot
            top_k = 8
            for g in cfg.groups:
                for s in g.slots:
                    if s.moe is not None:
                        top_k = s.moe.top_k
            n = n * top_k // e_dim
        total += n
    return total


def run_dryrun(arch: str, shape_name: str, *, multi_pod: bool = False,
               kind: str | None = None, pod_kwargs: dict | None = None,
               save_hlo: str | None = None, verbose: bool = True,
               policy: str | None = None) -> dict:
    import dataclasses
    shape = INPUT_SHAPES[shape_name]
    base_cfg = archs.get(arch)
    cfg = base_cfg.for_shape(shape)
    if policy:
        cfg = dataclasses.replace(cfg, sharding_policy=policy)
    if pod_kwargs and pod_kwargs.pop("moe_gather", False):
        cfg = dataclasses.replace(cfg, moe_gather_weights=True)
    if pod_kwargs and pod_kwargs.pop("residual_rep", False):
        cfg = dataclasses.replace(cfg, residual_replicated=True)
    if kind is None:
        kind = {"train": "train", "prefill": "prefill", "decode": "decode"}[shape.kind]

    mesh = make_production_mesh(multi_pod=multi_pod)
    pod = steplib.PodConfig(**(pod_kwargs or {}))
    fn, example, in_sh, out_sh = steplib.build_step(kind, cfg, shape, mesh, pod)

    # exact per-device residency from the shardings (CPU memory_analysis is
    # not a per-chip proxy): params + inputs/caches, the ZO method's entire
    # live state — there are no grads or optimizer moments.
    def _per_device(abs_tree, sh_tree):
        import numpy as np
        total = 0.0
        for leaf, sh in zip(jax.tree.leaves(abs_tree), jax.tree.leaves(sh_tree)):
            nbytes = float(np.prod(leaf.shape)) * leaf.dtype.itemsize
            shards = 1
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            for ax in jax.tree.leaves(tuple(sh.spec)):
                shards *= sizes.get(ax, 1)
            total += nbytes / shards
        return total

    resident = sum(_per_device(a, s) for a, s in zip(example, in_sh))

    t0 = time.time()   # lower/compile timing report only; never seeds anything
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*example)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                mem[k] = getattr(ma, k, None)
    except Exception as e:  # CPU backend may not implement it
        mem["error"] = str(e)

    cost = {}
    try:
        c = compiled.cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0]
        cost = {k: float(v) for k, v in c.items()
                if isinstance(v, (int, float))}
    except Exception as e:
        cost["error"] = str(e)

    hlo = compiled.as_text()
    coll = ra.parse_collectives_corrected(hlo)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)

    chips = mesh_size(mesh)
    # compute/memory numerators from the analytic model (cost_analysis counts
    # while bodies once — see roofline/cost_model.py); collectives from the
    # trip-count-corrected HLO parse.  coll.total_bytes is per-device link
    # traffic; × chips = network-total, as the roofline formula expects.
    mc = cost_model.step_cost(cfg, shape, kind,
                              rank=pod.rank,
                              n_clients=pod.n_clients or 16)
    flops, bytes_acc = mc.flops, mc.bytes
    n_active = active_params(cfg)
    tokens = shape.global_batch * (shape.seq if kind in ("train", "prefill") else 1)
    mf = ra.model_flops_estimate(n_active, tokens, kind,
                                 zo=kind == "train")
    roof = ra.roofline_terms(flops, bytes_acc, coll.total_bytes * chips,
                             chips, mf)

    record = {
        "arch": arch, "effective_arch": cfg.name, "shape": shape_name,
        "kind": kind, "multi_pod": multi_pod, "chips": chips,
        "mesh": list(mesh.devices.shape), "axes": list(mesh.axis_names),
        "policy": cfg.sharding_policy,
        "n_params": tf.count_params(cfg), "n_params_active": n_active,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem, "cost_analysis": cost,
        "resident_bytes_per_device": resident,
        "collectives": coll.to_json(), "roofline": roof.to_json(),
        "tokens": tokens,
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {'2x16x16' if multi_pod else '16x16'} "
              f"kind={kind} OK lower={t_lower:.1f}s compile={t_compile:.1f}s")
        print(f"  params={record['n_params']/1e9:.2f}B resident/dev="
              f"{resident/2**30:.2f}GiB flops={flops:.3e} bytes={bytes_acc:.3e} "
              f"coll={coll.total_bytes:.3e}B ({coll.count} ops)")
        print(f"  roofline: compute={ra.fmt_seconds(roof.compute_s)} "
              f"memory={ra.fmt_seconds(roof.memory_s)} "
              f"collective={ra.fmt_seconds(roof.collective_s)} "
              f"dominant={roof.dominant} useful={roof.useful_ratio:.2f}")
    return record


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True, choices=sorted(archs.REGISTRY))
    p.add_argument("--shape", required=True, choices=sorted(INPUT_SHAPES))
    p.add_argument("--multipod", action="store_true")
    p.add_argument("--kind", default=None,
                   choices=[None, "train", "train_dsgd", "prefill", "decode"])
    p.add_argument("--out", default=None, help="write JSON record here")
    p.add_argument("--hlo-out", default=None)
    p.add_argument("--apply-mode", default="fold", choices=["fold", "buffer"])
    p.add_argument("--rank", type=int, default=32)
    p.add_argument("--n-clients", type=int, default=0)
    p.add_argument("--policy", default=None,
                   help="override the arch's sharding policy (tp/fsdp_tp/ep)")
    p.add_argument("--moe-gather", action="store_true",
                   help="all-gather expert weights at use (§Perf)")
    p.add_argument("--residual-rep", action="store_true",
                   help="pin residual stream d_model axis replicated (§Perf)")
    args = p.parse_args(argv)

    record = run_dryrun(args.arch, args.shape, multi_pod=args.multipod,
                        kind=args.kind, save_hlo=args.hlo_out,
                        policy=args.policy,
                        pod_kwargs={"apply_mode": args.apply_mode,
                                    "rank": args.rank,
                                    "n_clients": args.n_clients,
                                    "moe_gather": args.moe_gather,
                                    "residual_rep": args.residual_rep})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(record, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
