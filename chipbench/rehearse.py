#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py <cell> [<cell> ...]

For a train cell: the SeedFlood pod step at the cell's clients and rows,
with the Pallas kernels.  For a serve cell: the paged decode at the cell's
batch and page bucket, each prefill shape, and the largest warmed fold.
Prints each program's ``memory_analysis()`` bytes; the compiler refuses
here what it would refuse on the chip (a program that does not fit, a
kernel block it cannot lay out).  A compile here is not a chip run.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(ma, k)) for k in keys}


def with_sharding(tree, sharding):
    import jax
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def rehearse(cell: str, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, SingleDeviceSharding

    from chipbench import run
    from repro.configs.base import InputShape
    from repro.launch import steps as steplib

    entry = next(w for w in run.benchmark_spec()["workloads"]
                 if w["name"] == cell)
    wl = run.load_json("workloads", entry["traffic"] + ".json")
    conf = run.load_json("configs", entry["config"] + ".json")
    arch = run.program_arch(conf)
    dtype = jnp.dtype(conf["dtype"])
    devs = np.asarray(topo.devices[:entry["chips"]])
    out = {}
    if wl["kind"] == "train":
        mesh = Mesh(devs.reshape(*wl["mesh"]), ("data", "model"))
        n, b, T = wl["clients"], wl["seqs_per_client"], wl["seq_len"]
        pod = steplib.PodConfig(
            lr=wl["lr"], eps=wl["eps"], rank=wl["rank"], tau=wl["tau"],
            param_dtype=dtype, n_clients=n, kernel_backend="pallas")
        fn, example, in_sh, out_sh = steplib.build_seedflood_train_step(
            arch, InputShape("bench", T, n * b, "train"), mesh, pod)
        c = jax.jit(fn, in_shardings=in_sh,
                    out_shardings=out_sh).lower(*example).compile()
        out["train_step"] = memory(c)
        return out

    from repro.core import subcge
    from repro.core.subcge import SubCGEConfig
    from repro.models import params as plib
    from repro.models import transformer as tf

    mesh = Mesh(devs.reshape(1, 1), ("data", "model"))
    one = SingleDeviceSharding(topo.devices[0])
    pod = steplib.PodConfig(param_dtype=dtype)
    page, S, B = wl["page_size"], wl["max_seq"], wl["max_batch"]
    geo = dict(page_size=page, n_pages=B * S // page)
    fn, example, in_sh, out_sh = steplib.build_paged_decode_step(
        arch, InputShape("serve", S, B, "decode"), mesh, pod,
        pages_per_req=S // page, **geo)
    out["decode"] = memory(jax.jit(fn, in_shardings=in_sh,
                                   out_shardings=out_sh).lower(
        *example).compile())
    for T in wl["prompt_lens"]:
        fn, example, in_sh, out_sh = steplib.build_paged_prefill_step(
            arch, InputShape("serve", T, wl["sessions_per_len"], "prefill"),
            mesh, pod, pages_per_req=S // page, **geo)
        out[f"prefill_{T}"] = memory(jax.jit(
            fn, in_shardings=in_sh, out_shardings=out_sh).lower(
            *example).compile())
    meta = plib.subcge_meta(tf.arch_spec(arch))
    scfg = SubCGEConfig(rank=wl["rank"], refresh_period=wl["tau"],
                        kernel_backend="pallas")
    K = max(wl["warm_fold_k"])

    def fold(params, seeds, coefs, steps, epochs):
        return subcge.apply_messages_epoch(params, meta, scfg, 0, seeds,
                                           coefs, steps, epochs)
    args = (with_sharding(plib.abstract_params(tf.arch_spec(arch), dtype),
                          one),
            jax.ShapeDtypeStruct((K,), jnp.uint32, sharding=one),
            jax.ShapeDtypeStruct((K,), jnp.float32, sharding=one),
            jax.ShapeDtypeStruct((K,), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one))
    out[f"fold_K{K}_E2"] = memory(jax.jit(fold).lower(*args).compile())
    return out


def main(argv=None) -> int:
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for cell in (argv or sys.argv[1:]):
        print(json.dumps({"cell": cell, "memory": rehearse(cell, topo)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
