"""Train window: the program's compiled SeedFlood pod step, step after step.

Set-up compiles ``launch/train.compile_step`` for the cell's clients, rows
and mesh, makes the weights and a feed of distinct token batches from the
seed, and drives the compiled step through its first ``check_steps`` steps,
keeping the loss of each and the per-leaf norms of the weights' change after
the first (the update the clients' messages made: ``−lr/n Σ α_k z_k``) and
after the last.  The window continues the same object from there: each step
is one call of the compiled step on the next batch of the feed, ending in
the host reading the loss, as ``launch/train.train`` does.

``correct`` compares those first steps with the reference's (the
configuration's reference module, ``ctx.ref``, whose ``zo_step`` runs in
float32): the loss of each step, and by the worst leaf the norm of the first
update and of the change after the last step.  The same module counts the
step's operations and bytes (``train_cost``), which the per-layer metrics
divide by the device's times.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, rng, weights

FEED_SALT = 1


def hparams(wl) -> dict:
    return {k: wl[k] for k in ("lr", "eps", "rank", "tau")}


def feed_maker(ctx):
    """jit(key -> tokens (F, n, b, T)) of the cell's feed."""
    wl = ctx.workload
    shape = (wl["feed_batches"], wl["clients"], wl["seqs_per_client"],
             wl["seq_len"])
    vocab = ctx.model["vocab"]
    return jax.jit(lambda key: jax.random.randint(key, shape, 0, vocab,
                                                  jnp.int32))


def feed_key(seed):
    return jax.random.fold_in(rng.seed_key(seed), FEED_SALT)


def abstract_params(ctx):
    from repro.models import params as plib
    from repro.models import transformer as tf
    return plib.abstract_params(tf.arch_spec(ctx.arch), ctx.dtype)


class State:
    pass


def setup(ctx) -> State:
    from repro.configs.base import InputShape
    from repro.launch import steps as steplib
    from repro.launch import train as trainlib
    from repro.launch.mesh import make_host_mesh

    wl = ctx.workload
    n, b, T = wl["clients"], wl["seqs_per_client"], wl["seq_len"]
    st = State()
    st.shape = InputShape("bench", T, n * b, "train")
    st.mesh = make_host_mesh(*wl["mesh"])
    st.pod = steplib.PodConfig(
        lr=wl["lr"], eps=wl["eps"], rank=wl["rank"], tau=wl["tau"],
        base_seed=wl["base_seed"], param_dtype=ctx.dtype, n_clients=n,
        kernel_backend=wl.get("kernel_backend", "auto"))
    st.step, in_sh, st.compile_s = trainlib.compile_step(
        ctx.arch, st.shape, st.mesh, st.pod)
    tokens = feed_maker(ctx)(feed_key(ctx.seed))
    st.feed = [{"tokens": jax.device_put(tokens[i], in_sh[1]["tokens"])}
               for i in range(tokens.shape[0])]
    del tokens
    p0 = weights.make(abstract_params(ctx), ctx.seed, ctx.dtype, in_sh[0])
    norms = weights.leaf_norms_fn()

    params, losses = p0, []
    for t in range(wl["check_steps"]):
        params, met = st.step(params, st.feed[t], jnp.int32(t))
        losses.append(float(met["loss"]))
        if t == 0:
            first = norms(params, p0)
    last = norms(params, p0)
    st.readings = {"losses": losses,
                   "first": {k: float(v) for k, v in first.items()},
                   "last": {k: float(v) for k, v in last.items()}}
    del p0
    st.params = params
    st.next_step = wl["check_steps"]
    return st


def measure(st: State, ctx) -> dict:
    F = len(st.feed)
    i, steps, bad = st.next_step, 0, 0
    params = st.params
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.batch"):
                batch, idx = st.feed[i % F], jnp.int32(i)
            with jax.profiler.TraceAnnotation("bench.step"):
                params, met = st.step(params, batch, idx)
                loss = float(met["loss"])
            bad += not np.isfinite(loss)
            i, steps = i + 1, steps + 1
            t1 = time.perf_counter()
            if t1 - t0 >= ctx.seconds:
                break
    st.params = params
    return {"attempted": steps, "failed": bad, "steps": steps,
            "elapsed_s": t1 - t0}


def tokens_per_step(wl) -> int:
    return wl["clients"] * wl["seqs_per_client"] * wl["seq_len"]


def end_to_end(st: State, ctx, rec) -> dict:
    return {"train_tokens_per_s": (rec["steps"] * tokens_per_step(
        ctx.workload) / rec["elapsed_s"], "tokens/s")}


def cost(st: State, ctx, rec) -> dict:
    return {"steps": rec["steps"],
            **ctx.ref.train_cost(ctx.model, ctx.workload)}


def finish(st: State, ctx, rec) -> None:
    ctx.program_readings = st.readings
    st.params = st.feed = st.step = None


def reference_readings(ctx, prec: str = "f32", fault=None) -> dict:
    """The reference's first steps from the same seed: losses and per-leaf
    norms of the change after the first and the last step.  ``prec`` below
    f32 or a ``fault`` give the controls that calibrate the limits."""
    wl, m, ref = ctx.workload, ctx.model, ctx.ref
    hp = {**hparams(wl), "fault": fault}
    step = jax.jit(lambda p, tok, t, bs: ref.zo_step(m, p, tok, t, bs, hp,
                                                     prec))
    p0 = weights.make(abstract_params(ctx), ctx.seed, ctx.dtype)
    tokens = feed_maker(ctx)(feed_key(ctx.seed))
    norms = weights.leaf_norms_fn()
    bs = jnp.uint32(wl["base_seed"])
    params, losses = p0, []
    for t in range(wl["check_steps"]):
        params, loss, _ = step(params, tokens[t], jnp.int32(t), bs)
        losses.append(float(loss))
        if t == 0:
            first = norms(params, p0)
    last = norms(params, p0)
    return {"losses": losses,
            "first": {k: float(v) for k, v in first.items()},
            "last": {k: float(v) for k, v in last.items()}}


def readings_gap(prog: dict, ref: dict) -> dict:
    return {"loss_gap": max(abs(a - b) for a, b in zip(prog["losses"],
                                                       ref["losses"])),
            "first_update_gap": compare.worst_leaf_gap(prog["first"],
                                                       ref["first"],
                                                       ref["first"]),
            "change_gap": compare.worst_leaf_gap(prog["last"], ref["last"],
                                                 ref["first"])}


def check(ctx, rec) -> dict:
    return readings_gap(ctx.program_readings, reference_readings(ctx))
