#!/usr/bin/env python3
"""Chip smoke test: drive the system's accelerator paths once on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: the sharded pod step only

One chip runs four phases, each through the entry points a user calls, with
random weights and synthetic data made from seed 0:

1. device     — a TPU is present and ``kernel_backend="auto"`` means Pallas;
2. pod step   — ``opt-1.3b`` at published widths in bf16 through
                ``repro.launch.train`` (2 clients, 8 sequences of 127 tokens
                each = 1016 rows per client, 3 steps); the compiled step
                holds Pallas kernels, and step 0 under the kernels and under
                ``jnp`` agrees in loss;
3. serving    — ``DecodeServer`` at ``tinyllama-1.1b`` widths in bf16 (4
                greedy requests, prompts of 32 and 48 tokens, 16 new tokens,
                16-token pages) with 8 flood messages over two subspace
                epochs folded live by a ``LiveUpdateBridge``; the kernel fold
                matches the ``jnp`` fold;
4. simulator  — the quickstart's SeedFlood run (16 clients, 3 steps).

``--four-chips`` runs only the ``opt-1.3b`` step on a 2x2 ("data", "model")
mesh — clients on the data axis, tensor parallelism on the model axis — and
compares it with the same step on a 1x1 mesh on device 0.

Without a TPU, or without the repository's ``src/`` next to it, it exits 1
and prints no result.  Its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The seconds it prints are bring-up timings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: |loss(a) - loss(b)| allowed between two implementations of one bf16 step
#: (~0.5% of the initial loss ln(50272) ≈ 10.8): bf16 rounding through 24
#: layers differs with the accumulation order, nothing more.
LOSS_ATOL = 0.05
#: share of a leaf's largest update that two folds of the same messages may
#: differ by, on top of two bf16 spacings of the weight itself.
FOLD_RTOL = 0.1
#: the same for two implementations of one train step, which also fold
#: different α: α = (L+ - L-)/2ε resolves bf16 loss noise of ~4e-4 only to
#: ~0.2, and step 0's alpha_rms differed by 6% (Pallas vs jnp) and 9% (2x2
#: vs 1x1 mesh) on a v5e, where the 2x2 update stayed within 0.11 of the max.
STEP_RTOL = 0.25
#: seed of every weight, prompt and message
SEED = 0


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def timing(phase: str, **secs) -> None:
    parts = "  ".join(f"{k}={v:.3f}s" for k, v in secs.items())
    print(f"  [{phase}] bring-up timing (not a benchmark): {parts}",
          flush=True)


def leaves_close(new, ref, base, rtol: float) -> float:
    """Largest per-leaf violation of |new - ref| <= 2 bf16 spacings of ref +
    rtol · max|ref - base|; <= 0 means every leaf is within."""
    import jax
    import numpy as np
    worst = -np.inf
    for a, b, w in zip(jax.tree.leaves(new), jax.tree.leaves(ref),
                       jax.tree.leaves(base)):
        a, b, w = (np.asarray(t, np.float32) for t in (a, b, w))
        spacing = np.spacing(np.abs(b)) * 2.0 ** 16    # f32 -> bf16 spacing
        tol = 2 * spacing + rtol * np.abs(b - w).max()
        worst = max(worst, float((np.abs(a - b) - tol).max()))
    return worst


def any_changed(new, base) -> bool:
    import jax
    import numpy as np
    return any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(base)))


def pod_args():
    # 8 x 127 = 1016 rows per client, a count with no aligned divisor.  The
    # step size resolves bf16 updates of the 2048-wide weights without
    # diverging (train.py's default 1e-2, sized for the reduced configs,
    # took opt-1.3b's loss from 11.2 to 20.5 in three steps on a v5e)
    from repro.launch import train as trainlib
    return trainlib.parse_args([
        "--arch", "opt-1.3b", "--seq", "127", "--batch", "16",
        "--n-clients", "2", "--steps", "3", "--lr", "1e-4"])


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_pod_step() -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import train as trainlib

    print("phase 2: pod train step (opt-1.3b, bf16, 2 clients)", flush=True)
    args = pod_args()
    run = trainlib.setup(args)
    rows = args.batch // args.n_clients * args.seq
    print(f"  rows per client: {rows}", flush=True)
    check("tpu_custom_call" in run.step.as_text(),
          "compiled step holds Pallas kernels (tpu_custom_call)")

    batch0 = trainlib.client_batch(run, 0)
    jnp_step, _, jnp_compile_s = trainlib.compile_step(
        run.cfg, run.shape, run.mesh,
        dataclasses.replace(run.pod, kernel_backend="jnp"))
    _, m_pal = run.step(run.params, batch0, jnp.int32(0))
    _, m_jnp = jnp_step(run.params, batch0, jnp.int32(0))
    loss_pal, loss_jnp = float(m_pal["loss"]), float(m_jnp["loss"])
    print(f"  step 0 alpha_rms: pallas {float(m_pal['alpha_rms'])!r}  "
          f"jnp {float(m_jnp['alpha_rms'])!r}", flush=True)
    check(abs(loss_pal - loss_jnp) <= LOSS_ATOL,
          f"step 0 loss pallas {loss_pal!r} vs jnp {loss_jnp!r} "
          f"within {LOSS_ATOL}")

    params, hist = trainlib.train(run, args.steps, log_every=1)
    losses = [h["loss"] for h in hist]
    check(len(losses) == args.steps and bool(np.isfinite(losses).all()),
          f"{args.steps} steps with finite losses {losses}")
    check(any_changed(params, run.params), "parameters changed")
    timing("pod step", compile=run.compile_s, compile_jnp=jnp_compile_s,
           first_step=hist[0]["wall_s"],
           steady_step=float(np.mean([h["wall_s"] for h in hist[1:]])))


def phase_serving() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import archs
    from repro.core.subcge import SubCGEConfig, epoch_slots
    from repro.models import params as plib
    from repro.models import transformer as tf
    from repro.serve import DecodeServer, LiveUpdateBridge, Request, ServeConfig

    print("phase 3: paged serving (tinyllama-1.1b, bf16) with a live fold",
          flush=True)
    cfg = archs.get("tinyllama-1.1b")
    prompt_lens, new, page = (32, 48, 32, 48), 16, 16
    ppr = -(-(max(prompt_lens) + new) // page)
    serve = ServeConfig(max_batch=len(prompt_lens), page_size=page,
                        n_pages=len(prompt_lens) * ppr, max_seq=ppr * page,
                        sampling="greedy", param_dtype=jnp.bfloat16)
    params0 = plib.init_params(tf.arch_spec(cfg), SEED, jnp.bfloat16)
    # τ = 4 over sender steps 0..7: the 8 messages span two subspace epochs
    scfg = SubCGEConfig(rank=16, refresh_period=4)
    rng = np.random.default_rng(SEED)
    msgs = (rng.integers(0, 2**32, 8, dtype=np.uint32),
            (1e-2 * rng.standard_normal(8)).astype(np.float32),
            np.arange(8, dtype=np.int32))
    check(len(epoch_slots(msgs[2], scfg)) == 2, "messages span 2 epochs")

    bridge = LiveUpdateBridge(cfg, scfg, global_seed=SEED, node=0)
    srv = DecodeServer(cfg, params0, serve, bridge=bridge)
    keys = jax.random.split(jax.random.PRNGKey(SEED), len(prompt_lens))
    for rid, (k, T) in enumerate(zip(keys, prompt_lens)):
        srv.submit(Request(rid=rid, prompt=np.asarray(
            jax.random.randint(k, (T,), 0, cfg.vocab), np.int32),
            max_new=new))
    walls = []
    while not srv.sched.done:
        if srv.n_steps == 2:        # fold between decode steps
            bridge.ingest_arrays(*msgs)
        t0 = time.perf_counter()
        srv.step()
        jax.block_until_ready(srv.params)
        walls.append(time.perf_counter() - t0)
    out = srv.results
    check(all(len(out[r]) == new for r in range(len(prompt_lens))),
          f"{len(prompt_lens)} requests x exactly {new} tokens")
    check(all(0 <= t < cfg.vocab for r in out for t in out[r]),
          "tokens in [0, vocab)")
    check(bridge.messages_folded == 8, "8 messages folded live")

    ref = LiveUpdateBridge(cfg, dataclasses.replace(scfg,
                                                    kernel_backend="jnp"),
                           global_seed=SEED, node=0)
    ref.ingest_arrays(*msgs)
    folded_ref = ref.fold(params0)
    check(any_changed(folded_ref, params0), "the fold moved the weights")
    worst = leaves_close(srv.params, folded_ref, params0, FOLD_RTOL)
    check(worst <= 0, f"Pallas fold matches jnp fold (worst excess {worst!r})")
    timing("serving", first_step_with_compile=walls[0],
           fold_step_with_compile=walls[2],
           steady_step=float(np.median(walls[3:])))


def phase_simulator() -> None:
    import numpy as np
    from repro.dtrain.runner import DTrainConfig, run

    print("phase 4: simulator step (quickstart SeedFlood, 16 clients)",
          flush=True)
    r = run(DTrainConfig(method="seedflood", n_clients=16, steps=3,
                         seed=SEED))
    check(len(r.loss_curve) > 0 and bool(np.isfinite(r.loss_curve).all()),
          f"finite losses {r.loss_curve}")
    timing("simulator", first_step_with_compile=r.compile_wall_s,
           run=r.wall_s)


def phase_four_chips() -> None:
    import jax
    import jax.numpy as jnp
    from repro.launch import train as trainlib
    from repro.launch.mesh import make_host_mesh

    print("four chips: opt-1.3b pod step on a 2x2 mesh vs a 1x1 mesh",
          flush=True)
    args = pod_args()
    run = trainlib.setup(args, mesh=make_host_mesh(2, 2),
                         spmd_client_axis=True)
    hlo = run.step.as_text()
    check("tpu_custom_call" in hlo, "sharded step holds Pallas kernels")
    check("all-reduce" in hlo, "row-parallel kernels psum their partials")
    one, in_sh1, compile1_s = trainlib.compile_step(
        run.cfg, run.shape, make_host_mesh(1, 1), run.pod)

    batch = trainlib.client_batch(run, 0)
    p4, m4 = run.step(run.params, batch, jnp.int32(0))
    params1 = jax.device_put(run.params, in_sh1[0])
    p1, m1 = one(params1, jax.device_put(batch, in_sh1[1]), jnp.int32(0))
    print(f"  step 0 alpha_rms: 2x2 {float(m4['alpha_rms'])!r}  "
          f"1x1 {float(m1['alpha_rms'])!r}", flush=True)
    check(abs(float(m4["loss"]) - float(m1["loss"])) <= LOSS_ATOL,
          f"loss 2x2 {float(m4['loss'])!r} vs 1x1 {float(m1['loss'])!r} "
          f"within {LOSS_ATOL}")
    worst = leaves_close(p4, p1, params1, STEP_RTOL)
    check(worst <= 0, f"updated parameters match (worst excess {worst!r})")
    timing("four chips", compile_2x2=run.compile_s, compile_1x1=compile1_s)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the pod step on a 2x2 mesh vs 1x1")
    args = p.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"FAIL: the repository's src/ is not next to this script ({e})",
              file=sys.stderr)
        return 1
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"phase 1: device {dev.platform} {dev.device_kind!r} "
          f"x{len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("FAIL: no TPU found", file=sys.stderr)
        return 1
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    print(f"  compile cache: {enable_compile_cache()}", flush=True)
    try:
        check(ops.resolve_backend("auto") == "pallas",
              'kernel_backend "auto" resolves to "pallas"')
        if args.four_chips:
            check(len(devices) >= 4, "four chips present")
            phase_four_chips()
        else:
            phase_pod_step()
            phase_serving()
            phase_simulator()
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
