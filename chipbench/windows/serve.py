"""Serve window: ``DecodeServer`` decoding long sessions under a live flood.

Sessions form a closed loop: ``sessions_per_len`` users at each prompt
length decode greedily up to ``max_seq`` positions, and each is replaced by
a new session of the same prompt length when it ends (the users of one
length start and end together, so prefill only runs at (users, length)).
A flood round of ``msgs_per_round`` SeedFlood messages — seed, coefficient
``−lr/msgs · α`` with α ~ N(0, alpha_std²), sender step — falls due every
``round_ms`` of wall time; before each server step the harness hands the
bridge (``LiveUpdateBridge``) every round due by then, and the step folds
them before it decodes.

Set-up makes the weights, prompts and rounds from the seed, warms every fold
shape (K messages × E epochs) with zero coefficients (an exact no-op), and
runs the first step: every prefill shape and the decode bucket compile
there.  Sender steps cross a τ boundary half-way through the window, so
folds under two subspaces occur.

``correct`` replays the served sessions in the configuration's reference
(``ctx.ref``; the dense cost of a step stays ``costs.py``'s, as only dense
decoders are served): a sample drawn
from the seed, one session of each prompt length, is prefilled and decoded
in float32 along its served tokens with the weights as each step had them
(every fold reapplied by the reference), and the widest gap by which a
served token's reference logit lies below the reference's best is compared;
so is, by the worst leaf, the norm of the weights' change after the last
fold.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, costs, rng, weights

PROMPT_SALT, ROUND_SALT, SAMPLE_SALT = 11, 12, 14


def abstract_params(ctx):
    from repro.models import params as plib
    from repro.models import transformer as tf
    return plib.abstract_params(tf.arch_spec(ctx.arch), ctx.dtype)


def n_rounds(ctx) -> int:
    """Rounds generated: enough for the window, twice over."""
    return int(math.ceil(2 * ctx.seconds * 1000 / ctx.workload["round_ms"])) + 8


def make_rounds(ctx) -> list[tuple[np.ndarray, np.ndarray, np.ndarray]]:
    wl = ctx.workload
    R, k = n_rounds(ctx), wl["msgs_per_round"]
    g = np.random.default_rng(rng.seed32(ctx.seed, ROUND_SALT))
    seeds = g.integers(0, 2**32, (R, k), dtype=np.uint32)
    alphas = g.normal(0.0, wl["alpha_std"], (R, k))
    coefs = (-(wl["lr"] / k) * alphas).astype(np.float32)
    in_window = int(ctx.seconds * 1000 / wl["round_ms"])
    step0 = max(0, wl["tau"] - in_window // 2)
    return [(seeds[r], coefs[r], np.full((k,), step0 + r, np.int32))
            for r in range(R)]


def prompt(ctx, T: int, i: int) -> np.ndarray:
    """The prompt of the ``i``-th user session of prompt length ``T``."""
    g = np.random.default_rng([rng.seed32(ctx.seed, PROMPT_SALT), T, i])
    return g.integers(0, ctx.model["vocab"], T, dtype=np.int32)


class State:
    pass


def setup(ctx) -> State:
    from repro.core.subcge import SubCGEConfig
    from repro.serve import DecodeServer, LiveUpdateBridge, Request, ServeConfig

    wl = ctx.workload
    st = State()
    st.Request = Request
    max_seq, page = wl["max_seq"], wl["page_size"]
    serve = ServeConfig(max_batch=wl["max_batch"], page_size=page,
                        n_pages=wl["max_batch"] * max_seq // page,
                        max_seq=max_seq, sampling="greedy",
                        param_dtype=ctx.dtype)
    scfg = SubCGEConfig(rank=wl["rank"], refresh_period=wl["tau"],
                        kernel_backend=wl.get("kernel_backend", "auto"))
    st.gseed = wl["global_seed"]
    st.bridge = LiveUpdateBridge(ctx.arch, scfg, global_seed=st.gseed, node=0)
    params = weights.make(abstract_params(ctx), ctx.seed, ctx.dtype)
    st.srv = DecodeServer(ctx.arch, params, serve, bridge=st.bridge)
    st.rounds = make_rounds(ctx)
    st.next_prompt = {T: 0 for T in wl["prompt_lens"]}
    st.sessions = {}                  # rid -> {"T", "prompt", "emits"}
    st.step_log = []                  # per step: (t_start, t_end, rounds)
    for T in wl["prompt_lens"]:
        for _ in range(wl["sessions_per_len"]):
            submit(st, ctx, T)

    for K in wl["warm_fold_k"]:       # every fold shape, as exact no-ops
        for E in (1, 2):
            steps = np.zeros((K,), np.int32)
            steps[K // 2:] = (E - 1) * wl["tau"]
            st.bridge.ingest_arrays(np.arange(K, dtype=np.uint32),
                                    np.zeros((K,), np.float32), steps)
            st.srv.params = st.bridge.fold(st.srv.params)
    st.warm_messages = st.bridge.messages_folded
    server_step(st, ctx, [])          # admits and prefills every session
    jax.block_until_ready(st.srv.params)
    return st


def submit(st: State, ctx, T: int) -> None:
    i = st.next_prompt[T]
    st.next_prompt[T] = i + 1
    p = prompt(ctx, T, i)
    rid = len(st.sessions)
    st.sessions[rid] = {"T": T, "prompt": p, "emits": [],
                        "max_new": ctx.workload["max_seq"] - T}
    st.srv.submit(st.Request(rid=rid, prompt=p,
                             max_new=ctx.workload["max_seq"] - T))


def server_step(st: State, ctx, rounds: list[int]) -> None:
    """One server step; records when each session's tokens came out and
    replaces the sessions that ended."""
    before = {rid: len(st.srv.results[rid]) for rid in st.sessions}
    t_start = time.perf_counter()
    with jax.profiler.TraceAnnotation("server.step"):
        st.srv.step()
    t_end = time.perf_counter()
    k = len(st.step_log)
    st.step_log.append((t_start, t_end, rounds))
    ended = []
    for rid, s in st.sessions.items():
        n = len(st.srv.results[rid]) - before[rid]
        s["emits"].extend([k] * n)
        if n and len(st.srv.results[rid]) == s["max_new"]:
            ended.append(s["T"])
    for T in ended:
        submit(st, ctx, T)


def measure(st: State, ctx) -> dict:
    wl = ctx.workload
    period = wl["round_ms"] / 1000.0
    r_next, R = 0, len(st.rounds)
    first = len(st.step_log)
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter()
            got = []
            with jax.profiler.TraceAnnotation("bench.ingest"):
                while r_next < R and t0 + (r_next + 1) * period <= now:
                    st.bridge.ingest_arrays(*st.rounds[r_next])
                    got.append(r_next)
                    r_next += 1
            server_step(st, ctx, got)
            if st.step_log[-1][1] - t0 >= ctx.seconds:
                break
    st.t0, st.first_step = t0, first
    t_end = st.step_log[-1][1]
    # rounds due before the last step began were all folded by a step
    due = {r: t0 + (r + 1) * period for r in range(r_next)}
    lags = [st.step_log[k][1] - due[r]
            for k in range(first, len(st.step_log))
            for r in st.step_log[k][2]
            if due[r] < st.step_log[-1][0]]
    gaps, tokens = [], 0
    for s in st.sessions.values():
        times = [st.step_log[k][1] for k in s["emits"]]
        ks = s["emits"]
        tokens += sum(1 for k in ks if k >= first)
        gaps += [b - a for a, b, k in zip(times, times[1:], ks[1:])
                 if k > first]
    return {"attempted": len(st.sessions), "failed": 0,
            "steps": len(st.step_log) - first, "elapsed_s": t_end - t0,
            "tokens": tokens, "gaps": gaps, "lags": lags,
            "rounds": r_next}


def p95(xs) -> float:
    return float(np.percentile(np.asarray(xs), 95)) if len(xs) else math.nan


def end_to_end(st: State, ctx, rec) -> dict:
    return {"serve_tokens_per_s": (rec["tokens"] / rec["elapsed_s"],
                                   "tokens/s"),
            "itl_p95_ms": (1000.0 * p95(rec["gaps"]), "ms"),
            "fold_lag_p95_ms": (1000.0 * p95(rec["lags"]), "ms")}


def cost(st: State, ctx, rec) -> dict:
    """Per window step, the (flops, bytes) its work requires: decode of the
    active sessions, prefill of those admitted, the fold of its messages."""
    m, wl = ctx.model, ctx.workload
    fold = costs.subcge_apply_cost(m, wl["rank"])
    emitted: dict[int, list[tuple[int, int]]] = {}    # step -> (T, n)
    for s in st.sessions.values():
        for n, k in enumerate(s["emits"]):
            emitted.setdefault(k, []).append((s["T"], n))
    steps, folds = [], 0
    for k in range(st.first_step, len(st.step_log)):
        f = b = 0.0
        positions = []
        for T, n in emitted.get(k, []):
            if n == 0:                                # prefill of the prompt
                f += costs.forward_flops(m, 1, T, T, causal=True)
                b += costs.kv_bytes_per_position(m) * T
            else:
                positions.append(T + n - 1)
        if positions:
            df, db = costs.decode_step_cost(m, len(positions), positions)
            f, b = f + df, b + db
        if st.step_log[k][2]:
            f, b, folds = f + fold[0], b + fold[1], folds + 1
        steps.append((f, b))
    return {"steps": steps, "folds": folds, "fold": fold}


def finish(st: State, ctx, rec) -> None:
    p0 = weights.make(abstract_params(ctx), ctx.seed, ctx.dtype)
    norms = weights.leaf_norms_fn()(st.srv.params, p0)
    ctx.program_readings = {
        "change": {k: float(v) for k, v in norms.items()},
        "sessions": {rid: {"T": s["T"], "prompt": s["prompt"],
                           "tokens": list(st.srv.results[rid]),
                           "emits": list(s["emits"])}
                     for rid, s in st.sessions.items()},
        "folds": [rs for _, _, rs in st.step_log],
        "rounds": st.rounds, "gseed": st.gseed,
        "warm_messages": st.warm_messages,
        "program_folded": st.bridge.messages_folded}
    st.srv = st.bridge = None


def sample_sessions(ctx, sessions: dict) -> list[int]:
    """One session of each prompt length among the first generation."""
    g = np.random.default_rng(rng.seed32(ctx.seed, SAMPLE_SALT))
    per, out = ctx.workload["sessions_per_len"], []
    for j, T in enumerate(ctx.workload["prompt_lens"]):
        out.append(j * per + int(g.integers(0, per)))
        assert sessions[out[-1]]["T"] == T
    return out


def fold_arrays(ctx, readings: dict, rounds: list[int]) -> tuple:
    """The messages of ``rounds`` as one fold's arrays, padded to a power
    of two: (seeds, coefs, sender steps, the refresh steps present)."""
    tau = ctx.workload["tau"]
    seeds = np.concatenate([readings["rounds"][r][0] for r in rounds])
    coefs = np.concatenate([readings["rounds"][r][1] for r in rounds])
    steps = np.concatenate([readings["rounds"][r][2] for r in rounds])
    pad = (1 << int(np.ceil(np.log2(len(seeds))))) - len(seeds)
    seeds = np.concatenate([seeds, np.zeros(pad, np.uint32)])
    coefs = np.concatenate([coefs, np.zeros(pad, np.float32)])
    steps = np.concatenate([steps, np.full(pad, -1, np.int32)])
    ep = np.unique((steps[steps >= 0] // tau) * tau)
    epochs = np.full((2,), -1, np.int32)
    epochs[:len(ep)] = ep
    return seeds, coefs, steps, epochs


def fold_fn(ctx, readings: dict, prec: str = "f32"):
    """jit(params, seeds, coefs, steps, epochs -> params): the reference's
    fold of one step's messages."""
    wl, ref = ctx.workload, ctx.ref
    return jax.jit(lambda params, seeds, coefs, steps, epochs:
                   ref.apply_messages(params, seeds, coefs, steps, epochs,
                                      wl["rank"], wl["tau"],
                                      readings["gseed"], prec))


def folded_weights(ctx, readings: dict, prec: str = "f32"):
    """The weights after every fold of the run, folded by the reference at
    ``prec`` (no decoding)."""
    jfold = fold_fn(ctx, readings, prec)
    params = weights.make(abstract_params(ctx), ctx.seed, ctx.dtype)
    for rounds in readings["folds"]:
        if rounds:
            params = jfold(params, *fold_arrays(ctx, readings, rounds))
    return params


def replay(ctx, readings: dict, precs=("f32",)):
    """Replay the sampled sessions in the reference, step by step, with
    every fold reapplied.  Yields per step the reference logits for each
    precision in ``precs`` beside the served next tokens."""
    m, wl, ref = ctx.model, ctx.workload, ctx.ref
    sess = readings["sessions"]
    rids = sample_sessions(ctx, sess)
    B, S = len(rids), wl["max_seq"]
    KV, hd, L = m["n_kv_heads"], m["head_dim"], m["n_layers"]
    Tmax = max(sess[r]["T"] for r in rids)
    prompts = np.zeros((B, Tmax), np.int32)
    for b, r in enumerate(rids):
        prompts[b, :sess[r]["T"]] = sess[r]["prompt"]
    lens = np.asarray([sess[r]["T"] for r in rids], np.int32)

    def prefill(params, tokens, lens, prec):
        pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        cache = (jnp.zeros((L, B, S, KV, hd), jnp.float32),) * 2
        logits, cache = ref.forward(m, params, tokens, pos, cache=cache,
                                    prec=prec)
        return logits[jnp.arange(B), lens - 1], cache

    def decode(params, cache, tok, pos, prec):
        logits, cache = ref.forward(m, params, tok[:, None], pos[:, None],
                                    cache=cache, prec=prec)
        return logits[:, 0], cache

    jprefill = jax.jit(prefill, static_argnames="prec")
    jdecode = jax.jit(decode, static_argnames="prec")
    jfold = fold_fn(ctx, readings)
    params = weights.make(abstract_params(ctx), ctx.seed, ctx.dtype)
    emits = [sess[r]["emits"] for r in rids]
    toks = [sess[r]["tokens"] for r in rids]
    assert all(e[0] == 0 for e in emits), "sampled sessions start at step 0"
    caches, logits = {}, {}
    for prec in precs:          # step 0 has no fold: prefill under θ0
        logits[prec], caches[prec] = jprefill(params, jnp.asarray(prompts),
                                              jnp.asarray(lens), prec)
    yield 0, list(range(B)), np.asarray([t[0] for t in toks]), logits
    # per session, the step that emitted each token after the first
    at = [dict() for _ in range(B)]
    for b in range(B):
        for n, k in enumerate(emits[b][1:], start=1):
            assert k not in at[b], "one decode token per session per step"
            at[b][k] = n
    for k, rounds in enumerate(readings["folds"]):
        if rounds:
            params = jfold(params, *fold_arrays(ctx, readings, rounds))
        active = [b for b in range(B) if k in at[b]]
        if not active:
            continue
        # a session's decode at step k reads its previous token at the
        # position after it; rows of ended sessions write their last slot
        n = [at[b].get(k, len(toks[b])) for b in range(B)]
        tok_in = jnp.asarray([toks[b][n[b] - 1] for b in range(B)], jnp.int32)
        pos = jnp.asarray([min(lens[b] + n[b] - 1, S - 1) for b in range(B)],
                          jnp.int32)
        for prec in precs:
            logits[prec], caches[prec] = jdecode(params, caches[prec],
                                                 tok_in, pos, prec)
        served = np.asarray([toks[b][n[b]] if b in active else 0
                             for b in range(B)], np.int32)
        yield k, active, served, logits
    yield -1, [], None, {"final_params": params}


def check(ctx, rec) -> dict:
    readings = ctx.program_readings
    gaps, final = [], None
    for k, active, served, lg in replay(ctx, readings):
        if k < 0:
            final = lg["final_params"]
            continue
        ref_logits = np.asarray(lg["f32"])[active]
        gaps.append(compare.widest_logit_gap(ref_logits, served[active]))
    p0 = weights.make(abstract_params(ctx), ctx.seed, ctx.dtype)
    ref_change = {k: float(v) for k, v in
                  weights.leaf_norms_fn()(final, p0).items()}
    return {"logit_gap": max(gaps) if gaps else math.inf,
            "fold_change_gap": compare.worst_leaf_gap(
                readings["change"], ref_change, ref_change)}
