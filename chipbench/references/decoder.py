"""Plain reference of the dense decoders the benchmark runs (OPT, Qwen1.5).

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernels, no paging, no fused perturbation.  It reads weights in the
program's tree layout (one scanned group ``g0/s0`` of stacked layers, plus
``embed``) but computes everything from the equations of the configuration's
``model`` block:

* pre-norm blocks: LayerNorm (OPT) or RMSNorm (Qwen), gains stored as
  offsets from 1;
* attention with optional q/k/v biases, learned positions (OPT, table
  rows clipped to the table) or split-half rotary embeddings (Qwen);
* ReLU MLP (OPT) or SwiGLU ``silu(x W1) · (x W3) W2`` (Qwen);
* tied output head, next-token cross entropy.

A configuration names this module with ``"reference": "decoder"``; the
windows reach it as ``ctx.ref``.  Besides the equations it gives the
family's count of operations and bytes of a train cell (``train_cost``,
from ``costs.py``).

``prec`` selects the arithmetic: ``"f32"`` is the reference; ``"bf16"`` and
``"fp8"`` (e4m3, one absmax scale per tensor) round every matmul operand
first — the lower-precision controls.

Perturbations follow SeedFlood's definition: a matrix leaf moves by
``s · U[:, i] V[:, j]ᵀ`` (materialized here, per layer), a vector leaf by
``s · z``; an update of K messages adds ``U A Vᵀ`` with
``A = Σ_k c_k E_{i_k j_k}`` under each message's sender epoch, and
``Σ_k c_k z_k`` to vectors, rounded once to the weight's dtype.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import costs, rng
from chipbench.weights import flat_paths, is_vector

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
GROUP = "g0/s0/"


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def quant(x, prec: str):
    if prec == "f32":
        return x
    if prec == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if prec == "fp8":
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        scale = 448.0 / amax
        return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    raise ValueError(prec)


def mm(eq: str, a, b, prec: str):
    return jnp.einsum(eq, quant(a, prec), quant(b, prec), precision=HIGHEST)


def layernorm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * (1.0 + g) + b


def rmsnorm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * (1.0 + g)


def rope(x, pos, theta):
    """x (B, T, H, hd), pos (B, T): rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[..., None, None] * freqs
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


# ---------------------------------------------------------------------------
# perturbation views
# ---------------------------------------------------------------------------

def leaf_shapes(params) -> dict:
    paths, leaves = flat_paths(params)
    return {p: tuple(x.shape) for p, x in zip(paths, leaves)}


def matrix_leaves(shapes: dict) -> dict:
    """path -> per-instance (rows, cols) of every matrix leaf."""
    return {p: s[-2:] for p, s in shapes.items() if not is_vector(p)}


def make_pert(shapes: dict, sub: dict, rank: int, seed):
    """One message's perturbation: {path: (u, v)} per matrix leaf, with the
    instance dims leading — u (*B, rows) — and {path: z} per vector leaf."""
    mats, vecs = {}, {}
    for path, shape in shapes.items():
        if is_vector(path):
            vecs[path] = rng.dense_z(path, shape, seed)
        else:
            i, j = rng.coords(path, shape[:-2], rank, seed)
            U, V = sub[path]
            mats[path] = (jnp.moveaxis(U[:, i], 0, -1),
                          jnp.moveaxis(V[:, j], 0, -1))
    return mats, vecs


class Pert:
    """A perturbation view with its scale; ``None`` parts mean unperturbed."""

    def __init__(self, mats=None, vecs=None, s=0.0):
        self.mats, self.vecs, self.s = mats or {}, vecs or {}, s

    def layer(self, l):
        """The slice of layer ``l`` of the scanned group."""
        mats = {p[len(GROUP):]: (u[l], v[l]) for p, (u, v) in self.mats.items()
                if p.startswith(GROUP)}
        vecs = {p[len(GROUP):]: z[l] for p, z in self.vecs.items()
                if p.startswith(GROUP)}
        return Pert(mats, vecs, self.s)

    def weight(self, name, W):
        W = W.astype(jnp.float32)
        if name in self.mats:
            u, v = self.mats[name]
            W = W + self.s * u[:, None] * v[None, :]
        return W

    def vec(self, name, b):
        b = b.astype(jnp.float32)
        if name in self.vecs:
            b = b + self.s * self.vecs[name]
        return b


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _layer(m, lp, pt: Pert, x, pos, cache_kv, prec):
    """One block.  x (B, T, d), pos (B, T).  ``cache_kv``: None, or
    (k, v) of (B, S, KV, hd) into which this block writes its new keys."""
    B, T, _ = x.shape
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]

    def norm(x, key):
        g = pt.vec(key + "_scale", lp[key + "_scale"])
        if m["norm"] == "layernorm":
            return layernorm(x, g, pt.vec(key + "_bias", lp[key + "_bias"]),
                             eps)
        return rmsnorm(x, g, eps)

    def proj(x, name, bias=None):
        y = mm("btn,nm->btm", x, pt.weight(name, lp[name]), prec)
        if bias is not None:
            y = y + pt.vec(bias, lp[bias])
        return y

    h = norm(x, "ln_attn")
    qb = m["qkv_bias"]
    q = proj(h, "wq", "bq" if qb else None).reshape(B, T, H, hd)
    k = proj(h, "wk", "bk" if qb else None).reshape(B, T, KV, hd)
    v = proj(h, "wv", "bv" if qb else None).reshape(B, T, KV, hd)
    if m["pos"] == "rope":
        q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
    if cache_kv is None:
        keys, vals, kpos, new_cache = k, v, pos, None
    else:
        ck, cv = cache_kv
        rows = jnp.arange(B)[:, None]
        ck, cv = ck.at[rows, pos].set(k), cv.at[rows, pos].set(v)
        keys, vals, new_cache = ck, cv, (ck, cv)
        kpos = jnp.broadcast_to(jnp.arange(ck.shape[1]), (B, ck.shape[1]))
    G = H // KV
    qg = q.reshape(B, T, KV, G, hd)
    s = mm("btkgd,bskd->bkgts", qg, keys, prec) / math.sqrt(hd)
    mask = kpos[:, None, :] <= pos[:, :, None]                  # (B, T, S)
    s = jnp.where(mask[:, None, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bkgts,bskd->btkgd", p, vals, prec).reshape(B, T, H * hd)
    x = x + proj(o, "wo")

    h = norm(x, "ln_mlp")
    act = ACTS[m["act"]]
    if m["gated_mlp"]:
        f = act(proj(h, "w1")) * proj(h, "w3")
    else:
        f = act(proj(h, "w1"))
    return x + proj(f, "w2"), new_cache


def forward(m, params, tokens, pos, pert: Pert | None = None, cache=None,
            prec: str = "f32"):
    """Logits (B, T, V) in f32.  tokens, pos (B, T).  ``cache``: None, or
    (k, v) stacked over layers, (L, B, S, KV, hd); returns (logits, cache)."""
    pert = pert or Pert()
    emb = params["embed"]
    E = pert.weight("embed/tok", emb["tok"])
    x = E[tokens]
    if m["pos"] == "learned":
        P = pert.weight("embed/pos", emb["pos"])
        x = x + P[jnp.clip(pos, 0, P.shape[0] - 1)]
    layers = params["g0"]["s0"]
    n_layers = m["n_layers"]

    def body(x, xs):
        l, ckv = xs
        lp = jax.tree.map(lambda a: a[l], layers)
        x, new = _layer(m, lp, pert.layer(l), x, pos, ckv, prec)
        return x, new

    ls = jnp.arange(n_layers)
    x, new_cache = jax.lax.scan(body, x, (ls, cache))
    pe = Pert({p[len("embed/"):]: uv for p, uv in pert.mats.items()
               if p.startswith("embed/")},
              {p[len("embed/"):]: z for p, z in pert.vecs.items()
               if p.startswith("embed/")}, pert.s)
    g = pe.vec("ln_f_scale", emb["ln_f_scale"])
    if m["norm"] == "layernorm":
        x = layernorm(x, g, pe.vec("ln_f_bias", emb["ln_f_bias"]),
                      m["norm_eps"])
    else:
        x = rmsnorm(x, g, m["norm_eps"])
    logits = mm("btd,vd->btv", x, E, prec)
    return logits, new_cache


def lm_loss(m, params, tokens, pert=None, prec="f32", half=False):
    """Mean next-token cross entropy.  ``half`` plants a fault: the mean is
    taken over the first half of the rows (of the positions, for one row)."""
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    logits, _ = forward(m, params, tokens, pos, pert, prec=prec)
    lg = logits[:, :-1]
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    nll = jax.scipy.special.logsumexp(lg, -1) - gold
    if half:
        nll = nll[: B // 2] if B > 1 else nll[:, : (T - 1) // 2]
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# SeedFlood update and training step
# ---------------------------------------------------------------------------

def apply_messages(params, seeds, coefs, steps, epochs, rank: int, tau: int,
                   global_seed, prec: str = "f32"):
    """Fold K messages (seeds, coefs, sender steps) into ``params``, each
    under its sender's subspace.  ``epochs``: the refresh steps present,
    a static-length array (unused slots match no message).  ``prec`` below
    f32 rounds the factors of every update first (a control)."""
    shapes = leaf_shapes(params)
    mats = matrix_leaves(shapes)
    coefs = coefs.astype(jnp.float32)
    ep = rng.refresh_step(steps, tau)
    subs = [rng.subspace(mats, rank, global_seed, epochs[e])
            for e in range(epochs.shape[0])]
    paths, leaves = flat_paths(params)
    out = []
    for path, W in zip(paths, leaves):
        shape = shapes[path]
        if is_vector(path):
            def body(acc, sc, path=path, shape=shape):
                s, c = sc
                return acc + c * quant(rng.dense_z(path, shape, s), prec), None
            upd, _ = jax.lax.scan(body, jnp.zeros(shape, jnp.float32),
                                  (seeds, coefs))
        else:
            bshape = shape[:-2]
            i, j = jax.vmap(lambda s, path=path, b=bshape:
                            rng.coords(path, b, rank, s))(seeds)
            bidx = tuple(jnp.broadcast_to(ix, i.shape)
                         for ix in jnp.indices(bshape)) if bshape else ()
            upd = jnp.zeros(shape, jnp.float32)
            for e, sub in enumerate(subs):
                c = jnp.where(ep == epochs[e], coefs, 0.0)
                c = jnp.broadcast_to(c.reshape((-1,) + (1,) * len(bshape)),
                                     i.shape)
                A = jnp.zeros(bshape + (rank, rank), jnp.float32)
                A = A.at[bidx + (i, j)].add(c)
                U, V = sub[path]
                upd = upd + jnp.einsum("nr,...rs,ms->...nm", quant(U, prec),
                                       quant(A, prec), quant(V, prec),
                                       precision=HIGHEST)
        out.append((W.astype(jnp.float32) + upd).astype(W.dtype))
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(params),
                                        out)


def zo_step(m, params, tokens, step, base_seed, hp, prec="f32"):
    """One SeedFlood step of n clients sharing ``params``: each client's
    dual perturbed loss gives α = (L+ − L−)/2ε; every client's message
    (seed, −lr·α/n) is folded.  tokens (n, b, T).  ``hp["fault"] ==
    "half_batch"`` plants the fault of a loss over half of each client's
    batch.  Returns (params, mean loss, alphas)."""
    n = tokens.shape[0]
    shapes = leaf_shapes(params)
    refresh = rng.refresh_step(step, hp["tau"])
    sub = rng.subspace(matrix_leaves(shapes), hp["rank"], base_seed, refresh)
    eps = hp["eps"]

    def client(k):
        seed = rng.client_seed(base_seed, step, k)
        mats, vecs = make_pert(shapes, sub, hp["rank"], seed)
        half = hp.get("fault") == "half_batch"
        lp = lm_loss(m, params, tokens[k], Pert(mats, vecs, eps), prec, half)
        lm = lm_loss(m, params, tokens[k], Pert(mats, vecs, -eps), prec, half)
        return (lp - lm) / (2 * eps), 0.5 * (lp + lm)

    alphas, losses = jax.lax.map(client, jnp.arange(n))
    seeds = jax.vmap(lambda k: rng.client_seed(base_seed, step, k))(
        jnp.arange(n))
    coefs = (-hp["lr"] / n) * alphas
    steps = jnp.full((n,), step, jnp.int32)
    new = apply_messages(params, seeds, coefs, steps, refresh[None],
                         hp["rank"], hp["tau"], base_seed)
    return new, jnp.mean(losses), alphas


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------

def train_cost(m, wl) -> dict:
    """What one SeedFlood step of the train cell ``wl`` requires: the whole
    step's model FLOPs (``step_flops``), (kernel, flops, bytes) of each
    rank-1 call site over the clients (``rank1``), the perturbed forwards per
    step that run them (``rank1_per_step``) and the fold's (flops, bytes)
    (``subcge_apply``)."""
    n, b, T = wl["clients"], wl["seqs_per_client"], wl["seq_len"]
    calls = costs.rank1_calls(m, b * T)
    return {"step_flops": costs.train_step_flops(m, n, b, T, wl["rank"]),
            "rank1": [(c["kernel"], *costs.rank1_cost(c, n)) for c in calls],
            "rank1_per_step": 2,                 # the ± forwards
            "subcge_apply": costs.subcge_apply_cost(m, wl["rank"])}
