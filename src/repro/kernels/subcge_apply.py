"""Pallas TPU kernel: fused SubCGE weight update  W ← W + U A V^T.

This is the paper's hot spot (Appendix A / Fig. 5): applying the aggregated
coefficient matrix A to every 2D weight.  On GPU the paper's win came from
replacing per-message axpys with batched GEMMs; on TPU we go further and
stream W through VMEM exactly once, fusing both thin GEMMs into the tile
visit — arithmetic intensity per W-tile is 2·r·(bn+bm) FLOPs at (bn·bm)
bytes, so the kernel is HBM-bandwidth-bound at precisely 1× W traffic, the
roofline floor for any update touching all of W.

Grid: (instances, ⌈n/bn⌉, ⌈m/bm⌉); instance dims (scan periods, experts)
are collapsed into the leading grid axis.  A (r×r per instance) and the U/V
column panels ride along in VMEM; tiles are the whole dim or multiples of
128, with a partial edge block where no aligned tile divides the weight
(``ops._tile``) — every output element depends only on its own row and
column, so the edge padding never reaches a written element.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ops import _tile


def _kernel(w_ref, u_ref, v_ref, a_ref, o_ref):
    ua = jnp.dot(u_ref[...].astype(jnp.float32), a_ref[0],
                 preferred_element_type=jnp.float32)          # (bn, r)
    delta = jnp.dot(ua, v_ref[...].astype(jnp.float32).T,
                    preferred_element_type=jnp.float32)       # (bn, bm)
    o_ref[0] = (w_ref[0].astype(jnp.float32) + delta).astype(o_ref.dtype)


def _apply(W, U, A, V, bn: int, bm: int, interpret: bool, name: str):
    """The kernel call of both entry points, named after the dispatcher in
    ``kernels/ops.py`` that reaches it: the name its op carries in a
    compiled program and a device profile."""
    batch = W.shape[:-2]
    n, m = W.shape[-2:]
    r = U.shape[-1]
    nb = 1
    for b in batch:
        nb *= b
    Wf = W.reshape(nb, n, m)
    Af = A.reshape(nb, r, r).astype(jnp.float32)

    bn = _tile(n, bn)
    bm = _tile(m, bm)
    grid = (nb, pl.cdiv(n, bn), pl.cdiv(m, bm))

    out = pl.pallas_call(
        _kernel,
        name=name,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn, bm), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((bn, r), lambda b, i, j: (i, 0)),
            pl.BlockSpec((bm, r), lambda b, i, j: (j, 0)),
            pl.BlockSpec((1, r, r), lambda b, i, j: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn, bm), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct(Wf.shape, W.dtype),
        interpret=interpret,
    )(Wf, U, V, Af)
    return out.reshape(W.shape)


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def subcge_apply(W: jax.Array, U: jax.Array, A: jax.Array, V: jax.Array,
                 *, bn: int = 256, bm: int = 256,
                 interpret: bool = False) -> jax.Array:
    """W (*B, n, m) + U (n, r) @ A (*B, r, r) @ V (m, r)^T."""
    return _apply(W, U, A, V, bn, bm, interpret, "subcge_apply")


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def subcge_apply_epochs(W: jax.Array, U: jax.Array, A: jax.Array,
                        V: jax.Array, *, bn: int = 256, bm: int = 256,
                        interpret: bool = False) -> jax.Array:
    """W (*B,n,m) + Σ_e U (E,n,r)[e] @ A (E,*B,r,r)[e] @ V (E,m,r)[e]^T.

    The epoch-grouped replay of delayed-flooding payloads: messages whose
    staleness crosses τ-refresh boundaries partition into E subspace epochs,
    each with its own (U_e, V_e, A_e).  Rather than streaming W once per
    epoch, the epochs fold into a single rank-(E·r) visit:

        Σ_e U_e A_e V_e^T  =  [U_1 … U_E] · blockdiag(A_1 … A_E) · [V_1 … V_E]^T

    so the fused-apply kernel runs unchanged at rank E·r — still exactly one
    HBM read+write of W.  E and r are small (E is pow2-bucketed by
    ``subcge.epoch_slots``; the block-diagonal is (E·r)² f32, VMEM-trivial).
    """
    E, n, r = U.shape
    m = V.shape[1]
    batch = W.shape[:-2]
    nb = 1
    for b in batch:
        nb *= b
    if E == 1:
        return _apply(W, U[0], A[0], V[0], bn, bm, interpret,
                      "subcge_apply_epochs")
    Uc = jnp.moveaxis(U, 0, 1).reshape(n, E * r)
    Vc = jnp.moveaxis(V, 0, 1).reshape(m, E * r)
    Af = A.reshape(E, nb, r, r).astype(jnp.float32)
    blk = jnp.zeros((nb, E * r, E * r), jnp.float32)
    for e in range(E):
        blk = blk.at[:, e * r:(e + 1) * r, e * r:(e + 1) * r].set(Af[e])
    out = _apply(W.reshape(nb, n, m), Uc, blk, Vc, bn, bm, interpret,
                 "subcge_apply_epochs")
    return out.reshape(W.shape)
