"""Pallas TPU kernel: fused rank-1-perturbed matmul  y = x W + s·(x u) v^T.

The ZO dual forward evaluates every client at W ± ε·u v^T.  Materializing the
perturbed weight would double W traffic (read + write of an n×m temp); this
kernel computes the rank-1 epilogue inside the matmul's k-loop: the extra
work per (bm × bk) x-tile is one (bk→1) dot for x·u, and the epilogue adds
s·(xu)·v to the accumulator on the final k step.  W is streamed exactly once,
same as an unperturbed matmul — the perturbation is compute-free at the
memory roofline.

Each call is named after its dispatcher in ``kernels/ops.py``
(``pallas_call(name=...)``), which is the name its op carries in a compiled
program and a device profile.

Grid: (⌈M/bm⌉, ⌈N/bn⌉, K/bk), k innermost/sequential; f32 accumulators in
VMEM scratch (acc for xW, xu for the rank-1 partial).  Output dims may end
in a partial edge block (its padding rows/columns are never written back);
the contracted K is tiled by an exact divisor or taken whole
(``ops._tile`` / ``ops._tile_k``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import _tile, _tile_k


def _kernel(x_ref, w_ref, u_ref, v_ref, s_ref, o_ref, acc_ref, xu_ref, *, nk):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xu_ref[...] = jnp.zeros_like(xu_ref)

    x = x_ref[...]
    acc_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    xu_ref[...] += jnp.dot(x, u_ref[...], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        s = s_ref[0, 0]
        o_ref[...] = (acc_ref[...]
                      + s * xu_ref[...] * v_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret", "out_dtype"))
def rank1_matmul(x: jax.Array, W: jax.Array, u: jax.Array, v: jax.Array,
                 s, *, bm: int = 256, bn: int = 256, bk: int = 512,
                 interpret: bool = False, out_dtype=None) -> jax.Array:
    """x (M,K) @ (W (K,N) + s·u (K,) v (N,)^T) -> (M,N) in ``out_dtype``
    (default x.dtype)."""
    M, K = x.shape
    K2, N = W.shape
    assert K == K2 and u.shape == (K,) and v.shape == (N,)
    bm = _tile(M, bm)
    bn = _tile(N, bn)
    bk = _tile_k(K, bk)
    nk = K // bk
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn), nk)

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        name="rank1_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),       # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),       # W
            pl.BlockSpec((bk, 1), lambda i, j, k: (k, 0)),        # u column
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),        # v row
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),         # s
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, W, u.reshape(K, 1), v.reshape(1, N),
      jnp.asarray(s, jnp.float32).reshape(1, 1))
    return out


def _kernel_t(x_ref, w_ref, v_ref, u_ref, s_ref, o_ref, acc_ref, xv_ref, *, nk):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xv_ref[...] = jnp.zeros_like(xv_ref)

    x = x_ref[...]
    # x (bm, bk) · W (bo, bk)^T contracted on the shared bk axis — the MXU
    # takes the transposed operand natively, no VMEM transpose materialized
    acc_ref[...] += jax.lax.dot_general(
        x, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    xv_ref[...] += jnp.dot(x, v_ref[...], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        s = s_ref[0, 0]
        o_ref[...] = (acc_ref[...]
                      + s * xv_ref[...] * u_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bo", "bk", "interpret", "out_dtype"))
def rank1_matmul_t(x: jax.Array, W: jax.Array, u: jax.Array, v: jax.Array,
                   s, *, bm: int = 256, bo: int = 256, bk: int = 512,
                   interpret: bool = False, out_dtype=None) -> jax.Array:
    """x (M,N) @ (W (O,N) + s·u (O,) v (N,)^T)^T -> (M,O).

    The tied-embedding logits matmul: W is stored output-major (vocab, d) and
    must not be transposed in HBM — the k-loop contracts x and W on their
    shared N axis, with the rank-1 epilogue s·(x·v)·u^T folded into the final
    k step exactly as in :func:`rank1_matmul`.
    """
    M, N = x.shape
    O, N2 = W.shape
    assert N == N2 and u.shape == (O,) and v.shape == (N,)
    bm = _tile(M, bm)
    bo = _tile(O, bo)
    bk = _tile_k(N, bk)
    nk = N // bk
    grid = (pl.cdiv(M, bm), pl.cdiv(O, bo), nk)

    out = pl.pallas_call(
        functools.partial(_kernel_t, nk=nk),
        name="rank1_matmul_t",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),       # x
            pl.BlockSpec((bo, bk), lambda i, j, k: (j, k)),       # W
            pl.BlockSpec((bk, 1), lambda i, j, k: (k, 0)),        # v column
            pl.BlockSpec((1, bo), lambda i, j, k: (0, j)),        # u row
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),         # s
        ],
        out_specs=pl.BlockSpec((bm, bo), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, O), out_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bo), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, W, v.reshape(N, 1), u.reshape(1, O),
      jnp.asarray(s, jnp.float32).reshape(1, 1))
    return out


def _kernel_expert(x_ref, w_ref, u_ref, v_ref, s_ref, o_ref, acc_ref, xu_ref,
                   *, nk):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xu_ref[...] = jnp.zeros_like(xu_ref)

    x = x_ref[0]
    acc_ref[...] += jnp.dot(x, w_ref[0], preferred_element_type=jnp.float32)
    # u row (1, bk) contracted against x's bk axis -> (bc, 1), in the
    # operands' common dtype (as jnp.dot promotes in the dense kernel)
    u = u_ref[0]
    dt = jnp.promote_types(x.dtype, u.dtype)
    xu_ref[...] += jax.lax.dot_general(
        x.astype(dt), u.astype(dt), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == nk - 1)
    def _done():
        s = s_ref[0, 0]
        o_ref[0] = (acc_ref[...]
                    + s * xu_ref[...] * v_ref[0].astype(jnp.float32)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bc", "bn", "bk", "interpret", "out_dtype"))
def rank1_matmul_expert(x: jax.Array, W: jax.Array, u: jax.Array,
                        v: jax.Array, s, *, bc: int = 256, bn: int = 256,
                        bk: int = 512, interpret: bool = False,
                        out_dtype=None) -> jax.Array:
    """Batched per-expert rank-1-perturbed matmul:
    x (E,C,n), W (E,n,m), u (n,E), v (m,E) ->
    y[e] = x[e] @ W[e] + s·(x[e]·u[:,e]) v[:,e]^T.

    Experts ride the leading (parallel) grid axis like the instance dim of
    ``subcge_apply``; the (dim, E) coordinate panels are laid out expert-major
    as (E, 1, dim) rows, so each expert's u/v block is a lane-dense (1, b)
    row, and the k-loop epilogue is per-expert.
    """
    E, C, n = x.shape
    E2, n2, m = W.shape
    assert E == E2 and n == n2 and u.shape == (n, E) and v.shape == (m, E)
    bc = _tile(C, bc)
    bn = _tile(m, bn)
    bk = _tile_k(n, bk)
    nk = n // bk
    grid = (E, pl.cdiv(C, bc), pl.cdiv(m, bn), nk)

    out = pl.pallas_call(
        functools.partial(_kernel_expert, nk=nk),
        name="rank1_matmul_expert",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bk), lambda e, i, j, k: (e, i, k)),   # x
            pl.BlockSpec((1, bk, bn), lambda e, i, j, k: (e, k, j)),   # W
            pl.BlockSpec((1, 1, bk), lambda e, i, j, k: (e, 0, k)),    # u row
            pl.BlockSpec((1, 1, bn), lambda e, i, j, k: (e, 0, j)),    # v row
            pl.BlockSpec((1, 1), lambda e, i, j, k: (0, 0)),           # s
        ],
        out_specs=pl.BlockSpec((1, bc, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, m), out_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bn), jnp.float32),
                        pltpu.VMEM((bc, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, W, u.T.reshape(E, 1, n), v.T.reshape(E, 1, m),
      jnp.asarray(s, jnp.float32).reshape(1, 1))
    return out
