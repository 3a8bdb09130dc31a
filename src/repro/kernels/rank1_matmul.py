"""Pallas TPU kernels: fused rank-1-perturbed matmuls  y = x W + s·(x u) v^T.

The ZO dual forward evaluates every client at W ± ε·u v^T.  Materializing the
perturbed weight would double W traffic (read + write of an n×m temp); these
kernels compute the rank-1 term inside the matmul instead: the side product
x·u (an f32 (bm, 1) partial) and the epilogue s·(xu)·v on each output tile's
final k step.

Schedule (:func:`rank1_blocks`).  Grid (⌈M/bm⌉, ⌈N/bn⌉, K/bk), k innermost;
f32 accumulators in VMEM scratch (``acc`` for x·W, ``xu`` for the side
product).  Each grid step fetches an x block (bm, bk) and a W block
(bk, bn): W is streamed once per row block (a W block whose index does not
change from one step to the next is not fetched again), and x once per
output tile.  The blocks are sized from the call's shapes so that every
step is compute-bound: bm·bn/(bm+bn) flop per byte fetched, 410 at the
default 512 × 2048 against a v5e ridge of ~240.  The output-tile axis j
runs sequentially ("arbitrary"), so x·u — which depends on the row block
and the k block only — is accumulated during the first output tile
(j == 0) and reused by the epilogue of every later one.  It stays in f32:
a broadcast-multiply of the x block by the lane-dense u row and a lane
sum, on the VPU, beside the MXU's bf16 x·W.  Under a ``vmap`` over clients
Pallas prepends a parallel client axis to the grid, outermost; the scratch
carries over the sequential axes inside each client's row block.

Each call is named after its dispatcher in ``kernels/ops.py``
(``pallas_call(name=...)``), which is the name its op carries in a compiled
program and a device profile.  Output dims may end in a partial edge block
(its padding rows/columns are never written back); the contracted K is
tiled by an exact divisor or taken whole.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import _tile, _tile_k

#: block targets of :func:`rank1_blocks`: rows, output lanes, contracted
ROWS, COLS, DEPTH = 512, 2048, 2048
#: what :func:`vmem_bytes` may reach: a third of a v5e's 128 MiB of VMEM
VMEM_BUDGET = 40 * 2**20
#: the compiler's default scoped VMEM limit, the least a call asks for
VMEM_DEFAULT = 16 * 2**20
#: what a call asks for beyond :func:`vmem_bytes` (the compiler's own
#: scratch; its allocations stayed within the count at every shape the
#: described-v5e compile tests cover)
VMEM_SLACK = 2 * 2**20


def vmem_bytes(bm: int, bn: int, bk: int, x_dtype, out_dtype) -> int:
    """VMEM one schedule holds: x, W and out blocks double-buffered (W in
    x's dtype), the u/v rows (f32, padded to 8 sublanes) double-buffered,
    the f32 accumulator, the (bm, 1) f32 side partial (padded to a 128-lane
    tile), and the body's f32 values: the (bm, bn) product of the MXU and
    the (bm, bk) product of the side sum."""
    xb = jnp.dtype(x_dtype).itemsize
    ob = jnp.dtype(out_dtype).itemsize
    pipelined = 2 * (xb * (bm * bk + bk * bn) + ob * bm * bn + 4 * 8 * (bk + bn))
    return pipelined + 4 * (2 * bm * bn + bm * 128 + bm * bk)


def _out_block(dim: int, target: int) -> int:
    """An output block of ~``target`` lanes: ``_tile``'s aligned divisor if
    it is at least half the target, else ``target`` with an edge block
    (a narrow divisor — 128 of Qwen's 151936 vocabulary — costs more grid
    steps than a partial last block wastes)."""
    t = _tile(dim, target)
    return t if t == dim or 2 * t >= target else target


def _halve(b: int) -> int:
    return max(128, (b // 2) // 128 * 128)


def rank1_blocks(M: int, K: int, N: int, x_dtype, out_dtype) -> tuple:
    """(bm, bn, bk) for x (M, K) @ W (K, N) -> (M, N); for the transposed
    kernel, (bm, bo, bk) with N the output width and K the contracted one.

    The whole row count up to :data:`ROWS` (one client's rows under the
    train step's vmap), output blocks of ~:data:`COLS` lanes, contracted
    blocks of up to :data:`DEPTH` that divide K; then, until
    :func:`vmem_bytes` fits :data:`VMEM_BUDGET`, narrower output blocks,
    shallower contracted blocks, fewer rows, in that order.  Every block is
    the whole dim or a multiple of 128, as Mosaic requires.
    """
    bm = _tile(M, ROWS)
    bn = _out_block(N, COLS)
    bk = _tile_k(K, DEPTH)

    def fits():
        return vmem_bytes(bm, bn, bk, x_dtype, out_dtype) <= VMEM_BUDGET

    while not fits() and bn > 128:
        bn = _halve(bn)
    while not fits() and bk > 128 and _tile_k(K, bk // 2) < bk:
        bk = _tile_k(K, bk // 2)
    while not fits() and bm > 128:
        bm = _halve(bm)
    return bm, bn, bk


def _kernel(x_ref, w_ref, u_ref, v_ref, s_ref, o_ref, acc_ref, xu_ref, *,
            nk, transposed):
    j, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((j == 0) & (k == 0))
    def _init_xu():
        xu_ref[...] = jnp.zeros_like(xu_ref)

    x = x_ref[...]
    if transposed:
        # x (bm, bk) · W (bo, bk)^T contracted on the shared bk axis — the
        # MXU takes the transposed operand natively, no VMEM transpose
        acc_ref[...] += jax.lax.dot_general(
            x, w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        acc_ref[...] += jnp.dot(x, w_ref[...],
                                preferred_element_type=jnp.float32)

    # x·u depends on (row block, k block) only: summed while j == 0, read
    # by every output tile's epilogue
    @pl.when(j == 0)
    def _side():
        xu_ref[...] += jnp.sum(x.astype(jnp.float32) * u_ref[...], axis=1,
                               keepdims=True)

    @pl.when(k == nk - 1)
    def _done():
        s = s_ref[0, 0]
        o_ref[...] = (acc_ref[...]
                      + s * xu_ref[...] * v_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def _call(x, W, a, b, s, *, name, transposed, blocks, interpret, out_dtype):
    """The shared pallas_call: x (M, K) against W ((K, N), or (N, K) when
    ``transposed``), ``a`` (K,) contracted with x, ``b`` (N,) the epilogue's
    output-side vector."""
    M, K = x.shape
    N = b.shape[0]
    out_dtype = out_dtype or x.dtype
    bm, bn, bk = rank1_blocks(M, K, N, x.dtype, out_dtype)
    bm = _tile(M, blocks[0]) if blocks[0] else bm
    bn = _tile(N, blocks[1]) if blocks[1] else bn
    bk = _tile_k(K, blocks[2]) if blocks[2] else bk
    nk = K // bk
    w_spec = (pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)) if transposed
              else pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)))
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, transposed=transposed),
        name=name,
        grid=(pl.cdiv(M, bm), pl.cdiv(N, bn), nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),       # x
            w_spec,                                               # W
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),        # a row
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),        # b row
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),         # s
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.float32)],
        # j sequential: the side product of j == 0 serves every later j
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(
                VMEM_DEFAULT,
                vmem_bytes(bm, bn, bk, x.dtype, out_dtype) + VMEM_SLACK)),
        interpret=interpret,
    )(x, W, a.reshape(1, K), b.reshape(1, N),
      jnp.asarray(s, jnp.float32).reshape(1, 1))


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret", "out_dtype"))
def rank1_matmul(x: jax.Array, W: jax.Array, u: jax.Array, v: jax.Array,
                 s, *, bm: int | None = None, bn: int | None = None,
                 bk: int | None = None, interpret: bool = False,
                 out_dtype=None) -> jax.Array:
    """x (M,K) @ (W (K,N) + s·u (K,) v (N,)^T) -> (M,N) in ``out_dtype``
    (default x.dtype).  Blocks from :func:`rank1_blocks`; ``bm``/``bn``/
    ``bk`` override them (tests)."""
    M, K = x.shape
    K2, N = W.shape
    assert K == K2 and u.shape == (K,) and v.shape == (N,)
    return _call(x, W, u, v, s, name="rank1_matmul", transposed=False,
                 blocks=(bm, bn, bk), interpret=interpret,
                 out_dtype=out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bo", "bk", "interpret", "out_dtype"))
def rank1_matmul_t(x: jax.Array, W: jax.Array, u: jax.Array, v: jax.Array,
                   s, *, bm: int | None = None, bo: int | None = None,
                   bk: int | None = None, interpret: bool = False,
                   out_dtype=None) -> jax.Array:
    """x (M,N) @ (W (O,N) + s·u (O,) v (N,)^T)^T -> (M,O).

    The tied-embedding logits matmul: W is stored output-major (vocab, d) and
    must not be transposed in HBM — the k-loop contracts x and W on their
    shared N axis, with the rank-1 epilogue s·(x·v)·u^T scheduled exactly
    as in :func:`rank1_matmul`.
    """
    M, N = x.shape
    O, N2 = W.shape
    assert N == N2 and u.shape == (O,) and v.shape == (N,)
    return _call(x, W, v, u, s, name="rank1_matmul_t", transposed=True,
                 blocks=(bm, bo, bk), interpret=interpret,
                 out_dtype=out_dtype)


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
