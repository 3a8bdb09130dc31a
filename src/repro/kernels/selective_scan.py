"""Pallas TPU kernel: blocked Mamba selective scan.

    h_t = a_t ⊙ h_{t-1} + b_t,      y_t = Σ_n h_t[d,n] · c_t[n]

GPU Mamba kernels lean on warp-level shuffles; the TPU-native shape is a
*blocked sequential* scan: grid (B, D/bd, T/bt) with the time axis as the
innermost ("arbitrary"/sequential) dimension, the running state h (bd, N)
resident in a VMEM scratch that persists across sequential grid steps, and
the within-block recurrence unrolled over bt VPU steps on (bd, N) panels.
This keeps HBM traffic at 1× read of (a, b, c) + 1× write of y — the same
roofline floor as attention-free inference — with zero recomputation (the
pure-JAX path in models/layers.py pays an associative-scan's extra state
materialization instead).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import _tile, _tile_k


def _kernel(a_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref, h_ref, *, bt, nt):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    def step(t, h):
        a_t = a_ref[0, t].astype(jnp.float32)       # (bd, N)
        b_t = b_ref[0, t].astype(jnp.float32)       # (bd, N)
        c_t = c_ref[0, t].astype(jnp.float32)       # (1, N)
        h = a_t * h + b_t
        y_ref[0, t] = jnp.sum(h * c_t, axis=-1).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bt, step, h_ref[...])
    h_ref[...] = h

    @pl.when(pl.program_id(2) == nt - 1)
    def _done():
        hout_ref[0] = h.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bd", "bt", "interpret"))
def selective_scan(a: jax.Array, bx: jax.Array, c: jax.Array, h0: jax.Array,
                   *, bd: int = 128, bt: int = 128,
                   interpret: bool = False):
    """a/bx (B,T,D,N) f32-castable, c (B,T,N), h0 (B,D,N)
    -> y (B,T,D) f32, h_last (B,D,N) f32."""
    B, T, D, N = a.shape
    bd = _tile(D, bd)
    bt = _tile_k(T, bt)         # the recurrence runs over T: no partial blocks
    nt = T // bt
    grid = (B, pl.cdiv(D, bd), nt)

    # layout: time-major blocks of (bt, bd, N)
    am = jnp.moveaxis(a, 1, 1)  # already (B,T,D,N)

    y, h_last = pl.pallas_call(
        functools.partial(_kernel, bt=bt, nt=nt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bd, N), lambda b, d, t: (b, t, d, 0)),
            pl.BlockSpec((1, bt, bd, N), lambda b, d, t: (b, t, d, 0)),
            pl.BlockSpec((1, bt, 1, N), lambda b, d, t: (b, t, 0, 0)),
            pl.BlockSpec((1, bd, N), lambda b, d, t: (b, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, bd, N), lambda b, d, t: (b, d, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, T, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, D, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(am, bx, c.reshape(B, T, 1, N), h0)
    return y, h_last
