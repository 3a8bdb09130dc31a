"""Public dispatch layer for the Pallas kernel suite.

Every hot-path op has two real implementations behind the one
``kernel_backend`` knob (legal values: :data:`repro.configs.base.KERNEL_BACKENDS`):

* ``"jnp"``       — the pure-jnp oracles in :mod:`repro.kernels.ref`.  This is
  bitwise the pre-kernel training stack (the golden-parity suite pins it) and
  the resolved default off-TPU.
* ``"pallas"``    — the compiled Pallas TPU lowerings.
* ``"interpret"`` — the *same* Pallas kernels through the Pallas interpreter,
  so CI exercises the real kernel bodies on CPU.
* ``"auto"``      — resolve once per process: ``pallas`` on TPU, ``jnp``
  elsewhere.

Backend resolution is explicit and cached: ``"auto"`` is resolved exactly once
(:func:`_resolve_auto` is memoized) instead of re-sniffing ``jax.default_backend()``
on every call, and the backend any jitted caller sees is a plain Python string
captured at trace time.  :func:`set_default_backend` changes the process
default for traces created *afterwards* — per-run code (the dtrain method
plugins, ``PodConfig``) threads the knob explicitly through fresh per-run jit
closures, so two runs in one process can never share a stale trace.

The kernel modules are imported lazily inside the dispatchers (they import
the tiling helpers from here, and the jnp path should not pay for Pallas
imports).  Under a mesh of more than one device the kernel backends run each
call per shard (see "sharded meshes" below).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import KERNEL_BACKENDS
from repro.kernels import ref as _ref

_default_backend = "auto"


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=None)
def _resolve_auto() -> str:
    """What ``"auto"`` means on this process — computed once, then frozen, so
    jitted callers cannot silently flip paths between traces."""
    return "pallas" if on_tpu() else "jnp"


def set_default_backend(backend: str) -> str:
    """Set the process-default backend; returns the previous value.

    Only affects traces created after the call — already-compiled jit caches
    keep the backend they captured.
    """
    global _default_backend
    if backend not in KERNEL_BACKENDS:
        raise ValueError(f"kernel_backend must be one of {KERNEL_BACKENDS}, "
                         f"got {backend!r}")
    prev, _default_backend = _default_backend, backend
    return prev


def get_default_backend() -> str:
    return _default_backend


@contextlib.contextmanager
def default_backend(backend: str):
    """Scoped :func:`set_default_backend` (tests, benchmarks)."""
    prev = set_default_backend(backend)
    try:
        yield
    finally:
        set_default_backend(prev)


def resolve_backend(backend: str | None = None) -> str:
    """Map a knob value (or None = process default) to a concrete backend:
    one of ``"jnp" | "pallas" | "interpret"``."""
    if backend is None:
        backend = _default_backend  # sfcheck: noqa[SF002] -- the ONE sanctioned trace-time read (DESIGN.md §7/§8): backend choice is captured per trace by design, set_default_backend/default_backend document that live traces keep their backend; every per-run path passes the knob explicitly
    if backend not in KERNEL_BACKENDS:
        raise ValueError(f"kernel_backend must be one of {KERNEL_BACKENDS}, "
                         f"got {backend!r}")
    return _resolve_auto() if backend == "auto" else backend


# ---------------------------------------------------------------------------
# tiling
# ---------------------------------------------------------------------------
#
# Mosaic accepts a block only if each of its last two dims is the whole
# array dim or aligned to the vreg tile (a multiple of 8 on the sublane, 128
# on the lane axis).  Every block dim the kernels choose is therefore either
# the whole dim or a multiple of 128, which satisfies both axes.

def _tile(dim: int, target: int) -> int:
    """Block size for an output (non-contracted) dim, used with a
    ``pl.cdiv`` grid.

    The whole ``dim`` when it fits in ``target`` (or in one 128 lane tile);
    otherwise the largest multiple of 128 ≤ ``target`` that divides ``dim``;
    otherwise the largest multiple of 128 ≤ ``target``, and the last grid
    block is a partial edge block — e.g. ``_tile(1016, 256) == 256`` (four
    blocks, the last holding 248 rows) and ``_tile(50272, 256) == 256``.
    """
    top = max(128, target - target % 128)
    if dim <= max(target, top):
        return dim
    for t in range(top, 0, -128):
        if dim % t == 0:
            return t
    return top


def _tile_k(dim: int, target: int) -> int:
    """Block size for a contracted dim, which must tile exactly (an edge
    block would sum its padding into the result): the whole ``dim`` when it
    fits in ``target``, else the largest multiple-of-128 divisor ≤
    ``target``, else the whole ``dim``."""
    if dim <= target:
        return dim
    for t in range(target - target % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


# ---------------------------------------------------------------------------
# sharded meshes
# ---------------------------------------------------------------------------
#
# XLA cannot partition a Mosaic kernel, so under a mesh of more than one
# device each kernel call runs inside ``jax.shard_map`` on the shards of its
# operands.  ``spec`` is the PartitionSpec of the weight operand
# (``models.params.tree_specs``; None = replicated).  A weight sharded on
# its output axis needs no collective; one sharded on its contracted axis
# gives per-shard partials x_k W_k + s·(x_k u_k) vᵀ, whose f32 psum is exact
# because the rank-1 term is linear in the shard too.

def kernel_mesh():
    """The abstract mesh kernel calls are traced under, or None when one
    device holds every operand (no mesh set, or a 1-device mesh)."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty or mesh.size == 1 else mesh


def _axes(spec, ndim: int) -> tuple:
    """Mesh-axis entries of a weight's PartitionSpec, padded to ``ndim``."""
    parts = tuple(spec) if spec is not None else ()
    return parts + (None,) * (ndim - len(parts))


def _on_mesh(fn, args, in_specs, out_spec, psum_axis=None):
    """``fn(*args)``, per shard under a multi-device mesh.  With
    ``psum_axis`` (a contracted axis is sharded) ``fn`` takes ``out_dtype``:
    the partials stay f32 through the psum, then cast to ``args[0]``'s."""
    mesh = kernel_mesh()
    if mesh is None:
        return fn(*args)
    body = fn
    if psum_axis is not None:
        def body(*a):
            y = jax.lax.psum(fn(*a, out_dtype=jnp.float32), psum_axis)
            return y.astype(a[0].dtype)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_spec, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# dispatchers
# ---------------------------------------------------------------------------

def subcge_apply(W, U, A, V, *, backend: str | None = None, spec=None):
    """W (*B,n,m) + U (n,r) A (*B,r,r) V (m,r)^T — the SubCGE replay."""
    b = resolve_backend(backend)
    if b == "jnp":
        return _ref.subcge_apply(W, U, A, V)
    from repro.kernels import subcge_apply as _apply
    *bs, ns, ms = _axes(spec, W.ndim)
    return _on_mesh(
        functools.partial(_apply.subcge_apply, interpret=(b == "interpret")),
        (W, U, A, V),
        (P(*bs, ns, ms), P(ns, None), P(*bs, None, None), P(ms, None)),
        P(*bs, ns, ms))


def subcge_apply_epochs(W, U, A, V, *, backend: str | None = None, spec=None):
    """W (*B,n,m) + Σ_e U (E,n,r)[e] A (E,*B,r,r)[e] V (E,m,r)[e]^T — the
    epoch-grouped padded replay layout (one fused visit of W for all τ-epochs
    present in a flood payload batch)."""
    b = resolve_backend(backend)
    if b == "jnp":
        return _ref.subcge_apply_epochs(W, U, A, V)
    from repro.kernels import subcge_apply as _apply
    *bs, ns, ms = _axes(spec, W.ndim)
    return _on_mesh(
        functools.partial(_apply.subcge_apply_epochs,
                          interpret=(b == "interpret")),
        (W, U, A, V),
        (P(*bs, ns, ms), P(None, ns, None), P(None, *bs, None, None),
         P(None, ms, None)),
        P(*bs, ns, ms))


def subcge_delta(U, A, V, dtype, *, backend: str | None = None):
    """U A V^T alone (no base weight), in ``dtype``.  Kernel backends stream
    a zero W through the fused-apply kernel (delta extraction is not a hot
    path; it exists so every A-application shares one lowering)."""
    b = resolve_backend(backend)
    if b == "jnp":
        return _ref.subcge_delta(U, A, V, dtype)
    zero = jnp.zeros(A.shape[:-2] + (U.shape[-2], V.shape[-2]), dtype)
    return subcge_apply(zero, U, A, V, backend=b)


def rank1_matmul(x, W, u, v, s, *, backend: str | None = None, spec=None):
    """x (M,K) @ (W (K,N) + s·u v^T) — the fused ZO dual forward matmul."""
    b = resolve_backend(backend)
    if b == "jnp":
        return _ref.rank1_matmul(x, W, u, v, s)
    from repro.kernels import rank1_matmul as _r1
    ks, ns = _axes(spec, 2)
    return _on_mesh(
        functools.partial(_r1.rank1_matmul, interpret=(b == "interpret")),
        (x, W, u, v, s),
        (P(None, ks), P(ks, ns), P(ks), P(ns), P()), P(None, ns),
        psum_axis=ks)


def rank1_matmul_t(x, W, u, v, s, *, backend: str | None = None, spec=None):
    """x (M,N) @ (W (O,N) + s·u v^T)^T — tied-embedding logits."""
    b = resolve_backend(backend)
    if b == "jnp":
        return _ref.rank1_matmul_t(x, W, u, v, s)
    from repro.kernels import rank1_matmul as _r1
    os_, ns = _axes(spec, 2)
    return _on_mesh(
        functools.partial(_r1.rank1_matmul_t, interpret=(b == "interpret")),
        (x, W, u, v, s),
        (P(None, ns), P(os_, ns), P(os_), P(ns), P()), P(None, os_),
        psum_axis=ns)


def rank1_matmul_expert(x, W, u, v, s, *, backend: str | None = None,
                        spec=None):
    """x (E,C,n) @ (W (E,n,m) + s·u[:,e] v[:,e]^T) — per-expert rank-1
    perturbations, u (n,E), v (m,E)."""
    b = resolve_backend(backend)
    if b == "jnp":
        return _ref.rank1_matmul_expert(x, W, u, v, s)
    from repro.kernels import rank1_matmul as _r1
    es, ks, ms = _axes(spec, 3)
    return _on_mesh(
        functools.partial(_r1.rank1_matmul_expert,
                          interpret=(b == "interpret")),
        (x, W, u, v, s),
        (P(es, None, ks), P(es, ks, ms), P(ks, es), P(ms, es), P()),
        P(es, None, ms), psum_axis=ks)


def selective_scan(a, bx, c, h0, *, backend: str | None = None):
    """Blocked Mamba selective scan (see kernels/selective_scan.py)."""
    b = resolve_backend(backend)
    if b == "jnp":
        return _ref.selective_scan(a, bx, c, h0)
    from repro.kernels import selective_scan as _scan
    return _scan.selective_scan(a, bx, c, h0, interpret=(b == "interpret"))
