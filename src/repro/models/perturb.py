"""Fused rank-1 perturbed forward machinery.

SeedFlood's perfect consensus means all n simulated clients share one θ; a
client's ZO forward differs only by its SubCGE perturbation, which is rank-1
per 2D leaf:  W_eff = W + s·u v^T  with  u = U[:, i], v = V[:, j].  Rather
than materializing per-client weights we fuse the rank-1 term into each
matmul:

    x (W + s u v^T)  =  x W  +  s · (x u) v^T          (O(T·(n+m)) extra)

``Bundle`` threads three parallel trees through the model — params, the
shared subspace (U/V, *not* per-client), and the per-client perturbation
(coords + dense Gaussians for non-2D leaves) — and exposes the handful of
parameterized ops the layers need.  pert=None gives the plain forward
(serving, FO baselines).

All of this vmaps over a client axis: params/subspace broadcast, pert mapped.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import seeds as seedlib
from repro.core import subcge
from repro.core.subcge import UV, LeafMeta, SubCGEConfig
from repro.kernels import ops as kops
from repro.models import params as plib


class Pert(NamedTuple):
    """One client's perturbation state (leaves carry NO client axis here —
    the client axis is introduced by vmap at the step level)."""
    ij: Any            # nested dict: IJ per matrix leaf
    zv: Any            # nested dict: dense Gaussian per non-frozen vector leaf
    scale: jax.Array   # ±ε (the dual forward flips the sign)

    def with_scale(self, s) -> "Pert":
        return Pert(self.ij, self.zv, jnp.asarray(s, jnp.float32))


def sample_pert(meta: dict[str, LeafMeta], cfg: SubCGEConfig, message_seed,
                scale) -> Pert:
    """RNG_S for one message seed, as *nested* trees mirroring the params."""
    coords = subcge.sample_coords(meta, cfg, message_seed)  # path -> IJ
    key = seedlib.message_key(message_seed)
    zv_flat: dict[str, jax.Array] = {}
    for path, m in sorted(meta.items()):
        if m.frozen or m.is_matrix:
            continue
        zv_flat[path] = seedlib.gaussian_like(seedlib.leaf_key(key, path),
                                              m.shape, jnp.float32)
    return Pert(plib.nest(coords), plib.nest(zv_flat),
                jnp.asarray(scale, jnp.float32))


def nest_subspace(sub_flat: dict[str, UV]) -> Any:
    return plib.nest(sub_flat)


def epoch_subspace(meta: dict[str, LeafMeta], cfg: SubCGEConfig, global_seed,
                   step) -> Any:
    """Nested shared (U, V) tree for the τ-epoch governing ``step`` (jit-safe).

    Sampling is epoch-parameterized *only* through the subspace: a message's
    coordinates and dense Gaussians (``sample_pert``) depend on the message
    seed alone, so reconstructing a sender's perturbation elsewhere needs
    exactly this subspace — regenerated at the SENDER's epoch — and nothing
    else.  The fused forward consumes the nested layout this returns.
    """
    return nest_subspace(subcge.subspace_at_step(meta, cfg, global_seed, step))


def _child(tree: Any, k: str):
    if tree is None or not isinstance(tree, dict):
        return None
    return tree.get(k)


def _mesh_active() -> bool:
    """True when a mesh is set (``jax.set_mesh``) for sharding constraints
    (simulator / CPU smoke paths run mesh-less and skip them)."""
    return not jax.sharding.get_abstract_mesh().empty


class Bundle:
    """params + subspace + perturbation view over one nesting level.

    ``kb`` is the *resolved* kernel backend ("jnp" | "pallas" | "interpret")
    the perturbed matmuls dispatch through (DESIGN.md §7) — a plain Python
    string fixed at trace time, threaded from ``forward(kernel_backend=…)``.
    The unperturbed forward (serving, FO baselines, eval) never dispatches:
    it is a plain matmul with nothing to fuse.

    ``sp`` mirrors the params with each leaf's PartitionSpec when the kernels
    run under a multi-device mesh (``models.params.tree_specs``), else None.
    """
    __slots__ = ("p", "uv", "ij", "zv", "scale", "kb", "sp")

    def __init__(self, p, uv=None, ij=None, zv=None, scale=None, kb="jnp",
                 sp=None):
        self.p = p
        self.uv = uv
        self.ij = ij
        self.zv = zv
        self.scale = scale
        self.kb = kb
        self.sp = sp

    @classmethod
    def make(cls, params, subspace_nested=None, pert: Pert | None = None,
             kernel_backend: str | None = None):
        kb = kops.resolve_backend(kernel_backend)
        if pert is None:
            return cls(params, subspace_nested, None, None, None, kb)
        return cls(params, subspace_nested, pert.ij, pert.zv, pert.scale, kb)

    def __getitem__(self, k: str) -> "Bundle":
        return Bundle(self.p[k], _child(self.uv, k), _child(self.ij, k),
                      _child(self.zv, k), self.scale, self.kb,
                      _child(self.sp, k))

    def _spec(self, k: str, ndim: int):
        """PartitionSpec of leaf k's trailing ``ndim`` dims (a scanned leaf
        arrives without its stacked layer dim), or None."""
        sp = _child(self.sp, k)
        return None if sp is None else P(*tuple(sp)[-ndim:])

    def __contains__(self, k: str) -> bool:
        return k in self.p

    # -- leaf accessors --------------------------------------------------

    def _rank1(self, k: str):
        """(u, v, s) for leaf k if perturbed, else None.  i/j may carry
        residual instance dims (e.g. experts) — u/v then gain those dims
        *last*: u = U[:, i] has shape (rows, *inst)."""
        ij = _child(self.ij, k)
        uv = _child(self.uv, k)
        if ij is None or uv is None or self.scale is None:
            return None
        return uv.U[:, ij.i], uv.V[:, ij.j], self.scale

    def dense(self, k: str, x: jax.Array, bias: str | None = None) -> jax.Array:
        """y = x @ W (+b), with the fused rank-1 epilogue when perturbed.
        W (n, m); x (..., n).  Scalar i/j only (scan/vmap already sliced).

        Perturbed + non-jnp backend: one ``ops.rank1_matmul`` kernel call —
        the rank-1 term rides the matmul's k-loop, W is streamed once."""
        W = self.p[k]
        r1 = self._rank1(k)
        if r1 is not None and self.kb != "jnp":
            u, v, s = r1
            y = kops.rank1_matmul(x.reshape(-1, x.shape[-1]), W, u, v, s,
                                  backend=self.kb, spec=self._spec(k, 2))
            y = y.reshape(x.shape[:-1] + (W.shape[-1],))
        else:
            y = jnp.einsum("...n,nm->...m", x, W)
            if r1 is not None:
                u, v, s = r1
                y = y + s.astype(y.dtype) * jnp.einsum("...n,n->...", x, u.astype(x.dtype))[..., None] \
                    * v.astype(y.dtype)
        if bias is not None:
            y = y + self.vec(bias).astype(y.dtype)
        return y

    def dense_t(self, k: str, x: jax.Array) -> jax.Array:
        """y = x @ W^T — for tied-embedding logits.  W (m, n); x (..., n).
        Rank-1: x (W + s u v^T)^T = x W^T + s (x·v) u^T
        (``ops.rank1_matmul_t`` on the kernel backends)."""
        W = self.p[k]
        r1 = self._rank1(k)
        if r1 is not None and self.kb != "jnp":
            u, v, s = r1
            y = kops.rank1_matmul_t(x.reshape(-1, x.shape[-1]), W, u, v, s,
                                    backend=self.kb, spec=self._spec(k, 2))
            return y.reshape(x.shape[:-1] + (W.shape[0],))
        y = jnp.einsum("...n,mn->...m", x, W)
        if r1 is not None:
            u, v, s = r1
            y = y + s.astype(y.dtype) * jnp.einsum("...n,n->...", x, v.astype(x.dtype))[..., None] \
                * u.astype(y.dtype)
        return y

    def embed(self, k: str, ids: jax.Array) -> jax.Array:
        """Perturbed embedding lookup: (E + s u v^T)[ids] = E[ids] + s·u[ids]·v^T."""
        E = self.p[k]
        out = E[ids]
        r1 = self._rank1(k)
        if r1 is not None:
            u, v, s = r1
            out = out + s.astype(out.dtype) * u[ids][..., None].astype(out.dtype) \
                * v.astype(out.dtype)
        return out

    def matw(self, k: str) -> jax.Array:
        """Materialized perturbed weight — for small leaves (conv kernels,
        dt_proj) where fusing is not worth it."""
        W = self.p[k]
        r1 = self._rank1(k)
        if r1 is None:
            return W
        u, v, s = r1
        # instance dims (if any) trail in u/v; move them in front of the outer
        if u.ndim == 1:
            z = u[:, None] * v[None, :]
        else:  # (rows, *inst) x (cols, *inst) -> (*inst, rows, cols)
            u = jnp.moveaxis(u, 0, -1)
            v = jnp.moveaxis(v, 0, -1)
            z = u[..., :, None] * v[..., None, :]
        return W + s.astype(W.dtype) * z.astype(W.dtype)

    def vec(self, k: str) -> jax.Array:
        """Vector leaf with its dense-Gaussian perturbation (paper's non-2D
        fallback)."""
        b = self.p[k]
        z = _child(self.zv, k)
        if z is None or self.scale is None:
            return b
        return b + self.scale.astype(b.dtype) * z.astype(b.dtype)

    def expert_dense(self, k: str, x: jax.Array,
                     weight_spec=None) -> jax.Array:
        """Batched expert matmul with per-expert rank-1 perturbations.
        x (E, C, n), W (E, n, m), coords per expert (E,).

        ``weight_spec``: optional PartitionSpec constraint applied to W at
        use-time.  Under fsdp_tp the stored weight shards its n (=d_model)
        axis over "data"; constraining the *used* weight to be replicated on
        that axis forces XLA to all-gather the weight (GBs) instead of
        psumming the (E,C,·) activation buffers (hundreds of GBs) — see
        EXPERIMENTS.md §Perf.
        """
        W = self.p[k]
        if weight_spec is not None and _mesh_active():
            W = jax.lax.with_sharding_constraint(W, weight_spec)
        r1 = self._rank1(k)
        if r1 is not None and self.kb != "jnp":
            u, v, s = r1          # u (n, E), v (m, E)
            return kops.rank1_matmul_expert(
                x, W, u, v, s, backend=self.kb,
                spec=weight_spec or self._spec(k, 3))
        y = jnp.einsum("ecn,enm->ecm", x, W)
        if r1 is not None:
            u, v, s = r1          # u (n, E), v (m, E)
            xu = jnp.einsum("ecn,ne->ec", x, u.astype(x.dtype))
            y = y + s.astype(y.dtype) * xu[..., None] * v.T[:, None, :].astype(y.dtype)
        return y


def scan_xs(bundle_tree_params, pert: Pert | None, group_key: str):
    """xs trees for lax.scan over a group: params + coords + vector-z slices.
    (The subspace is NOT scanned — U/V are shared across instances.)"""
    p = bundle_tree_params[group_key]
    ij = _child(pert.ij, group_key) if pert is not None else None
    zv = _child(pert.zv, group_key) if pert is not None else None
    return p, ij, zv
