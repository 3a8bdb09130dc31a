"""SubCGE — Subspace Canonical-basis Gradient Estimation (paper §3.4).

Every 2D weight ``W ∈ R^{n×m}`` gets a globally shared pair of Gaussian
subspace matrices ``U ∈ R^{n×r}``, ``V ∈ R^{m×r}`` regenerated every ``τ``
steps from the global seed (so all clients hold identical subspaces without
communicating them).  A perturbation is one *canonical coordinate* of that
subspace,

    z = U[:, i] V[:, j]^T ,     (i, j) ~ Unif[r]^2,

and the aggregate of n received messages with coefficients {α_k} is

    ΔW = U ( Σ_k α_k E_{i_k j_k} ) V^T  =  U A V^T,

i.e. a scatter-add into the tiny ``A ∈ R^{r×r}`` followed by two thin matmuls:
O(n + r·d) instead of the O(n·d) of replaying n rank-1 axpys (MeZO-style).

Generalization to stacked / expert leaves
-----------------------------------------
Production models store layers stacked for ``lax.scan`` — a leaf looks like
``(P, n, m)`` (periods) or ``(P, E, n, m)`` (periods × experts).  Each
instance along the leading *batch dims* is its own "2D layer" in the paper's
sense: it shares the per-tensor (U, V) but samples its own coordinate, and the
coefficient tensor becomes ``A ∈ R^{*B, r, r}``.

Leaves whose trailing (non-batch) shape is not 2D fall back to the paper's
dense Gaussian perturbation (Algorithm 1's ``else`` branch).

Everything here is functional and jit-safe; the structures are plain pytrees:

* ``meta``      : dict path -> LeafMeta (static)
* ``subspace``  : dict path -> UV(U, V) for matrix leaves only
* ``coords``    : dict path -> IJ(i, j) int32 arrays of the leaf's batch shape
* ``A-tree``    : dict path -> coefficient tensor (*B, r, r)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import seeds as seedlib
from repro.core.messages import pad_pow2
from repro.kernels import ops as kops


class UV(NamedTuple):
    U: jax.Array  # (rows, r)
    V: jax.Array  # (cols, r)


class IJ(NamedTuple):
    i: jax.Array  # (*batch_dims,) int32
    j: jax.Array  # (*batch_dims,) int32


@dataclasses.dataclass(frozen=True)
class LeafMeta:
    """Static description of one parameter leaf.

    ``n_batch_dims`` leading dims are layer/expert instances (scan stacking);
    the remainder is the per-instance tensor.  A leaf participates in SubCGE
    iff that remainder is 2D.
    """
    shape: tuple[int, ...]
    n_batch_dims: int = 0
    frozen: bool = False  # excluded from perturbation/update (e.g. stub frontends)

    @property
    def batch_shape(self) -> tuple[int, ...]:
        return self.shape[: self.n_batch_dims]

    @property
    def inst_shape(self) -> tuple[int, ...]:
        return self.shape[self.n_batch_dims:]

    @property
    def is_matrix(self) -> bool:
        return (not self.frozen) and len(self.inst_shape) == 2


@dataclasses.dataclass(frozen=True)
class SubCGEConfig:
    rank: int = 32
    refresh_period: int = 1000   # τ; Algorithm 1 block (A)
    eps: float = 1e-3            # perturbation scale ε
    subspace_dtype: Any = jnp.float32
    # which implementation the matrix-leaf replay runs through (DESIGN.md §7):
    # "auto" -> Pallas on TPU, the bitwise pure-jnp path elsewhere;
    # "interpret" runs the real kernels through the Pallas interpreter.
    kernel_backend: str = "auto"

    def backend(self, override: str | None = None) -> str:
        """Concrete backend for this config (override wins when given)."""
        return kops.resolve_backend(
            override if override is not None else self.kernel_backend)


# ---------------------------------------------------------------------------
# meta construction
# ---------------------------------------------------------------------------

def infer_meta(params: Any,
               n_batch_dims_fn: Callable[[str, jax.Array], int] | None = None,
               frozen_fn: Callable[[str], bool] | None = None) -> dict[str, LeafMeta]:
    """Build a LeafMeta dict from a params pytree.

    Default heuristic: no batch dims; leaves with ndim >= 2 are matrices on
    their last two dims with everything before treated as batch dims.  Model
    code should pass ``n_batch_dims_fn`` for exact control (norm scales stored
    as (P, d) are *stacked vectors*, not matrices).
    """
    meta: dict[str, LeafMeta] = {}

    def visit(path: str, leaf: jax.Array):
        nb = (n_batch_dims_fn(path, leaf) if n_batch_dims_fn is not None
              else max(0, leaf.ndim - 2))
        frz = frozen_fn(path) if frozen_fn is not None else False
        meta[path] = LeafMeta(tuple(leaf.shape), nb, frz)
        return leaf

    seedlib.map_with_paths(visit, params)
    return meta


# ---------------------------------------------------------------------------
# subspace generation (Algorithm 1, block (A))
# ---------------------------------------------------------------------------

def make_subspace(meta: dict[str, LeafMeta], cfg: SubCGEConfig,
                  global_seed, step) -> dict[str, UV]:
    """(Re)generate the shared low-rank subspace for every matrix leaf.

    Deterministic in (global_seed, refresh-step, path): any client calling
    this with the same arguments obtains bitwise-identical U/V — this is the
    "globally shared without communication" property.
    """
    out: dict[str, UV] = {}
    for path, m in sorted(meta.items()):
        if not m.is_matrix:
            continue
        rows, cols = m.inst_shape
        k = seedlib.subspace_key(global_seed, step, path)
        ku, kv = jax.random.split(k)
        U = jax.random.normal(ku, (rows, cfg.rank), cfg.subspace_dtype)
        V = jax.random.normal(kv, (cols, cfg.rank), cfg.subspace_dtype)
        out[path] = UV(U, V)
    return out


def refresh_step(step, cfg: SubCGEConfig):
    """The refresh step governing the current subspace: τ·⌊t/τ⌋."""
    tau = jnp.asarray(cfg.refresh_period, jnp.int32)
    return (jnp.asarray(step, jnp.int32) // tau) * tau


def subspace_at_step(meta, cfg: SubCGEConfig, global_seed, step):
    """Subspace in effect at iteration ``step`` (jit-safe: regenerates from
    the governing refresh step — identical on every client/shard)."""
    return make_subspace(meta, cfg, global_seed, refresh_step(step, cfg))


# ---------------------------------------------------------------------------
# coordinate sampling (RNG_S, matrix branch)
# ---------------------------------------------------------------------------

def sample_coords(meta: dict[str, LeafMeta], cfg: SubCGEConfig,
                  message_seed) -> dict[str, IJ]:
    """RNG_S: from one message seed, sample (i, j) for every matrix-leaf
    instance.  Deterministic in the seed — this is what makes the message
    reconstructible anywhere."""
    key = seedlib.message_key(message_seed)
    out: dict[str, IJ] = {}
    for path, m in sorted(meta.items()):
        if not m.is_matrix:
            continue
        i, j = seedlib.coord_sample(seedlib.leaf_key(key, path),
                                    m.batch_shape, cfg.rank)
        out[path] = IJ(i, j)
    return out


# ---------------------------------------------------------------------------
# perturbation materialization (simulator / oracle path)
# ---------------------------------------------------------------------------

def _outer_from_coords(uv: UV, ij: IJ) -> jax.Array:
    """z[*B] = U[:, i[*B]] ⊗ V[:, j[*B]]  -> (*B, rows, cols)."""
    u = jnp.moveaxis(uv.U[:, ij.i], 0, -1)      # (*B, rows)
    v = jnp.moveaxis(uv.V[:, ij.j], 0, -1)      # (*B, cols)
    return u[..., :, None] * v[..., None, :]


def materialize_z(params: Any, meta: dict[str, LeafMeta], cfg: SubCGEConfig,
                  subspace: dict[str, UV], message_seed) -> Any:
    """Full perturbation pytree z for one message (RNG_S of Algorithm 1).

    Matrix leaves: canonical-coordinate rank-1 outer products.
    Other leaves : dense Gaussian from the message seed.
    Frozen leaves: zeros.
    Only used by the simulator / tests — the sharded runtime never
    materializes z (it fuses the rank-1 term into the matmuls).
    """
    coords = sample_coords(meta, cfg, message_seed)
    key = seedlib.message_key(message_seed)

    def visit(path: str, leaf: jax.Array):
        m = meta[path]
        if m.frozen:
            return jnp.zeros_like(leaf)
        if m.is_matrix:
            return _outer_from_coords(subspace[path], coords[path]).astype(leaf.dtype)
        return seedlib.gaussian_like(seedlib.leaf_key(key, path),
                                     m.shape, leaf.dtype)

    return seedlib.map_with_paths(visit, params)


# ---------------------------------------------------------------------------
# aggregation: scatter into A, apply U A V^T  (paper eq. 10)
# ---------------------------------------------------------------------------

def scatter_A(i: jax.Array, j: jax.Array, coefs: jax.Array,
              rank: int) -> jax.Array:
    """Σ_k coef_k · E_{i_k j_k}, batched over leading instance dims.

    i, j   : (K, *B) int32 — coordinates of K messages for each instance
    coefs  : (K,) or (K, *B) — message coefficients
    returns: (*B, rank, rank)
    """
    K = i.shape[0]
    B = i.shape[1:]
    if coefs.ndim == 1:
        coefs = jnp.broadcast_to(coefs.reshape((K,) + (1,) * len(B)), (K,) + B)
    A = jnp.zeros(B + (rank, rank), coefs.dtype)
    if B:
        bidx = tuple(jnp.broadcast_to(b, (K,) + B) for b in jnp.indices(B))
    else:
        bidx = ()
    return A.at[bidx + (i, j)].add(coefs)


def apply_A(leaf: jax.Array, uv: UV, A: jax.Array,
            backend: str | None = None, spec=None) -> jax.Array:
    """leaf + U A V^T (batched over instance dims), via the kernel layer.

    ``backend=None`` resolves the process default (jnp off-TPU — bitwise the
    historical einsum); callers holding a :class:`SubCGEConfig` pass
    ``cfg.kernel_backend`` so the knob is captured at trace time.  ``spec``
    is the leaf's PartitionSpec on a sharded mesh (see ``kernels.ops``).
    """
    return kops.subcge_apply(leaf, uv.U, A, uv.V, backend=backend, spec=spec)


def delta_from_A(uv: UV, A: jax.Array, dtype,
                 backend: str | None = None) -> jax.Array:
    return kops.subcge_delta(uv.U, A, uv.V, dtype, backend=backend)


def apply_messages(params: Any, meta: dict[str, LeafMeta], cfg: SubCGEConfig,
                   subspace: dict[str, UV], message_seeds: jax.Array,
                   coefs: jax.Array, specs: dict[str, Any] | None = None) -> Any:
    """Apply K seed-scalar messages at once (Algorithm 1 block (C) inner
    update, vectorized).  ``message_seeds``: (K,) uint32; ``coefs``: (K,)
    already carrying the -η·α/n sign/scale convention of the caller.

    Matrix leaves: one scatter + one batched U A V^T per leaf — O(K + r·d),
    dispatched through the kernel layer per ``cfg.kernel_backend``.
    Vector leaves: Σ_k coef_k · N(seed_k) via a scan (memory-light).
    ``specs`` maps a path to its leaf's PartitionSpec on a sharded mesh.
    """
    backend = cfg.backend()
    coords_k = jax.vmap(lambda s: sample_coords(meta, cfg, s))(message_seeds)

    def visit(path: str, leaf: jax.Array):
        m = meta[path]
        if m.frozen:
            return leaf
        if m.is_matrix:
            ij = coords_k[path]
            A = scatter_A(ij.i, ij.j, coefs.astype(jnp.float32), cfg.rank)
            return apply_A(leaf, subspace[path], A, backend,
                           (specs or {}).get(path))

        def body(acc, sc):
            s, c = sc
            z = seedlib.gaussian_like(
                seedlib.leaf_key(seedlib.message_key(s), path),
                m.shape, jnp.float32)
            return acc + c * z, None

        upd, _ = jax.lax.scan(body, jnp.zeros(m.shape, jnp.float32),
                              (message_seeds, coefs.astype(jnp.float32)))
        return leaf + upd.astype(leaf.dtype)

    return seedlib.map_with_paths(visit, params)


# ---------------------------------------------------------------------------
# epoch-correct replay: apply each message under ITS SENDER's subspace
# ---------------------------------------------------------------------------
#
# The seed-scalar reconstruction guarantee (paper §3.1) only holds if the
# receiver regenerates the perturbation the *sender* used.  The canonical
# coordinates (i, j) depend solely on the message seed, but the subspace
# (U, V) is a function of the sender's τ-epoch ⌊t_send/τ⌋ — so a message
# whose staleness crosses a refresh boundary (delayed flooding with k < D,
# anti-entropy catch-up after an outage) MUST be applied under the epoch of
# its sender step, not the receiver's current step.  ``apply_messages_epoch``
# makes this structural: payloads carry sender steps, and the batch is
# partitioned over the epochs actually present.

#: Sentinel for unused epoch slots (matches no real refresh step, which are
#: all >= 0; slot coefficients mask to zero so the slot is an exact no-op).
EPOCH_PAD = -1


def epoch_slots(steps, cfg: SubCGEConfig, minimum: int = 1) -> np.ndarray:
    """Host-side: the distinct subspace refresh steps governing a batch of
    sender steps, padded with :data:`EPOCH_PAD` to a power-of-two length so
    jit retraces of the epoch loop stay bounded.

    ``steps`` may be any int array (e.g. the (n, K) padded matrix); negative
    entries — payload padding — are ignored.
    """
    steps = np.asarray(steps)
    tau = int(cfg.refresh_period)
    valid = steps[steps >= 0]
    uniq = np.unique((valid // tau) * tau).astype(np.int32)
    out = np.full(pad_pow2(uniq.size, minimum), EPOCH_PAD, np.int32)
    out[:uniq.size] = uniq
    return out


def apply_messages_epoch(params: Any, meta: dict[str, LeafMeta],
                         cfg: SubCGEConfig, global_seed,
                         message_seeds: jax.Array, coefs: jax.Array,
                         steps: jax.Array, epochs: jax.Array) -> Any:
    """Apply K seed-scalar messages, each under the subspace of its SENDER's
    τ-epoch (jit-safe; vmaps over a leading client axis).

    message_seeds : (K,) uint32
    coefs         : (K,)  — 0 entries are exact no-ops (payload padding)
    steps         : (K,) int32 sender steps (negative = padding)
    epochs        : (E,) int32 refresh-step slots from :func:`epoch_slots`;
                    every non-padding message's epoch must appear here

    Matrix leaves get one scatter per epoch slot; on the jnp backend the
    U_e A_e V_e^T applications run sequentially (bitwise the historical
    path — with the common single-epoch batch this is exactly
    :func:`apply_messages`), while the kernel backends fold all E slots into
    one rank-(E·r) fused visit of each weight
    (:func:`repro.kernels.ops.subcge_apply_epochs` — W streamed once, not E
    times).  Dense Gaussian (non-2D) leaves depend only on the message seed,
    never the subspace, so they are applied once, epoch-free.
    """
    backend = cfg.backend()
    coords_k = jax.vmap(lambda s: sample_coords(meta, cfg, s))(message_seeds)
    cf32 = coefs.astype(jnp.float32)
    msg_epoch = refresh_step(steps, cfg)              # (K,) — floor for < 0
    n_slots = int(epochs.shape[0])                    # static
    slot_coefs = [jnp.where(msg_epoch == epochs[e], cf32, 0.0)
                  for e in range(n_slots)]
    slot_subs = [make_subspace(meta, cfg, global_seed, epochs[e])
                 for e in range(n_slots)]

    def visit(path: str, leaf: jax.Array):
        m = meta[path]
        if m.frozen:
            return leaf
        if m.is_matrix:
            ij = coords_k[path]
            if backend == "jnp":
                out = leaf
                for sub, c_e in zip(slot_subs, slot_coefs):
                    A = scatter_A(ij.i, ij.j, c_e, cfg.rank)
                    out = apply_A(out, sub[path], A, backend)
                return out
            A_e = jnp.stack([scatter_A(ij.i, ij.j, c_e, cfg.rank)
                             for c_e in slot_coefs])          # (E, *B, r, r)
            U_e = jnp.stack([sub[path].U for sub in slot_subs])
            V_e = jnp.stack([sub[path].V for sub in slot_subs])
            return kops.subcge_apply_epochs(leaf, U_e, A_e, V_e,
                                            backend=backend)

        def body(acc, sc):
            s, c = sc
            z = seedlib.gaussian_like(
                seedlib.leaf_key(seedlib.message_key(s), path),
                m.shape, jnp.float32)
            return acc + c * z, None

        upd, _ = jax.lax.scan(body, jnp.zeros(m.shape, jnp.float32),
                              (message_seeds, cf32))
        return leaf + upd.astype(leaf.dtype)

    return seedlib.map_with_paths(visit, params)


# ---------------------------------------------------------------------------
# buffer mode (paper Appendix A): accumulate A, fold lazily
# ---------------------------------------------------------------------------

def apply_vector_messages(params: Any, meta: dict[str, LeafMeta],
                          cfg: SubCGEConfig, message_seeds: jax.Array,
                          coefs: jax.Array) -> Any:
    """Apply K messages to NON-matrix leaves only (buffer mode keeps matrix
    updates in A-buffers, but the paper's App. A follows MeZO directly for
    1D tensors — those must be applied immediately)."""
    def visit(path: str, leaf: jax.Array):
        m = meta[path]
        if m.frozen or m.is_matrix:
            return leaf

        def body(acc, sc):
            s, c = sc
            z = seedlib.gaussian_like(
                seedlib.leaf_key(seedlib.message_key(s), path),
                m.shape, jnp.float32)
            return acc + c * z, None

        upd, _ = jax.lax.scan(body, jnp.zeros(m.shape, jnp.float32),
                              (message_seeds, coefs.astype(jnp.float32)))
        return leaf + upd.astype(leaf.dtype)

    return seedlib.map_with_paths(visit, params)


def zero_buffers(meta: dict[str, LeafMeta], cfg: SubCGEConfig) -> dict[str, jax.Array]:
    """A-buffers for every matrix leaf (the paper's per-layer ``A_ℓ``)."""
    return {p: jnp.zeros(m.batch_shape + (cfg.rank, cfg.rank), jnp.float32)
            for p, m in sorted(meta.items()) if m.is_matrix}


def accumulate_buffers(buffers: dict[str, jax.Array], meta, cfg: SubCGEConfig,
                       message_seeds: jax.Array, coefs: jax.Array):
    """Coordinate updates only — O(K) per leaf.  (Appendix A 'coordinate
    update' row of Table 4.)"""
    coords_k = jax.vmap(lambda s: sample_coords(meta, cfg, s))(message_seeds)
    out = dict(buffers)
    for path in buffers:
        ij = coords_k[path]
        out[path] = buffers[path] + scatter_A(ij.i, ij.j,
                                              coefs.astype(jnp.float32), cfg.rank)
    return out


def fold_buffers(params: Any, meta, subspace: dict[str, UV],
                 buffers: dict[str, jax.Array],
                 backend: str | None = None,
                 specs: dict[str, Any] | None = None) -> Any:
    """Fold W <- W + U A V^T and conceptually reset A (caller zeroes it).
    Must be called before any subspace refresh (the buffer is only valid
    against the U/V it was accumulated under)."""
    def visit(path: str, leaf: jax.Array):
        if path in buffers:
            return apply_A(leaf, subspace[path], buffers[path], backend,
                           (specs or {}).get(path))
        return leaf
    return seedlib.map_with_paths(visit, params)


def effective_params(params: Any, meta, subspace, buffers,
                     backend: str | None = None,
                     specs: dict[str, Any] | None = None) -> Any:
    """Buffer-mode effective weights W + U A V^T (computed on the fly in the
    forward pass, as the paper's GPU implementation does)."""
    return fold_buffers(params, meta, subspace, buffers, backend, specs)


# ---------------------------------------------------------------------------
# beyond-paper: subspace momentum
# ---------------------------------------------------------------------------
#
# Classical momentum needs an O(d) velocity — exactly the optimizer state ZO
# methods exist to avoid.  But under SubCGE every update lives in the shared
# r×r coefficient space, so a velocity μ_ℓ ∈ R^{*B,r,r} per leaf (KBs, not
# GBs) gives momentum-SGD semantics at O(r²) state:
#
#     μ ← β μ + A_t,        W ← W + U μ V^T .
#
# Consensus-safe: μ is a deterministic function of the (identical) message
# stream, so all clients hold the same velocity without communication.  The
# velocity is only meaningful within one subspace window — reset (or fold)
# at τ-refresh boundaries.  Non-2D leaves keep plain SGD (their Gaussian
# updates would need O(d) state).

def momentum_apply(params: Any, meta: dict[str, LeafMeta], cfg: SubCGEConfig,
                   subspace: dict[str, UV], velocity: dict[str, jax.Array],
                   message_seeds: jax.Array, coefs: jax.Array,
                   beta: float = 0.9):
    """One momentum step from K messages; returns (params, new_velocity).

    Matrix leaves: μ ← β μ + Σ_k coef_k E_{i_k j_k};  W += U μ V^T
    (the fold dispatched through the kernel layer per ``cfg.kernel_backend``).
    Vector leaves: plain (momentum-free) application.
    """
    backend = cfg.backend()
    coords_k = jax.vmap(lambda s: sample_coords(meta, cfg, s))(message_seeds)
    new_vel: dict[str, jax.Array] = {}

    def visit(path: str, leaf: jax.Array):
        m = meta[path]
        if m.frozen:
            return leaf
        if m.is_matrix:
            ij = coords_k[path]
            A = scatter_A(ij.i, ij.j, coefs.astype(jnp.float32), cfg.rank)
            mu = beta * velocity[path] + A
            new_vel[path] = mu
            return apply_A(leaf, subspace[path], mu, backend)

        def body(acc, sc):
            s, c = sc
            z = seedlib.gaussian_like(
                seedlib.leaf_key(seedlib.message_key(s), path),
                m.shape, jnp.float32)
            return acc + c * z, None

        upd, _ = jax.lax.scan(body, jnp.zeros(m.shape, jnp.float32),
                              (message_seeds, coefs.astype(jnp.float32)))
        return leaf + upd.astype(leaf.dtype)

    out = seedlib.map_with_paths(visit, params)
    return out, new_vel
