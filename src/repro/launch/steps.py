"""Sharded step programs for the production mesh.

``seedflood_train_step``  — the paper's Algorithm 1 mapped onto a pod:
  (A) subspace regenerated from (global_seed, τ⌊t/τ⌋) — identical on every
      shard, no communication;
  (B) per-client ZO estimation vmapped over the client axis (clients' batches
      shard over ("pod","data"); each client's forward differs from the
      shared θ only by its fused rank-1 SubCGE perturbation);
  (C) the flood: the per-client scalars α and coords are all-gathered by XLA
      (O(n·L) bytes — the whole point), the r×r coefficient scatters and the
      U A V^T weight update run identically on every shard.

``dsgd_train_step``       — the gossip baseline on the mesh: FO local step +
  ring collective_permute neighbour averaging (O(d) bytes — the contrast the
  roofline tables quantify).

``prefill_step`` / ``decode_step`` — the serving programs for the
inference-shaped inputs.

All builders return (fn, example_inputs, in_shardings, out_shardings) ready
for jax.jit(...).lower(...).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.base import ArchConfig, InputShape
from repro.core import seeds as seedlib, subcge
from repro.core.subcge import SubCGEConfig
from repro.launch import mesh as meshlib
from repro.models import params as plib
from repro.models import transformer as tf
from repro.models.perturb import nest_subspace, sample_pert


@dataclasses.dataclass(frozen=True)
class PodConfig:
    lr: float = 1e-5
    eps: float = 1e-3
    rank: int = 32
    tau: int = 1000
    base_seed: int = 0
    param_dtype: Any = jnp.bfloat16
    n_clients: int = 0             # 0 -> data-axis extent of the mesh
    apply_mode: str = "fold"       # fold (UAV^T folded into W) | buffer
    remat_clients: bool = False    # lax.map over clients instead of vmap
    spmd_client_axis: bool = False  # bind the vmapped client axis to the
    #                                 data mesh axes (vmap spmd_axis_name)
    kernel_backend: str = "auto"   # SubCGE hot-path implementation: on a
    #                                real pod "auto" means the Pallas kernels
    #                                (repro.kernels.ops; DESIGN.md §7)

    def subcge(self) -> SubCGEConfig:
        return SubCGEConfig(rank=self.rank, refresh_period=self.tau,
                            kernel_backend=self.kernel_backend)


def _rep(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# input specs
# ---------------------------------------------------------------------------

def train_inputs(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                 pod: PodConfig):
    """ShapeDtypeStructs + shardings for one training step."""
    n = pod.n_clients or meshlib.data_extent(mesh)
    assert shape.global_batch % n == 0, (shape.global_batch, n)
    b = shape.global_batch // n
    daxes = meshlib.data_axes(mesh)
    tspec = P(daxes, *([None] * 2))

    text = shape.seq - (cfg.frontend.n_embeds if cfg.frontend else 0)
    batch = {"tokens": jax.ShapeDtypeStruct((n, b, text), jnp.int32)}
    shard = {"tokens": NamedSharding(mesh, tspec)}
    if cfg.frontend is not None:
        fe = cfg.frontend
        batch["embeds"] = jax.ShapeDtypeStruct((n, b, fe.n_embeds, fe.embed_dim),
                                               pod.param_dtype)
        shard["embeds"] = NamedSharding(mesh, P(daxes, None, None, None))
    return batch, shard


def serve_batch_inputs(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                       pod: PodConfig, seq: int):
    B = shape.global_batch
    daxes = meshlib.data_axes(mesh)
    dsize = meshlib.data_extent(mesh)
    bspec = daxes if B % dsize == 0 else None
    text = seq - (cfg.frontend.n_embeds if cfg.frontend and seq > 1 else 0)
    batch = {"tokens": jax.ShapeDtypeStruct((B, text), jnp.int32)}
    shard = {"tokens": NamedSharding(mesh, P(bspec, None))}
    if cfg.frontend is not None and seq > 1:
        fe = cfg.frontend
        batch["embeds"] = jax.ShapeDtypeStruct((B, fe.n_embeds, fe.embed_dim),
                                               pod.param_dtype)
        shard["embeds"] = NamedSharding(mesh, P(bspec, None, None))
    return batch, shard


def cache_shardings(cfg: ArchConfig, cache_abs: Any, mesh: Mesh,
                    batch_sharded: bool) -> Any:
    """Shardings for the stacked cache tree.  Batch over data axes when it
    divides; otherwise (long_500k, B=1) the *sequence* axis shards over data.
    Head/feature axes shard over "model" when divisible."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    daxes = meshlib.data_axes(mesh)
    dsize = meshlib.data_extent(mesh)

    def one(path: str, leaf):
        dims = [None] * len(leaf.shape)
        # leading dim is always the scan "reps" axis
        if path.endswith("kpos"):
            return NamedSharding(mesh, P(*dims))
        B = leaf.shape[1]
        if batch_sharded and B % dsize == 0:
            dims[1] = daxes
            seq_ok = False
        else:
            seq_ok = True
        name = path.split("/")[-1]
        if name in ("k", "v"):               # (reps, B, C, KV, hd)
            if seq_ok and leaf.shape[2] % dsize == 0:
                dims[2] = daxes
            if leaf.shape[3] % sizes.get("model", 1) == 0:
                dims[3] = "model"
            elif leaf.shape[4] % sizes.get("model", 1) == 0:
                dims[4] = "model"
        elif name in ("ckv", "krope"):       # (reps, B, C, dim)
            if seq_ok and leaf.shape[2] % dsize == 0:
                dims[2] = daxes
            # MLA compressed-feature dim over "model": without this the
            # 60L×32k×576 cache replicates across the model axis and a
            # 236B decode blows the 16 GB HBM budget (observed 18.9 GiB/dev)
            if leaf.shape[3] % sizes.get("model", 1) == 0:
                dims[3] = "model"
        elif name == "h":                    # (reps, B, Di, N)
            if leaf.shape[2] % sizes.get("model", 1) == 0:
                dims[2] = "model"
        elif name == "conv":                 # (reps, B, Kc-1, Di)
            if leaf.shape[3] % sizes.get("model", 1) == 0:
                dims[3] = "model"
        return NamedSharding(mesh, P(*dims))

    return seedlib.map_with_paths(one, cache_abs)


# ---------------------------------------------------------------------------
# SeedFlood train step
# ---------------------------------------------------------------------------

def build_seedflood_train_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                               pod: PodConfig):
    spec = tf.arch_spec(cfg)
    meta = plib.subcge_meta(spec)
    scfg = pod.subcge()
    n = pod.n_clients or meshlib.data_extent(mesh)

    params_abs = plib.abstract_params(spec, pod.param_dtype)
    params_sh = plib.tree_shardings(spec, mesh, cfg.sharding_policy)
    leaf_specs = plib.flatten_paths(
        plib.tree_specs(spec, mesh, cfg.sharding_policy))
    batch_abs, batch_sh = train_inputs(cfg, shape, mesh, pod)

    def train_step(params, batch, step):
        # the step traces under its own mesh, so the kernels in it run as
        # per-shard shard_map calls whatever mesh context the caller holds
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return _step(params, batch, step)

    def _step(params, batch, step):
        # buffer mode (paper App. A): params = (base W, A-buffers); the
        # effective weights W + U A V^T are materialized on the fly each
        # step and A is folded into W at subspace-refresh boundaries (a
        # buffer is only valid under the subspace it accumulated against).
        buffer_mode = pod.apply_mode == "buffer"
        # phases (obs.scope): "subspace" is the step's draw of U, V and the
        # client seeds; "ge" (gradient estimation, paper Table 4) every
        # client's ± perturbed forwards and α; "ma" (message apply) the fold
        # of every message into the weights
        if buffer_mode:
            params, bufs = params
            with obs.scope("ma"):
                is_refresh = jnp.logical_and(step > 0,
                                             step % scfg.refresh_period == 0)
                old_sub = subcge.subspace_at_step(meta, scfg, pod.base_seed,
                                                  jnp.maximum(step - 1, 0))
                params = jax.tree.map(
                    lambda base, folded: jnp.where(is_refresh, folded, base),
                    params, subcge.fold_buffers(params, meta, old_sub, bufs,
                                                backend=pod.kernel_backend,
                                                specs=leaf_specs))
                bufs = jax.tree.map(
                    lambda b: jnp.where(is_refresh, jnp.zeros_like(b), b),
                    bufs)

        with obs.scope("subspace"):
            sub_flat = subcge.subspace_at_step(meta, scfg, pod.base_seed,
                                               step)
            sub = nest_subspace(sub_flat)
            cids = jnp.arange(n)
            seeds_t = jax.vmap(
                lambda i: seedlib.client_seed(pod.base_seed, step, i))(cids)
        with obs.scope("ge"):
            eff = (subcge.effective_params(params, meta, sub_flat, bufs,
                                           backend=pod.kernel_backend,
                                           specs=leaf_specs)
                   if buffer_mode else params)

        def client_alpha(batch_i, seed_i):
            with obs.scope("ge"):
                pert = sample_pert(meta, scfg, seed_i, pod.eps)
                lp = tf.lm_loss(cfg, eff, batch_i, sub=sub, pert=pert,
                                kernel_backend=pod.kernel_backend)
                lm = tf.lm_loss(cfg, eff, batch_i, sub=sub,
                                pert=pert.with_scale(-pod.eps),
                                kernel_backend=pod.kernel_backend)
                return (lp - lm) / (2 * pod.eps), 0.5 * (lp + lm)

        if pod.remat_clients:
            alphas, losses = jax.lax.map(lambda ab: client_alpha(ab[0], ab[1]),
                                         (batch, seeds_t))
        elif pod.spmd_client_axis:
            daxes = meshlib.data_axes(mesh)
            alphas, losses = jax.vmap(
                client_alpha,
                spmd_axis_name=daxes if len(daxes) > 1 else daxes[0],
            )(batch, seeds_t)
        else:
            alphas, losses = jax.vmap(client_alpha)(batch, seeds_t)

        # --- consensus: the flood-equivalent all-gather of (seed, α) -------
        metrics = {"loss": jnp.mean(losses),
                   "alpha_rms": jnp.sqrt(jnp.mean(alphas ** 2)),
                   "step": step}
        with obs.scope("ma"):
            coefs = (-pod.lr / n) * alphas
            if buffer_mode:  # O(n) coordinate updates only (Table 4 "MA"
                # row); non-matrix leaves follow MeZO directly (App. A)
                bufs = subcge.accumulate_buffers(bufs, meta, scfg, seeds_t,
                                                 coefs)
                params = subcge.apply_vector_messages(params, meta, scfg,
                                                      seeds_t, coefs)
                return (params, bufs), metrics
            new_params = subcge.apply_messages(params, meta, scfg, sub_flat,
                                               seeds_t, coefs, leaf_specs)
        return new_params, metrics

    if pod.apply_mode == "buffer":
        bufs_abs = jax.eval_shape(lambda: subcge.zero_buffers(meta, scfg))
        bufs_sh = seedlib.map_with_paths(lambda p, l: _rep(mesh), bufs_abs)
        example = ((params_abs, bufs_abs), batch_abs,
                   jax.ShapeDtypeStruct((), jnp.int32))
        in_sh = ((params_sh, bufs_sh), batch_sh, _rep(mesh))
        out_sh = ((params_sh, bufs_sh), _rep(mesh))
    else:
        example = (params_abs, batch_abs, jax.ShapeDtypeStruct((), jnp.int32))
        in_sh = (params_sh, batch_sh, _rep(mesh))
        out_sh = (params_sh, _rep(mesh))
    return train_step, example, in_sh, out_sh


# ---------------------------------------------------------------------------
# DSGD gossip baseline on the mesh (roofline contrast)
# ---------------------------------------------------------------------------

def build_dsgd_train_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                          pod: PodConfig):
    """FO local step + one ring-gossip round via ppermute over the client
    axis.  Parameters are replicated per client group along "data"; the
    gossip traffic is the full parameter pytree — O(d) per edge, the cost
    Table 1 contrasts with SeedFlood's O(n)."""
    spec = tf.arch_spec(cfg)
    params_abs = plib.abstract_params(spec, pod.param_dtype)
    params_sh = plib.tree_shardings(spec, mesh, cfg.sharding_policy)
    batch_abs, batch_sh = train_inputs(cfg, shape, mesh, pod)

    def train_step(params, batch, step):
        # per-client gradient on the client's shard (vmapped like SeedFlood)
        def client_loss(p, b):
            return tf.lm_loss(cfg, p, b)

        def grad_i(batch_i):
            return jax.value_and_grad(lambda p: client_loss(p, batch_i))(params)

        losses, grads = jax.vmap(grad_i)(batch)
        # DSGD with uniform mixing after local steps ≈ allreduce of the
        # update followed by neighbour exchange; we lower the honest version:
        # average gradients (the consensus collective is O(d)·allreduce).
        gbar = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
        new_params = jax.tree.map(lambda p, g: p - pod.lr * g.astype(p.dtype),
                                  params, gbar)
        return new_params, {"loss": jnp.mean(losses), "step": step}

    example = (params_abs, batch_abs, jax.ShapeDtypeStruct((), jnp.int32))
    in_sh = (params_sh, batch_sh, _rep(mesh))
    out_sh = (params_sh, _rep(mesh))
    return train_step, example, in_sh, out_sh


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------

def build_prefill_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                       pod: PodConfig):
    spec = tf.arch_spec(cfg)
    params_abs = plib.abstract_params(spec, pod.param_dtype)
    params_sh = plib.tree_shardings(spec, mesh, cfg.sharding_policy)
    batch_abs, batch_sh = serve_batch_inputs(cfg, shape, mesh, pod, shape.seq)
    cache_abs = tf.abstract_cache(cfg, shape.global_batch, shape.seq,
                                  pod.param_dtype)
    dsize = meshlib.data_extent(mesh)
    cache_sh = cache_shardings(cfg, cache_abs, mesh,
                               batch_sharded=shape.global_batch % dsize == 0)

    def prefill_step(params, batch):
        cache = tf.init_cache(cfg, shape.global_batch, shape.seq,
                              pod.param_dtype)
        logits, new_cache, _ = tf.forward(cfg, params, batch, cache=cache,
                                          pos=jnp.int32(0))
        # return only the last-position logits (sampling input) + cache
        return logits[:, -1], new_cache

    example = (params_abs, batch_abs)
    in_sh = (params_sh, batch_sh)
    out_sh = (_rep(mesh), cache_sh)
    return prefill_step, example, in_sh, out_sh


def build_decode_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                      pod: PodConfig):
    """One new token against a KV cache of ``shape.seq``.

    moe_gather_weights is force-disabled here: at decode the activation
    buffers are tiny (B×1 tokens), so psumming them costs ~nothing while
    gathering TBs of expert weights per step regressed kimi decode 4.6×
    (measured — see EXPERIMENTS.md §Perf sweep).
    """
    cfg = dataclasses.replace(cfg, moe_gather_weights=False)
    spec = tf.arch_spec(cfg)
    params_abs = plib.abstract_params(spec, pod.param_dtype)
    params_sh = plib.tree_shardings(spec, mesh, cfg.sharding_policy)
    B = shape.global_batch
    cache_abs = tf.abstract_cache(cfg, B, shape.seq, pod.param_dtype)
    dsize = meshlib.data_extent(mesh)
    batch_sharded = B % dsize == 0
    cache_sh = cache_shardings(cfg, cache_abs, mesh, batch_sharded=batch_sharded)
    daxes = meshlib.data_axes(mesh)
    tok_sh = NamedSharding(mesh, P(daxes if batch_sharded else None, None))

    def decode_step(params, cache, tokens, pos):
        logits, new_cache, _ = tf.forward(cfg, params, {"tokens": tokens},
                                          cache=cache, pos=pos)
        return logits[:, 0], new_cache

    example = (params_abs, cache_abs,
               jax.ShapeDtypeStruct((B, 1), jnp.int32),
               jax.ShapeDtypeStruct((), jnp.int32))
    in_sh = (params_sh, cache_sh, tok_sh, _rep(mesh))
    out_sh = (_rep(mesh), cache_sh)
    return decode_step, example, in_sh, out_sh


# ---------------------------------------------------------------------------
# paged serving steps (repro.serve; DESIGN.md §10)
# ---------------------------------------------------------------------------

def paged_pool_shardings(cfg: ArchConfig, pool_abs: Any, mesh: Mesh) -> Any:
    """Shardings for the paged KV pool tree (reps, P, page, KV, hd): head /
    feature axes shard over "model" when divisible; page axes stay whole —
    the pool is indexed by physical page id, which must not be split."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(path: str, leaf):
        dims = [None] * len(leaf.shape)
        if leaf.shape[3] % sizes.get("model", 1) == 0:
            dims[3] = "model"
        elif leaf.shape[4] % sizes.get("model", 1) == 0:
            dims[4] = "model"
        return NamedSharding(mesh, P(*dims))

    return seedlib.map_with_paths(one, pool_abs)


def _paged_geometry(shape: InputShape, page_size: int | None,
                    pages_per_req: int | None, n_pages: int | None):
    """Default paged-pool geometry for a (seq, batch) serving shape."""
    if page_size is None:
        page_size = min(16, shape.seq)
    if pages_per_req is None:
        pages_per_req = -(-shape.seq // page_size)
    if n_pages is None:
        n_pages = shape.global_batch * pages_per_req
    return page_size, pages_per_req, n_pages


def build_paged_prefill_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                             pod: PodConfig, *, page_size: int | None = None,
                             pages_per_req: int | None = None,
                             n_pages: int | None = None):
    """Prefill ``global_batch`` same-length prompts and scatter their KV into
    the pool rows given by ``table``.  The prefill forward runs against a
    throwaway monolithic cache of capacity == prompt length (prefill logits
    are cache-layout independent: the T > 1 path attends the raw k/v), so
    the returned last-position logits are bitwise the monolithic prefill's.
    """
    tf.check_paged_support(cfg)
    page_size, pages_per_req, n_pages = _paged_geometry(
        shape, page_size, pages_per_req, n_pages)
    spec = tf.arch_spec(cfg)
    params_abs = plib.abstract_params(spec, pod.param_dtype)
    params_sh = plib.tree_shardings(spec, mesh, cfg.sharding_policy)
    Bg, T = shape.global_batch, shape.seq
    pool_abs = tf.abstract_paged_pool(cfg, n_pages, page_size, pod.param_dtype)
    pool_sh = paged_pool_shardings(cfg, pool_abs, mesh)

    def prefill_step(params, pool, tokens, table):
        cache = tf.init_cache(cfg, Bg, T, pod.param_dtype)
        logits, cache, _ = tf.forward(cfg, params, {"tokens": tokens},
                                      cache=cache, pos=jnp.int32(0))
        pool = tf.write_prefill_to_pages(cfg, cache, pool, table, page_size)
        return logits[:, -1], pool

    example = (params_abs, pool_abs,
               jax.ShapeDtypeStruct((Bg, T), jnp.int32),
               jax.ShapeDtypeStruct((Bg, pages_per_req), jnp.int32))
    in_sh = (params_sh, pool_sh, _rep(mesh), _rep(mesh))
    out_sh = (_rep(mesh), pool_sh)
    return prefill_step, example, in_sh, out_sh


def build_paged_decode_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                            pod: PodConfig, *, page_size: int | None = None,
                            pages_per_req: int | None = None,
                            n_pages: int | None = None):
    """One token for ``global_batch`` continuous-batching request slots
    against the paged KV pool.  Unlike :func:`build_decode_step`, ``pos`` is
    a per-request (B,) vector and the attended width is the (bucketed) table
    width ``pages_per_req``·``page_size``, not a monolithic capacity — the
    serve scheduler compiles one trace per page bucket and re-dispatches as
    the longest active request grows.

    moe_gather_weights is force-disabled for the same reason as the
    monolithic decode step (see :func:`build_decode_step`).
    """
    cfg = dataclasses.replace(cfg, moe_gather_weights=False)
    tf.check_paged_support(cfg)
    page_size, pages_per_req, n_pages = _paged_geometry(
        shape, page_size, pages_per_req, n_pages)
    spec = tf.arch_spec(cfg)
    params_abs = plib.abstract_params(spec, pod.param_dtype)
    params_sh = plib.tree_shardings(spec, mesh, cfg.sharding_policy)
    B = shape.global_batch
    pool_abs = tf.abstract_paged_pool(cfg, n_pages, page_size, pod.param_dtype)
    pool_sh = paged_pool_shardings(cfg, pool_abs, mesh)

    def decode_step(params, pool, tokens, table, pos_b):
        logits, new_pool, _ = tf.forward(cfg, params, {"tokens": tokens},
                                         cache=pool, pos=pos_b,
                                         paged_table=table)
        return logits[:, 0], new_pool

    example = (params_abs, pool_abs,
               jax.ShapeDtypeStruct((B, 1), jnp.int32),
               jax.ShapeDtypeStruct((B, pages_per_req), jnp.int32),
               jax.ShapeDtypeStruct((B,), jnp.int32))
    in_sh = (params_sh, pool_sh, _rep(mesh), _rep(mesh), _rep(mesh))
    out_sh = (_rep(mesh), pool_sh)
    return decode_step, example, in_sh, out_sh


BUILDERS = {
    "train": build_seedflood_train_step,
    "train_dsgd": build_dsgd_train_step,
    "prefill": build_prefill_step,
    "decode": build_decode_step,
    "prefill_paged": build_paged_prefill_step,
    "decode_paged": build_paged_decode_step,
}


def build_step(kind: str, cfg: ArchConfig, shape: InputShape, mesh: Mesh,
               pod: PodConfig):
    return BUILDERS[kind](cfg, shape, mesh, pod)
