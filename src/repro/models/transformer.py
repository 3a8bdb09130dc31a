"""Composable decoder stack: spec builder + scanned forward + caches + loss.

A model is fully described by an ``ArchConfig``; this module turns it into

* ``arch_spec(cfg)``    — LeafSpec tree (init/sharding/SubCGE metadata source)
* ``forward(...)``      — train / prefill / decode forward, perturbation-aware
* ``init_cache(...)``   — stacked KV/SSM caches for the serve path
* ``lm_loss(...)``      — next-token CE (modality-frontend aware)

Layers within a group period are unrolled; periods are lax.scan'ed, so HLO
size scales with the period length, not depth.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ArchConfig, LayerCfg
from repro.kernels import ops as kops
from repro.models import layers as L
from repro.models import params as plib
from repro.models.params import LeafSpec, matrix, vector
from repro.models.perturb import Bundle, Pert, _child

LEARNED_POS_LEN = 4_096  # OPT-style learned position table length


# ---------------------------------------------------------------------------
# spec construction
# ---------------------------------------------------------------------------

def _norm_spec(s: dict, key: str, dim: int, cfg: ArchConfig, stack) -> None:
    s[key + "_scale"] = vector(dim, "embed", stack=stack, init="zeros")
    if cfg.norm == "layernorm":
        s[key + "_bias"] = vector(dim, "embed", stack=stack, init="zeros")


def _slot_spec(slot: LayerCfg, cfg: ArchConfig, reps: int) -> dict[str, LeafSpec]:
    stack = ((reps, "layers"),)
    d = cfg.d_model
    s: dict[str, LeafSpec] = {}

    if slot.mixer == "attn":
        a = slot.attn
        _norm_spec(s, "ln_attn", d, cfg, stack)
        if a.is_mla:
            nope, rd, vd = a.head_dim, a.rope_head_dim, (a.v_head_dim or a.head_dim)
            if a.q_lora > 0:
                s["wdq"] = matrix(d, a.q_lora, "embed", "mla_latent", stack=stack)
                s["q_ln_scale"] = vector(a.q_lora, "mla_latent", stack=stack, init="zeros")
                s["wuq"] = matrix(a.q_lora, a.n_heads * (nope + rd),
                                  "mla_latent", "heads_embed", stack=stack)
            else:
                s["wq"] = matrix(d, a.n_heads * (nope + rd),
                                 "embed", "heads_embed", stack=stack)
            s["wdkv"] = matrix(d, a.kv_lora + rd, "embed", "mla_latent", stack=stack)
            s["kv_ln_scale"] = vector(a.kv_lora, "mla_latent", stack=stack, init="zeros")
            s["wukv"] = matrix(a.kv_lora, a.n_heads * (nope + vd),
                               "mla_latent", "heads_embed", stack=stack)
            s["wo"] = matrix(a.n_heads * vd, d, "heads_embed", "embed", stack=stack)
        else:
            H, KV, hd = a.n_heads, a.n_kv_heads, a.head_dim
            s["wq"] = matrix(d, H * hd, "embed", "heads_embed", stack=stack)
            s["wk"] = matrix(d, KV * hd, "embed", "kv_embed", stack=stack)
            s["wv"] = matrix(d, KV * hd, "embed", "kv_embed", stack=stack)
            s["wo"] = matrix(H * hd, d, "heads_embed", "embed", stack=stack)
            if a.qkv_bias:
                s["bq"] = vector(H * hd, "heads_embed", stack=stack)
                s["bk"] = vector(KV * hd, "kv_embed", stack=stack)
                s["bv"] = vector(KV * hd, "kv_embed", stack=stack)
    elif slot.mixer == "mamba":
        m = slot.mamba
        Di, N, Kc = m.d_inner, m.d_state, m.d_conv
        dtr = m.dt_rank or -(-d // 16)
        _norm_spec(s, "ln_attn", d, cfg, stack)
        s["in_proj"] = matrix(d, 2 * Di, "embed", "mamba_inner", stack=stack)
        s["conv_w"] = matrix(Di, Kc, "mamba_inner", "conv", stack=stack)
        s["conv_b"] = vector(Di, "mamba_inner", stack=stack)
        s["x_proj"] = matrix(Di, dtr + 2 * N, "mamba_inner", "dt_rank", stack=stack)
        s["dt_proj"] = matrix(dtr, Di, "dt_rank", "mamba_inner", stack=stack)
        s["dt_bias"] = vector(Di, "mamba_inner", stack=stack, init="dt_bias")
        s["A_log"] = matrix(Di, N, "mamba_inner", "state", stack=stack, init="s4d")
        s["D_skip"] = vector(Di, "mamba_inner", stack=stack, init="ones")
        s["out_proj"] = matrix(Di, d, "mamba_inner", "embed", stack=stack)

    if slot.ffn == "dense":
        _norm_spec(s, "ln_mlp", d, cfg, stack)
        s["w1"] = matrix(d, slot.d_ff, "embed", "mlp", stack=stack)
        if cfg.gated_mlp:
            s["w3"] = matrix(d, slot.d_ff, "embed", "mlp", stack=stack)
        s["w2"] = matrix(slot.d_ff, d, "mlp", "embed", stack=stack)
    elif slot.ffn == "moe":
        mo = slot.moe
        estack = stack + ((mo.n_experts, "experts"),)
        _norm_spec(s, "ln_mlp", d, cfg, stack)
        s["router"] = matrix(d, mo.n_experts, "embed", "experts", stack=stack)
        # expert weights use their own d_model axis name ("expert_embed") so
        # policies can fsdp-shard the big expert tensors over "data" without
        # dragging the residual stream / attention weights along (§Perf)
        s["w1"] = matrix(d, mo.d_ff_expert, "expert_embed", "mlp", stack=estack)
        if cfg.gated_mlp:
            s["w3"] = matrix(d, mo.d_ff_expert, "expert_embed", "mlp", stack=estack)
        s["w2"] = matrix(mo.d_ff_expert, d, "mlp", "expert_embed", stack=estack)
        if mo.n_shared > 0:
            fs = mo.n_shared * mo.d_ff_expert
            s["sw1"] = matrix(d, fs, "embed", "mlp", stack=stack)
            if cfg.gated_mlp:
                s["sw3"] = matrix(d, fs, "embed", "mlp", stack=stack)
            s["sw2"] = matrix(fs, d, "mlp", "embed", stack=stack)
    return s


def arch_spec(cfg: ArchConfig) -> dict[str, Any]:
    spec: dict[str, Any] = {"embed": {}}
    spec["embed"]["tok"] = matrix(cfg.vocab, cfg.d_model, "vocab", "embed",
                                  scale=0.02)
    if not cfg.tie_embeddings:
        spec["embed"]["out"] = matrix(cfg.d_model, cfg.vocab, "embed", "vocab")
    _norm_spec(spec["embed"], "ln_f", cfg.d_model, cfg, ())
    if cfg.pos == "learned":
        spec["embed"]["pos"] = matrix(LEARNED_POS_LEN, cfg.d_model,
                                      None, "embed", scale=0.02)
    if cfg.frontend is not None:
        spec["frontend"] = {
            "proj": matrix(cfg.frontend.embed_dim, cfg.d_model, "vit", "embed"),
        }
    for gi, g in enumerate(cfg.groups):
        spec[f"g{gi}"] = {f"s{si}": _slot_spec(slot, cfg, g.reps)
                          for si, slot in enumerate(g.slots)}
    return spec


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _slot_cache(slot: LayerCfg, cfg: ArchConfig, reps: int, B: int,
                capacity: int, dtype) -> dict | None:
    if slot.mixer == "attn":
        a = slot.attn
        C = capacity if a.window is None else min(a.window, capacity)
        if a.is_mla:
            rd = a.rope_head_dim
            return {"ckv": jnp.zeros((reps, B, C, a.kv_lora), dtype),
                    "krope": jnp.zeros((reps, B, C, rd), dtype),
                    "kpos": jnp.full((reps, C), -1, jnp.int32)}
        return {"k": jnp.zeros((reps, B, C, a.n_kv_heads, a.head_dim), dtype),
                "v": jnp.zeros((reps, B, C, a.n_kv_heads, a.head_dim), dtype),
                "kpos": jnp.full((reps, C), -1, jnp.int32)}
    if slot.mixer == "mamba":
        m = slot.mamba
        return {"h": jnp.zeros((reps, B, m.d_inner, m.d_state), jnp.float32),
                "conv": jnp.zeros((reps, B, m.d_conv - 1, m.d_inner), dtype)}
    return None


def init_cache(cfg: ArchConfig, B: int, capacity: int, dtype=jnp.bfloat16):
    cache: dict[str, Any] = {}
    for gi, g in enumerate(cfg.groups):
        cache[f"g{gi}"] = {f"s{si}": _slot_cache(slot, cfg, g.reps, B, capacity, dtype)
                           for si, slot in enumerate(g.slots)}
    return cache


def abstract_cache(cfg: ArchConfig, B: int, capacity: int, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: init_cache(cfg, B, capacity, dtype))


# ---------------------------------------------------------------------------
# paged KV pool (serving; repro.serve / DESIGN.md §10)
# ---------------------------------------------------------------------------
#
# Instead of one (B, capacity) buffer per request batch, serving keeps a
# shared pool of fixed-size pages per attention slot and a per-request page
# table (host side: repro.serve.paged_cache).  Pools are allocated with
# ``n_pages + 1`` physical pages: the extra LAST page is the dump page that
# inactive decode slots write into (same trick as the MoE overflow slot), so
# the decode step runs at a fixed batch width with no scatter corruption.

def check_paged_support(cfg: ArchConfig) -> None:
    """Paged serving covers standard (GQA) attention slots; MLA's compressed
    cache and Mamba's recurrent state need their own paging story (ROADMAP)."""
    if cfg.frontend is not None:
        raise ValueError("paged serving is text-decode only (frontend archs "
                         "serve through the monolithic path)")
    for g in cfg.groups:
        for slot in g.slots:
            if slot.mixer == "mamba":
                raise ValueError("paged serving does not support mamba slots")
            if slot.mixer == "attn" and slot.attn.is_mla:
                raise ValueError("paged serving does not support MLA slots")


def _slot_paged_pool(slot: LayerCfg, cfg: ArchConfig, reps: int, n_pages: int,
                     page_size: int, dtype) -> dict | None:
    if slot.mixer != "attn":
        return None
    a = slot.attn
    return {"k": jnp.zeros((reps, n_pages + 1, page_size, a.n_kv_heads,
                            a.head_dim), dtype),
            "v": jnp.zeros((reps, n_pages + 1, page_size, a.n_kv_heads,
                            a.head_dim), dtype)}


def init_paged_pool(cfg: ArchConfig, n_pages: int, page_size: int,
                    dtype=jnp.bfloat16):
    """Per-attention-slot page pools (+1 dump page; see module comment)."""
    check_paged_support(cfg)
    pool: dict[str, Any] = {}
    for gi, g in enumerate(cfg.groups):
        pool[f"g{gi}"] = {f"s{si}": _slot_paged_pool(slot, cfg, g.reps,
                                                     n_pages, page_size, dtype)
                          for si, slot in enumerate(g.slots)}
    return pool


def abstract_paged_pool(cfg: ArchConfig, n_pages: int, page_size: int,
                        dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: init_paged_pool(cfg, n_pages, page_size,
                                                  dtype))


def write_prefill_to_pages(cfg: ArchConfig, cache: Any, pool: Any,
                           table: jax.Array, page_size: int) -> Any:
    """Scatter a freshly prefilled monolithic cache into pool pages.

    ``cache``: the (Bg, T)-shaped tree a prefill ``forward`` just filled;
    ``table``: (Bg, pages) int32 page rows for the Bg admitted requests.
    Prefill logits never read the cache layout (the T > 1 path attends the
    raw k/v), so prefill-then-scatter is bitwise the monolithic prefill.
    """
    out: dict[str, Any] = {}
    for gi, g in enumerate(cfg.groups):
        gk = f"g{gi}"
        out[gk] = {}
        for si, slot in enumerate(g.slots):
            sk = f"s{si}"
            if slot.mixer != "attn":
                out[gk][sk] = pool[gk][sk]
                continue
            c, p = cache[gk][sk], pool[gk][sk]
            # prefill caches are allocated with capacity == prompt length,
            # so slot s of the (full) ring holds absolute position s
            T = c["k"].shape[2]
            pos_vals = jnp.arange(T, dtype=jnp.int32)
            phys = table[:, pos_vals // page_size]            # (Bg, T)
            off = jnp.broadcast_to(pos_vals % page_size, phys.shape)
            out[gk][sk] = {
                "k": p["k"].at[:, phys, off].set(c["k"].astype(p["k"].dtype)),
                "v": p["v"].at[:, phys, off].set(c["v"].astype(p["v"].dtype)),
            }
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _apply_slot(slot: LayerCfg, sb: Bundle, x: jax.Array, cache_slot,
                pos, cfg: ArchConfig, paged_table=None):
    new_cache = None
    if slot.mixer == "attn":
        h = L.norm(sb, "ln_attn", x, cfg.norm)
        mixer_cache = cache_slot if cache_slot is not None else None
        if paged_table is not None:
            if slot.attn.is_mla:
                raise ValueError("paged decode does not support MLA slots")
            y, new_cache = L.paged_attention(
                sb, h, slot.attn, pos, mixer_cache, paged_table,
                cfg.rope_theta,
                pos_kind="rope" if cfg.pos == "rope" else "none")
        elif slot.attn.is_mla:
            y, new_cache = L.mla_attention(sb, h, slot.attn, pos, mixer_cache,
                                           cfg.rope_theta)
        else:
            y, new_cache = L.attention(sb, h, slot.attn, pos, mixer_cache,
                                       cfg.rope_theta,
                                       pos_kind="rope" if cfg.pos == "rope" else "none")
        x = x + y
    elif slot.mixer == "mamba" and paged_table is not None:
        raise ValueError("paged decode does not support mamba slots")
    elif slot.mixer == "mamba":
        h = L.norm(sb, "ln_attn", x, cfg.norm)
        y, new_cache = L.mamba(sb, h, slot.mamba, cache_slot)
        x = x + y

    aux = jnp.zeros((), jnp.float32)
    with obs.scope("mlp"):
        if slot.ffn == "dense":
            h = L.norm(sb, "ln_mlp", x, cfg.norm)
            x = x + L.mlp(sb, h, cfg.act, cfg.gated_mlp)
        elif slot.ffn == "moe":
            h = L.norm(sb, "ln_mlp", x, cfg.norm)
            y, aux = L.moe(sb, h, slot.moe, cfg.act, cfg.gated_mlp,
                           gather_weights=cfg.moe_gather_weights)
            x = x + y
    return x, new_cache, aux


def forward(cfg: ArchConfig, params: Any, batch: dict, *,
            sub: Any = None, pert: Pert | None = None,
            cache: Any = None, pos=0, kernel_backend: str | None = None,
            paged_table: jax.Array | None = None):
    """Run the decoder.  Returns (logits, new_cache, aux_loss).

    batch: {"tokens": (B, T) int32, optional "embeds": (B, P, edim)} —
    ``embeds`` are the stubbed modality-frontend outputs, prepended after
    projection.  ``pos`` is the absolute position of tokens[:, 0].
    ``kernel_backend`` picks the implementation of the perturbed matmuls
    (None -> process default; see repro.kernels.ops / DESIGN.md §7).

    With ``paged_table`` set (the repro.serve decode path, DESIGN.md §10),
    ``cache`` is a paged pool tree (:func:`init_paged_pool`), ``pos`` is a
    per-request (B,) int32 position vector, T must be 1, and attention runs
    :func:`repro.models.layers.paged_attention` against the (B, Pb) table.
    """
    paged = paged_table is not None
    root = Bundle.make(params, sub, pert, kernel_backend)
    mesh = kops.kernel_mesh()
    if pert is not None and root.kb != "jnp" and mesh is not None:
        # the perturbed matmuls run as per-shard kernels (kernels.ops)
        root.sp = plib.tree_specs(arch_spec(cfg), mesh, cfg.sharding_policy)
    be = root["embed"]
    tokens = batch["tokens"]
    x = be.embed("tok", tokens)

    if "embeds" in batch and "frontend" in params:
        xf = root["frontend"].dense("proj", batch["embeds"].astype(x.dtype))
        x = jnp.concatenate([xf, x], axis=1)
    T = x.shape[1]
    if paged:
        q_pos = jnp.asarray(pos)[:, None] + jnp.arange(T)    # (B, T)
    else:
        q_pos = pos + jnp.arange(T)

    if cfg.pos == "learned":
        x = x + be.embed("pos", jnp.clip(q_pos, 0, LEARNED_POS_LEN - 1))
    elif cfg.pos == "sinusoidal":
        pe = L.sinusoidal_pos(q_pos, cfg.d_model)
        x = x + (pe if paged else pe[None]).astype(x.dtype)

    if cfg.residual_replicated:
        from jax.sharding import PartitionSpec as _P
        U = _P.UNCONSTRAINED
        x = jax.lax.with_sharding_constraint(
            x, _P(*([U] * (x.ndim - 1)), None))

    new_cache: dict[str, Any] = {}
    aux_total = jnp.zeros((), jnp.float32)
    for gi, g in enumerate(cfg.groups):
        gk = f"g{gi}"
        gp = params[gk]
        gij = _child(pert.ij, gk) if pert is not None else None
        gzv = _child(pert.zv, gk) if pert is not None else None
        guv = _child(sub, gk)
        gsp = _child(root.sp, gk)
        gcache = cache[gk] if cache is not None else None
        scale = pert.scale if pert is not None else None

        def body(carry, xs, g=g, guv=guv, gsp=gsp, scale=scale, kb=root.kb):
            xc, aux_c = carry
            pslice, ijslice, zvslice, cslice = xs
            ncs: dict[str, Any] = {}
            for si, slot in enumerate(g.slots):
                sk = f"s{si}"
                sb = Bundle(pslice[sk], _child(guv, sk), _child(ijslice, sk),
                            _child(zvslice, sk), scale, kb, _child(gsp, sk))
                cslot = cslice[sk] if cslice is not None else None
                xc, nc, aux = _apply_slot(slot, sb, xc, cslot, pos, cfg,
                                          paged_table=paged_table)
                ncs[sk] = nc
            return (xc, aux_c + aux), ncs

        (x, aux_total), ncache = jax.lax.scan(
            body, (x, aux_total), (gp, gij, gzv, gcache))
        new_cache[gk] = ncache

    with obs.scope("head"):
        x = L.norm(be, "ln_f", x, cfg.norm)
        if cfg.tie_embeddings:
            logits = be.dense_t("tok", x)
        else:
            logits = be.dense("out", x)
    return logits, (new_cache if cache is not None else None), aux_total


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def lm_loss(cfg: ArchConfig, params: Any, batch: dict, *,
            sub: Any = None, pert: Pert | None = None,
            kernel_backend: str | None = None) -> jax.Array:
    """Mean next-token cross-entropy over the text segment (frontend embeds,
    if any, are context only)."""
    logits, _, aux = forward(cfg, params, batch, sub=sub, pert=pert,
                             kernel_backend=kernel_backend)
    tokens = batch["tokens"]
    off = logits.shape[1] - tokens.shape[1]          # n frontend embeds
    Tt = tokens.shape[1]
    with obs.scope("head"):
        lg = logits[:, off: off + Tt - 1].astype(jnp.float32)
        labels = tokens[:, 1:]
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        # gold logit via masked reduction, NOT take_along_axis: a gather
        # across a vocab-sharded axis would force an all-gather of the full
        # logits under SPMD; the select+reduce keeps partial sums shard-local.
        vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lg.shape,
                                              lg.ndim - 1)
        gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], lg, 0.0),
                       axis=-1)
        return jnp.mean(lse - gold) + aux


# ---------------------------------------------------------------------------
# convenience
# ---------------------------------------------------------------------------

def init_params(cfg: ArchConfig, seed: int = 0, dtype=jnp.float32):
    return plib.init_params(arch_spec(cfg), seed, dtype)


def count_params(cfg: ArchConfig) -> int:
    return plib.n_params(arch_spec(cfg))
