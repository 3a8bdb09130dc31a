"""Parameter specs: shapes, logical sharding axes, SubCGE metadata.

No flax here — models are functional and parameters are nested dicts of
arrays.  A model definition first produces a *spec tree* (same nesting,
``LeafSpec`` leaves); everything else derives from it:

* ``init_params``     — deterministic initialization
* ``abstract_params`` — ShapeDtypeStruct stand-ins (dry-run, no allocation)
* ``tree_shardings``  — NamedSharding per leaf from logical→mesh rules
* ``subcge_meta``     — LeafMeta dict for the SubCGE machinery

Logical axes vocabulary (MaxText-style): "layers" (scan stacking),
"experts", "embed" (d_model), "mlp" (d_ff), "heads_embed" (H·hd fused),
"kv_embed" (KV·hd fused), "vocab", "mamba_inner", "state", "conv",
"dt_rank", "lora", "vit".  ``None`` means never sharded.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import seeds as seedlib
from repro.core.subcge import LeafMeta


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]          # logical axis names, len == ndim
    n_batch_dims: int = 0                 # leading scan/expert instance dims
    init: str = "normal"                  # normal | zeros | ones
    scale: float | None = None            # None -> 1/sqrt(fan_in)
    frozen: bool = False                  # excluded from ZO perturbation

    def __post_init__(self):
        assert len(self.axes) == len(self.shape), (self.shape, self.axes)

    @property
    def fan_in(self) -> int:
        if len(self.shape) >= 2:
            return self.shape[-2]
        return self.shape[-1]


def matrix(rows: int, cols: int, raxis: str | None, caxis: str | None,
           stack: tuple[tuple[int, str | None], ...] = (), **kw) -> LeafSpec:
    """A (possibly stacked) 2D weight — SubCGE's bread and butter."""
    sdims = tuple(s for s, _ in stack)
    saxes = tuple(a for _, a in stack)
    return LeafSpec(sdims + (rows, cols), saxes + (raxis, caxis),
                    n_batch_dims=len(stack), **kw)


def vector(dim: int, axis: str | None,
           stack: tuple[tuple[int, str | None], ...] = (),
           init: str = "zeros", **kw) -> LeafSpec:
    sdims = tuple(s for s, _ in stack)
    saxes = tuple(a for _, a in stack)
    return LeafSpec(sdims + (dim,), saxes + (axis,),
                    n_batch_dims=len(stack), init=init, **kw)


# ---------------------------------------------------------------------------
# derivations
# ---------------------------------------------------------------------------

def init_params(specs: Any, seed: int, dtype=jnp.float32) -> Any:
    key = jax.random.PRNGKey(seed)

    def one(path: str, spec: LeafSpec):
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dtype)
        if spec.init == "s4d":
            # Mamba A_log: log(1..N) broadcast over channels
            n_state = spec.shape[-1]
            row = jnp.log(jnp.arange(1, n_state + 1, dtype=jnp.float32))
            return jnp.broadcast_to(row, spec.shape).astype(dtype)
        if spec.init == "dt_bias":
            # softplus^-1(0.01) ≈ -4.6: small initial step sizes
            return jnp.full(spec.shape, -4.6, dtype)
        scale = spec.scale if spec.scale is not None else 1.0 / math.sqrt(spec.fan_in)
        k = seedlib.leaf_key(key, path)
        return (scale * jax.random.normal(k, spec.shape, jnp.float32)).astype(dtype)

    return seedlib.map_with_paths(one, specs)


def abstract_params(specs: Any, dtype=jnp.float32) -> Any:
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs)


def n_params(specs: Any) -> int:
    return sum(int(math.prod(s.shape)) for s in jax.tree.leaves(specs))


def subcge_meta(specs: Any) -> dict[str, LeafMeta]:
    meta: dict[str, LeafMeta] = {}

    def visit(path: str, spec: LeafSpec):
        meta[path] = LeafMeta(tuple(spec.shape), spec.n_batch_dims, spec.frozen)
        return spec

    seedlib.map_with_paths(visit, specs)
    return meta


# ---------------------------------------------------------------------------
# sharding policies
# ---------------------------------------------------------------------------

#: logical axis -> preferred mesh axis, in first-come-first-served order per
#: leaf (a mesh axis is used at most once per leaf).
POLICIES: dict[str, dict[str, str]] = {
    # tensor parallel only: weights over "model", everything else replicated
    "tp": {
        "mlp": "model", "heads_embed": "model", "kv_embed": "model",
        "vocab": "model", "experts": "model", "mamba_inner": "model",
        "lora": "model", "vit": "model",
    },
    # fsdp+tp: additionally shard the embed axis of weights over "data"
    # (ZeRO-3 style; XLA inserts per-scan-step all-gathers)
    "fsdp_tp": {
        "mlp": "model", "heads_embed": "model", "kv_embed": "model",
        "vocab": "model", "experts": "model", "mamba_inner": "model",
        "lora": "model", "vit": "model",
        "embed": "data", "expert_embed": "data", "dt_rank": "data",
    },
    # moe_fsdp (beyond-paper §Perf): ZeRO-3 only where it's needed — the
    # expert tensors (experts×model×data = 256-way) — while the residual
    # stream, attention and embeddings stay pure-TP (replicated over data).
    # Viable because ZO training keeps no grads/moments; pairs with
    # moe_gather_weights so the per-layer fsdp cost is a weight all-gather.
    "moe_fsdp": {
        "mlp": "model", "heads_embed": "model", "kv_embed": "model",
        "vocab": "model", "experts": "model", "mamba_inner": "model",
        "lora": "model", "vit": "model",
        "expert_embed": "data",
    },
    # expert-parallel (beyond-paper §Perf): experts over "data", expert-ff
    # over "model"; dense/attention weights column-parallel over "model"
    # only (replicated over data — viable because ZO training stores no
    # grads/moments).  Turns the FSDP d-contraction all-reduces of expert
    # buffers into token all-to-alls.
    "ep": {
        "experts": "data", "mlp": "model", "heads_embed": "model",
        "kv_embed": "model", "vocab": "model", "mamba_inner": "model",
        "lora": "model", "vit": "model",
    },
}


def spec_partition(axes: tuple[str | None, ...], rules: dict[str, str],
                   mesh: Mesh) -> P:
    """Logical axes -> PartitionSpec, FCFS on mesh axes, divisibility-checked
    by the caller via ``shard_or_none``."""
    used: set[str] = set()
    parts: list[str | None] = []
    for a in axes:
        m = rules.get(a) if a is not None else None
        if m is not None and m in mesh.axis_names and m not in used:
            used.add(m)
            parts.append(m)
        else:
            parts.append(None)
    return P(*parts)


def leaf_spec(spec: LeafSpec, mesh, rules: dict[str, str]) -> P:
    """PartitionSpec of one leaf on ``mesh`` (a ``Mesh`` or the
    ``AbstractMesh`` a trace runs under)."""
    parts = list(spec_partition(spec.axes, rules, mesh))
    # drop assignments that don't divide the dimension
    sizes = dict(mesh.shape)
    for d, m in enumerate(parts):
        if m is not None and spec.shape[d] % sizes[m] != 0:
            parts[d] = None
    return P(*parts)


def leaf_sharding(spec: LeafSpec, mesh: Mesh, rules: dict[str, str]) -> NamedSharding:
    return NamedSharding(mesh, leaf_spec(spec, mesh, rules))


def tree_specs(specs: Any, mesh, policy: str) -> Any:
    """PartitionSpec per leaf — what :func:`tree_shardings` places, for code
    that needs the layout inside a trace (``kernels.ops`` under a mesh)."""
    rules = POLICIES[policy]
    return jax.tree.map(lambda s: leaf_spec(s, mesh, rules), specs)


def tree_shardings(specs: Any, mesh: Mesh, policy: str) -> Any:
    rules = POLICIES[policy]
    return jax.tree.map(lambda s: leaf_sharding(s, mesh, rules), specs)


def subspace_shardings(specs: Any, mesh: Mesh, policy: str) -> dict[str, Any]:
    """Shardings for the SubCGE subspace dict: U follows the leaf's row axis,
    V follows its column axis (rank axis replicated)."""
    rules = POLICIES[policy]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out: dict[str, Any] = {}

    def visit(path: str, spec: LeafSpec):
        if spec.frozen or len(spec.shape) - spec.n_batch_dims != 2:
            return spec
        rax, cax = spec.axes[-2], spec.axes[-1]
        rows, cols = spec.shape[-2], spec.shape[-1]
        rm = rules.get(rax) if rax else None
        cm = rules.get(cax) if cax else None
        if rm is not None and rows % sizes.get(rm, 1) != 0:
            rm = None
        if cm is not None and cols % sizes.get(cm, 1) != 0:
            cm = None
        out[path] = (NamedSharding(mesh, P(rm, None)),
                     NamedSharding(mesh, P(cm, None)))
        return spec

    seedlib.map_with_paths(visit, specs)
    return out


# ---------------------------------------------------------------------------
# path utilities
# ---------------------------------------------------------------------------

def nest(flat: dict[str, Any]) -> dict[str, Any]:
    """{'a/b': x} -> {'a': {'b': x}} — used to turn path-keyed SubCGE dicts
    into trees that mirror the params nesting."""
    out: dict[str, Any] = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def flatten_paths(tree: Any) -> dict[str, Any]:
    out: dict[str, Any] = {}

    def visit(path: str, leaf):
        out[path] = leaf
        return leaf

    seedlib.map_with_paths(visit, tree)
    return out
