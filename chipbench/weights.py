"""Weights made from ``--seed`` on the device, in one jitted call.

The tree has the program's layout (its leaf paths and shapes are the system
under test's interface); the values are the benchmark's own: matrices
N(0, 1/rows), embeddings and position tables N(0, 0.02²), biases and norm
gains N(0, 0.02²) (gains are stored as offsets from 1).  The reference
regenerates the same tree from the same seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import rng

VECTOR_SUFFIXES = ("_scale", "_bias")
VECTOR_NAMES = ("bq", "bk", "bv")
TABLE_NAMES = ("tok", "pos")


def leaf_name(path: str) -> str:
    return path.rsplit("/", 1)[-1]


def is_vector(path: str) -> bool:
    name = leaf_name(path)
    return name.endswith(VECTOR_SUFFIXES) or name in VECTOR_NAMES


def std(path: str, shape) -> float:
    name = leaf_name(path)
    if is_vector(path) or name in TABLE_NAMES:
        return 0.02
    return float(shape[-2]) ** -0.5


def flat_paths(tree) -> tuple[list[str], list]:
    """The tree's leaf paths (``g0/s0/wq``, as the program names them) and
    its leaves, in flattening order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
            for p, _ in flat], [leaf for _, leaf in flat]


def make(abstract_tree, seed: int, dtype, out_shardings=None):
    """Weights shaped like ``abstract_tree`` from ``seed``, in ``dtype``."""
    paths, leaves = flat_paths(abstract_tree)
    treedef = jax.tree_util.tree_structure(abstract_tree)
    shapes = [tuple(leaf.shape) for leaf in leaves]

    def init(key):
        out = []
        for idx, (path, shape) in enumerate(zip(paths, shapes)):
            k = jax.random.fold_in(key, idx)
            out.append((std(path, shape)
                        * jax.random.normal(k, shape, jnp.float32)).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(init, out_shardings=out_shardings)(rng.seed_key(seed))


def leaf_norms_fn():
    """jit((a, b) -> {path: ‖a − b‖₂ in f32}) over two trees alike."""
    def norms(a, b):
        pa, la = flat_paths(a)
        _, lb = flat_paths(b)
        return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                               - y.astype(jnp.float32))))
                for p, x, y in zip(pa, la, lb)}
    return jax.jit(norms)
