"""SeedFlood's seed derivation, as the benchmark's reference needs it.

A message is (seed, coefficient, sender step); the perturbation it names is
defined by this derivation, which every client shares: the shared subspace
``U, V`` of a matrix leaf from ``(global seed, τ-refresh step, leaf path)``,
the canonical coordinates ``(i, j)`` and the dense Gaussians of vector leaves
from ``(message seed, leaf path)``.  The reference regenerates both from the
seeds alone, so this file restates the protocol's derivation (threefry keys,
a blake2s hash of the leaf path folded in) without importing the program.
"""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp


def path_hash(path: str) -> int:
    h = hashlib.blake2s(path.encode("utf-8"), digest_size=4).digest()
    return int.from_bytes(h, "little") & 0x7FFFFFFF


def leaf_key(key, path: str):
    return jax.random.fold_in(key, path_hash(path))


def message_key(seed):
    return jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32))


def client_seed(base_seed, step, client):
    """The seed client ``client`` attaches to its step-``step`` message."""
    return (jnp.asarray(base_seed, jnp.uint32)
            + jnp.asarray(step, jnp.uint32) * jnp.uint32(65536)
            + jnp.asarray(client, jnp.uint32)).astype(jnp.uint32)


def refresh_step(step, tau: int):
    return (jnp.asarray(step, jnp.int32) // tau) * tau


def subspace(matrix_leaves: dict, rank: int, global_seed, refresh):
    """{path: (U (rows, r), V (cols, r))} for the refresh step ``refresh``.
    ``matrix_leaves`` maps a path to its per-instance (rows, cols)."""
    base = jax.random.fold_in(
        jax.random.PRNGKey(jnp.asarray(global_seed, jnp.uint32)),
        jnp.asarray(refresh, jnp.uint32))
    out = {}
    for path, (rows, cols) in sorted(matrix_leaves.items()):
        ku, kv = jax.random.split(leaf_key(base, path))
        out[path] = (jax.random.normal(ku, (rows, rank), jnp.float32),
                     jax.random.normal(kv, (cols, rank), jnp.float32))
    return out


def coords(path: str, batch_shape, rank: int, seed):
    """Canonical coordinates (i, j) of every instance of a matrix leaf."""
    ki, kj = jax.random.split(leaf_key(message_key(seed), path))
    return (jax.random.randint(ki, tuple(batch_shape), 0, rank, jnp.int32),
            jax.random.randint(kj, tuple(batch_shape), 0, rank, jnp.int32))


def dense_z(path: str, shape, seed):
    """The dense Gaussian perturbation of a vector leaf."""
    return jax.random.normal(leaf_key(message_key(seed), path), tuple(shape),
                             jnp.float32)


def seed_key(seed: int):
    """A key for any whole-number ``--seed`` (beyond 32 bits too)."""
    seed = int(seed)
    key = jax.random.PRNGKey(jnp.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def seed32(seed: int, salt: int) -> int:
    """A 32-bit seed for the program's own knobs, drawn from ``--seed``."""
    h = hashlib.blake2s(f"{int(seed)}:{salt}".encode(), digest_size=4)
    return int.from_bytes(h.digest(), "little")
