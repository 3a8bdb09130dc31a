"""Compile rehearsals: the main-path kernels at published widths, compiled for
a described TPU v5e (no chip attached).  The TPU compiler refuses what the
Pallas interpreter accepts — misaligned blocks, too much VMEM, a kernel the
SPMD partitioner would have to split — so these guard the chip path from
the CPU.  Nothing runs; a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, so every test-runner
worker must collect the same tests and only the worker running this file
may load it.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

BF16 = jnp.bfloat16
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    """Lower + compile ``fn`` for the described chip; returns HLO text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _kernels(hlo):
    """Names of the kernel ops in a compiled program, numeric suffix
    dropped: each is its dispatcher's name (``pallas_call(name=...)``),
    which a device profile shows."""
    return {m[1] for m in re.finditer(
        r"%([\w\-]+?)(?:\.\d+)? = [^\n]*tpu_custom_call", hlo)}


def _abs(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("op,M,wshape", [
    # opt-1.3b fc1 at the pod step's 1016 rows per client (8 x 127)
    ("rank1_matmul", 1016, (2048, 8192)),
    # opt-1.3b tied-embedding logits: vocab 50272 has no aligned divisor
    ("rank1_matmul_t", 1016, (50272, 2048)),
    # tinyllama-1.1b untied LM head
    ("rank1_matmul", 1016, (2048, 32000)),
])
def test_rank1_matmul_compiles(one_chip, op, M, wshape):
    transposed = op == "rank1_matmul_t"
    K = wshape[1] if transposed else wshape[0]
    rows, cols = wshape
    hlo = _compile(
        lambda x, W, u, v: getattr(ops, op)(x, W, u, v, 1e-3,
                                            backend="pallas"),
        _abs((M, K), BF16, one_chip), _abs(wshape, BF16, one_chip),
        _abs((rows,), F32, one_chip), _abs((cols,), F32, one_chip))
    assert _kernels(hlo) == {op}


@pytest.mark.parametrize("op,M,wshape", [
    # the train cell's form: 16 clients vmapped over one shared W, 512 rows
    # each (2 x 256 tokens); opt-1.3b's q/k/v/o, fc1, fc2 and tied head
    ("rank1_matmul", 512, (2048, 2048)),
    ("rank1_matmul", 512, (2048, 8192)),
    ("rank1_matmul", 512, (8192, 2048)),
    ("rank1_matmul_t", 512, (50272, 2048)),
    # qwen1.5-0.5b's fc2: 2816 = 22 x 128 contracted, 256 rows per client
    ("rank1_matmul", 256, (2816, 1024)),
])
def test_rank1_matmul_compiles_vmapped(one_chip, op, M, wshape):
    """Default blocks (``rank1_blocks``) under the step's vmap: the VMEM
    they take passes the compiler, and the op keeps its name."""
    C = 16
    transposed = op == "rank1_matmul_t"
    K = wshape[1] if transposed else wshape[0]
    rows, cols = wshape
    hlo = _compile(
        jax.vmap(lambda x, W, u, v, s: getattr(ops, op)(x, W, u, v, s,
                                                        backend="pallas"),
                 in_axes=(0, None, 0, 0, 0)),
        _abs((C, M, K), BF16, one_chip), _abs(wshape, BF16, one_chip),
        _abs((C, rows), F32, one_chip), _abs((C, cols), F32, one_chip),
        _abs((C,), F32, one_chip))
    assert _kernels(hlo) == {op}


def test_rank1_matmul_expert_compiles(one_chip):
    # kimi-k2 expert widths (d_model 7168, expert ff 2048), 4 experts held
    E, C, n, m = 4, 1016, 7168, 2048
    hlo = _compile(
        lambda x, W, u, v: ops.rank1_matmul_expert(x, W, u, v, 1e-3,
                                                   backend="pallas"),
        _abs((E, C, n), BF16, one_chip), _abs((E, n, m), BF16, one_chip),
        _abs((n, E), F32, one_chip), _abs((m, E), F32, one_chip))
    assert _kernels(hlo) == {"rank1_matmul_expert"}


@pytest.mark.parametrize("E", [None, 2])
def test_subcge_apply_compiles(one_chip, E):
    L, n, m, r = 24, 2048, 8192, 16
    if E is None:
        fn = lambda W, U, A, V: ops.subcge_apply(W, U, A, V, backend="pallas")
        ushape, ashape, vshape = (n, r), (L, r, r), (m, r)
    else:
        fn = lambda W, U, A, V: ops.subcge_apply_epochs(W, U, A, V,
                                                        backend="pallas")
        ushape, ashape, vshape = (E, n, r), (E, L, r, r), (E, m, r)
    hlo = _compile(fn, _abs((L, n, m), BF16, one_chip),
                   _abs(ushape, F32, one_chip), _abs(ashape, F32, one_chip),
                   _abs(vshape, F32, one_chip))
    assert _kernels(hlo) == {"subcge_apply" if E is None
                             else "subcge_apply_epochs"}


@pytest.mark.parametrize("wspec", [P("model", None), P(None, "model")])
def test_rank1_matmul_compiles_sharded(topo, wspec):
    """A kernel on a 1x4 ("data", "model") mesh: a weight sharded on its
    contracted axis (row-parallel, psum of per-shard partials) and on its
    output axis (column-parallel, no collective)."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    ks, ns = wspec
    M, K, N = 1016, 8192, 2048

    def fn(x, W, u, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return ops.rank1_matmul(x, W, u, v, 1e-3, backend="pallas",
                                    spec=wspec)

    sh = lambda *s: NamedSharding(mesh, P(*s))
    hlo = _compile(fn, _abs((M, K), BF16, sh(None, ks)),
                   _abs((K, N), BF16, sh(ks, ns)), _abs((K,), F32, sh(ks)),
                   _abs((N,), F32, sh(ns)))
    assert "tpu_custom_call" in hlo
    assert ("all-reduce" in hlo) == (ks is not None)
