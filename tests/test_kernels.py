"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps,
the shared ``_tile`` helper, and the kernel_backend dispatch contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import KERNEL_BACKENDS
from repro.kernels import ops, ref  # sfcheck: noqa[SF006] -- this suite IS the oracle-parity gate; it needs the raw ref kernels


# ---------------------------------------------------------------------------
# _tile: one shared helper, bug-fixed (ISSUE 5 satellite)
# ---------------------------------------------------------------------------

def test_tile_320_256_regression():
    # 160 (the largest divisor) is neither a multiple of 128 nor the whole
    # dim, which the TPU compiler refuses as a block; no aligned divisor
    # exists, so the tile is 256 with a partial edge block
    assert ops._tile(320, 256) == 256


@pytest.mark.parametrize("dim,target,want", [
    (128, 256, 128),    # whole dim fits
    (256, 256, 256),
    (512, 256, 256),    # aligned divisor at target
    (896, 256, 128),    # 128 divides 896; the larger 224 is unaligned
    (384, 256, 128),    # ditto: 192 is larger but unaligned
    (320, 256, 256),    # no aligned divisor -> 256 with an edge block
    (1016, 256, 256),   # the pod step's rows per client (8 x 127)
    (50272, 256, 256),  # OPT's vocabulary = 32 x 1571
    (96, 256, 96),
    (7, 256, 7),
    (100, 64, 100),     # one lane tile holds the whole dim
    (1, 256, 1),
])
def test_tile_cases(dim, target, want):
    assert ops._tile(dim, target) == want


@pytest.mark.parametrize("dim", [1, 7, 96, 100, 320, 512, 896, 1000, 1016,
                                 50272])
@pytest.mark.parametrize("target", [1, 128, 256, 512])
def test_tile_properties(dim, target):
    t = ops._tile(dim, target)
    # chip-legal block: the whole dim, or lane-aligned and inside the dim
    assert t == dim or (t % 128 == 0 and t < dim)
    assert t <= max(dim if dim <= 128 else 128, target)
    # preference contract: when the dim does not fit, an aligned divisor
    # within target wins, and the largest such
    aligned = [d for d in range(128, target + 1, 128) if dim % d == 0]
    if t != dim and aligned:
        assert t == max(aligned)


@pytest.mark.parametrize("dim,target,want", [
    (2048, 512, 512), (5632, 512, 512), (8192, 512, 512),
    (896, 512, 128),    # exact aligned divisor
    (320, 512, 320),    # fits whole
    (1000, 512, 1000),  # no aligned divisor: the whole contraction
    (64, 512, 64),
])
def test_tile_k_cases(dim, target, want):
    t = ops._tile_k(dim, target)
    assert t == want and dim % t == 0


def test_tile_shared_by_all_kernel_modules():
    from repro.kernels import rank1_matmul, selective_scan, subcge_apply  # sfcheck: noqa[SF006] -- asserts the kernel modules share ops._tile
    assert subcge_apply._tile is ops._tile
    assert rank1_matmul._tile is ops._tile
    assert selective_scan._tile is ops._tile


# ---------------------------------------------------------------------------
# backend resolution: explicit, cached, no per-call sniffing
# ---------------------------------------------------------------------------

def test_resolve_backend_values():
    assert ops.resolve_backend("jnp") == "jnp"
    assert ops.resolve_backend("pallas") == "pallas"
    assert ops.resolve_backend("interpret") == "interpret"
    assert ops.resolve_backend("auto") in ("jnp", "pallas")
    with pytest.raises(ValueError):
        ops.resolve_backend("cuda")


def test_auto_resolution_is_cached(monkeypatch):
    # the "auto" meaning is frozen at first use: even if the platform sniff
    # were to change mid-process, already-resolved callers keep their path
    first = ops.resolve_backend("auto")
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert ops.resolve_backend("auto") == first


def test_default_backend_roundtrip():
    assert ops.get_default_backend() in KERNEL_BACKENDS
    prev = ops.set_default_backend("interpret")
    try:
        assert ops.get_default_backend() == "interpret"
        assert ops.resolve_backend() == "interpret"
    finally:
        ops.set_default_backend(prev)
    with pytest.raises(ValueError):
        ops.set_default_backend("nope")
    with ops.default_backend("jnp"):
        assert ops.resolve_backend() == "jnp"


def test_jnp_dispatch_is_bitwise_the_oracle():
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    W = jax.random.normal(ks[0], (96, 80))
    U = jax.random.normal(ks[1], (96, 8))
    V = jax.random.normal(ks[2], (80, 8))
    A = jax.random.normal(ks[3], (8, 8))
    got = ops.subcge_apply(W, U, A, V, backend="jnp")
    want = ref.subcge_apply(W, U, A, V)
    assert (np.asarray(got) == np.asarray(want)).all()


# ---------------------------------------------------------------------------
# subcge_apply: W += U A V^T  (instance/batch dims share U/V)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,r", [
    ((128, 128), 8), ((256, 512), 32), ((384, 128), 16),
    ((320, 896), 8),                      # non-divisible-by-256 dims
    ((320, 64), 4), ((96, 320), 2),       # odd tiles both axes, multiple ranks
    ((3, 128, 256), 32), ((2, 4, 128, 128), 8),
    ((2, 320, 96), 16),                   # batch dims x non-divisible dims
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_subcge_apply_kernel(shape, r, dtype):
    n, m = shape[-2:]
    ks = jax.random.split(jax.random.PRNGKey(sum(shape) + r), 4)
    W = jax.random.normal(ks[0], shape, dtype)
    U = jax.random.normal(ks[1], (n, r), jnp.float32)
    V = jax.random.normal(ks[2], (m, r), jnp.float32)
    A = jax.random.normal(ks[3], shape[:-2] + (r, r), jnp.float32)
    got = ops.subcge_apply(W, U, A, V, backend="interpret")
    want = ref.subcge_apply(W, U, A, V)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, jnp.float32),
                               np.asarray(want, jnp.float32),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("E", [1, 2, 4])
@pytest.mark.parametrize("batch", [(), (3,)])
def test_subcge_apply_epochs_kernel(E, batch):
    n, m, r = 96, 320, 5
    ks = jax.random.split(jax.random.PRNGKey(E + len(batch)), 4)
    W = jax.random.normal(ks[0], batch + (n, m))
    U = jax.random.normal(ks[1], (E, n, r))
    V = jax.random.normal(ks[2], (E, m, r))
    A = jax.random.normal(ks[3], (E,) + batch + (r, r))
    got = ops.subcge_apply_epochs(W, U, A, V, backend="interpret")
    want = ref.subcge_apply_epochs(W, U, A, V)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_subcge_apply_epochs_matches_sequential_single_epoch_applies():
    # the rank-(E·r) block-diagonal fold == applying each epoch in turn
    n, m, r, E = 64, 80, 4, 3
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    W = jax.random.normal(ks[0], (n, m))
    U = jax.random.normal(ks[1], (E, n, r))
    V = jax.random.normal(ks[2], (E, m, r))
    A = jax.random.normal(ks[3], (E, r, r))
    seq = W
    for e in range(E):
        seq = ref.subcge_apply(seq, U[e], A[e], V[e])
    got = ops.subcge_apply_epochs(W, U, A, V, backend="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(seq),
                               rtol=1e-4, atol=1e-3)


def test_subcge_delta():
    n, m, r = 320, 96, 6
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    U = jax.random.normal(ks[0], (n, r))
    V = jax.random.normal(ks[1], (m, r))
    A = jax.random.normal(ks[2], (r, r))
    got = ops.subcge_delta(U, A, V, jnp.float32, backend="interpret")
    want = ref.subcge_delta(U, A, V, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# rank1_matmul family: the fused ZO dual forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mkn", [(128, 128, 128), (256, 512, 128),
                                 (64, 384, 256), (512, 128, 512),
                                 (40, 320, 96), (24, 896, 320)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s", [0.0, 1e-3, -2.5])
def test_rank1_matmul_kernel(mkn, dtype, s):
    M, K, N = mkn
    ks = jax.random.split(jax.random.PRNGKey(M + K + N), 4)
    x = jax.random.normal(ks[0], (M, K), dtype)
    W = jax.random.normal(ks[1], (K, N), dtype)
    u = jax.random.normal(ks[2], (K,), jnp.float32)
    v = jax.random.normal(ks[3], (N,), jnp.float32)
    got = ops.rank1_matmul(x, W, u, v, s, backend="interpret")
    want = ref.rank1_matmul(x, W, u, v, s)
    tol = 1e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, jnp.float32),
                               np.asarray(want, jnp.float32),
                               rtol=tol, atol=tol * 20)


# shapes the TPU compiler refused before edge blocks: a row count with no
# aligned divisor (the pod step's 1016 = 8·127 rows, scaled to 504 = 8·63)
# and an output width with no multiple-of-128 divisor (OPT's vocabulary
# 50272 = 32·1571, scaled to 416 = 32·13)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("op", ["rank1_matmul", "rank1_matmul_t"])
def test_rank1_matmul_unaligned_rows_and_width(op, dtype):
    M, K, N = 504, 256, 416
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(ks[0], (M, K), dtype)
    wshape, ushape = ((K, N), (K,)) if op == "rank1_matmul" else ((N, K), (N,))
    W = jax.random.normal(ks[1], wshape, dtype)
    u = jax.random.normal(ks[2], ushape, jnp.float32)
    v = jax.random.normal(ks[3], (N,) if op == "rank1_matmul" else (K,),
                          jnp.float32)
    got = getattr(ops, op)(x, W, u, v, 0.3, backend="interpret")
    want = getattr(ref, op)(x, W, u, v, 0.3)
    assert got.shape == (M, N)
    tol = 1e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, jnp.float32),
                               np.asarray(want, jnp.float32),
                               rtol=tol, atol=tol * 20)


@pytest.mark.parametrize("shape", [(416, 504), (2, 504, 416)])
def test_subcge_apply_unaligned_edge_blocks(shape):
    n, m = shape[-2:]
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    W = jax.random.normal(ks[0], shape)
    U = jax.random.normal(ks[1], (n, 8))
    V = jax.random.normal(ks[2], (m, 8))
    A = jax.random.normal(ks[3], shape[:-2] + (8, 8))
    got = ops.subcge_apply(W, U, A, V, backend="interpret")
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.subcge_apply(W, U, A, V)),
                               rtol=1e-4, atol=1e-3)


def test_rank1_matmul_zero_scale_is_plain_matmul():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (128, 256))
    W = jax.random.normal(ks[1], (256, 128))
    u = jax.random.normal(ks[2], (256,))
    v = jax.random.normal(ks[3], (128,))
    got = ops.rank1_matmul(x, W, u, v, 0.0, backend="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ W),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("mno", [(40, 96, 320), (128, 128, 256),
                                 (64, 320, 896)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s", [1e-3, -0.7])
def test_rank1_matmul_t_kernel(mno, dtype, s):
    M, N, O = mno                 # x (M,N) @ W (O,N)^T -> (M,O)
    ks = jax.random.split(jax.random.PRNGKey(M + N + O), 4)
    x = jax.random.normal(ks[0], (M, N), dtype)
    W = jax.random.normal(ks[1], (O, N), dtype)
    u = jax.random.normal(ks[2], (O,), jnp.float32)
    v = jax.random.normal(ks[3], (N,), jnp.float32)
    got = ops.rank1_matmul_t(x, W, u, v, s, backend="interpret")
    want = ref.rank1_matmul_t(x, W, u, v, s)
    tol = 1e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, jnp.float32),
                               np.asarray(want, jnp.float32),
                               rtol=tol, atol=tol * 20)


def test_rank1_matmul_t_is_rank1_matmul_of_transpose():
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (32, 96))
    W = jax.random.normal(ks[1], (80, 96))
    u = jax.random.normal(ks[2], (80,))
    v = jax.random.normal(ks[3], (96,))
    a = ops.rank1_matmul_t(x, W, u, v, 1.3, backend="interpret")
    b = ops.rank1_matmul(x, W.T, v, u, 1.3, backend="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("ecnm", [(4, 24, 96, 64), (2, 128, 64, 320),
                                  (8, 16, 320, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rank1_matmul_expert_kernel(ecnm, dtype):
    E, C, n, m = ecnm
    ks = jax.random.split(jax.random.PRNGKey(E * C + n + m), 4)
    x = jax.random.normal(ks[0], (E, C, n), dtype)
    W = jax.random.normal(ks[1], (E, n, m), dtype)
    u = jax.random.normal(ks[2], (n, E), jnp.float32)
    v = jax.random.normal(ks[3], (m, E), jnp.float32)
    got = ops.rank1_matmul_expert(x, W, u, v, -0.3, backend="interpret")
    want = ref.rank1_matmul_expert(x, W, u, v, -0.3)
    tol = 1e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, jnp.float32),
                               np.asarray(want, jnp.float32),
                               rtol=tol, atol=tol * 20)


# the schedule: several output tiles (one a partial edge tile), several k
# steps and row blocks, forced by the block overrides at 128.  x·u is summed
# during the first output tile only and reused by the others; vmapped over
# clients with a shared W (the train step's form) the scratch carries over
# the client grid axis too, which a stale x·u would show
@pytest.mark.parametrize("clients", [None, 3])
@pytest.mark.parametrize("M", [64, 192])
@pytest.mark.parametrize("op", ["rank1_matmul", "rank1_matmul_t"])
def test_rank1_schedule_tiles_and_clients(op, M, clients):
    from repro.kernels import rank1_matmul as r1  # sfcheck: noqa[SF006] -- drives the kernels' block overrides
    K, N = 384, 416
    lead = () if clients is None else (clients,)
    ks = jax.random.split(jax.random.PRNGKey(M + (clients or 0)), 5)
    x = jax.random.normal(ks[0], lead + (M, K), jnp.bfloat16)
    wshape = (K, N) if op == "rank1_matmul" else (N, K)
    W = jax.random.normal(ks[1], wshape, jnp.bfloat16)
    a = jax.random.normal(ks[2], lead + (K,), jnp.float32)   # contracted
    b = jax.random.normal(ks[3], lead + (N,), jnp.float32)   # output side
    s = jax.random.normal(ks[4], lead, jnp.float32)
    if op == "rank1_matmul":
        def got_fn(x, W, a, b, s):
            return r1.rank1_matmul(x, W, a, b, s, bm=128, bn=128, bk=128,
                                   interpret=True)
        want_fn = lambda x, W, a, b, s: ref.rank1_matmul(x, W, a, b, s)
    else:
        def got_fn(x, W, a, b, s):
            return r1.rank1_matmul_t(x, W, b, a, s, bm=128, bo=128, bk=128,
                                     interpret=True)
        want_fn = lambda x, W, a, b, s: ref.rank1_matmul_t(x, W, b, a, s)
    if clients is not None:
        axes = (0, None, 0, 0, 0)
        got_fn, want_fn = jax.vmap(got_fn, axes), jax.vmap(want_fn, axes)
    got = got_fn(x, W, a, b, s)
    want = want_fn(x, W, a, b, s)
    assert got.shape == lead + (M, N)
    np.testing.assert_allclose(np.asarray(got, jnp.float32),
                               np.asarray(want, jnp.float32),
                               rtol=4e-2, atol=4e-2 * 20)


# the block rule at the shapes that run it: the train cell's per-client
# OPT-1.3B matrices and tied head, Qwen1.5-0.5B's (2816 = 22·128, a 152k
# head), chip_smoke's 1016 rows and TinyLlama's 32000 head; f32 operands
# (the simulator's models) must fit the same budget
OPT_SHAPES = [(512, 2048, 2048), (512, 2048, 8192), (512, 8192, 2048),
              (512, 2048, 50272)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("mkn", OPT_SHAPES + [
    (256, 1024, 1024), (256, 1024, 2816), (256, 2816, 1024),
    (256, 1024, 151936), (1016, 2048, 8192), (1016, 2048, 32000),
    (1016, 2048, 50272), (64, 384, 416), (7, 96, 10)])
def test_rank1_blocks(mkn, dtype):
    from repro.kernels import rank1_matmul as r1  # sfcheck: noqa[SF006] -- the kernels' block rule
    M, K, N = mkn
    bm, bn, bk = r1.rank1_blocks(M, K, N, dtype, dtype)
    for blk, dim in ((bm, M), (bn, N), (bk, K)):
        assert blk == dim or (blk % 128 == 0 and blk < dim), (blk, dim)
    assert K % bk == 0
    assert r1.vmem_bytes(bm, bn, bk, dtype, dtype) <= r1.VMEM_BUDGET
    if dtype == jnp.bfloat16 and mkn in OPT_SHAPES:
        # whole rows of a client, wide and deep tiles: compute-bound steps
        assert (bm, bn, bk) == (512, 2048, 2048)
        assert bm * bn / (bm + bn) >= 240
    if dtype == jnp.bfloat16 and mkn == (256, 1024, 2816):
        assert bn == 1408          # 2816 = 2 x 1408, no edge tile
    if mkn == (256, 1024, 151936):
        assert bn >= 1024          # an edge tile, not the 128 divisor


def test_rank1_kernels_accept_traced_scale():
    # the dual forward flips s = ±ε under jit — s must be traceable
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    x = jax.random.normal(ks[0], (16, 64))
    W = jax.random.normal(ks[1], (64, 32))
    u = jax.random.normal(ks[2], (64,))
    v = jax.random.normal(ks[3], (32,))

    @jax.jit
    def f(s):
        return ops.rank1_matmul(x, W, u, v, s, backend="interpret")

    np.testing.assert_allclose(np.asarray(f(0.5)),
                               np.asarray(ref.rank1_matmul(x, W, u, v, 0.5)),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("btdn", [(1, 64, 128, 16), (2, 128, 128, 8),
                                  (1, 96, 256, 4)])
def test_selective_scan_kernel(btdn):
    B, T, D, N = btdn
    ks = jax.random.split(jax.random.PRNGKey(B * T + D), 4)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, D, N)))
    bx = 0.1 * jax.random.normal(ks[1], (B, T, D, N))
    c = jax.random.normal(ks[2], (B, T, N))
    h0 = jax.random.normal(ks[3], (B, D, N))
    got_y, got_h = ops.selective_scan(a, bx, c, h0, backend="interpret")
    want_y, want_h = ref.selective_scan(a, bx, c, h0)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-4, atol=1e-4)


def test_selective_scan_kernel_matches_model_layer():
    """Kernel == the chunked associative scan used by models/layers.py."""
    from repro.models.layers import _ssm_chunked
    B, T, D, N = 2, 64, 128, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, D, N)))
    bx = 0.1 * jax.random.normal(ks[1], (B, T, D, N))
    h0 = jnp.zeros((B, D, N))
    c = jax.random.normal(ks[2], (B, T, N))
    y_k, h_k = ops.selective_scan(a, bx, c, h0, backend="interpret")
    h_all, h_last = _ssm_chunked(a, bx, h0, chunk=16)
    y_ref = jnp.einsum("btdn,btn->btd", h_all, c)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_last),
                               rtol=1e-4, atol=1e-4)
