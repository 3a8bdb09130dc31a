"""costs.py against hand counts at opt-1.3b's widths."""
import json
import os

from chipbench import costs

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPT = json.load(open(os.path.join(HERE, "configs", "opt-1.3b.json")))["model"]


def test_rank1_matmul_call_by_hand():
    # one client's q projection: x (512, 2048) @ (W (2048, 2048) + s u vᵀ)
    call = dict(kernel="rank1_matmul", M=512, K=2048, N=2048, count=1)
    flops, byts = costs.rank1_cost(call, clients=1)
    assert flops == 2 * 512 * 2048 * 2048 + 2 * 512 * 2048 + 2 * 512 * 2048
    assert byts == (2 * 2048 * 2048          # W, bf16, once
                    + 2 * 512 * 2048 * 2     # x read, y written, bf16
                    + 4 * (2048 + 2048))     # u, v in f32
    # 16 clients share one read of W
    f16, b16 = costs.rank1_cost(call, clients=16)
    assert f16 == 16 * flops
    assert b16 == 2 * 2048 * 2048 + 16 * (2 * 512 * 2048 * 2 + 4 * 4096)


def test_subcge_apply_call_by_hand():
    # the fold of the stacked q projections: 24 × (2048, 2048), rank 16
    flops, byts = costs.subcge_leaf_cost(24, 2048, 2048, 16)
    assert flops == 24 * (2 * 2048 * 16 * 16 + 2 * 2048 * 16 * 2048
                          + 2048 * 2048)
    assert byts == 24 * (2 * 2 * 2048 * 2048 + 4 * 16 * 16) \
        + 4 * 16 * (2048 + 2048)
    assert (24, 2048, 2048) in costs.fold_leaves(OPT)


def test_opt_sizes():
    # 24 layers × (4 d² + 2 d·ff) + the tied 50272 × 2048 head
    d, ff = 2048, 8192
    assert costs.matmul_params(OPT) == 24 * (4 * d * d + 2 * d * ff) \
        + 50272 * d
    # bf16 weights ≈ 2.6 GB, the figure the compiled step's arguments show
    assert 2.6e9 < costs.weight_bytes(OPT) < 2.7e9


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.least_seconds(1000.0, 50.0, peak) == 10.0
    assert costs.least_seconds(100.0, 50.0, peak) == 5.0


def test_decoder_train_cost_is_the_count_the_train_window_used():
    """The train cell's count, now taken from its reference module, is
    the dict the window built from costs.py before: same keys, same
    numbers to the last bit, at 16 clients × 2 × 256 tokens, rank 16."""
    from chipbench.references import decoder
    wl = {"clients": 16, "seqs_per_client": 2, "seq_len": 256, "rank": 16}
    got = decoder.train_cost(OPT, wl)
    n, b, T = 16, 2, 256
    calls = costs.rank1_calls(OPT, b * T)
    assert got == {"step_flops": costs.train_step_flops(OPT, n, b, T, 16),
                   "rank1": [(c["kernel"], *costs.rank1_cost(c, n))
                             for c in calls],
                   "rank1_per_step": 2,
                   "subcge_apply": costs.subcge_apply_cost(OPT, 16)}
    layer = [("rank1_matmul", 1650878054400.0, 1818230784.0)] * 4 \
        + [("rank1_matmul", 6601096298496.0, 4847566848.0)] * 2
    assert got == {"step_flops": 43442929123328.0,
                   "rank1": layer + [("rank1_matmul_t", 1687705616384.0,
                                      1066473472.0)],
                   "rank1_per_step": 2,
                   "subcge_apply": (43791400960.0, 5283471360.0)}
    assert list(got) == ["step_flops", "rank1", "rank1_per_step",
                         "subcge_apply"]
