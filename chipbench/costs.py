"""Operations and bytes the ``decoder`` family's work requires, from shapes
alone.

The arithmetic follows the program's analytic cost model (GEMMs at 2·m·k·n,
causal attention over half the context for train and prefill, the
SubCGE fold as one read and one write of each weight), restated here for the
dense decoders (``"reference": "decoder"``) so that no change to the
program moves the yardstick.  A train cell reaches it through
``references/decoder.train_cost``; the serve window calls it directly, as
only dense decoders are served.  Another family's reference module brings
its own count.  ``m`` is a configuration's ``model`` block without
``layers``.
"""
from __future__ import annotations


def matmuls(m) -> list[tuple[str, int, int]]:
    """(name, K, N) of every weight matmul in one layer."""
    d, ff = m["d_model"], m["d_ff"]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    out = [("wq", d, H * hd), ("wk", d, KV * hd), ("wv", d, KV * hd),
           ("wo", H * hd, d), ("w1", d, ff), ("w2", ff, d)]
    if m["gated_mlp"]:
        out.append(("w3", d, ff))
    return out


def matmul_params(m) -> int:
    """Weights multiplied per token: every layer's matrices and the head."""
    per_layer = sum(K * N for _, K, N in matmuls(m))
    return m["n_layers"] * per_layer + m["vocab"] * m["d_model"]


def weight_bytes(m, db=2) -> float:
    """Every parameter of the model (embeddings, tables, norms, biases)."""
    d, L = m["d_model"], m["n_layers"]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    n = matmul_params(m)                 # the tied table counted once
    if m["pos"] == "learned":
        n += m["pos_table"] * d
    n_norms = 2 if m["norm"] == "layernorm" else 1
    n += L * 2 * n_norms * d + n_norms * d
    if m["qkv_bias"]:
        n += L * (H + 2 * KV) * hd
    return float(db) * n


def attention_flops(m, B, T, S, causal) -> float:
    """Scores and values of every layer: 2·2·B·H·T·S'·hd, S' = S/2 when a
    prefix attends causally to itself."""
    s_eff = S * (0.5 if causal and T > 1 else 1.0)
    return m["n_layers"] * 4.0 * B * m["n_heads"] * T * s_eff * m["head_dim"]


def forward_flops(m, B, T, ctx, causal=True) -> float:
    """One forward of B rows of T tokens attending to ``ctx`` positions."""
    return 2.0 * B * T * matmul_params(m) + attention_flops(m, B, T, ctx,
                                                            causal)


def rank1_calls(m, rows) -> list[dict]:
    """The fused rank-1 matmuls of one perturbed forward of ``rows`` tokens:
    the layer matrices (``rank1_matmul``) and the tied head
    (``rank1_matmul_t``).  Each: x (M,K) @ (W (K,N) + s·u vᵀ)."""
    calls = [dict(kernel="rank1_matmul", M=rows, K=K, N=N,
                  count=m["n_layers"]) for _, K, N in matmuls(m)]
    calls.append(dict(kernel="rank1_matmul_t", M=rows, K=m["d_model"],
                      N=m["vocab"], count=1))
    return calls


def rank1_cost(c, clients, db=2):
    """(flops, bytes) of one call site over ``clients`` clients that share
    the weight: per client x·W and the rank-1 terms x·u, (xu)·vᵀ; the weight
    read once, each client's x read and y written once."""
    M, K, N = c["M"], c["K"], c["N"]
    flops = clients * (2.0 * M * K * N + 2.0 * M * K + 2.0 * M * N)
    byts = db * K * N + clients * (db * (M * K + M * N) + 4.0 * (K + N))
    return flops * c["count"], byts * c["count"]


def fold_leaves(m) -> list[tuple[int, int, int]]:
    """(instances, rows, cols) of every matrix leaf the SubCGE fold visits."""
    L, d = m["n_layers"], m["d_model"]
    out = [(L, K, N) for _, K, N in matmuls(m)]
    out.append((1, m["vocab"], d))
    if m["pos"] == "learned":
        out.append((1, m["pos_table"], d))
    return out


def subcge_leaf_cost(inst, n, k, r, db=2):
    """(flops, bytes) of ``W + U A Vᵀ`` on one leaf of ``inst`` instances of
    n×k: (U A) then (U A) Vᵀ and the add; W read and written once, U, V (f32)
    and each instance's A (f32) read once."""
    flops = inst * (2.0 * n * r * r + 2.0 * n * r * k + n * k)
    byts = inst * (2.0 * db * n * k + 4.0 * r * r) + 4.0 * r * (n + k)
    return flops, byts


def subcge_apply_cost(m, rank, epochs=1, db=2):
    """(flops, bytes) of the fold over every matrix leaf (``epochs`` subspaces
    fold as one of rank epochs·rank)."""
    parts = [subcge_leaf_cost(inst, n, k, rank * epochs, db)
             for inst, n, k in fold_leaves(m)]
    return sum(f for f, _ in parts), sum(b for _, b in parts)


def kv_bytes_per_position(m, db=2) -> float:
    return float(db) * 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"]


def least_seconds(flops, byts, peak) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops"], byts / peak["hbm_bytes_per_s"])


def train_step_flops(m, clients, rows_per_client, seq, rank) -> float:
    """Model FLOPs of one SeedFlood step: two perturbed forwards per client
    (with their rank-1 terms) and the fold."""
    fwd = clients * forward_flops(m, rows_per_client, seq, seq, causal=True)
    r1 = sum(clients * c["count"] * (2.0 * c["M"] * (c["K"] + c["N"]))
             for c in rank1_calls(m, rows_per_client * seq))
    return 2.0 * (fwd + r1) + subcge_apply_cost(m, rank)[0]


def decode_step_cost(m, batch, positions, db=2):
    """(flops, bytes) one decode step requires: ``batch`` tokens through
    every weight (read once), each attending to its own ``positions`` entry
    (keys and values of positions in use read once)."""
    flops = 2.0 * batch * matmul_params(m)
    flops += sum(4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * (p + 1)
                 for p in positions)
    byts = weight_bytes(m, db)
    byts += kv_bytes_per_position(m, db) * sum(p + 1 for p in positions)
    return flops, byts
