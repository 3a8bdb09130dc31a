"""Reduced stand-ins of the cells for runs on the CPU: the same windows,
references and checks at widths a test can hold."""
RED_OPT = {"d_model": 64, "n_layers": 1, "n_heads": 4, "n_kv_heads": 4,
           "head_dim": 16, "d_ff": 128, "vocab": 256, "norm": "layernorm",
           "norm_eps": 1e-5, "pos": "learned", "pos_table": 4096,
           "rope_theta": 0, "act": "relu", "gated_mlp": False,
           "qkv_bias": True, "tie_embeddings": True}
RED_QWEN = {**RED_OPT, "norm": "rmsnorm", "norm_eps": 1e-6, "pos": "rope",
            "pos_table": 0, "rope_theta": 1e6, "act": "silu",
            "gated_mlp": True}
#: RED_OPT with its layer stated as a run of ``layers``, as a configuration
#: that is not one scanned dense group states them; test_discovery's new
#: configuration runs it under a reference module of its own
RED_OPT_LAYERS = {
    **{k: v for k, v in RED_OPT.items()
       if k not in ("n_heads", "n_kv_heads", "head_dim", "qkv_bias", "d_ff")},
    "layers": [{"count": 1, "mixer": "attn", "n_heads": 4, "n_kv_heads": 4,
                "head_dim": 16, "qkv_bias": True, "window": None,
                "q_lora": 0, "kv_lora": 0, "rope_head_dim": 0,
                "v_head_dim": 0, "ffn": "dense", "d_ff": 128}]}

#: limits for float32 runs at these widths, where program and reference
#: agree to rounding (~1e-5)
TRAIN_LIMITS = {"loss_gap": 1e-3, "first_update_gap": 1e-2,
                "change_gap": 1e-2}
SERVE_LIMITS = {"logit_gap": 1e-3, "fold_change_gap": 1e-3}


#: every cell with files under chipbench/, listed in BENCHMARK.json or not
CONFIGS = {"train.opt-1.3b.c16": "opt-1.3b",
           "serve.opt-1.3b.live": "opt-1.3b",
           "train.qwen1.5-0.5b.c16": "qwen1.5-0.5b"}


def entry(cell: str) -> dict:
    """The cell's BENCHMARK.json entry (its traffic file has its name)."""
    return {"name": cell, "config": CONFIGS[cell], "traffic": cell,
            "chips": 1}


def overrides(cell: str, backend: str = "interpret", dtype="float32"):
    return {**reduced(cell, backend, dtype), "entry": entry(cell)}


def reduced(cell: str, backend: str, dtype) -> dict:
    if cell.startswith("serve."):
        return {"config": {"arch_reduced": {"d_model": 64}, "model": RED_OPT,
                           "dtype": dtype},
                "workload": {"max_batch": 4, "max_seq": 48,
                             "prompt_lens": [16, 32], "sessions_per_len": 2,
                             "msgs_per_round": 4, "lr": 1e-2,
                             "warm_fold_k": [4, 8, 16, 32, 64, 128],
                             "kernel_backend": backend,
                             "limits": SERVE_LIMITS}}
    model = RED_QWEN if "qwen" in cell else RED_OPT
    return {"config": {"arch_reduced": {"d_model": 64}, "model": model,
                       "dtype": dtype},
            "workload": {"clients": 2, "seqs_per_client": 2, "seq_len": 16,
                         "feed_batches": 4, "lr": 1e-2,
                         "kernel_backend": backend, "limits": TRAIN_LIMITS}}

#: limits for bfloat16 runs at these widths, between the program's readings
#: (loss ≤ 0.002, first update ≤ 0.016, change ≤ 0.08, logits ≤ 0.0007 on
#: the CPU) and the fp8 control's (first update ≥ 0.38, logits ≥ 0.014)
BF16_LIMITS = {"loss_gap": 0.01, "first_update_gap": 0.1, "change_gap": 0.3,
               "logit_gap": 0.005, "fold_change_gap": 0.01}
