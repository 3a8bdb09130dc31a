"""run.program_arch checks an architecture layer by layer: multi-group MoE,
MLA and hybrid architectures pass against ``layers`` blocks written out from
their published numbers, one changed field is refused with its layer and
name, the dense configurations' blocks without ``layers`` still pass, and a
configuration naming a reference that does not exist is refused.  No
compile."""
import copy
import json
import os

import pytest

from chipbench import run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NO_LORA = {"window": None, "q_lora": 0, "kv_lora": 0, "rope_head_dim": 0,
           "v_head_dim": 0}


def attn(heads, kv, hd, **kw):
    return {"mixer": "attn", "n_heads": heads, "n_kv_heads": kv,
            "head_dim": hd, "qkv_bias": False, **NO_LORA, **kw}


def moe(experts, top_k, width, shared):
    return {"ffn": "moe", "n_experts": experts, "top_k": top_k,
            "d_ff_expert": width, "n_shared": shared}


def model(d, vocab, theta, layers):
    return {"d_model": d, "vocab": vocab, "norm": "rmsnorm", "pos": "rope",
            "rope_theta": theta, "act": "silu", "gated_mlp": True,
            "tie_embeddings": False, "layers": layers}


# arXiv:2405.04434: 60 layers at d5120, the first dense (ff 12288), then 59
# of 160 routed experts (ff 1536) top-6 and 2 shared; MLA in every layer
DSV2_MLA = attn(128, 128, 128, q_lora=1536, kv_lora=512, rope_head_dim=64,
                v_head_dim=128)
DSV2 = model(5120, 102_400, 1e4, [
    {"count": 1, **DSV2_MLA, "ffn": "dense", "d_ff": 12_288},
    {"count": 59, **DSV2_MLA, **moe(160, 6, 1536, 2)}])

# arXiv:2501.kimi2: 61 layers at d7168, 64 heads (kv 8), 384 experts top-8
# of ff 2048 and one shared
KIMI = model(7168, 163_840, 5e4, [
    {"count": 61, **attn(64, 8, 128), **moe(384, 8, 2048, 1)}])


def jamba_layer(i):
    """arXiv:2403.19887: a period of 8, attention first and Mamba (d_inner
    16384, state 16, conv 4) after; 16 experts top-2 on every odd layer, a
    dense ff 24576 on the others."""
    mixer = (attn(64, 8, 128) if i % 8 == 0 else
             {"mixer": "mamba", "d_inner": 16_384, "d_state": 16,
              "d_conv": 4, "dt_rank": 0})
    ffn = (moe(16, 2, 24_576, 0) if i % 2 else
           {"ffn": "dense", "d_ff": 24_576})
    return {"count": 1, **mixer, **ffn}


JAMBA = model(8192, 65_536, 1e4, [jamba_layer(i) for i in range(72)])

PUBLISHED = {"deepseek-v2-236b": DSV2, "kimi-k2-1t-a32b": KIMI,
             "jamba-1.5-large-398b": JAMBA}


def config(arch, m):
    return {"name": arch, "arch": arch, "model": m}


@pytest.mark.parametrize("arch", sorted(PUBLISHED))
def test_published_layers_pass(arch):
    got = run.program_arch(config(arch, PUBLISHED[arch]))
    assert got.name == arch
    assert sum(r["count"] for r in PUBLISHED[arch]["layers"]) == got.n_layers


@pytest.mark.parametrize("arch,run_idx,field,value,layer", [
    ("deepseek-v2-236b", 1, "n_experts", 64, 1),
    ("deepseek-v2-236b", 1, "top_k", 8, 1),
    ("deepseek-v2-236b", 1, "d_ff_expert", 1408, 1),
    ("deepseek-v2-236b", 1, "n_shared", 1, 1),
    ("deepseek-v2-236b", 0, "kv_lora", 256, 0),
    ("deepseek-v2-236b", 1, "kv_lora", 256, 1),
    ("deepseek-v2-236b", 0, "count", 2, 0),
    ("deepseek-v2-236b", 1, "count", 58, 1),
    ("jamba-1.5-large-398b", 8, "mixer", "mamba", 8),
    ("jamba-1.5-large-398b", 3, "n_experts", 8, 3),
    ("jamba-1.5-large-398b", 5, "count", 2, 5),
    ("kimi-k2-1t-a32b", 0, "top_k", 6, 0),
    ("kimi-k2-1t-a32b", 0, "mixer", "mamba", 0),
])
def test_one_changed_field_is_refused(arch, run_idx, field, value, layer):
    m = copy.deepcopy(PUBLISHED[arch])
    m["layers"][run_idx][field] = value
    with pytest.raises(run.BenchError) as e:
        run.program_arch(config(arch, m))
    msg = str(e.value)
    assert f"layer {layer} " in msg and f"{field}=" in msg, msg


def test_runs_missing_or_extra_are_refused():
    m = copy.deepcopy(DSV2)
    m["layers"].pop()
    with pytest.raises(run.BenchError, match=r"layer 1 \(run 1\): program "
                       r"has count=59, configuration states none"):
        run.program_arch(config("deepseek-v2-236b", m))
    m = copy.deepcopy(KIMI)
    m["layers"].append(dict(m["layers"][0], count=1))
    with pytest.raises(run.BenchError, match=r"layer 61 \(run 1\): program "
                       r"has count=0"):
        run.program_arch(config("kimi-k2-1t-a32b", m))


def test_a_field_left_out_or_added_is_refused():
    m = copy.deepcopy(DSV2)
    del m["layers"][0]["window"]
    with pytest.raises(run.BenchError, match="layer 0 .*window=None"):
        run.program_arch(config("deepseek-v2-236b", m))
    m = copy.deepcopy(KIMI)
    m["layers"][0]["d_ff"] = 18_432            # a dense width on an MoE layer
    with pytest.raises(run.BenchError, match="layer 0 .*d_ff='\\(none\\)'"):
        run.program_arch(config("kimi-k2-1t-a32b", m))


def test_whole_model_keys_and_dense_keys_beside_layers_are_refused():
    m = dict(DSV2, d_model=2048)
    with pytest.raises(run.BenchError, match="d_model=5120"):
        run.program_arch(config("deepseek-v2-236b", m))
    m = dict(DSV2, n_heads=128)
    with pytest.raises(run.BenchError, match="n_heads"):
        run.program_arch(config("deepseek-v2-236b", m))


@pytest.mark.parametrize("name", ["opt-1.3b", "qwen1.5-0.5b"])
def test_dense_configurations_still_pass(name):
    conf = json.load(open(os.path.join(HERE, "configs", name + ".json")))
    assert "layers" not in conf["model"]
    arch = run.program_arch(conf)
    assert arch.name == name and len(run.program_runs(arch)) == 1
    bad = dict(conf, model=dict(conf["model"], d_ff=1024))
    with pytest.raises(run.BenchError, match=r"layer 0 \(run 0\): program "
                       r"has d_ff="):
        run.program_arch(bad)


def test_a_missing_reference_is_refused():
    from chipbench.tests import cells
    ov = cells.overrides("train.opt-1.3b.c16")
    ov["config"] = dict(ov["config"], reference="no_such_family")
    with pytest.raises(run.BenchError,
                       match="chipbench/references/no_such_family.py"):
        run.make_ctx("train.opt-1.3b.c16", 1, 1.0, require_chip=False,
                     overrides=ov)
    conf = json.load(open(os.path.join(HERE, "configs", "opt-1.3b.json")))
    assert run.load_reference(conf).__name__ == "chipbench.references.decoder"
    for bad in ("../run", None):
        with pytest.raises(run.BenchError, match="no reference"):
            run.load_reference(dict(conf, reference=bad))
