"""A configuration brings its model by name: the program's ``ModelConfig``
from every key of its file, the weight tree, the reference and the work
counts from the reference module it names. For qwen2.5-3b each is pinned
to what the benchmark computed before the reference became a module of
its own (``bench/reference.py``, its ``weights.shapes`` and
``layerstats`` counts), kept here as test-side copies and recorded
numbers."""
import gzip
import json
import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import harness, layerstats, references, trace_reduce, weights

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _data(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _earlier_shapes(cfg):
    """The weight tree as ``weights.shapes`` built it for every
    configuration before the reference module owned it."""
    L, d, V = cfg["n_layers"], cfg["d_model"], cfg["vocab_size"]
    nq, nkv, hd, ff = (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
                       cfg["d_ff"])

    def dense(i, o, bias=False):
        p = {"w": ("normal", (L, i, o), i ** -0.5)}
        if bias:
            p["b"] = ("bias", (L, o), 0.1)
        return p

    bias = cfg["qkv_bias"]
    tree = {
        "embed": {"table": ("normal", (V, d), 1.0)},
        "layers": {
            "norm1": {"scale": ("scale", (L, d), 0.1)},
            "mix": {"wq": dense(d, nq * hd, bias),
                    "wk": dense(d, nkv * hd, bias),
                    "wv": dense(d, nkv * hd, bias), "wo": dense(nq * hd, d)},
            "norm2": {"scale": ("scale", (L, d), 0.1)},
            "mlp": {"w_gate": dense(d, ff), "w_up": dense(d, ff),
                    "w_down": dense(ff, d)},
        },
        "final_norm": {"scale": ("scale", (d,), 0.1)},
        "bneck_modes": tuple(
            {"norm": {"scale": ("scale", (d,), 0.1)},
             "down": {"w": ("normal", (d, w), d ** -0.5)},
             "up": {"w": ("normal", (w, d), w ** -0.5)}}
            for w in ([cfg["d_bottleneck"]] if cfg["d_bottleneck"] else [])),
    }
    if not cfg["tie_embeddings"]:
        tree["lm_head"] = {"w": ("normal", (d, V), d ** -0.5)}
    return tree


def test_qwen_model_config_is_the_one_written_out():
    from repro.configs.base import ModelConfig, SplitConfig
    got = harness.model_config(harness.load_config("qwen2.5-3b"))
    want = ModelConfig(
        name="qwen2.5-3b", arch_type="dense", n_layers=36, d_model=2048,
        n_heads=16, n_kv_heads=2, d_ff=11008, vocab_size=151936,
        head_dim=128, qkv_bias=True, rope_theta=1000000.0, norm="rmsnorm",
        act="silu", tie_embeddings=False, dtype="bfloat16",
        split=SplitConfig(split_at=18, d_bottleneck=512, quant_bits=8),
        source="https://huggingface.co/Qwen/Qwen2.5-3B/blob/main/config.json")
    assert got == want


@pytest.mark.parametrize("name", ["qwen2.5-3b", "tiny"])
def test_weight_tree_is_the_earlier_one(name):
    cfg = (harness.load_config(name) if name != "tiny"
           else _data("tiny.json"))
    ref = references.load(cfg["reference"])
    assert ref.shapes(cfg) == _earlier_shapes(cfg)


def test_tiny_weights_are_bit_identical():
    cfg = _data("tiny.json")
    seed = 2 ** 31 + 11
    got = weights.make(references.load("llama_split").shapes(cfg), seed)
    want = weights.make(_earlier_shapes(cfg), seed)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_moved_reference_gives_the_recorded_logits():
    """``tiny-reference-logits.json`` holds what ``bench/reference.py``
    gave on the tiny config (weights from seed 2**31 + 11, two rows of 256
    tokens from ``default_rng(0)``, mode 1 from position 100 of row 0),
    every 17th position."""
    rec = _data("tiny-reference-logits.json")
    cfg = _data("tiny.json")
    ref = references.load(cfg["reference"])
    params = weights.make(ref.shapes(cfg), rec["seed"])
    rng = np.random.default_rng(rec["inputs_rng"])
    toks = rng.integers(0, 512, (2, 256)).astype(np.int32)
    modes = np.zeros((2, 256), np.int32)
    modes[0, 100:] = 1
    served = rng.integers(0, 512, (2, 256)).astype(np.int32)
    pos = rec["positions"]
    best, got, _, am = ref.run(params, cfg, toks, modes, served)
    np.testing.assert_allclose(best[:, pos], rec["best"], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got[:, pos], rec["got"], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(am[:, pos], rec["argmax"])
    _, _, fp8, _ = ref.run(params, cfg, toks, modes, served, prec="fp8")
    np.testing.assert_allclose(fp8[:, pos], rec["got_fp8"], rtol=1e-5,
                               atol=1e-5)


def test_qwen_work_counts():
    cfg = harness.load_config("qwen2.5-3b")
    # per layer: q and o 2 x 2048 x 2048, k and v 2 x 2048 x 256, the MLP
    # 3 x 2048 x 11008
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008
    assert layerstats.matmul_params(cfg) == 36 * per_layer == 2_774_532_096
    assert layerstats.attention_flops(cfg, 1000) == 4 * 16 * 128 * 1000 * 36


def _chip_ctx(cfg):
    with gzip.open(os.path.join(DATA, "chip_trace_chat.textproto.gz"),
                   "rt") as f:
        red = trace_reduce.reduce_data(ProfileData.from_text_proto(f.read()))

    def sess(rid, p, a, n):
        return SimpleNamespace(
            request=SimpleNamespace(rid=rid, prompt_len=p), admitted_tick=a,
            tokens=[0] * n)
    return {"cfg": cfg, "mix": {"loop": "open"}, "n_slots": 16,
            "base": {"t": 0.0, "decode_ticks": 0, "slot_ticks": 0},
            "window": {"t": 51.0, "decode_ticks": 900, "slot_ticks": 5000},
            "sessions": [sess(0, 1020, 90, 40), sess(1, 64, 101, 20),
                         sess(2, 2048, 50, 300), sess(3, 500, 102, 5),
                         sess(4, 1500, 99, 3), sess(5, 300, 10, 60)],
            "due": {}, "device_kind": "TPU v5 lite", "n_devices": 1,
            "stretch": {
                "pre": {"t": 20.0, "decode_ticks": 400, "slot_ticks": 2000},
                "on": {"t": 20.1, "tick": 100, "decode_ticks": 400,
                       "prefill_tokens": 0},
                "off": {"t": 20.22, "tick": 103, "decode_ticks": 403,
                        "prefill_tokens": 564}},
            "trace": red, "kernel_work": harness.kernel_work}


def test_readers_read_as_before_on_the_recorded_chip_trace():
    """The stored chip trace with six qwen2.5-3b sessions over ticks
    100-102: the numbers the readers gave before the work counts moved into
    the reference module."""
    ctx = _chip_ctx(harness.load_config("qwen2.5-3b"))
    rd = lambda n: harness.metric_reader(n).read(ctx)
    assert rd("step_mfu") == pytest.approx(8.207061636548223, rel=1e-12)
    assert rd("paged_attention_roofline") == pytest.approx(
        1.9815048670387037, rel=1e-12)
    assert rd("decode_tail_roofline") == pytest.approx(60.807775343737816,
                                                       rel=1e-12)


def test_expert_keys_reach_the_model_config():
    cfg = harness.model_config(_data("tiny-mixtral.json"))
    assert (cfg.n_experts, cfg.experts_per_tok, cfg.sliding_window) == \
        (4, 2, 64)
    assert cfg.is_moe and cfg.arch_type == "moe"


def test_list_keys_become_tuples():
    c = dict(_data("tiny.json"), block_pattern=["attn", "attn"])
    assert harness.model_config(c).block_pattern == ("attn", "attn")


@pytest.mark.parametrize("key", ["n_expert", "num_experts_per_tok"])
def test_an_unknown_key_raises(key):
    c = dict(_data("tiny-mixtral.json"), **{key: 8})
    with pytest.raises(ValueError, match=key):
        harness.model_config(c)


def test_an_unknown_reference_raises():
    c = dict(_data("tiny.json"), reference="no_such_model")
    with pytest.raises(ValueError, match="no_such_model"):
        harness.model_config(c)
