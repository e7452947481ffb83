"""The mellum2-12b-a2.5b configuration and what the benchmark reads of it:
the program's ``ModelConfig`` from its file, its weight tree against the
program's layout, the reference's work counts, the two expert readers on a
stretch worked out by hand, and the ``code-longctx`` mix."""
import dataclasses
import json
import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import harness, layerstats, loadgen, moe_stats, references, \
    weights

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAME = "mellum2-12b-a2.5b"


def _cfg():
    return harness.load_config(NAME)


def test_model_config_field_by_field():
    """The served model is the program's published Mellum2 at one chip's
    share: 16 of the router's 64 experts held, from expert 0; the 28
    published layer types; every weight in bf16, the router's too, as a
    served checkpoint stores them."""
    from repro.configs import get_config
    got = harness.model_config(_cfg())
    want = dataclasses.replace(
        get_config(NAME), n_experts=16, n_routed_experts=64, expert_offset=0,
        layer_types=tuple(["sliding_attention"] * 3 + ["full_attention"])
        * 7, router_dtype="bfloat16")
    assert got == want
    assert (got.n_layers, got.d_model, got.n_heads, got.n_kv_heads,
            got.head_dim, got.d_ff, got.vocab_size) == \
        (28, 2304, 32, 4, 128, 896, 98304)
    assert (got.experts_per_tok, got.routed_experts, got.sliding_window) \
        == (8, 64, 1024)
    assert [got.layer_window(i) for i in range(8)] == [1024] * 3 + [0] \
        + [1024] * 3 + [0]
    assert (got.rope_theta, got.yarn_factor, got.yarn_original_max_position,
            got.yarn_beta_fast, got.yarn_beta_slow,
            got.yarn_attention_factor) == (500000.0, 16.0, 8192, 32.0, 1.0,
                                           1.2772588722239782)
    assert (got.split.split_at, got.split.d_bottleneck,
            got.split.quant_bits) == (14, 512, 8)
    assert not got.tie_embeddings and not got.qkv_bias


def test_file_holds_the_published_config():
    """Every key of the published config is in the file, equal to the
    program's own fields where they share a meaning; only ``n_experts``
    is cut, as ``reduced`` and BENCHMARK.json say."""
    c = _cfg()
    m = harness.model_config(c)
    assert (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["moe_intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"]) == (m.n_layers, m.d_model, m.n_heads,
                                          m.n_kv_heads, m.d_ff,
                                          m.routed_experts,
                                          m.experts_per_tok)
    yarn = c["rope_parameters"]["full_attention"]
    assert (yarn["factor"], yarn["original_max_position_embeddings"],
            yarn["attention_factor"]) == (m.yarn_factor,
                                          m.yarn_original_max_position,
                                          m.yarn_attention_factor)
    assert list(c["reduced"]) == ["n_experts"]
    assert c["reduced"]["n_experts"]["published"] == c["num_experts"] == 64
    entry = {x["name"]: x for x in harness.load_benchmark()["configs"]}[NAME]
    assert entry["reduced"] == ["n_experts"]
    assert entry["source"] == c["source"]


def test_weight_tree_is_the_program_layout():
    c = _cfg()
    from repro.core import split as SP
    program = jax.eval_shape(
        lambda k: SP.init_split_params(k, harness.model_config(c)),
        jax.random.PRNGKey(0))
    made = jax.eval_shape(
        lambda: weights.make(references.load("mellum_split").shapes(c), 1))
    weights.check_layout(made, program)
    n = sum(int(np.prod(a.shape)) * a.dtype.itemsize
            for a in jax.tree.leaves(made))
    assert 7.6e9 < n < 7.7e9                 # 7.65 GB of bf16 on the chip


def test_work_counts():
    c = _cfg()
    d, f = 2304, 896
    # attention q, o 2 x 2304 x 4096, k and v 2 x 2304 x 512; the router
    # 2304 x 64; 8 of 64 experts a token, 16 held: 2 experts' 3 d f
    per_layer = 2 * d * 4096 + 2 * d * 512 + d * 64 + 2 * 3 * d * f
    assert layerstats.matmul_params(c) == 28 * per_layer
    # 7 full layers over all rows, 21 sliding ones over at most 1,024
    per_row = 4 * 32 * 128
    assert layerstats.attention_flops(c, 3000) == \
        per_row * (7 * 3000 + 21 * 1024)
    assert layerstats.attention_flops(c, 500) == per_row * 28 * 500


# ---------------------------------------------------------------------------
# the cell's readers, on a stretch worked out by hand
# ---------------------------------------------------------------------------

CFG = {"d_model": 256, "d_ff": 128}


def _session(prompt, admitted, n_tokens, rows, touched):
    return SimpleNamespace(
        request=SimpleNamespace(rid=admitted, prompt_len=prompt),
        admitted_tick=admitted, tokens=[0] * n_tokens, moe_rows=rows,
        moe_touched=touched)


def _ctx(red, sessions):
    """Ticks 10 and 11 in the stretch. A (admitted at 8, 6 tokens) decodes
    ticks 8..12 with rows 3, 4, 5, 6, 7; B (admitted at 11, 3 tokens)
    decodes ticks 11, 12 with rows 2, 9. Touched: 4 at tick 10, 6 at
    tick 11."""
    return {"cfg": CFG, "sessions": sessions, "device_kind": "TPU v5 lite",
            "stretch": {"on": {"tick": 10, "decode_ticks": 100},
                        "off": {"tick": 12, "decode_ticks": 102}},
            "trace": red, "kernel_work": harness.kernel_work}


def _sessions():
    return [_session(20, 8, 6, [3, 4, 5, 6, 7], [5, 5, 4, 6, 7]),
            _session(5, 11, 3, [2, 9], [6, 7])]


def test_stretch_expert_ticks():
    assert moe_stats.stretch_ticks(_ctx({}, _sessions())) == \
        {10: (5, 4), 11: (6 + 2, 6)}


def test_moe_gmm_roofline_from_the_kernel_time():
    # bytes: touched x 3 d f x 2 + rows x d x (2 + 4); flops 6 d f rows.
    # Both ticks are bound by bandwidth at 819 GB/s.
    need = sum((t * 3 * 256 * 128 * 2 + r * 256 * 6) / 819e9
               for r, t in ((5, 4), (8, 6)))
    red = {"kernels": {}, "breakdown": {"device_ops": [
        ["paged_attention", 1.0], ["moe_gmm", 4e-6]]}}
    got = harness.metric_reader("moe_gmm_roofline").read(
        _ctx(red, _sessions()))
    assert got == pytest.approx(100 * need / 4e-6)
    assert 0 < got <= 100


def test_moe_ms_per_tick():
    red = {"kernels": {}, "breakdown": {"device_ops": [["moe_gmm", 0.03]]}}
    got = harness.metric_reader("moe_ms_per_tick").read(
        _ctx(red, _sessions()))
    assert got == pytest.approx(1e3 * 0.03 / 2)


def test_decode_tick_ms_code():
    """The cell's decode tick: the window program's device time over the
    stretch's decode ticks, and nothing where the trace lacks it."""
    rd = harness.metric_reader("decode_tick_ms.code").read
    red = {"modules": {"mixed_step_dev": 0.222}, "kernels": {}}
    assert rd(_ctx(red, _sessions())) == pytest.approx(1e3 * 0.222 / 2)
    assert rd(_ctx({"modules": {}, "kernels": {}}, _sessions())) is None
    assert rd(dict(_ctx(red, _sessions()), stretch=None)) is None


def test_readers_find_nothing_in_a_program_without_counters():
    """A program whose sessions carry no expert counts (one that predates
    them), or a trace without the kernel: both readers give None, and do
    not raise."""
    bare = [SimpleNamespace(request=SimpleNamespace(rid=0, prompt_len=20),
                            admitted_tick=8, tokens=[0] * 6)]
    red = {"kernels": {}, "breakdown": {"device_ops": [["moe_gmm", 0.03]]}}
    rd = lambda n, ctx: harness.metric_reader(n).read(ctx)
    assert rd("moe_gmm_roofline", _ctx(red, bare)) is None
    none = {"kernels": {}, "breakdown": {"device_ops": [["fusion", 1.0]]}}
    assert rd("moe_gmm_roofline", _ctx(none, _sessions())) is None
    assert rd("moe_ms_per_tick", _ctx(none, _sessions())) is None
    assert rd("moe_ms_per_tick", dict(_ctx(red, bare), stretch=None)) is None


# ---------------------------------------------------------------------------
# the traffic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5, 4_170_000_123])
def test_code_longctx_sends_the_same_work_on_every_seed(seed):
    mix = loadgen.load_mix("code-longctx")
    base = loadgen.build(mix, 7, 51.0, 98304)
    items = loadgen.build(mix, seed, 51.0, 98304)
    assert len(items) == 96 == mix["replay_size"]
    assert sorted(len(i.prompt) for i in items) == \
        sorted(len(i.prompt) for i in base)
    assert sorted(i.max_new for i in items) == sorted(i.max_new for i in base)
    assert min(len(i.prompt) for i in items) >= 1024
    assert max(len(i.prompt) for i in items) <= 4096
    assert loadgen.max_context(mix) == 4096 + 1024 - 1


def test_code_longctx_fits_its_arena():
    """The mix's clients at once reserve no more pages than the arena
    holds for the seed's first contexts, and the longest request fits
    alone."""
    mix = loadgen.load_mix("code-longctx")
    e = mix["engine"]
    per = -(-loadgen.max_context(mix) // e["page_len"])
    assert per <= e["n_pages"] and mix["clients"] <= e["n_slots"]
    for seed in (1, 2, 3):
        items = loadgen.build(mix, seed, 51.0, 98304)
        first = items[:mix["clients"]]
        need = sum(-(-(len(i.prompt) + i.max_new) // e["page_len"])
                   for i in first)
        assert need <= e["n_pages"]


def test_limits_file_names_only_compared_gaps():
    with open(os.path.join(harness.HERE, "limits",
                           NAME + ".code-longctx.json")) as f:
        lim = json.load(f)
    assert set(lim) <= {"max_logit_gap", "mean_logit_gap"} and lim
