"""The per-layer readers against a traced stretch worked out by hand.

Model: 2 layers, d 128, 4 heads of 32 (2 kv), d_ff 384, vocab 512. The
stretch covers engine ticks 10 and 11. Session A (prompt 20, admitted at
tick 8, 10 tokens) decodes at ticks 8..16 with contexts 21..29, so 23 at
tick 10 and 24 at tick 11. Session B (prompt 5, admitted at tick 11, 3
tokens) is prefilled in the stretch and decodes at ticks 11 and 12, with
context 6 at tick 11.
"""
from types import SimpleNamespace

import pytest

from bench import harness, layerstats

CFG = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
       "head_dim": 32, "d_ff": 384, "vocab_size": 512}


def _session(rid, prompt, admitted, n_tokens):
    req = SimpleNamespace(rid=rid, prompt_len=prompt, t_admit=0.0)
    return SimpleNamespace(request=req, admitted_tick=admitted,
                           tokens=[0] * n_tokens)


def _ctx(kernels=None, modules=None, busy=1e-6):
    on = {"tick": 10, "decode_ticks": 100, "prefill_tokens": 1000}
    off = {"tick": 12, "decode_ticks": 102, "prefill_tokens": 1005}
    return {"cfg": CFG, "mix": {"loop": "open"}, "n_slots": 4,
            "base": {"t": 0.0, "decode_ticks": 0, "slot_ticks": 0},
            "window": {"t": 10.0, "decode_ticks": 50, "slot_ticks": 150},
            "sessions": [_session(0, 20, 8, 10), _session(1, 5, 11, 3)],
            "due": {}, "device_kind": "TPU v5 lite", "n_devices": 1,
            "stretch": {"on": on, "off": off},
            "trace": {"window_s": 1e-6, "busy_s": busy,
                      "kernels": kernels or {}, "modules": modules or {}},
            "kernel_work": harness.kernel_work}


def test_stretch_ticks_and_prompts():
    ctx = _ctx()
    assert dict(layerstats.stretch_ticks(ctx)) == {10: [23], 11: [24, 6]}
    assert layerstats.stretch_prompts(ctx) == [5]


def test_paged_attention_roofline():
    # per layer: tick 10 reads 23 rows: 2*23*2*32*2 = 5,888 B of K/V and
    # 512 B of q and o; tick 11 reads 30 rows: 7,680 + 1,024 B. Both are
    # bound by bandwidth: (6,400 + 8,704) B x 2 layers / 819 GB/s.
    need = (6400 + 8704) * 2 / 819e9
    ctx = _ctx(kernels={"paged_attention": 1e-6})
    got = harness.metric_reader("paged_attention_roofline").read(ctx)
    assert got == pytest.approx(100 * need / 1e-6)


def test_decode_tail_roofline():
    # one call a tick over its live rows (1, then 2): the head is read once
    need = sum((128 * 512 * 2 + n * (128 * 2 + 4) + 128 * 2) / 819e9
               for n in (1, 2))
    ctx = _ctx(kernels={"decode_tail": 2e-6})
    got = harness.metric_reader("decode_tail_roofline").read(ctx)
    assert got == pytest.approx(100 * need / 2e-6)


def test_step_mfu():
    # layer parameters 2 x (2*128*128 + 2*128*64 + 3*128*384) = 393,216;
    # head 128 x 512 = 65,536. Three decoded tokens: 3 x 2 x 458,752
    # = 2,752,512 plus attention 4*4*32 x 2 layers x (23 + 24 + 6) rows
    # = 54,272. Prefill of 5 tokens: 2 x 393,216 x 5 = 3,932,160, one head
    # row 131,072, causal attention 1,024 x 15 = 15,360.
    flops = 2752512 + 54272 + 3932160 + 131072 + 15360
    assert flops == 6885376
    got = harness.metric_reader("step_mfu").read(_ctx())
    assert got == pytest.approx(100 * flops / (1e-6 * 197e12))


def test_step_and_engine_readers():
    ctx = _ctx(modules={"mixed_step_dev": 0.1, "mixed_prefill": 0.02},
               busy=0.75e-6)
    rd = lambda n: harness.metric_reader(n).read(ctx)
    assert rd("decode_tick_ms.chat") == pytest.approx(50.0)    # 0.1 s / 2
    assert rd("decode_tick_ms.longctx") == pytest.approx(50.0)
    assert rd("prefill_ms_per_ktok") == pytest.approx(4000.0)  # 20 ms / 5
    assert rd("device_idle_share") == pytest.approx(25.0)
    assert rd("slot_occupancy") == pytest.approx(75.0)         # 150 / 200


def test_readers_find_nothing_without_a_stretch():
    ctx = _ctx()
    ctx["stretch"] = None
    for name in ("paged_attention_roofline", "decode_tail_roofline",
                 "step_mfu", "decode_tick_ms.chat", "prefill_ms_per_ktok"):
        assert harness.metric_reader(name).read(ctx) is None
    ctx = _ctx()             # kernels that did not run: no share, never 0
    assert harness.metric_reader("paged_attention_roofline").read(ctx) is None


def test_slot_occupancy_before_the_profiler_starts():
    ctx = _ctx()
    ctx["stretch"]["pre"] = {"decode_ticks": 20, "slot_ticks": 40}
    assert harness.metric_reader("slot_occupancy").read(ctx) == 50.0
