"""Mellum2-style serving on the CPU at a reduced shape, in float32: the
held-experts MoE layer, per-layer sliding windows and YaRN rotary tables,
the paged engine that serves them, and its expert counters.

The shape is ``mellum2-12b-a2.5b``'s reduced preset: 8 layers (two periods
of three sliding layers and one full layer), d 256, 8 query and 2 kv heads
of width 32, window 16, YaRN over an original length of 64; 16 routed
experts of width 128, top-4, of which 4 are held (ids 4-7) where a test
cuts the layer to one chip's share.

The plain reference is the benchmark's (``bench/references/mellum_split.py``),
which reads the published config's keys and nothing of the program.
"""
import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import split as SP
from repro.kernels import ref as kref
from repro.kernels.paged_attention import paged_attention
from repro.models import moe as MOE
from repro.models import transformer as T
from repro.models.layers import rope_inv_freq, yarn_attention_factor
from repro.serving import ContinuousBatchingEngine, Request, Telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench.references import mellum_split as REF  # noqa: E402

HELD = 4          # experts held in the cut shape (of 16)
E0 = 4            # the first held expert


def _cfg(held: bool = True):
    base = dataclasses.replace(get_reduced("mellum2-12b-a2.5b"),
                               dtype="float32")
    if not held:
        return base
    return dataclasses.replace(base, n_experts=HELD, n_routed_experts=16,
                               expert_offset=E0)


def _published(cfg):
    """The published-config keys the reference reads, for ``cfg``."""
    L = cfg.n_layers
    return {
        "model_type": "mellum", "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False,
        "use_sliding_window": True, "max_window_layers": 0,
        "mlp_layer_types": ["sparse"] * L,
        "layer_types": [cfg.layer_types[i % len(cfg.layer_types)]
                        for i in range(L)],
        "num_hidden_layers": L, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size, "moe_intermediate_size": cfg.d_ff,
        "num_experts": cfg.routed_experts,
        "num_experts_per_tok": cfg.experts_per_tok, "norm_topk_prob": True,
        "rms_norm_eps": 1e-6, "sliding_window": cfg.sliding_window,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": cfg.yarn_factor,
                "original_max_position_embeddings":
                    cfg.yarn_original_max_position,
                "beta_fast": cfg.yarn_beta_fast,
                "beta_slow": cfg.yarn_beta_slow},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.rope_theta}},
        "n_experts": cfg.n_experts, "expert_offset": cfg.expert_offset,
        "split_at": cfg.split.split_at, "d_bottleneck": 0, "quant_bits": 8,
    }


def _ref_logits(params, cfg, tokens):
    """The reference's full forward [n, T, V], float32 at HIGHEST: its own
    blocks and rotary tables, then the final norm and head."""
    c = _published(cfg)
    p = REF._published(c)
    ck = tuple(sorted({"nq": p["nq"], "nkv": p["nkv"], "hd": p["hd"],
                       "eps": p["eps"], "k": p["k"], "R": p["R"],
                       "held": p["held"], "norm_topk": True}.items()))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(
            jnp.float32)
        for li, kind in enumerate(c["layer_types"]):
            inv, scale = REF.rope_table(c, kind)
            x = REF._block(x, params["layers"], jnp.int32(li),
                           jnp.asarray(inv, jnp.float32), jnp.float32(scale),
                           window=(p["window"] if kind == "sliding_attention"
                                   else 0), cfg=ck, prec="f32",
                           qb=tokens.shape[1])
        h = REF._rms(x, params["final_norm"]["scale"], p["eps"])
        return REF._mm(h, params["lm_head"]["w"], "f32")


def _skew_router(params, expert: int, gain: float):
    """Scale every layer's router column ``expert`` by ``gain``: its logit
    spreads wider, so it is among the top k for far more tokens than the
    others (uneven rows per expert)."""
    w = params["layers"]["mlp"]["router"]["w"]
    params["layers"]["mlp"]["router"]["w"] = w.at[:, :, expert].multiply(
        gain)
    return params


# ---------------------------------------------------------------------------
# (a) prefill, then decode through the paged cache, against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("skew", [False, True], ids=["even", "skewed"])
def test_paged_prefill_then_decode_match_reference(skew):
    """Prefill a 40-token prompt (two rows, one right-padded) into the
    paged arena, decode 8 more tokens through it, and compare every
    position's logits with the reference's full forward over the same
    tokens. ``skewed`` widens one held expert's router logits so that it
    takes about 25 of a 40-token row's 160 candidate rows: uneven groups,
    and twice what the old capacity layer kept of one expert in a row
    (1.25 k S / E = 12).

    Tolerance 2e-4 absolute on logits of magnitude up to ~10: both sides
    are float32 with matmuls at HIGHEST, and differ only in summation order
    (blocked online softmax, grouped expert rows, paged gathers); a
    routing difference would move a logit by ~1e-1, and the same forward
    in bfloat16 moves them by ~1e-2."""
    cfg = _cfg()
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    if skew:
        params = _skew_router(params, E0 + 1, 4.0)
    B, P, n_dec = 2, 40, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, P + n_dec), 0,
                              cfg.vocab_size)
    lengths = jnp.array([P, P - 9], jnp.int32)
    plen, nb = 8, 8
    arena = T.init_paged_state(cfg, 1 + B * nb, plen)
    bt = 1 + jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    prompt = jnp.where(jnp.arange(P)[None] < lengths[:, None], toks[:, :P], 0)
    logits, arena = jax.jit(lambda p, t, a: T.prefill(
        p, t, cfg, a, lengths=lengths, block_table=bt))(params, prompt, arena)
    got = {0: logits[:, 0]}
    step = jax.jit(lambda p, t, a, pos: T.decode_step(
        p, t, a, pos, cfg, block_table=bt))
    pos = lengths
    for j in range(1, n_dec + 1):
        tok = jnp.take_along_axis(toks, pos[:, None], axis=1)
        lg, arena = step(params, tok, arena, pos)
        got[j] = lg[:, 0]
        pos = pos + 1
    for b in range(B):
        L_b = int(lengths[b])
        seq = toks[b:b + 1, :L_b + n_dec]
        want = _ref_logits(params, cfg, seq)[0]
        for j in range(n_dec + 1):
            np.testing.assert_allclose(np.asarray(got[j][b]),
                                       np.asarray(want[L_b - 1 + j]),
                                       rtol=0, atol=2e-4)


def test_windows_and_yarn_change_the_logits():
    """The comparison above would not pass if the program ignored the
    windows or YaRN: the reference's logits move by far more than its
    tolerance when every layer is full attention with plain rotary
    tables."""
    cfg = _cfg()
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 48), 0,
                              cfg.vocab_size)
    plain = dataclasses.replace(cfg, layer_types=(), sliding_window=0,
                                yarn_factor=0.0)
    a, _ = T.forward(params, toks, cfg)
    b, _ = T.forward(params, toks, plain)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-2


# ---------------------------------------------------------------------------
# the held-experts layer: dropless, and its shares sum to the uncut layer
# ---------------------------------------------------------------------------

def _dense_moe(p, x, k):
    """Every expert on every token, weighted by the renormalised top-k
    gate (zero where not chosen): the uncut layer."""
    R = p["router"]["w"].shape[1]
    gates, idx = MOE.route(p["router"]["w"], x, k)
    g = jnp.sum(jax.nn.one_hot(idx, R) * gates[..., None], axis=-2)
    out = 0.0
    for e in range(R):
        a = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
        out = out + g[:, e:e + 1] * (a @ p["w_down"][e])
    return out


def _layer(seed=0, R=16, d=256, f=128):
    return MOE.moe_init(jax.random.PRNGKey(seed), d, f, R,
                        dtype=jnp.float32)


def _share(p, e0, n):
    return {"router": p["router"],
            **{w: p[w][e0:e0 + n] for w in ("w_gate", "w_up", "w_down")}}


@pytest.mark.parametrize("tokens", [3, 64, 8192],
                         ids=["decode", "prefill", "prefill-chunked"])
def test_shares_sum_to_the_uncut_layer(tokens):
    """The four shares of a 16-expert layer (4 experts each, one chip of
    4-way expert parallelism apiece) add up to the uncut layer, and the
    uncut layer served whole (all 16 held) is the dense reference. 8,192
    tokens of top-4 go through in two chunks (``moe.CHUNK_ROWS``).
    Tolerance 1e-5: one float32 summation order against another."""
    p = _layer()
    x = jax.random.normal(jax.random.PRNGKey(3), (tokens, 256), jnp.float32)
    k = 4
    assert (tokens * k > MOE.CHUNK_ROWS) == (tokens == 8192)
    whole, _ = MOE.moe_held_apply(p, x, k=k)
    parts = [MOE.moe_held_apply(_share(p, e0, 4), x, k=k, e0=e0)[0]
             for e0 in range(0, 16, 4)]
    dense = _dense_moe(p, x, k)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(dense),
                               rtol=0, atol=1e-5)


def test_no_row_is_dropped_far_above_the_old_capacity():
    """A 256-token prefill with one expert taking most tokens: the old
    capacity layer (``moe_apply``, 1.25 k S / E = 80 rows an expert)
    drops rows, the held layer computes every one (the dense reference),
    and a held expert no token chose is left out, not misread."""
    p = _layer(seed=1)
    # every token shares a direction; expert 5's router column points along
    # it and expert 6's against it
    x = jax.random.normal(jax.random.PRNGKey(4), (256, 256), jnp.float32) \
        + 2.0
    p["router"]["w"] = p["router"]["w"].at[:, 5].add(0.05)
    p["router"]["w"] = p["router"]["w"].at[:, 6].add(-0.05)
    k = 4
    _, idx = MOE.route(p["router"]["w"], x, k)
    counts = np.bincount(np.asarray(idx).reshape(-1), minlength=16)
    assert counts[5] > 1.25 * k * 256 / 16 and counts[6] == 0
    y, stats = MOE.moe_held_apply(_share(p, 4, 4), x, k=k, e0=4)
    gates, _ = MOE.route(p["router"]["w"], x, k)
    g = jnp.sum(jax.nn.one_hot(idx, 16) * gates[..., None], axis=-2)
    share_dense = 0.0
    for e in range(4, 8):
        a = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
        share_dense = share_dense + g[:, e:e + 1] * (a @ p["w_down"][e])
    # 1e-4: float32 summation order, on outputs up to ~10 (x has mean 2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(share_dense),
                               rtol=0, atol=1e-4)
    dropped, _ = MOE.moe_apply(p, x[None], k=k)
    assert float(jnp.max(jnp.abs(dropped[0] - _dense_moe(p, x, k)))) > 1e-3
    # the counters: rows routed here per token, held experts with a row
    held = (np.asarray(idx) >= 4) & (np.asarray(idx) < 8)
    np.testing.assert_array_equal(np.asarray(stats["rows"]), held.sum(-1))
    assert int(stats["touched"]) == 3


@pytest.mark.parametrize("tokens", [4, 40])
def test_grouped_kernel_matches_its_reference(tokens):
    """``moe_gmm`` in interpret mode against ``ref.moe_gmm_ref`` (the CPU
    path): uneven groups, an empty group, and rows of experts not held
    that must come back zero. Tolerance 1e-5: float32 summation order."""
    from repro.kernels import ops
    p = _layer(seed=2)
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens * 4, 256),
                          jnp.float32)
    n = tokens * 4
    sizes = np.array([n // 2, 0, n // 4, 1], np.int32)      # rest: not held
    sizes[-1] = min(sizes[-1], n - sizes[:-1].sum())
    w = _share(p, 0, 4)
    got = ops.moe_gmm_op(x, w["w_gate"], w["w_up"], w["w_down"],
                         jnp.asarray(sizes), interpret=True)
    want = kref.moe_gmm_ref(x, w["w_gate"], w["w_up"], w["w_down"],
                            jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5)
    assert not np.asarray(got)[sizes.sum():].any()


# ---------------------------------------------------------------------------
# (c) the window's boundary row, in the kernel and in the reference
# ---------------------------------------------------------------------------

def _boundary_case(p, W, plen=16):
    """Pages of random K/V for one sequence at position ``p``; returns
    (q, kp, vp, bt) with the table in order."""
    nb = p // plen + 1
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(keys[0], (1, 4, 128), jnp.bfloat16)
    kp = jax.random.normal(keys[1], (nb + 1, 2, plen, 128), jnp.bfloat16)
    vp = jax.random.normal(keys[2], (nb + 1, 2, plen, 128), jnp.bfloat16)
    bt = (1 + jnp.arange(nb, dtype=jnp.int32))[None]
    return q, kp, vp, bt


def _touch_row(a, t, plen=16):
    """``a`` with logical row t (page t // plen + 1) set to large values."""
    return a.at[t // plen + 1, :, t % plen].set(8.0)


@pytest.mark.parametrize("p", [1100, 1031])
def test_window_boundary_row_in_the_kernel(p):
    """A query at p with window 1,024 attends rows p - 1023 .. p: the
    kernel (interpret mode) output moves when row p - 1023 changes and not
    when row p - 1024 does; and it is bit-identical to its oracle."""
    W = 1024
    q, kp, vp, bt = _boundary_case(p, W)
    pos = jnp.array([p], jnp.int32)
    start = jnp.array([p - W + 1], jnp.int32)
    run = lambda v: paged_attention(q, kp, v, bt, pos, start, interpret=True)
    base = run(vp)
    assert (np.asarray(base) == np.asarray(
        kref.paged_attention_ref(q, kp, vp, bt, np.asarray(pos),
                                 np.asarray(start)))).all()
    assert (np.asarray(run(_touch_row(vp, p - W))) == np.asarray(base)).all()
    assert not (np.asarray(run(_touch_row(vp, p - W + 1)))
                == np.asarray(base)).all()


def test_window_boundary_row_in_the_reference():
    """The reference's sliding mask keeps q - k < 1,024: at query 1,100
    the output moves with key row 77 and not with row 76."""
    W, T, p = 1024, 1280, 1100
    keys = jax.random.split(jax.random.PRNGKey(10), 3)
    q = jax.random.normal(keys[0], (1, T, 2, 16))
    k = jax.random.normal(keys[1], (1, T, 1, 16))
    v = jax.random.normal(keys[2], (1, T, 1, 16))
    run = lambda vv: np.asarray(REF._attend(q, k, vv, W, 256, "f32"))[0, p]
    base = run(v)
    assert (run(v.at[0, p - W].set(8.0)) == base).all()
    assert not (run(v.at[0, p - W + 1].set(8.0)) == base).all()


def test_program_window_mask_matches_the_reference_convention():
    """The program's paged decode (jnp path) attends the same rows: the
    reference's mask and ``attention.window_start`` agree at the edge."""
    from repro.models.attention import window_start
    assert int(window_start(jnp.int32(1100), jnp.int32(1024))) == 77
    assert int(window_start(jnp.int32(5), jnp.int32(1024))) == 0
    assert int(window_start(jnp.int32(1100), jnp.int32(0))) == 0


# ---------------------------------------------------------------------------
# (d) the YaRN table, against one computed by hand
# ---------------------------------------------------------------------------

def test_yarn_table_against_a_hand_computed_one():
    """hd 16, theta 10,000, factor 4 over an original 64 positions, beta
    32 / 1. Pair i turns 64 theta^(-i/8) / (2 pi) times over the original
    length; the pair that turns 32 times sits at i = 8 ln(64 / (64 pi)) /
    ln 10,000 < 0 (so 0), the pair that turns once at i = 8 ln(64 /
    (2 pi)) / ln 10,000 = 2.0175.. (ceil: 3). So pairs 0 keep their
    frequency, pairs from 3 on are divided by 4, and pairs 1 and 2 blend
    with weights 1/3 and 2/3 of the interpolated one. The attention
    factor is 0.1 ln 4 + 1."""
    hd, theta, factor = 16, 10000.0, 4.0
    plain = [theta ** (-2 * i / hd) for i in range(hd // 2)]
    assert math.floor(hd * math.log(64 / (32 * 2 * math.pi))
                      / (2 * math.log(theta))) < 0
    assert math.ceil(hd * math.log(64 / (2 * math.pi))
                     / (2 * math.log(theta))) == 3
    ramp = [0.0, 1 / 3, 2 / 3, 1.0, 1.0, 1.0, 1.0, 1.0]
    want = np.array([f / factor * r + f * (1 - r)
                     for f, r in zip(plain, ramp)])
    got = rope_inv_freq(hd, theta, yarn_factor=factor, original_max=64,
                        beta_fast=32.0, beta_slow=1.0)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_allclose(rope_inv_freq(hd, theta), plain, rtol=1e-12)
    ref_inv, ref_scale = REF.rope_table(
        {"head_dim": hd, "rope_parameters": {"full_attention": {
            "rope_type": "yarn", "rope_theta": theta, "factor": factor,
            "original_max_position_embeddings": 64, "beta_fast": 32,
            "beta_slow": 1}}}, "full_attention")
    np.testing.assert_allclose(ref_inv, want, rtol=1e-12)
    assert ref_scale == pytest.approx(0.1 * math.log(4) + 1)
    assert yarn_attention_factor(4.0) == pytest.approx(0.1 * math.log(4) + 1)
    assert yarn_attention_factor(16.0, 1.2772588722239782) == \
        1.2772588722239782


def test_layer_meta_of_the_published_model():
    """Published Mellum2: layers 3, 7, .. are full with YaRN (scale
    1.27726), the rest slide over 1,024 rows with the plain table."""
    from repro.configs import get_config
    cfg = get_config("mellum2-12b-a2.5b")
    meta = T.layer_meta(cfg)
    assert meta["window"].tolist() == [1024, 1024, 1024, 0] * 7
    assert meta["rope_scale"].tolist() == pytest.approx(
        [1.0, 1.0, 1.0, 1.2772588722239782] * 7)
    np.testing.assert_allclose(meta["inv_freq"][0],
                               rope_inv_freq(128, 500000.0), rtol=1e-6)
    assert meta["inv_freq"][3][-1] == pytest.approx(
        500000.0 ** (-126 / 128) / 16, rel=1e-6)
    assert T.layer_meta(get_reduced("qwen2.5-3b")) is None
    assert T.full_attention_arch(cfg) and cfg.homogeneous


# ---------------------------------------------------------------------------
# (e) a sliding window in the kernel, against its oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_windowed_kernel_parity(dtype):
    """Rows of several sequences, each with its own first row (inside a
    page, on a page edge, and 0): interpret mode against the oracle, bit
    for bit in bf16 and to a few ulp in f32, as the full-attention kernel
    (``test_paged.py``), whose window-0 path is unchanged."""
    B, nb, plen, n_kv, g, hd = 3, 6, 8, 2, 2, 32
    n_pages = B * nb + 1
    keys = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(keys[0], (B, n_kv * g, hd)).astype(dtype)
    kp = jax.random.normal(keys[1], (n_pages, n_kv, plen, hd)).astype(dtype)
    vp = jax.random.normal(keys[2], (n_pages, n_kv, plen, hd)).astype(dtype)
    pos = np.array([45, 30, 20], np.int32)
    starts = np.array([29, 16, 0], np.int32)
    bt = (1 + np.arange(B * nb, dtype=np.int32)).reshape(B, nb)
    out_k = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos),
                            jnp.asarray(starts), interpret=True)
    out_r = kref.paged_attention_ref(q, kp, vp, jnp.asarray(bt), pos, starts)
    if dtype == jnp.bfloat16:
        assert (np.asarray(out_k) == np.asarray(out_r)).all()
    else:
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# the engine: the counters, and no compile for telemetry
# ---------------------------------------------------------------------------

def _run(params, cfg, telemetry=None):
    eng = ContinuousBatchingEngine(params, cfg, n_slots=4, cache_len=96,
                                   paged=True, page_len=8, n_pages=48,
                                   max_window=8, freeze_modes=True,
                                   telemetry=telemetry)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 20 + 7 * i,
                                               dtype=np.int32),
                    max_new_tokens=10 + i) for i in range(5)]
    done = eng.run(reqs)
    eng.close()
    return done


def test_engine_serves_the_reference_and_counts_expert_rows():
    """The paged engine's greedy tokens are the reference's argmax at every
    position, and each decoded token carries its rows routed to held
    experts (at most k per layer) and the tick's touched held experts (at
    most the held count per layer); the registry's ``moe.*`` counters are
    the sessions' sums."""
    cfg = _cfg()
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    tel = Telemetry()
    done = _run(params, cfg, tel)
    assert len(done) == 5
    rows = touched = 0
    for s in done:
        n_dec = len(s.tokens) - 1
        assert len(s.moe_rows) == len(s.moe_touched) == n_dec
        assert all(0 <= r <= cfg.experts_per_tok * cfg.n_layers
                   for r in s.moe_rows)
        assert all(0 < t <= HELD * cfg.n_layers for t in s.moe_touched)
        rows += sum(s.moe_rows)
        seq = np.concatenate([s.request.prompt,
                              np.asarray(s.tokens[:-1], np.int32)])
        want = _ref_logits(params, cfg, jnp.asarray(seq)[None])[0]
        P = s.request.prompt_len
        np.testing.assert_array_equal(np.argmax(np.asarray(want[P - 1:]), -1),
                                      s.tokens)
    snap = tel.registry.snapshot()
    assert snap["moe.rows_here"] == rows > 0
    ticks = snap["moe.decode_ticks"]
    assert 0 < ticks and snap["moe.touched_experts"] > 0
    touched = {}
    for s in done:
        for j, t in enumerate(s.moe_touched):
            touched[s.admitted_tick + j] = t
    assert len(touched) == ticks
    assert sum(touched.values()) == snap["moe.touched_experts"]


def test_telemetry_window_compiles_nothing_new():
    """A window that materializes the expert counters with telemetry
    attached runs the plain engine's programs: after a plain run, an
    instrumented run of the same workload compiles nothing."""
    cfg = dataclasses.replace(_cfg(), name="mellum-telemetry-compile")
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    _run(params, cfg)
    compiles = []

    def listen(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        _run(params, cfg, Telemetry())
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
