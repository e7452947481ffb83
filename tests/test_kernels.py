"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles
across shape/dtype sweeps (per-kernel allclose, per the deliverable)."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.bottleneck_quant import bottleneck_quant
from repro.kernels.dequant_matmul import dequant_matmul
from repro.kernels.rglru_scan import rglru_scan

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("M,K,N", [
    (128, 512, 128), (256, 1024, 256), (384, 512, 128), (128, 2048, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bottleneck_quant_sweep(M, K, N, dtype):
    x = (0.5 * jax.random.normal(KEY, (M, K))).astype(dtype)
    w = (0.02 * jax.random.normal(jax.random.PRNGKey(1), (K, N))).astype(dtype)
    codes, scales = bottleneck_quant(x, w, block_m=128, block_k=512,
                                     interpret=True)
    c_ref, s_ref = ref.bottleneck_quant_ref(x, w)
    # int8 codes may differ by 1 ulp where round() ties differ across orders
    diff = np.abs(codes.astype(np.int32) - np.asarray(c_ref, np.int32))
    assert diff.max() <= 1
    assert (diff > 0).mean() < 0.01
    np.testing.assert_allclose(np.asarray(scales), np.asarray(s_ref),
                               rtol=1e-3)


@pytest.mark.parametrize("M,N,D", [
    (128, 128, 512), (256, 256, 1024), (128, 512, 512),
])
def test_dequant_matmul_sweep(M, N, D):
    x = jax.random.normal(KEY, (M, N))
    codes, scales = ref.bottleneck_quant_ref(x, jnp.eye(N))
    w = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (N, D))
    y = dequant_matmul(codes, scales, w, block_m=128, block_d=min(D, 512),
                       interpret=True)
    y_ref = ref.dequant_matmul_ref(codes, scales, w)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
        rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("B,S,D,bs,bd", [
    (1, 512, 128, 256, 128), (2, 1024, 256, 256, 128), (2, 512, 512, 128, 256),
])
def test_rglru_scan_sweep(B, S, D, bs, bd):
    a = jax.nn.sigmoid(jax.random.normal(KEY, (B, S, D)))
    b = jax.random.normal(jax.random.PRNGKey(3), (B, S, D))
    h = rglru_scan(a, b, block_s=bs, block_d=bd, interpret=True)
    h_ref = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)


def test_rglru_scan_carry_across_time_blocks():
    """The VMEM carry must persist across sequential grid steps: compare a
    2-block run to the oracle on a signal where state matters."""
    B, S, D = 1, 512, 128
    a = jnp.full((B, S, D), 0.999)          # long memory
    b = jnp.zeros((B, S, D)).at[:, 0, :].set(1.0)
    h = rglru_scan(a, b, block_s=256, block_d=128, interpret=True)
    h_ref = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-5)
    # state visibly decays across the block boundary
    assert float(h[0, 257, 0]) == pytest.approx(0.999 ** 257, rel=1e-3)


@pytest.mark.parametrize("bits", [8, 4, 1])
def test_bottleneck_quant_agrees_with_quant_module(bits):
    """The fused kernel (and its oracle) must produce the SAME wire format
    as ``repro.core.quant`` for every calibrated bit width — including the
    bits=1 ternary code, which divided by a zero qmax before the floor fix
    (inf scales -> NaN payloads)."""
    from repro.core import quant
    x = jax.random.normal(KEY, (128, 512))
    w = 0.02 * jax.random.normal(jax.random.PRNGKey(7), (512, 128))
    z = x @ w
    q_codes, q_scales = quant.quantize(z, bits)
    k_codes, k_scales = bottleneck_quant(x, w, bits=bits, block_m=128,
                                         block_k=512, interpret=True)
    r_codes, r_scales = ref.bottleneck_quant_ref(x, w, bits)
    for codes, scales in [(k_codes, k_scales), (r_codes, r_scales)]:
        assert np.isfinite(np.asarray(scales)).all()
        np.testing.assert_allclose(np.asarray(scales),
                                   np.asarray(q_scales), rtol=1e-5)
        diff = np.abs(np.asarray(codes, np.int32)
                      - np.asarray(q_codes, np.int32))
        assert diff.max() <= 1           # round() ties may break either way
        assert (diff > 0).mean() < 0.01
        assert np.abs(np.asarray(codes)).max() <= quant.qmax(bits)


# ---------------------------------------------------------------------------
# fused mixed-mode boundary kernel (kernels/boundary_mixed.py)
# ---------------------------------------------------------------------------

from repro.kernels.boundary_mixed import boundary_mixed_grouped  # noqa: E402


def _stacked_bank(widths_bits, d=128, seed=0, dtype=jnp.bfloat16):
    """A synthetic stacked mode bank (same pytree as bottleneck.bank_stack
    produces) with the given [(width, bits)] heads."""
    wmax = max(w for w, _ in widths_bits)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 * len(widths_bits))
    downs, ups = [], []
    for i, (w, _) in enumerate(widths_bits):
        dw = 0.05 * jax.random.normal(keys[2 * i], (d, w))
        uw = 0.05 * jax.random.normal(keys[2 * i + 1], (w, d))
        downs.append(jnp.pad(dw, ((0, 0), (0, wmax - w))).astype(dtype))
        ups.append(jnp.pad(uw, ((0, wmax - w), (0, 0))).astype(dtype))
    return {
        "down_w": jnp.stack(downs),
        "up_w": jnp.stack(ups),
        "norm_scale": jnp.ones((len(widths_bits), d), dtype),
        "width": jnp.asarray([w for w, _ in widths_bits], jnp.int32),
        "bits": jnp.asarray([b for _, b in widths_bits], jnp.int32),
    }


# widths cover full-wmax, narrow (fewer chunks than wmax), and a
# non-chunk-aligned width (masked last chunk); bits cover int8 / int4 /
# ternary / unquantized
HET_BANK = [(128, 8), (256, 4), (200, 1), (384, 0)]


def _grouped_parity(stacked, x, modes):
    """Run the Pallas kernel (interpret) and the blocked jnp oracle on the
    SAME mode-grouped layout and return both plus the serving reference."""
    B, S, d = x.shape
    block_r = 16 if jnp.dtype(x.dtype).itemsize == 2 else 8
    rmode = jnp.repeat(jnp.asarray(modes, jnp.int32), S)
    dest, tb = ops.group_layout(stacked, rmode, block_r, 128)
    xp = jnp.zeros((tb["P"], d), x.dtype).at[dest].set(x.reshape(B * S, d))
    yk = boundary_mixed_grouped(
        xp, stacked["down_w"], stacked["up_w"], stacked["norm_scale"],
        tb["hid"], tb["nchunk"], tb["width"], tb["bits"],
        block_r=block_r, block_w=128, interpret=True)
    yo = ref.boundary_mixed_grouped_ref(
        xp, stacked["down_w"], stacked["up_w"], stacked["norm_scale"],
        np.asarray(tb["hid"]), np.asarray(tb["nchunk"]),
        np.asarray(tb["width"]), np.asarray(tb["bits"]),
        block_r=block_r, block_w=128)
    return yk, yo


@pytest.mark.parametrize("mode", [0, 1, 2, 3, 4])
def test_boundary_kernel_bitwise_every_calibrated_mode(mode):
    """Uniform-mode batches: the Pallas kernel must match the blocked jnp
    oracle BIT FOR BIT for every calibrated mode — bits 8, 4, the ternary
    bits=1 code, the unquantized bits=0 wire, and the raw mode-0
    passthrough."""
    stacked = _stacked_bank(HET_BANK)
    x = jax.random.normal(jax.random.PRNGKey(9), (8, 1, 128)
                          ).astype(jnp.bfloat16)
    modes = jnp.full((8,), mode, jnp.int32)
    yk, yo = _grouped_parity(stacked, x, modes)
    np.testing.assert_array_equal(np.asarray(yk, np.float32),
                                  np.asarray(yo, np.float32))
    # and the dispatcher output must agree with the serving jnp reference
    y_op = ops.boundary_mixed_op(stacked, x, modes, interpret=True)
    y_ref = ref.boundary_mixed_ref(stacked, x, modes)
    np.testing.assert_allclose(np.asarray(y_op, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("B", [1, 8, 32])
def test_boundary_kernel_heterogeneous_pool_sizes(B):
    """Mixed-mode pools (every slot on its own head) at pool sizes 1/8/32:
    bit-for-bit vs the blocked oracle, tight agreement vs the serving
    reference, and exact passthrough for raw-mode rows."""
    stacked = _stacked_bank(HET_BANK)
    rng = np.random.default_rng(B)
    x = jnp.asarray(rng.normal(size=(B, 1, 128)), jnp.bfloat16)
    modes = jnp.asarray(rng.integers(0, 5, B), jnp.int32)
    yk, yo = _grouped_parity(stacked, x, modes)
    np.testing.assert_array_equal(np.asarray(yk, np.float32),
                                  np.asarray(yo, np.float32))
    y_op = np.asarray(ops.boundary_mixed_op(stacked, x, modes,
                                            interpret=True), np.float32)
    y_ref = np.asarray(ref.boundary_mixed_ref(stacked, x, modes), np.float32)
    np.testing.assert_allclose(y_op, y_ref, atol=2e-2, rtol=2e-2)
    raw = np.asarray(modes) == 0
    np.testing.assert_array_equal(y_op[raw], np.asarray(x, np.float32)[raw])


def test_boundary_kernel_prefill_rows():
    """[B, S, d] prefill-shaped inputs (S > 1): every token row of a batch
    row rides that row's mode; parity must hold with per-token grouping."""
    stacked = _stacked_bank(HET_BANK)
    rng = np.random.default_rng(5)
    B, S = 5, 3
    x = jnp.asarray(rng.normal(size=(B, S, 128)), jnp.bfloat16)
    modes = jnp.asarray(rng.integers(0, 5, B), jnp.int32)
    yk, yo = _grouped_parity(stacked, x, modes)
    np.testing.assert_array_equal(np.asarray(yk, np.float32),
                                  np.asarray(yo, np.float32))
    y_op = np.asarray(ops.boundary_mixed_op(stacked, x, modes,
                                            interpret=True), np.float32)
    y_ref = np.asarray(ref.boundary_mixed_ref(stacked, x, modes), np.float32)
    np.testing.assert_allclose(y_op, y_ref, atol=2e-2, rtol=2e-2)


def test_boundary_dispatcher_unaligned_widths_fall_back():
    """A bank whose widest head is not 128-aligned cannot tile the kernel;
    the dispatcher must route to the jnp reference and agree EXACTLY."""
    stacked = _stacked_bank([(32, 8), (48, 4), (24, 1)])   # wmax = 48
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(6, 1, 128)), jnp.bfloat16)
    modes = jnp.asarray(rng.integers(0, 4, 6), jnp.int32)
    y_op = ops.boundary_mixed_op(stacked, x, modes, interpret=True)
    y_ref = ref.boundary_mixed_ref(stacked, x, modes)
    np.testing.assert_array_equal(np.asarray(y_op, np.float32),
                                  np.asarray(y_ref, np.float32))


# ---------------------------------------------------------------------------
# fused decode-tail megakernel (kernels/boundary_mixed.decode_tail_grouped)
# ---------------------------------------------------------------------------

from repro.kernels.boundary_mixed import decode_tail_grouped  # noqa: E402


def _tail_inputs(B, d=128, V=512, H=1, seed=0, norm_kind="rmsnorm"):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (B, 1, d)).astype(jnp.bfloat16)
    scale = (0.1 * jax.random.normal(ks[1], (d,)) + 1.0).astype(jnp.bfloat16)
    bias = (0.1 * jax.random.normal(ks[2], (d,))).astype(jnp.bfloat16) \
        if norm_kind == "layernorm" else None
    heads = jax.random.normal(ks[3], (H, d, V)).astype(jnp.bfloat16)
    return x, scale, bias, heads


@pytest.mark.parametrize("norm_kind", ["rmsnorm", "layernorm"])
@pytest.mark.parametrize("B", [1, 8, 32])
def test_decode_tail_kernel_bitwise_pool_sizes(B, norm_kind):
    """The tail megakernel must match its blocked jnp oracle BIT FOR BIT on
    the same head-grouped layout, at pool sizes 1/8/32 and for both norm
    families the serving archs use (rmsnorm / xLSTM layernorm)."""
    H = 3
    x, scale, bias, heads = _tail_inputs(B, H=H, seed=B, norm_kind=norm_kind)
    hidx = jax.random.randint(jax.random.PRNGKey(B + 7), (B,), 0, H)
    block_r = 16
    dest, hid_g, P = ops.head_layout(hidx.astype(jnp.int32), H, block_r)
    xp = jnp.zeros((P, x.shape[-1]), x.dtype).at[dest].set(x[:, 0])
    bias_arr = bias if bias is not None \
        else jnp.zeros((x.shape[-1],), scale.dtype)
    tk = decode_tail_grouped(xp, heads, scale, bias_arr, hid_g,
                             block_r=block_r, block_v=128,
                             norm_kind=norm_kind, interpret=True)
    to = ref.decode_tail_grouped_ref(np.asarray(xp), heads, scale, bias_arr,
                                     np.asarray(hid_g), block_r=block_r,
                                     block_v=128, norm_kind=norm_kind)
    np.testing.assert_array_equal(np.asarray(tk), np.asarray(to))
    # dispatcher tokens == serving reference tokens (argmax is exact: the
    # kernel computes the same f32 logits chunk-by-chunk)
    t_op = ops.decode_tail_op(x, scale, bias, heads, hidx,
                              norm_kind=norm_kind, interpret=True)
    t_ref = ref.decode_tail_ref(x, scale, bias, heads, hidx,
                                norm_kind=norm_kind)
    np.testing.assert_array_equal(np.asarray(t_op), np.asarray(t_ref))


def test_decode_tail_matches_legacy_chain():
    """The op's CPU path must reproduce the legacy
    norm_apply -> lm_logits -> argmax chain EXACTLY (expression identity,
    not allclose) for both the untied matmul head and the tied embedding
    einsum — this is what lets serving swap the chain for the op with
    pinned token streams."""
    d, V = 128, 512
    x, scale, _, heads = _tail_inputs(16, d=d, V=V)
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
    xn = (y * scale.astype(jnp.float32)).astype(x.dtype)
    # untied: x_f32 @ w_f32 (lm_logits expression)
    legacy = jnp.argmax(xn.astype(jnp.float32)
                        @ heads[0].astype(jnp.float32), -1).astype(jnp.int32)
    got = ops.decode_tail_op(x, scale, None, heads[:1])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(legacy))
    # tied: einsum("bsd,vd->bsv") against the embedding table
    table = jax.random.normal(jax.random.PRNGKey(11), (V, d), jnp.bfloat16)
    legacy_t = jnp.argmax(
        jnp.einsum("bsd,vd->bsv", xn.astype(jnp.float32),
                   table.astype(jnp.float32)), -1).astype(jnp.int32)
    got_t = ops.decode_tail_op(x, scale, None, table[None], tied=True)
    np.testing.assert_array_equal(np.asarray(got_t), np.asarray(legacy_t))
    # and the interpret-mode kernel path picks the same tokens
    got_tk = ops.decode_tail_op(x, scale, None, table[None], tied=True,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(got_tk), np.asarray(legacy_t))


def test_decode_tail_after_boundary_all_bit_widths():
    """The full fused tick pipeline (boundary kernel -> tail kernel) vs the
    full reference chain, with heterogeneous modes covering bits
    {8, 4, 1, 0} and raw passthrough in ONE pool: tokens must agree
    position-for-position."""
    stacked = _stacked_bank(HET_BANK)
    rng = np.random.default_rng(12)
    B = 16
    x = jnp.asarray(rng.normal(size=(B, 1, 128)), jnp.bfloat16)
    modes = jnp.asarray(np.r_[rng.integers(0, 5, B - 5), [0, 1, 2, 3, 4]],
                        jnp.int32)
    _, scale, _, heads = _tail_inputs(B, seed=13)
    y_k = ops.boundary_mixed_op(stacked, x, modes, interpret=True)
    t_k = ops.decode_tail_op(y_k, scale, None, heads, interpret=True)
    y_r = ref.boundary_mixed_ref(stacked, x, modes)
    t_r = ref.decode_tail_ref(y_r, scale, None, heads)
    # boundary outputs differ by blocked-vs-gather GEMM rounding (allclose,
    # not bitwise), so compare tokens through the SAME boundary output too
    t_same = ops.decode_tail_op(y_k, scale, None, heads)
    np.testing.assert_array_equal(np.asarray(t_k), np.asarray(t_same))
    assert (np.asarray(t_k) == np.asarray(t_r)).mean() > 0.9


def test_decode_tail_unaligned_vocab_falls_back():
    """A non-128-aligned vocab (or model width) cannot tile the kernel; the
    dispatcher must route to the jnp reference and agree exactly."""
    x, scale, _, _ = _tail_inputs(6)
    heads = jax.random.normal(jax.random.PRNGKey(14), (1, 128, 1000),
                              jnp.bfloat16)
    got = ops.decode_tail_op(x, scale, None, heads, interpret=True)
    want = ref.decode_tail_ref(x, scale, None, heads)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(np.max(got)) < 1000


def test_decode_tail_argmax_tie_break_matches_jnp():
    """Duplicate maxima across vocab chunks: the kernel's two-stage lane
    argmax must keep the FIRST occurrence, like jnp.argmax."""
    d, V = 128, 512
    x = jnp.ones((4, 1, d), jnp.bfloat16)
    scale = jnp.ones((d,), jnp.bfloat16)
    # identical columns -> every logit equal -> argmax must be 0
    heads = jnp.ones((1, d, V), jnp.bfloat16)
    got = ops.decode_tail_op(x, scale, None, heads, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), 0)
    # duplicate the true max into a later chunk: first index must win
    w = jax.random.normal(jax.random.PRNGKey(15), (1, d, V), jnp.bfloat16)
    w = w.at[:, :, 300].set(w[:, :, 37])
    w = w.at[:, :, 37].set(w[:, :, 37] * 0 + 3.0)   # big, equal col at 37
    w = w.at[:, :, 300].set(3.0)                    # same big col later
    got = np.asarray(ops.decode_tail_op(x, scale, None, w, interpret=True))
    ref_tok = np.asarray(ref.decode_tail_ref(x, scale, None, w))
    np.testing.assert_array_equal(got, ref_tok)
    np.testing.assert_array_equal(got, 37)


# ---------------------------------------------------------------------------
# rglru scan op dispatch (h0 absorption + CPU/unaligned fallback)
# ---------------------------------------------------------------------------

def test_rglru_scan_op_h0_paths_agree():
    """The op must honor a non-zero initial carry on every path: the CPU
    reference scans from h0 directly; the kernel path absorbs it into the
    first step (b1 += a1*h0, bit-identical in f32)."""
    B, S, D = 2, 16, 128
    a = jax.nn.sigmoid(jax.random.normal(KEY, (B, S, D)))
    b = jax.random.normal(jax.random.PRNGKey(16), (B, S, D))
    h0 = jax.random.normal(jax.random.PRNGKey(17), (B, D))
    want = ref.rglru_scan_ref(a, b, h0)
    got_cpu = ops.rglru_scan_op(a, b, h0=h0)
    np.testing.assert_array_equal(np.asarray(got_cpu), np.asarray(want))
    got_k = ops.rglru_scan_op(a, b, h0=h0, interpret=True)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_rglru_scan_op_unaligned_falls_back():
    """Non-block-multiple S/D must take the reference even when the kernel
    is requested."""
    B, S, D = 3, 13, 96
    a = jax.nn.sigmoid(jax.random.normal(KEY, (B, S, D)))
    b = jax.random.normal(jax.random.PRNGKey(18), (B, S, D))
    for h0 in (None, jax.random.normal(jax.random.PRNGKey(19), (B, D))):
        got = ops.rglru_scan_op(a, b, h0=h0, interpret=True)
        want = ref.rglru_scan_ref(a, b, h0)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tpu_fallbacks_are_recorded(monkeypatch):
    """On a TPU a dispatcher that must take the jnp reference for a shape
    its kernel cannot tile records it in ``ops.FALLBACKS`` (what
    ``chip_smoke.py`` fails on) instead of falling back silently; aligned
    shapes and paged pages of whole bf16 tiles record nothing."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "FALLBACKS", collections.Counter())
    a = jax.nn.sigmoid(jax.random.normal(KEY, (2, 13, 96)))
    b = jax.random.normal(jax.random.PRNGKey(18), (2, 13, 96))
    with pytest.warns(UserWarning, match="rglru_scan"):
        got = ops.rglru_scan_op(a, b)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.rglru_scan_ref(a, b)))
    assert list(ops.FALLBACKS) == [("rglru_scan",
                                    "S=13 D=96 not multiples of (8, 128)")]
    assert ops.paged_kernel_eligible(n_q=16, n_kv=2, hd=128, page_len=16)
    assert len(ops.FALLBACKS) == 1
    with pytest.warns(UserWarning, match="paged_attention"):
        assert not ops.paged_kernel_eligible(n_q=16, n_kv=2, hd=128,
                                             page_len=8)
    assert ("paged_attention",
            "page [8, 128] is not whole bfloat16 tiles") in ops.FALLBACKS


def test_ops_fallback_on_odd_shapes():
    """Non-tileable shapes must route to the reference implementation."""
    x = jax.random.normal(KEY, (13, 100))
    w = jax.random.normal(jax.random.PRNGKey(4), (100, 60))
    codes, scales = ops.bottleneck_quant_op(x, w)
    c_ref, s_ref = ref.bottleneck_quant_ref(x, w)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(c_ref))


def test_ops_batched_leading_dims():
    x = jax.random.normal(KEY, (2, 64, 512))
    w = 0.02 * jax.random.normal(jax.random.PRNGKey(5), (512, 128))
    codes, scales = ops.bottleneck_quant_op(x, w)
    assert codes.shape == (2, 64, 128)
    assert scales.shape == (2, 64, 1)
    c_ref, s_ref = ref.bottleneck_quant_ref(x.reshape(128, 512), w)
    np.testing.assert_array_equal(
        np.asarray(codes).reshape(128, 128), np.asarray(c_ref))
