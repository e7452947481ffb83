"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bottleneck_quant_ref(x, w, bits: int = 8):
    """Fused down-projection + row-wise symmetric int8 quantization.

    x: [M, K] bf16/f32, w: [K, N] -> (codes int8 [M, N], scales f32 [M, 1]).
    """
    z = (x.astype(jnp.float32) @ w.astype(jnp.float32))
    # same floor as quant.qmax: bits=1 is the ternary {-1, 0, 1} code, never
    # a zero qmax (which made the scale infinite and the roundtrip NaN)
    qm = max((1 << (bits - 1)) - 1, 1)
    absmax = jnp.max(jnp.abs(z), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / qm
    codes = jnp.clip(jnp.round(z / scale), -qm, qm).astype(jnp.int8)
    return codes, scale


def boundary_mixed_ref(stacked, x, mode_idx, *, dtype=jnp.bfloat16):
    """Per-row mixed-mode bottleneck boundary (the fused-kernel oracle).

    x: [B, S, d]; mode_idx: [B] int32 in [0, M] where 0 transmits the raw
    code z and m >= 1 routes row b through head m-1 of ``stacked`` (see
    ``bottleneck.bank_stack``): rmsnorm + down-projection (layer A), the
    quantize -> dequantize wire round-trip at that row's bit width, and the
    up-projection adapter (layer B). Returns [B, S, d] in ``x.dtype``.
    """
    eps = 1e-6
    hid = jnp.clip(mode_idx - 1, 0, stacked["width"].shape[0] - 1)  # [B]
    # layer A: per-row rmsnorm + down-projection
    xf = x.astype(jnp.float32)
    h = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    h = h * stacked["norm_scale"][hid][:, None, :].astype(jnp.float32)
    z = jnp.einsum("bsd,bdw->bsw", h.astype(x.dtype),
                   stacked["down_w"][hid]).astype(jnp.float32)
    lane = jnp.arange(z.shape[-1])
    z = jnp.where(lane[None, None, :] < stacked["width"][hid][:, None, None],
                  z, 0.0)
    # wire: row-wise symmetric quantization with per-row bit width
    # (bits == 0 modes ship the code unquantized, so the roundtrip is skipped)
    bits_h = stacked["bits"][hid][:, None, None]
    # same floor-at-1 as quant.qmax: bits=1 is the ternary code, never a
    # zero qmax (the two wire paths are pinned to agree by tests)
    qm = jnp.maximum(
        jnp.left_shift(1, jnp.maximum(bits_h, 1) - 1) - 1, 1
    ).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(z), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / qm
    codes = jnp.clip(jnp.round(z / scale), -qm, qm)
    wired = jnp.where(bits_h == 0, z, codes * scale)
    # layer B: up-projection adapter back into the decoder width
    y = jnp.einsum("bsw,bwd->bsd", wired.astype(dtype),
                   stacked["up_w"][hid])
    return jnp.where(mode_idx[:, None, None] == 0, x, y.astype(x.dtype))


def boundary_mixed_grouped_ref(xp, down_w, up_w, norm_scale, hid_g, nchunk_g,
                               width_g, bits_g, *, block_r: int,
                               block_w: int = 128, dtype=jnp.bfloat16):
    """Pure-jnp oracle for ``boundary_mixed.boundary_mixed_grouped`` that
    mirrors the kernel's blocked computation EXACTLY (same block shapes,
    same dtypes, same op order), so the Pallas kernel is pinned bit-for-bit
    against it in tests. It differs from :func:`boundary_mixed_ref` only by
    GEMM accumulation shape (mode-grouped block dots vs one batched-gather
    einsum), i.e. by bf16 rounding noise — never by wire semantics.
    Test-scale only (python loop over row blocks).
    """
    P, d = xp.shape
    M, _, wmax = down_w.shape
    outs = []
    for g in range(P // block_r):
        rows = xp[g * block_r:(g + 1) * block_r]
        hid, nch = int(hid_g[g]), int(nchunk_g[g])
        width, bits = int(width_g[g]), int(bits_g[g])
        if nch == 0:                           # raw passthrough (mode 0)
            outs.append(rows)
            continue
        xf = rows.astype(jnp.float32)
        h = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
        h = (h * norm_scale[hid].astype(jnp.float32)).astype(xp.dtype)
        z = jnp.zeros((block_r, wmax), jnp.float32)
        for w in range(nch):
            zc = jnp.dot(
                h, down_w[hid, :, w * block_w:(w + 1) * block_w],
                preferred_element_type=jnp.float32
            ).astype(xp.dtype).astype(jnp.float32)
            lane = w * block_w + jnp.arange(block_w)
            z = z.at[:, w * block_w:(w + 1) * block_w].set(
                jnp.where(lane[None, :] < width, zc, 0.0))
        qm = float(max((1 << (max(bits, 1) - 1)) - 1, 1))
        absmax = jnp.max(jnp.abs(z), axis=-1, keepdims=True)
        scale = jnp.maximum(absmax, 1e-8) / qm
        codes = jnp.clip(jnp.round(z / scale), -qm, qm)
        wired = z if bits == 0 else codes * scale
        y = jnp.dot(wired.astype(dtype), up_w[hid],
                    preferred_element_type=jnp.float32)
        outs.append(y.astype(xp.dtype))
    return jnp.concatenate(outs, axis=0)


def paged_attention_ref(q, k_pages, v_pages, block_table, positions,
                        starts=None):
    """Blocked jnp oracle for ``paged_attention.paged_attention``.

    Walks (sequence, page) exactly like the kernel grid — same page-skip
    guard, same f32 online softmax, same ``q.dtype`` rounding barriers at
    the score / probability / accumulator hand-offs, same op order — so the
    Pallas kernel is pinned bit-for-bit against it in interpret mode for
    sub-f32 dtypes (bf16); f32 matches to a few ulp (the barriers are no-op
    casts there and cannot quantize away XLA's fusion freedom).
    q: [B, nq, hd]; ``k_pages``/``v_pages``: [n_pages, n_kv, page_len, hd];
    ``block_table``: [B, nb]; ``positions``: [B] and optional ``starts``
    [B], each sequence's first attended row (concrete host values — they
    steer the python page loop). Returns [B, nq, hd] in ``q.dtype``.
    Test-scale only (python loop over sequences, pages and kv heads).
    """
    import math

    NEG_INF = -1e30
    B, nq, hd = q.shape
    n_kv, plen = k_pages.shape[1], k_pages.shape[2]
    g = nq // n_kv
    nb = block_table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    dt = q.dtype

    def barrier(x):
        return x.astype(dt).astype(jnp.float32)

    outs = []
    for b in range(B):
        pos_b = int(positions[b])
        st_b = 0 if starts is None else int(starts[b])
        qg = q[b].reshape(n_kv, g, hd)
        heads = []
        for h in range(n_kv):
            m = jnp.full((g, 1), NEG_INF, jnp.float32)
            l = jnp.zeros((g, 1), jnp.float32)
            acc = jnp.zeros((g, hd), jnp.float32)
            for j in range(nb):
                if j * plen > pos_b or (j + 1) * plen <= st_b:
                    continue
                page = block_table[b, j]
                s = barrier(jnp.dot(qg[h], k_pages[page, h].T,
                                    preferred_element_type=jnp.float32)
                            * scale)
                t_abs = j * plen + jnp.arange(plen)[None, :]
                keep = t_abs <= pos_b
                if starts is not None:
                    keep = keep & (t_abs >= st_b)
                s = jnp.where(keep, s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = barrier(jnp.exp(s - m_new))
                corr = barrier(jnp.exp(m - m_new))
                m = m_new
                l = barrier(l * corr) + jnp.sum(p, axis=-1, keepdims=True)
                acc = barrier(acc * corr) + barrier(jnp.dot(
                    p.astype(dt), v_pages[page, h],
                    preferred_element_type=jnp.float32))
            heads.append((acc / l).astype(dt))
        outs.append(jnp.concatenate(heads, axis=0))
    return jnp.stack(outs)


def moe_gmm_ref(x, w_gate, w_up, w_down, group_sizes, *, act="silu"):
    """jnp twin of ``moe_gmm.moe_gmm``: rows sorted by held expert, group
    ``g`` (``group_sizes[g]`` rows from the end of group g - 1) through
    expert g's gated MLP with the kernel's casts (f32 products, the
    activation product rounded to ``x.dtype`` before the down-projection);
    rows past the groups come back zero. Returns [M, d] float32."""
    gs = group_sizes.astype(jnp.int32)
    h = jax.lax.ragged_dot(x, w_gate, gs, preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(x, w_up, gs, preferred_element_type=jnp.float32)
    a = jax.nn.silu(h) if act == "silu" else jax.nn.gelu(h)
    a = (a * u).astype(x.dtype)
    y = jax.lax.ragged_dot(a, w_down, gs, preferred_element_type=jnp.float32)
    held = jnp.arange(x.shape[0]) < jnp.sum(gs)
    return jnp.where(held[:, None], y, 0.0)


def dequant_matmul_ref(codes, scales, w, out_dtype=jnp.bfloat16):
    """Decoder-side fused dequantize + up-projection.

    codes: int8 [M, N], scales: f32 [M, 1], w: [N, D] -> [M, D].
    """
    z = codes.astype(jnp.float32) * scales
    return (z @ w.astype(jnp.float32)).astype(out_dtype)


def rglru_scan_ref(a, b, h0=None):
    """Gated linear recurrence h_t = a_t * h_{t-1} + b_t.

    a, b: [B, S, D] f32; ``h0``: optional [B, D] initial carry (zeros when
    omitted — the post-reset decode case). Returns h: [B, S, D] f32.
    """
    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    B, S, D = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, D), jnp.float32)
    _, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                         (a.swapaxes(0, 1), b.swapaxes(0, 1)))
    return hs.swapaxes(0, 1)


def decode_tail_ref(x, norm_scale, norm_bias, heads, head_idx=None, *,
                    norm_kind: str = "rmsnorm", tied: bool = False):
    """Serving reference for the fused decode tail (final norm -> LM-head
    gather -> argmax), expression-identical to the legacy
    ``norm_apply(final_norm) -> lm_logits -> jnp.argmax`` chain so routing
    the serving tick through it cannot move a single token on CPU.

    x: [B, S, d]; ``heads``: [H, d, V] stacked LM heads, or the [1, V, d]
    embedding table when ``tied``; ``head_idx``: [B] int32 per-row head (None
    = head 0 everywhere). Returns int32 tokens [B, S].
    """
    xf = x.astype(jnp.float32)
    if norm_kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + 1e-6)
    else:                                # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
    y = y * norm_scale.astype(jnp.float32)
    if norm_bias is not None:
        y = y + norm_bias.astype(jnp.float32)
    xn = y.astype(x.dtype).astype(jnp.float32)
    if tied:
        logits = jnp.einsum("bsd,vd->bsv", xn, heads[0].astype(jnp.float32))
    elif heads.shape[0] == 1:
        logits = xn @ heads[0].astype(jnp.float32)
    else:
        hid = jnp.zeros(x.shape[0], jnp.int32) if head_idx is None \
            else head_idx.astype(jnp.int32)
        logits = jnp.einsum("bsd,bdv->bsv", xn,
                            heads[hid].astype(jnp.float32))
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def decode_tail_grouped_ref(xp, heads, norm_scale, norm_bias, hid_g, *,
                            block_r: int, block_v: int = 512,
                            norm_kind: str = "rmsnorm"):
    """Pure-jnp oracle for ``boundary_mixed.decode_tail_grouped`` mirroring
    the kernel's blocked computation EXACTLY: same per-row-block head gather,
    same f32 norm rounded through the model dtype, same vocab-chunked MXU
    dots, same strict-``>`` running lane max with earliest-chunk tie-keeping
    and final min-index reduce. Test-scale only (python loop over blocks).
    Returns [P, 128] int32 (token broadcast across lanes, like the kernel).
    """
    P, d = xp.shape
    n_v = heads.shape[-1] // block_v
    outs = []
    for g in range(P // block_r):
        rows = xp[g * block_r:(g + 1) * block_r]
        hid = int(hid_g[g])
        xf = rows.astype(jnp.float32)
        if norm_kind == "rmsnorm":
            y = xf * jax.lax.rsqrt(
                jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
        else:
            mu = jnp.mean(xf, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
            y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
        y = y * norm_scale.astype(jnp.float32)
        y = y + norm_bias.astype(jnp.float32)
        h = y.astype(xp.dtype).astype(jnp.float32)
        best = jnp.full((block_r, block_v), -jnp.inf, jnp.float32)
        bidx = jnp.zeros((block_r, block_v), jnp.int32)
        for v in range(n_v):
            logits = jnp.dot(
                h, heads[hid, :, v * block_v:(v + 1) * block_v].astype(
                    jnp.float32),
                preferred_element_type=jnp.float32)
            lane = v * block_v + jnp.arange(block_v, dtype=jnp.int32)[None, :]
            better = logits > best
            best = jnp.where(better, logits, best)
            bidx = jnp.where(better, lane, bidx)
        m = jnp.max(best, axis=-1, keepdims=True)
        tok = jnp.min(jnp.where(best == m, bidx, jnp.int32(2 ** 31 - 1)),
                      axis=-1, keepdims=True)
        outs.append(jnp.broadcast_to(tok, (block_r, 128)).astype(jnp.int32))
    return jnp.concatenate(outs, axis=0)
