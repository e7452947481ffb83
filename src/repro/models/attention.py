"""GQA multi-head attention with causal / sliding-window masking and a
decode-time KV cache (rolling buffer for SWA/local-attention archs).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init

NEG_INF = -1e30


def attn_init(key, d: int, n_q: int, n_kv: int, hd: int, *,
              qkv_bias: bool = False, dtype=jnp.bfloat16):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d, n_q * hd, bias=qkv_bias, dtype=dtype),
        "wk": dense_init(kk, d, n_kv * hd, bias=qkv_bias, dtype=dtype),
        "wv": dense_init(kv, d, n_kv * hd, bias=qkv_bias, dtype=dtype),
        "wo": dense_init(ko, n_q * hd, d, dtype=dtype),
    }


def _project_qkv(p, x, n_q, n_kv, hd):
    B, S = x.shape[:2]
    q = (x @ p["wq"]["w"]).reshape(B, S, n_q, hd)
    k = (x @ p["wk"]["w"]).reshape(B, S, n_kv, hd)
    v = (x @ p["wv"]["w"]).reshape(B, S, n_kv, hd)
    if "b" in p["wq"]:
        q = q + p["wq"]["b"].reshape(n_q, hd)
        k = k + p["wk"]["b"].reshape(n_kv, hd)
        v = v + p["wv"]["b"].reshape(n_kv, hd)
    return q, k, v


def _gqa_scores(q, k):
    """q: [B,S,nq,hd], k: [B,T,nkv,hd] -> [B,nkv,G,S,T] without materializing
    repeated KV heads."""
    B, S, n_q, hd = q.shape
    n_kv = k.shape[2]
    g = n_q // n_kv
    qg = q.reshape(B, S, n_kv, g, hd)
    return jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                      k.astype(jnp.float32))


def _gqa_out(probs, v):
    """probs: [B,nkv,G,S,T], v: [B,T,nkv,hd] -> [B,S,nq*hd]."""
    B, n_kv, g, S, T = probs.shape
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(B, S, n_kv * g * v.shape[-1])


def _windowed(mask, i, j, window):
    """``mask`` restricted to keys ``j`` inside the sliding window of
    queries ``i`` (``j > i - window``). A Python int window is the one
    window of the whole model (0: none); an array is one layer's own,
    scanned over the layers (0: a full layer)."""
    if isinstance(window, int):
        return mask & (j > i - window) if window else mask
    return mask & ((window <= 0) | (j > i - window))


def window_start(pos, window):
    """First row a query at ``pos`` attends through ``window`` (0 when the
    window is 0)."""
    return jnp.where(window > 0, jnp.maximum(pos - window + 1, 0), 0)


# sequences at or above this length use the blocked online-softmax path
# (bounded memory — the pure-JAX analogue of flash/splash attention, which is
# what a real TPU deployment would run for 32k prefill)
BLOCKED_ATTN_THRESHOLD = 2048
_BLOCK_Q = 512
_BLOCK_K = 512


def _dense_attention(q, k, v, positions, hd, window):
    scores = _gqa_scores(q, k) / math.sqrt(hd)   # [B,kv,G,S,T] fp32
    i = positions[:, None, None, :, None]        # query pos
    j = positions[:, None, None, None, :]        # key pos
    mask = _windowed(j <= i, i, j, window)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(probs, v)


def _blocked_attention(q, k, v, positions, hd, window,
                       block_q: int = _BLOCK_Q, block_k: int = _BLOCK_K):
    """Online-softmax attention over [block_q x block_k] tiles; peak memory
    is O(S * block_k) instead of O(S^2)."""
    B, S, n_q_heads, _ = q.shape
    n_kv = k.shape[2]
    g = n_q_heads // n_kv
    nq, nk = S // block_q, S // block_k
    qb = q.reshape(B, nq, block_q, n_kv, g, hd)
    kb = k.reshape(B, nk, block_k, n_kv, hd)
    vb = v.reshape(B, nk, block_k, n_kv, hd)
    pos_q = positions.reshape(B, nq, block_q)
    pos_k = positions.reshape(B, nk, block_k)
    scale = 1.0 / math.sqrt(hd)

    def q_block(qi, q_i, pq_i):
        # q_i: [B, block_q, n_kv, g, hd]; pq_i: [B, block_q]
        qf = q_i.astype(jnp.float32)

        def kv_step(carry, inp):
            m, l, acc = carry
            k_j, v_j, pk_j = inp                 # [B,block_k,n_kv,hd], pos
            s = jnp.einsum("bqkgh,btkh->bkgqt", qf,
                           k_j.astype(jnp.float32)) * scale
            i_ = pq_i[:, None, None, :, None]
            j_ = pk_j[:, None, None, None, :]
            mask = _windowed(j_ <= i_, i_, j_, window)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkh->bkgqh", p, v_j.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((B, n_kv, g, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, n_kv, g, block_q), jnp.float32)
        a0 = jnp.zeros((B, n_kv, g, block_q, hd), jnp.float32)
        kv_xs = (kb.swapaxes(0, 1), vb.swapaxes(0, 1), pos_k.swapaxes(0, 1))
        step = jax.checkpoint(kv_step,
                              policy=jax.checkpoint_policies.nothing_saveable)
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), kv_xs)
        out = acc / jnp.maximum(l, 1e-30)[..., None]   # [B,kv,g,bq,hd]
        return out.transpose(0, 3, 1, 2, 4).reshape(B, block_q, n_kv * g * hd)

    outs = jax.lax.map(
        lambda i: q_block(i, qb[:, i], pos_q[:, i]), jnp.arange(nq))
    # [nq, B, block_q, n_heads*hd] -> [B, S, n_heads*hd]
    return outs.swapaxes(0, 1).reshape(B, S, n_q_heads * hd)


def full_attention(p, x, positions, *, n_q: int, n_kv: int, hd: int,
                   rope_theta: float, window=0, rope=None):
    """Train / prefill path: full causal (optionally sliding-window) attention.

    x: [B, S, d]; positions: [B, S] absolute token positions. ``window``
    and ``rope`` (a rotary table, ``layers.apply_rope``) may be one layer's
    own arrays.
    """
    S = x.shape[1]
    q, k, v = _project_qkv(p, x, n_q, n_kv, hd)
    q = apply_rope(q, positions, rope_theta, rope)
    k = apply_rope(k, positions, rope_theta, rope)

    if S >= BLOCKED_ATTN_THRESHOLD and S % _BLOCK_Q == 0 \
            and S % _BLOCK_K == 0:
        out = _blocked_attention(q, k, v, positions, hd, window)
    else:
        out = _dense_attention(q, k, v, positions, hd, window)
    return out.astype(x.dtype) @ p["wo"]["w"]


def prefill_attention(p, x, positions, cache, *, n_q: int, n_kv: int,
                      hd: int, rope_theta: float, window=0, lengths=None,
                      rope=None):
    """Full-sequence prefill that also populates the decode cache.

    Runs causal (optionally sliding-window) attention over the whole prompt
    in ONE pass and scatters each sequence's K/V rows into its rolling cache
    slots — the batched replacement for feeding the prompt through
    ``decode_attention`` token by token.

    x: [B, S, d]; positions: [B, S]; ``lengths``: optional [B] true prompt
    lengths when the batch is right-padded to a bucket length (pad positions
    are never written to the cache and, being *after* every real position,
    are masked out of real queries by causality).
    Returns (out [B, S, d], populated cache).
    """
    B, S = x.shape[:2]
    clen = cache["k"].shape[1]
    q, k, v = _project_qkv(p, x, n_q, n_kv, hd)
    q = apply_rope(q, positions, rope_theta, rope)
    k = apply_rope(k, positions, rope_theta, rope)
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)

    quantized = "k_s" in cache
    if quantized:
        from repro.core import quant as Q
        kq, ks = Q.quantize(k, 8)
        vq, vs = Q.quantize(v, 8)
        # attend the dequantized values so prefill matches what decode will
        # read back from the int8 cache
        k_att = (kq.astype(jnp.float32) * ks).astype(k.dtype)
        v_att = (vq.astype(jnp.float32) * vs).astype(v.dtype)
    else:
        k_att, v_att = k, v

    # decode can only ever see the last ``clen`` positions, so cap the
    # prefill window to the cache (clen == window for SWA archs by
    # construction; full-attention archs rely on the engine's capacity rule
    # to keep S <= clen); a per-layer window array never rolls the cache
    w_eff = min(window, clen) if isinstance(window, int) and window \
        else window
    if S >= BLOCKED_ATTN_THRESHOLD and S % _BLOCK_Q == 0 \
            and S % _BLOCK_K == 0:
        out = _blocked_attention(q, k_att, v_att, positions, hd, w_eff)
    else:
        out = _dense_attention(q, k_att, v_att, positions, hd, w_eff)

    # scatter each row's last min(len, clen) REAL positions into its rolling
    # cache slot; invalid rows get the out-of-bounds index clen, which the
    # scatter drops — identical end state to sequential per-token writes
    keep = min(S, clen)
    idx = lengths[:, None] - keep + jnp.arange(keep)[None, :]     # [B, keep]
    valid = idx >= 0
    idx_c = jnp.clip(idx, 0, S - 1)
    pos_g = jnp.take_along_axis(positions, idx_c, axis=1)
    slot = jnp.where(valid, jnp.mod(pos_g, clen), clen)
    b_ix = jnp.arange(B)[:, None]

    def gather_rows(a):
        return jnp.take_along_axis(a, idx_c[:, :, None, None], axis=1)

    def scatter(buf, rows):
        return buf.at[b_ix, slot].set(rows, mode="drop")

    if quantized:
        new_cache = {
            "k": scatter(cache["k"], gather_rows(kq)),
            "k_s": scatter(cache["k_s"], gather_rows(ks)),
            "v": scatter(cache["v"], gather_rows(vq)),
            "v_s": scatter(cache["v_s"], gather_rows(vs)),
        }
    else:
        new_cache = {"k": scatter(cache["k"], gather_rows(k)),
                     "v": scatter(cache["v"], gather_rows(v))}
    return out.astype(x.dtype) @ p["wo"]["w"], new_cache


def init_cache(batch: int, n_kv: int, hd: int, cache_len: int,
               dtype=jnp.bfloat16, kv_bits: int = 0):
    """Per-layer rolling KV cache. ``cache_len`` = window for SWA archs,
    full context otherwise.

    ``kv_bits=8``: store int8 codes + per-(pos, head) fp32 scales instead of
    bf16 — halves the decode memory-roofline term, which dominates the
    32k-decode shapes (EXPERIMENTS.md §Perf decode addendum). The decode
    path dispatches on the presence of the scale leaves."""
    if kv_bits == 0:
        return {
            "k": jnp.zeros((batch, cache_len, n_kv, hd), dtype=dtype),
            "v": jnp.zeros((batch, cache_len, n_kv, hd), dtype=dtype),
        }
    assert kv_bits == 8, kv_bits
    return {
        "k": jnp.zeros((batch, cache_len, n_kv, hd), dtype=jnp.int8),
        "k_s": jnp.zeros((batch, cache_len, n_kv, 1), dtype=jnp.float32),
        "v": jnp.zeros((batch, cache_len, n_kv, hd), dtype=jnp.int8),
        "v_s": jnp.zeros((batch, cache_len, n_kv, 1), dtype=jnp.float32),
    }


def paged_prefill_attention(p, x, positions, arena, block_table, *,
                            n_q: int, n_kv: int, hd: int, rope_theta: float,
                            lengths=None, window=0, rope=None):
    """Full-sequence prefill that scatters K/V rows through a block table
    into a paged arena instead of ``mod(pos, cache_len)`` rolling slots.

    ``arena``: per-layer ``{"k","v"}`` leaves of shape
    ``[n_pages, n_kv, page_len, hd]`` shared by every slot; ``block_table``:
    ``[B, nb]`` page ids, one row per sequence, covering at least
    ``ceil(length / page_len)`` pages. Pad rows (``s >= lengths[b]``) get an
    out-of-bounds page index and are dropped by the scatter, mirroring the
    dense prefill's drop trick. Attention itself never reads the cache, so
    the output is identical to :func:`prefill_attention` on the same prompt.
    A sliding-window layer (``window``) masks keys behind its window; every
    row still goes to the arena, whose table all layers share.
    """
    B, S = x.shape[:2]
    n_pages, plen = arena["k"].shape[0], arena["k"].shape[2]
    nb = block_table.shape[1]
    q, k, v = _project_qkv(p, x, n_q, n_kv, hd)
    q = apply_rope(q, positions, rope_theta, rope)
    k = apply_rope(k, positions, rope_theta, rope)
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)

    if S >= BLOCKED_ATTN_THRESHOLD and S % _BLOCK_Q == 0 \
            and S % _BLOCK_K == 0:
        out = _blocked_attention(q, k, v, positions, hd, window)
    else:
        out = _dense_attention(q, k, v, positions, hd, window)

    valid = jnp.arange(S)[None, :] < lengths[:, None]             # [B, S]
    pg_ix = jnp.clip(positions // plen, 0, nb - 1)
    pg = jnp.where(valid,
                   block_table[jnp.arange(B)[:, None], pg_ix], n_pages)
    row = jnp.mod(positions, plen)
    new_arena = {"k": arena["k"].at[pg, :, row].set(k, mode="drop"),
                 "v": arena["v"].at[pg, :, row].set(v, mode="drop")}
    return out.astype(x.dtype) @ p["wo"]["w"], new_arena


def paged_decode_attention(p, x, arena, block_table, cur_pos, *, n_q: int,
                           n_kv: int, hd: int, rope_theta: float, window=0,
                           rope=None):
    """One-token decode against a paged arena through a block table.

    x: [B, 1, d]; cur_pos: [B] per-sequence absolute positions; ``arena``
    leaves ``[n_pages, n_kv, page_len, hd]``; ``block_table`` ``[B, nb]``.
    The caller guarantees the page holding row ``cur_pos`` is allocated for
    every live sequence; idle sequences carry all-zero block-table rows, so
    their drifting writes land in the reserved scratch page 0 (never read
    unmasked). Gathers the table's pages into logical row order — row ``t``
    is absolute position ``t``; full attention never wraps — and applies
    the exact dense-path score/mask/softmax ops, so on equal logical
    capacity the output is bit-identical to :func:`decode_attention`.
    A sliding-window layer (``window``, a layer's own array or a nonzero
    int) attends rows ``pos - window + 1 .. pos`` only: the kernel skips
    the pages wholly below the window.
    Returns (out [B,1,d], updated arena).
    """
    B = x.shape[0]
    plen = arena["k"].shape[2]
    nb = block_table.shape[1]
    q, k, v = _project_qkv(p, x, n_q, n_kv, hd)
    pos = jnp.asarray(cur_pos, dtype=jnp.int32).reshape(B, 1)
    q = apply_rope(q, pos, rope_theta, rope)
    k = apply_rope(k, pos, rope_theta, rope)
    starts = (None if isinstance(window, int) and not window
              else window_start(pos[:, 0], window))

    pg = block_table[jnp.arange(B), jnp.clip(pos[:, 0] // plen, 0, nb - 1)]
    row = jnp.mod(pos[:, 0], plen)
    new_arena = {"k": arena["k"].at[pg, :, row].set(k[:, 0]),
                 "v": arena["v"].at[pg, :, row].set(v[:, 0])}

    from repro.kernels import ops as K
    if K.paged_kernel_eligible(n_q=n_q, n_kv=n_kv, hd=hd, page_len=plen,
                               dtype=q.dtype):
        ctx = K.paged_attention_op(q[:, 0], new_arena["k"], new_arena["v"],
                                   block_table, pos[:, 0], starts)
        out = ctx.reshape(B, 1, n_q * hd).astype(x.dtype)
    else:
        def logical(a):                # [B, nb, n_kv, plen, hd] -> rows
            return a[block_table].swapaxes(2, 3).reshape(
                B, nb * plen, n_kv, hd)
        ck, cv = logical(new_arena["k"]), logical(new_arena["v"])
        scores = _gqa_scores(q, ck) / math.sqrt(hd)   # [B,kv,G,1,T]
        t = jnp.arange(nb * plen)
        n_fill = jnp.minimum(pos[:, 0] + 1, nb * plen)
        written = t[None, :] < n_fill[:, None]            # [B, T]
        if starts is not None:
            written &= t[None, :] >= starts[:, None]
        scores = jnp.where(written[:, None, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(probs, cv).astype(x.dtype)
    return out @ p["wo"]["w"], new_arena


def decode_attention(p, x, cache, cur_pos, *, n_q: int, n_kv: int, hd: int,
                     rope_theta: float, window=0, rope=None):
    """One-token decode against the cache.

    x: [B, 1, d]; cur_pos: scalar int32 (all sequences aligned, as in
    synchronous batched serving) or a [B] vector of per-sequence absolute
    positions (continuous batching: each slot is at its own depth).
    An int ``window`` is the model's one window, the cache's length; a
    layer's own window array masks keys behind it in a cache that never
    wraps (slot t holds position t).
    Returns (out [B,1,d], updated cache).
    """
    B = x.shape[0]
    cache_len = cache["k"].shape[1]
    q, k, v = _project_qkv(p, x, n_q, n_kv, hd)
    cur_pos = jnp.asarray(cur_pos, dtype=jnp.int32)
    ragged = cur_pos.ndim == 1
    pos = cur_pos.reshape(B, 1) if ragged \
        else jnp.full((B, 1), cur_pos, dtype=jnp.int32)
    q = apply_rope(q, pos, rope_theta, rope)
    k = apply_rope(k, pos, rope_theta, rope)

    slot = jnp.mod(pos[:, 0], cache_len) if ragged \
        else jnp.mod(cur_pos, cache_len)          # rolling for SWA

    def store(buf, new):
        """Write the new token's row at each sequence's own cache slot."""
        if ragged:
            return buf.at[jnp.arange(B), slot].set(new[:, 0])
        return jax.lax.dynamic_update_slice_in_dim(buf, new, slot, axis=1)

    quantized = "k_s" in cache
    if quantized:
        from repro.core import quant as Q
        kq, ks = Q.quantize(k, 8)
        vq, vs = Q.quantize(v, 8)
        new_cache = {
            "k": store(cache["k"], kq),
            "k_s": store(cache["k_s"], ks),
            "v": store(cache["v"], vq),
            "v_s": store(cache["v_s"], vs),
        }
        ck = (new_cache["k"].astype(jnp.float32) * new_cache["k_s"]
              ).astype(k.dtype)
        cv = (new_cache["v"].astype(jnp.float32) * new_cache["v_s"]
              ).astype(v.dtype)
    else:
        ck = store(cache["k"], k)
        cv = store(cache["v"], v)

    scores = _gqa_scores(q, ck) / math.sqrt(hd)   # [B,kv,G,1,T]
    # slot t holds absolute position: t if t<=slot else t + cache_len*(n_wraps)
    # validity: a slot is attendable iff its absolute position is in
    # (cur_pos - effective_window, cur_pos]. With the rolling cache of size
    # cache_len == min(window, ctx) every written slot is within the window
    # by construction, so the mask reduces to "has been written".
    t = jnp.arange(cache_len)
    n_fill = jnp.minimum(pos[:, 0] + 1, cache_len)    # valid slots per seq
    written = t[None, :] < n_fill[:, None]            # [B, T]
    if not isinstance(window, int):
        written &= t[None, :] >= window_start(pos, window)
    scores = jnp.where(written[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, cv).astype(x.dtype)
    return out @ p["wo"]["w"], (new_cache if quantized
                                else {"k": ck, "v": cv})
