"""Pallas TPU kernel: paged decode attention — one-token GQA against a
block-table-indexed page arena.

The paged pool stores every slot's KV rows in ``page_len``-row pages of a
global arena laid out ``[n_pages, n_kv, page_len, hd]``: one page of one
kv head is a ``[page_len, hd]`` tile, so a page is a stack of ``n_kv``
whole tiles and the kernel never relayouts it. A per-slot block table maps
logical row ``t`` to arena page ``bt[b, t // page_len]``. Dense decode
attention gathers the whole logical cache per step; here the grid walks
(sequence, page) and the scalar-prefetch block table drives the K/V
BlockSpec index maps, so each grid step streams exactly ONE page of K/V
into VMEM — never a materialized ``[B, nb * page_len, ...]`` gather — and
pages entirely past a sequence's position are skipped by a ``pl.when``
guard (their index maps still clamp to a valid page id, the pool's reserved
scratch page for short sequences).

Inside a page every kv head's ``g = nq / n_kv`` query heads are one 2-D
problem: scores ``[g, hd] x [page_len, hd]^T`` and context
``[g, page_len] x [page_len, hd]`` are plain MXU dots in the model dtype
with f32 accumulation — no batched 3-D contraction, no head repeat.

Grid: (B, nb) with the page dimension innermost, so each sequence's online
softmax (m / l / acc in VMEM scratch, f32, one row per query head) completes
before its epilogue. The oracle ``ref.paged_attention_ref`` mirrors the
blocked computation op-for-op; interpret mode is pinned **bit-for-bit in
sub-f32 dtypes** (bf16 — the ``q.dtype`` rounding barriers quantize away
fusion noise, exactly like the boundary kernel) and to a few f32 ulp
otherwise: XLA may rematerialize the interpreted kernel body with different
FMA fusion than the oracle's op-by-op eager execution, which f32 barriers
cannot quantize away (they are no-op casts).

``page_len`` must be a multiple of the model dtype's sublane tile (16 rows
for bf16, 8 for f32) — ``ops.paged_kernel_eligible``.

A sliding-window layer passes ``starts``, each sequence's first attended
row, as a third scalar-prefetch array: pages wholly below it are skipped
like pages past the position (their index maps clamp to the first page in
the window, so no DMA is issued for them), and rows below it are masked in
the first page read. Without ``starts`` the kernel is the full-attention
one, unchanged.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.boundary_mixed import mxu_precision

NEG_INF = -1e30

#: contract the last dim of both operands: ``[g, hd] x [plen, hd] -> [g, plen]``
_NT = (((1,), (1,)), ((), ()))


def _kernel(bt_ref, pos_ref, *refs, nb: int, plen: int, n_kv: int,
            scale: float):
    *st_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    windowed = bool(st_ref)            # a third scalar array: the starts
    b = pl.program_id(0)
    j = pl.program_id(1)
    pos_b = pos_ref[b]
    dt = q_ref.dtype
    prec = mxu_precision(dt)

    def barrier(x):
        # explicit rounding barriers at the score and probability hand-offs
        # (same trick as the boundary kernel's GEMM chunks): the q-dtype
        # casts pin compiled, interpret, and oracle paths bit-for-bit by
        # quantizing away fusion/FMA rounding differences
        return x.astype(dt).astype(jnp.float32)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # page j holds logical rows [j*plen, (j+1)*plen); skip pages that start
    # past the current position (page 0 always runs: row 0 <= pos) and, in
    # a window, pages that end before its first row
    live = j * plen <= pos_b
    if windowed:
        st_b = st_ref[0][b]
        live = jnp.logical_and(live, (j + 1) * plen > st_b)

    @pl.when(live)
    def _page():
        t_abs = j * plen + jax.lax.broadcasted_iota(jnp.int32, (1, plen), 1)
        keep = t_abs <= pos_b
        if windowed:
            keep = jnp.logical_and(keep, t_abs >= st_b)
        for h in range(n_kv):                      # static: one kv group
            s = barrier(jax.lax.dot_general(
                q_ref[0, h], k_ref[0, h], _NT, precision=prec,
                preferred_element_type=jnp.float32) * scale)   # [g, plen]
            s = jnp.where(keep, s, NEG_INF)
            m_old = m_scr[h]                                   # [g, 1]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            p = barrier(jnp.exp(s - m_new))                    # [g, plen]
            corr = barrier(jnp.exp(m_old - m_new))
            m_scr[h] = m_new
            l_scr[h] = barrier(l_scr[h] * corr) + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_scr[h] = barrier(acc_scr[h] * corr) + barrier(jnp.dot(
                p.astype(dt), v_ref[0, h], precision=prec,
                preferred_element_type=jnp.float32))           # [g, hd]

    @pl.when(j == nb - 1)
    def _epilogue():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_table, positions,
                    starts=None, *, interpret: bool = False):
    """Paged one-token GQA decode attention.

    q: [B, nq, hd] (rope already applied), ``k_pages``/``v_pages``:
    [n_pages, n_kv, page_len, hd] arenas with the current token's row
    already written, ``block_table``: [B, nb] int32 arena page ids,
    ``positions``: [B] int32 absolute positions, ``starts``: optional [B]
    int32 first attended row (a sliding window). Every page id must be a
    valid arena index (the pool guarantees this — unallocated table entries
    point at the reserved scratch page). Query head ``i`` reads kv head
    ``i // (nq / n_kv)``. Returns the attention context [B, nq, hd] in
    ``q.dtype`` (pre-``wo``).
    """
    B, nq, hd = q.shape
    n_pages, n_kv, plen, hd2 = k_pages.shape
    assert hd == hd2 and nq % n_kv == 0, (q.shape, k_pages.shape)
    nb = block_table.shape[1]
    g = nq // n_kv
    qg = q.reshape(B, n_kv, g, hd)                 # kv-group-major heads

    windowed = starts is not None
    scalars = [block_table, positions] + ([starts] if windowed else [])

    def page_ix(b, j, bt, pos, *st):
        if windowed:                   # pages below the window: no new DMA
            j = jnp.maximum(j, st[0][b] // plen)
        return (bt[b, j], 0, 0, 0)

    def row_ix(b, j, *_):
        return (b, 0, 0, 0)

    page = pl.BlockSpec((1, n_kv, plen, hd), page_ix)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, n_kv, g, hd), row_ix),
            page,
            page,
        ],
        out_specs=pl.BlockSpec((1, n_kv, g, hd), row_ix),
        scratch_shapes=[
            pltpu.VMEM((n_kv, g, 1), jnp.float32),     # running max
            pltpu.VMEM((n_kv, g, 1), jnp.float32),     # running denominator
            pltpu.VMEM((n_kv, g, hd), jnp.float32),    # context accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, nb=nb, plen=plen, n_kv=n_kv,
                          scale=1.0 / math.sqrt(hd)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv, g, hd), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(*(a.astype(jnp.int32) for a in scalars), qg, k_pages, v_pages)
    return out.reshape(B, nq, hd)
