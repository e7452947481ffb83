"""Jit'd public wrappers for the Pallas kernels: shape-padding, block-size
selection, and CPU (interpret-mode) dispatch so the same call sites work in
tests and on real TPUs.

Every dispatcher decides its path when the call is traced (never at
import): Pallas on a TPU, the jnp reference on CPU, or a path forced by
``interpret``. On a TPU a shape the kernel cannot tile still takes the jnp
reference, but never silently: the miss is counted in :data:`FALLBACKS`
and warned about, and ``chip_smoke.py`` fails on any entry.
"""
from __future__ import annotations

import collections
import functools
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import bottleneck_quant as _bq
from repro.kernels import boundary_mixed as _bm
from repro.kernels import dequant_matmul as _dq
from repro.kernels import moe_gmm as _mg
from repro.kernels import paged_attention as _pa
from repro.kernels import rglru_scan as _rs
from repro.kernels import ref

#: ``(kernel, reason) -> count`` of traced calls that took the jnp reference
#: on a TPU because the kernel could not tile the shape.
FALLBACKS: collections.Counter = collections.Counter()


def on_tpu() -> bool:
    """Whether the default backend is a TPU (asked at trace time)."""
    return jax.default_backend() == "tpu"


def _route(kernel: str, interpret, misaligned: str = "") -> tuple:
    """(use_pallas, interpret_mode) for one traced dispatcher call.

    ``interpret=None``: Pallas compiled on a TPU, the jnp reference
    elsewhere. ``True``/``False`` force the interpreted kernel / the
    reference (tests). ``misaligned`` names why the kernel cannot take this
    shape; the call then takes the reference, and on a TPU that is recorded
    in :data:`FALLBACKS`."""
    tpu = on_tpu()
    use = tpu if interpret is None else bool(interpret)
    if use and misaligned:
        if interpret is None:
            _note_fallback(kernel, misaligned)
        return False, False
    return use, (not tpu if interpret is None else bool(interpret))


def _note_fallback(kernel: str, reason: str):
    """Record (on a TPU) that ``kernel`` took its jnp reference."""
    if on_tpu():
        FALLBACKS[(kernel, reason)] += 1
        warnings.warn(f"{kernel}: jnp reference on TPU ({reason})",
                      stacklevel=3)


def _pick_block(dim: int, preferred: int, align: int = 128) -> int:
    """Largest block <= preferred that divides dim, preferring MXU-aligned."""
    for b in (preferred, preferred // 2, preferred // 4, align):
        if b and dim % b == 0:
            return b
    for b in range(min(preferred, dim), 0, -1):
        if dim % b == 0:
            return b
    return dim


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def bottleneck_quant_op(x, w, *, bits: int = 8, interpret: bool | None = None):
    """Fused down-proj + int8 quantize. x: [..., K], w: [K, N]."""
    lead = x.shape[:-1]
    M = 1
    for s in lead:
        M *= s
    K, N = w.shape
    x2 = x.reshape(M, K)
    bm = _pick_block(M, 128)
    bk = _pick_block(K, 512)
    interp = not on_tpu() if interpret is None else interpret
    if M % bm or K % bk or N % 128:
        if interpret is None:
            _note_fallback("bottleneck_quant", f"M={M} K={K} N={N}")
        codes, scales = ref.bottleneck_quant_ref(x2, w, bits)
    else:
        codes, scales = _bq.bottleneck_quant(x2, w, bits=bits, block_m=bm,
                                             block_k=bk, interpret=interp)
    return codes.reshape(*lead, N), scales.reshape(*lead, 1)


def _group_rows(mode_idx, n_modes: int, block_r: int):
    """Mode-uniform row-block layout for the fused boundary kernel.

    Rows are stably sorted by mode and each mode's run is padded up to a
    multiple of ``block_r``, so every ``block_r``-row block of the permuted
    layout carries exactly one mode. Returns (dest [B] int32 — each row's
    slot in the padded layout, starts [n_modes] int32 — each mode's padded
    offset, total padded row count P). P is static:
    ``(ceil(B / block_r) + n_modes) * block_r`` always suffices, because
    each mode group wastes at most ``block_r - 1`` pad rows.
    """
    B = mode_idx.shape[0]
    order = jnp.argsort(mode_idx)                       # stable in jax
    counts = jnp.zeros(n_modes, jnp.int32).at[mode_idx].add(1)
    padded = ((counts + block_r - 1) // block_r) * block_r
    starts = jnp.cumsum(padded) - padded                # exclusive cumsum
    cum = jnp.cumsum(counts) - counts
    sortedm = mode_idx[order]
    rank = jnp.arange(B, dtype=jnp.int32) - cum[sortedm]
    dest = jnp.zeros(B, jnp.int32).at[order].set(
        (starts[sortedm] + rank).astype(jnp.int32))
    P = (-(-B // block_r) + n_modes) * block_r
    return dest, starts, padded, P


def boundary_mixed_op(stacked, x, mode_idx, *, dtype=jnp.bfloat16,
                      interpret: bool | None = None):
    """Fused mixed-mode bottleneck boundary (dispatcher).

    Deliberately NOT jitted itself: every serving caller already invokes it
    inside a jitted step (where it traces straight through), and wrapping a
    jit here would change eager callers' op-by-op bf16 rounding against the
    pinned per-mode reference path.

    x: [B, S, d] boundary activations, ``mode_idx``: [B] int32 in [0, M]
    (0 = raw passthrough, m >= 1 = head m-1 of the ``stacked`` bank).
    Routes to the Pallas kernel on TPU (or when ``interpret=True`` — the
    CPU correctness path for tests); everything else — including
    non-128-aligned model/bank widths — takes the jnp reference, which is
    also the fast CPU serving path (interpret mode is a correctness tool,
    not a speed tool).
    """
    d = x.shape[-1]
    M, _, wmax = stacked["down_w"].shape
    use_pallas, interp = _route(
        "boundary_mixed", interpret,
        f"d={d} wmax={wmax} not multiples of 128" if d % 128 or wmax % 128
        else "")
    if not use_pallas:
        return ref.boundary_mixed_ref(stacked, x, mode_idx, dtype=dtype)

    B, S = x.shape[0], x.shape[1]
    block_r = 16 if jnp.dtype(x.dtype).itemsize == 2 else 8
    block_w = 128
    rmode = jnp.repeat(mode_idx.astype(jnp.int32), S)   # per-token mode
    dest, tables = group_layout(stacked, rmode, block_r, block_w)
    xp = jnp.zeros((tables["P"], d), x.dtype).at[dest].set(
        x.reshape(B * S, d))
    yp = _bm.boundary_mixed_grouped(
        xp, stacked["down_w"], stacked["up_w"], stacked["norm_scale"],
        tables["hid"], tables["nchunk"], tables["width"], tables["bits"],
        block_r=block_r, block_w=block_w, dtype=dtype, interpret=interp)
    return yp[dest].reshape(B, S, d)


def boundary_mixed_sharded(stacked, x, mode_idx, mesh, *,
                           dtype=jnp.bfloat16,
                           interpret: bool | None = None):
    """``boundary_mixed_op`` on a serving mesh, run per-shard inside a
    fully-manual ``shard_map`` region with every operand replicated.

    Replicated-in / replicated-out looks like a no-op, but it is the
    bit-identity fix: the reference path's batched gather-einsum lowers
    differently on CPU depending on the (sharded) batch extent, so letting
    GSPMD partition this op makes a dp-sharded step diverge from the
    unsharded engine at the last mantissa bits. Pinning the whole boundary
    to one replicated manual region makes every shard compute the same
    full-batch result with single-device lowering — the Pallas/CPU dispatch
    and unaligned fallbacks inside ``boundary_mixed_op`` run per-shard,
    untouched. A plain ``with_sharding_constraint`` does NOT achieve this
    (the partitioner still specializes the lowering)."""
    from jax.sharding import PartitionSpec as P

    from repro.models.sharding import shard_map

    fn = shard_map(
        lambda s, xx, mm: boundary_mixed_op(s, xx, mm, dtype=dtype,
                                            interpret=interpret),
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), stacked), P(), P()),
        out_specs=P())
    return fn(stacked, x, mode_idx)


def group_layout(stacked, rmode, block_r: int, block_w: int):
    """Row permutation + per-block tables for the grouped boundary kernel.

    ``rmode``: [rows] int32 mode per row. Returns (dest [rows] int32 — each
    row's slot in the mode-grouped padded layout, tables) where tables has
    the static padded row count ``P`` and per-row-block int32 arrays:
    ``hid`` (stacked-bank head), ``nchunk`` (width chunks; 0 = raw
    passthrough), ``width``, ``bits``. Blocks past the used span behave as
    raw rows and are never gathered back.
    """
    M = stacked["width"].shape[0]
    dest, starts, padded, P = _group_rows(rmode, M + 1, block_r)
    G = P // block_r
    bstart = jnp.arange(G, dtype=jnp.int32) * block_r
    used = bstart < jnp.sum(padded)
    bmode = jnp.clip(jnp.searchsorted(starts, bstart, side="right") - 1,
                     0, M)
    bmode = jnp.where(used, bmode, 0).astype(jnp.int32)
    hid_g = jnp.clip(bmode - 1, 0, M - 1).astype(jnp.int32)
    width_g = jnp.where(bmode >= 1, stacked["width"][hid_g], 0)
    bits_g = jnp.where(bmode >= 1, stacked["bits"][hid_g], 0)
    nchunk_g = (width_g + block_w - 1) // block_w
    return dest, {"P": P, "hid": hid_g,
                  "nchunk": nchunk_g.astype(jnp.int32),
                  "width": width_g.astype(jnp.int32),
                  "bits": bits_g.astype(jnp.int32)}


def head_layout(head_idx, n_heads: int, block_r: int):
    """Head-uniform row-block layout for the fused decode-tail kernel.

    Same machinery as ``group_layout`` but keyed by LM-head row instead of
    bottleneck mode: rows are stably sorted by head and padded so every
    ``block_r``-row block gathers exactly one head. Returns (dest [rows]
    int32, hid_g [P/block_r] int32, static padded row count P). Blocks past
    the used span read head 0 and are never gathered back.
    """
    dest, starts, padded, P = _group_rows(head_idx, n_heads, block_r)
    G = P // block_r
    bstart = jnp.arange(G, dtype=jnp.int32) * block_r
    used = bstart < jnp.sum(padded)
    hid_g = jnp.clip(jnp.searchsorted(starts, bstart, side="right") - 1,
                     0, n_heads - 1)
    hid_g = jnp.where(used, hid_g, 0).astype(jnp.int32)
    return dest, hid_g, P


def decode_tail_op(x, norm_scale, norm_bias, heads, head_idx=None, *,
                   norm_kind: str = "rmsnorm", tied: bool = False,
                   interpret: bool | None = None):
    """Fused decode tail: final norm -> LM-head gather -> argmax -> int32
    token, in ONE kernel (dispatcher). Together with ``boundary_mixed_op``
    this makes the device-resident serving tick exactly two kernels — the
    f32 logits never leave VMEM.

    Deliberately NOT jitted itself, for the same reason as the boundary op:
    serving callers trace it inside a jitted step, and eager callers keep
    the pinned op-by-op numerics of the legacy norm/lm_logits/argmax chain.

    x: [B, S, d] decoder output; ``heads``: [H, d, V] stacked LM heads (or
    the [1, V, d] embedding table when ``tied`` — transposed on the kernel
    path only); ``head_idx``: [B] int32 per-row head, None = head 0.
    Routes to the Pallas kernel on TPU (or ``interpret=True`` for tests);
    CPU and non-128-aligned d/V take :func:`ref.decode_tail_ref`, which is
    expression-identical to the legacy chain. Returns int32 tokens [B, S].
    """
    B, S, d = x.shape
    V = heads.shape[1] if tied else heads.shape[2]
    use_pallas, interp = _route(
        "decode_tail", interpret,
        f"d={d} V={V} not multiples of 128" if d % 128 or V % 128 else "")
    if not use_pallas:
        return ref.decode_tail_ref(x, norm_scale, norm_bias, heads, head_idx,
                                   norm_kind=norm_kind, tied=tied)
    hv = jnp.swapaxes(heads, 1, 2) if tied else heads
    H = hv.shape[0]
    hidx = jnp.zeros(B, jnp.int32) if head_idx is None \
        else head_idx.astype(jnp.int32)
    rhid = jnp.repeat(hidx, S)                          # per-token head
    block_r = 16 if jnp.dtype(x.dtype).itemsize == 2 else 8
    dest, hid_g, P = head_layout(rhid, H, block_r)
    xp = jnp.zeros((P, d), x.dtype).at[dest].set(x.reshape(B * S, d))
    bias = norm_bias if norm_bias is not None \
        else jnp.zeros((d,), norm_scale.dtype)
    tokp = _bm.decode_tail_grouped(
        xp, hv, norm_scale, bias, hid_g, block_r=block_r,
        block_v=_pick_block(V, 512), norm_kind=norm_kind, interpret=interp)
    return tokp[dest, 0].reshape(B, S)


def sublane_tile(dtype) -> int:
    """Rows of one TPU vreg tile: 8 for 32-bit dtypes, 16 for bf16."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def paged_kernel_eligible(*, n_q: int, n_kv: int, hd: int, page_len: int,
                          dtype=jnp.bfloat16) -> bool:
    """Whether the serving decode path routes paged attention through the
    Pallas kernel: on a TPU, when one page of one kv head (``[page_len,
    hd]``) is whole tiles of ``dtype``. Elsewhere the model layer's
    logical-gather jnp path is both the fast path and the one pinned
    bit-identical to dense decode (interpret mode is a correctness tool,
    not a speed tool); on a TPU a shape that misses is a recorded
    fallback."""
    if hd % 128 or page_len % sublane_tile(dtype):
        why = f"page [{page_len}, {hd}] is not whole {jnp.dtype(dtype)} tiles"
    elif n_q % n_kv:
        why = f"n_q={n_q} is not a multiple of n_kv={n_kv}"
    else:
        why = ""
    return _route("paged_attention", None, why)[0]


def paged_attention_op(q, k_pages, v_pages, block_table, positions,
                       starts=None, *, interpret: bool | None = None):
    """Paged decode attention (dispatcher) — the Pallas kernel, compiled on
    a TPU or interpreted elsewhere. Deliberately NOT jitted itself —
    serving callers invoke it inside a jitted step, like the boundary op,
    once :func:`paged_kernel_eligible` has accepted the shapes.

    q: [B, nq, hd] (rope applied), ``k_pages``/``v_pages``:
    [n_pages, n_kv, page_len, hd], ``block_table``: [B, nb] arena page ids,
    ``positions``: [B], ``starts``: optional [B] first attended row of a
    sliding-window layer. Returns the attention context [B, nq, hd] in
    ``q.dtype`` (pre-``wo``)."""
    interp = not on_tpu() if interpret is None else bool(interpret)
    return _pa.paged_attention(q, k_pages, v_pages, block_table, positions,
                               starts, interpret=interp)


def moe_gmm_op(x, w_gate, w_up, w_down, group_sizes, *, act: str = "silu",
               name: str = "moe_gmm", interpret: bool | None = None):
    """Grouped expert MLP over rows sorted by held expert (dispatcher).
    Not jitted itself: the served MoE layer traces it inside its step.

    x: [M, d]; ``w_gate``/``w_up``: [G, d, f], ``w_down``: [G, f, d];
    ``group_sizes``: [G] int32 rows of each held expert, in order from row
    0; rows past their sum belong to experts not held and come back zero.
    Routes to the Pallas kernel on a TPU (or ``interpret=True``); the CPU
    and widths that are not whole 128-lane tiles take
    :func:`ref.moe_gmm_ref` (``jax.lax.ragged_dot``). ``name`` tells call
    sites apart in a profile. Returns [M, d] float32."""
    M, d = x.shape
    f = w_gate.shape[2]
    use_pallas, interp = _route(
        "moe_gmm", interpret,
        f"d={d} f={f} not multiples of 128" if d % 128 or f % 128 else "")
    if not use_pallas:
        return ref.moe_gmm_ref(x, w_gate, w_up, w_down, group_sizes, act=act)
    tm = 128 if M <= 4096 else 256
    Mp = -(-M // tm) * tm
    xp = jnp.pad(x, ((0, Mp - M), (0, 0)))
    sizes = jnp.concatenate([group_sizes.astype(jnp.int32),
                             (Mp - jnp.sum(group_sizes)).astype(
                                 jnp.int32)[None]])
    y = _mg.moe_gmm(xp, w_gate, w_up, w_down, sizes, act=act, tm=tm,
                    tf=128, name=name, interpret=interp)
    return y[:M]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_matmul_op(codes, scales, w, *, interpret: bool | None = None):
    """Fused dequant + up-proj. codes: [..., N] int8 -> [..., D] bf16."""
    lead = codes.shape[:-1]
    M = 1
    for s in lead:
        M *= s
    N, D = w.shape
    c2 = codes.reshape(M, N)
    s2 = scales.reshape(M, 1)
    bm = _pick_block(M, 128)
    bd = _pick_block(D, 512)
    interp = not on_tpu() if interpret is None else interpret
    if M % bm or D % bd or N % 128:
        if interpret is None:
            _note_fallback("dequant_matmul", f"M={M} N={N} D={D}")
        y = ref.dequant_matmul_ref(c2, s2, w)
    else:
        y = _dq.dequant_matmul(c2, s2, w, block_m=bm, block_d=bd,
                               interpret=interp)
    return y.reshape(*lead, D)


def rglru_scan_op(a, b, h0=None, *, interpret: bool | None = None):
    """Blocked linear recurrence h_t = a_t * h_{t-1} + b_t (dispatcher).

    Deliberately NOT jitted itself: the model layers call it inside jitted
    prefill/decode steps (where it traces straight through), and the CPU
    path must stay the plain ``lax.scan`` reference — bit-identical to the
    ``chunked_scan`` cell path it replaces — not the interpreted kernel.

    a, b: [B, S, D] f32; ``h0``: optional [B, D] initial carry. A non-zero
    ``h0`` is absorbed into the first step (``b_1 += a_1 * h0``) so the
    zero-carry Pallas kernel applies unchanged; the absorbed form is
    bit-identical because ``a_1*h0 + b_1`` is the same f32 expression
    either way. Routes to the Pallas kernel on TPU (or ``interpret=True``
    for tests); CPU and non-block-multiple S/D take the jnp reference.
    """
    B, S, D = a.shape
    # MXU-sane tiles only: sublane-multiple time blocks, lane-multiple
    # feature blocks — anything else takes the reference
    use_pallas, interp = _route(
        "rglru_scan", interpret,
        f"S={S} D={D} not multiples of (8, 128)" if S % 8 or D % 128
        else "")
    if not use_pallas:
        return ref.rglru_scan_ref(a, b, h0)
    if h0 is not None:
        b = b.at[:, 0, :].add(a[:, 0, :] * h0.astype(jnp.float32))
    return _rs.rglru_scan(a, b, block_s=_pick_block(S, 256, align=8),
                          block_d=_pick_block(D, 512), interpret=interp)
