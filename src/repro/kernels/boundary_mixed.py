"""Pallas TPU kernel: fused mixed-mode bottleneck boundary — the whole
UE->wire->edge crossing (layer A, quantize -> dequantize, layer B) for a
continuous batch where every row rides its own orchestrator-chosen mode.

This is the operation the paper inserts on *every* query, so its cost — not
just its wire bytes — governs the complexity/relevance tradeoff. The jnp
path (``kernels.ref.boundary_mixed_ref``) pads every row to the widest mode
and gathers a per-row weight tensor; here the caller (``kernels.ops``)
pre-groups rows into mode-uniform blocks so that, per block:

* the block's head weights are gathered ONCE via scalar-prefetch index maps
  (no [B, d, wmax] materialized gather, no cross-mode branching);
* the down-projection runs chunk-by-chunk over the head's TRUE width —
  ``ceil(width / block_w)`` grid steps instead of ``wmax / block_w`` — so
  narrow-mode rows do narrow-mode work instead of wmax-padded work;
* the f32 activation, the quantization scale, and the dequantized code all
  live in VMEM scratch; nothing but the final decoder-side activation (in
  the model dtype) is ever written back to HBM;
* raw-mode rows (mode 0) skip every matmul and pass the boundary through.

Grid: (row_blocks, wmax / block_w) — the width-chunk dimension is innermost
so each block's z accumulator completes before its quantize + up-projection
epilogue. Scalar-prefetch tables (head id, chunk count, true width, bit
width — one entry per row block) drive both the index maps and the in-kernel
``pl.when`` guards.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def mxu_precision(dtype):
    """Contraction precision of an in-kernel dot on ``dtype`` operands.
    bf16 products are exact in one MXU pass, and Mosaic refuses a
    multi-pass (fp32) contraction of bf16 operands — which a process-wide
    ``jax_default_matmul_precision="highest"`` would otherwise request.
    f32 operands keep the full-precision passes."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype).itemsize >= 4
            else jax.lax.Precision.DEFAULT)


def _kernel(hid_ref, nch_ref, wid_ref, bit_ref, x_ref, down_ref, up_ref,
            norm_ref, out_ref, h_scr, z_scr, *, n_w: int, block_w: int,
            dtype):
    g = pl.program_id(0)
    w = pl.program_id(1)
    nch = nch_ref[g]                    # chunks of this block's true width
    width = wid_ref[g]                  # true bottleneck width (0 = raw)
    bits = bit_ref[g]                   # wire bit width (0 = unquantized)

    @pl.when((w == 0) & (nch > 0))
    def _prep():
        # layer A prologue: rmsnorm in f32, cast back to the model dtype —
        # shared by every width chunk of this row block
        z_scr[...] = jnp.zeros_like(z_scr)
        xf = x_ref[...].astype(jnp.float32)
        h = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
        h = h * norm_ref[0].astype(jnp.float32)
        h_scr[...] = h.astype(h_scr.dtype)

    @pl.when(w < nch)
    def _down_chunk():
        # one MXU tile of the down-projection; chunks past ``nch`` are
        # skipped entirely (their index maps clamp to the last real chunk,
        # so no extra weight traffic either). f32 accumulation + explicit
        # round to the model dtype == XLA's own bf16-GEMM semantics, and is
        # reproducible between compiled, interpret, and oracle paths.
        z = jnp.dot(h_scr[...], down_ref[0],
                    precision=mxu_precision(h_scr.dtype),
                    preferred_element_type=jnp.float32
                    ).astype(h_scr.dtype).astype(jnp.float32)
        lane = w * block_w + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_w), 1)
        z_scr[:, pl.ds(pl.multiple_of(w * block_w, block_w), block_w)] = \
            jnp.where(lane < width, z, 0.0)

    @pl.when(w == n_w - 1)
    def _epilogue():
        @pl.when(nch == 0)
        def _raw():                      # mode 0: transmit the raw code z
            out_ref[...] = x_ref[...]

        @pl.when(nch > 0)
        def _wire_and_up():
            # wire round-trip in VMEM: row-wise symmetric quantization at
            # this block's bit width (same floor-at-1 as quant.qmax —
            # bits=1 is the ternary code), then layer B
            z = z_scr[...]
            qm = jnp.maximum(
                jnp.left_shift(1, jnp.maximum(bits, 1) - 1) - 1, 1
            ).astype(jnp.float32)
            absmax = jnp.max(jnp.abs(z), axis=-1, keepdims=True)
            scale = jnp.maximum(absmax, 1e-8) / qm
            codes = jnp.clip(jnp.round(z / scale), -qm, qm)
            wired = jnp.where(bits == 0, z, codes * scale)
            y = jnp.dot(wired.astype(dtype), up_ref[0],
                        precision=mxu_precision(dtype),
                        preferred_element_type=jnp.float32)
            out_ref[...] = y.astype(out_ref.dtype)


def _tail_kernel(hid_ref, x_ref, heads_ref, scale_ref, bias_ref, out_ref,
                 h_scr, best_scr, idx_scr, *, n_v: int, block_v: int,
                 norm_kind: str):
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _prep():
        # final-norm prologue in f32, rounded through the model dtype —
        # exactly what norm_apply hands lm_logits — shared by every vocab
        # chunk of this row block; running lane-max/lane-argmax reset
        xf = x_ref[...].astype(jnp.float32)
        if norm_kind == "rmsnorm":
            y = xf * jax.lax.rsqrt(
                jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
        else:                            # layernorm
            mu = jnp.mean(xf, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
            y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
        y = y * scale_ref[0].astype(jnp.float32)
        y = y + bias_ref[0].astype(jnp.float32)
        h_scr[...] = y.astype(x_ref.dtype).astype(jnp.float32)
        best_scr[...] = jnp.full_like(best_scr, -jnp.inf)
        idx_scr[...] = jnp.zeros_like(idx_scr)

    # one MXU tile of this block's head: the [block_r, block_v] logit chunk
    # lives only in registers/VMEM — argmax folds it into the running
    # per-lane max immediately, so the [B, V] f32 logits never touch HBM.
    # Strict > keeps the EARLIEST chunk on ties, matching jnp.argmax.
    logits = jnp.dot(h_scr[...], heads_ref[0].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    lane = v * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    better = logits > best_scr[...]
    best_scr[...] = jnp.where(better, logits, best_scr[...])
    idx_scr[...] = jnp.where(better, lane, idx_scr[...])

    @pl.when(v == n_v - 1)
    def _argmax():
        # cross-lane reduce: global max, then the smallest index holding it
        # (each lane's stored index is already its earliest occurrence)
        best = best_scr[...]
        m = jnp.max(best, axis=-1, keepdims=True)
        tok = jnp.min(jnp.where(best == m, idx_scr[...],
                                jnp.int32(2 ** 31 - 1)),
                      axis=-1, keepdims=True)
        out_ref[...] = jnp.broadcast_to(tok, out_ref.shape).astype(jnp.int32)


def decode_tail_grouped(xp, heads, norm_scale, norm_bias, hid_g, *,
                        block_r: int, block_v: int = 512,
                        norm_kind: str = "rmsnorm",
                        interpret: bool = False):
    """Fused decode tail: final norm -> per-block LM-head gather -> streaming
    argmax -> int32 token, one ``pallas_call`` (the serving tick's second and
    last kernel — see ``ops.decode_tail_op``).

    ``xp``: [P, d] decoder-output rows already permuted so each
    ``block_r``-row block is head-uniform (``ops.head_layout``); ``heads``:
    [H, d, V] stacked LM heads; ``norm_scale``/``norm_bias``: [d] final-norm
    params (bias zeros for rmsnorm); ``hid_g``: [P/block_r] int32 per-block
    head row. Returns [P, 128] int32 (the token broadcast across lanes;
    callers read column 0).

    P % block_r == 0, d % 128 == 0, V % block_v == 0 required (ops.py falls
    back to the jnp reference otherwise).
    """
    P, d = xp.shape
    H, d2, V = heads.shape
    assert d == d2, (xp.shape, heads.shape)
    assert P % block_r == 0 and d % 128 == 0 and V % block_v == 0, \
        (P, d, V, block_r, block_v)
    G = P // block_r
    n_v = V // block_v
    assert hid_g.shape == (G,), (hid_g.shape, G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, n_v),
        in_specs=[
            pl.BlockSpec((block_r, d), lambda g, v, *s: (g, 0)),
            pl.BlockSpec((1, d, block_v),
                         lambda g, v, hid: (hid[g], 0, v)),
            pl.BlockSpec((1, d), lambda g, v, *s: (0, 0)),
            pl.BlockSpec((1, d), lambda g, v, *s: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, 128), lambda g, v, *s: (g, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_r, d), jnp.float32),        # normed activation
            pltpu.VMEM((block_r, block_v), jnp.float32),  # running lane max
            pltpu.VMEM((block_r, block_v), jnp.int32),    # running lane argmax
        ],
    )
    return pl.pallas_call(
        functools.partial(_tail_kernel, n_v=n_v, block_v=block_v,
                          norm_kind=norm_kind),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, 128), jnp.int32),
        interpret=interpret,
        name="decode_tail",
    )(hid_g, xp, heads, norm_scale.reshape(1, d), norm_bias.reshape(1, d))


def boundary_mixed_grouped(xp, down_w, up_w, norm_scale, hid_g, nchunk_g,
                           width_g, bits_g, *, block_r: int,
                           block_w: int = 128, dtype=jnp.bfloat16,
                           interpret: bool = False):
    """Mode-grouped fused boundary. ``xp``: [P, d] rows already permuted so
    each ``block_r``-row block is mode-uniform (see ``ops._group_rows``);
    ``down_w``/``up_w``/``norm_scale``: the stacked bank ([M, d, wmax] /
    [M, wmax, d] / [M, d]); per-block int32 tables: ``hid_g`` head row,
    ``nchunk_g`` width chunks (0 = raw passthrough), ``width_g`` true
    width, ``bits_g`` wire bits. Returns [P, d] decoder-side activations.

    P % block_r == 0, d % 128 == 0, wmax % block_w == 0 required
    (ops.py falls back to the jnp reference otherwise).
    """
    P, d = xp.shape
    M, d2, wmax = down_w.shape
    assert d == d2, (xp.shape, down_w.shape)
    assert P % block_r == 0 and d % 128 == 0 and wmax % block_w == 0, \
        (P, d, wmax, block_r, block_w)
    G = P // block_r
    n_w = wmax // block_w
    assert hid_g.shape == (G,), (hid_g.shape, G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(G, n_w),
        in_specs=[
            pl.BlockSpec((block_r, d), lambda g, w, *s: (g, 0)),
            pl.BlockSpec(
                (1, d, block_w),
                lambda g, w, hid, nch, wd, bt: (
                    hid[g], 0, jnp.minimum(w, jnp.maximum(nch[g] - 1, 0)))),
            pl.BlockSpec((1, wmax, d), lambda g, w, hid, *s: (hid[g], 0, 0)),
            pl.BlockSpec((1, d), lambda g, w, hid, *s: (hid[g], 0)),
        ],
        out_specs=pl.BlockSpec((block_r, d), lambda g, w, *s: (g, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_r, d), xp.dtype),          # normed activation
            pltpu.VMEM((block_r, wmax), jnp.float32),    # z accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_w=n_w, block_w=block_w, dtype=dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, d), xp.dtype),
        interpret=interpret,
        name="boundary_mixed",
    )(hid_g, nchunk_g, width_g, bits_g, xp, down_w, up_w, norm_scale)
