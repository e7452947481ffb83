"""Pallas TPU kernel: fused bottleneck down-projection + row-wise int8
quantization — the encoder-side transmit op the paper's mechanism inserts on
every query (layer A + wire format).

TPU adaptation: the GPU formulation would be a GEMM followed by a separate
quantize kernel; on TPU we tile the GEMM for the MXU (128-aligned blocks),
accumulate in an f32 VMEM scratch, and fuse the absmax/scale/round into the
epilogue of the final K-step so the full-precision activation NEVER leaves
VMEM — only int8 codes and one f32 scale per row are written to HBM, which is
exactly the wire payload.

Grid: (M/BM, K/BK) — K innermost so each row-block's accumulator completes
before its quantization epilogue. N (the bottleneck width, <= 2048 in all
assigned configs) fits one VMEM block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, codes_ref, scales_ref, acc_ref, *, n_k: int,
            qmax: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        z = acc_ref[...]                                   # [BM, N] f32
        absmax = jnp.max(jnp.abs(z), axis=-1, keepdims=True)
        scale = jnp.maximum(absmax, 1e-8) / qmax
        q = jnp.clip(jnp.round(z / scale), -qmax, qmax)
        codes_ref[...] = q.astype(jnp.int8)
        scales_ref[...] = scale


def bottleneck_quant(x, w, *, bits: int = 8, block_m: int = 128,
                     block_k: int = 512, interpret: bool = False):
    """x: [M, K], w: [K, N] -> (codes int8 [M, N], scales f32 [M, 1]).

    M % block_m == 0, K % block_k == 0 required (ops.py pads otherwise).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    assert M % block_m == 0 and K % block_k == 0, (M, K, block_m, block_k)
    n_k = K // block_k
    # floor at 1 to match quant.qmax and boundary_mixed: bits=1 is the
    # ternary {-1, 0, 1} wire code, not a division by zero
    qmax = max((1 << (bits - 1)) - 1, 1)

    grid = (M // block_m, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, qmax=qmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda m, k: (m, k)),
            pl.BlockSpec((block_k, N), lambda m, k: (k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, N), lambda m, k: (m, 0)),
            pl.BlockSpec((block_m, 1), lambda m, k: (m, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.int8),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_m, N), jnp.float32)],
        interpret=interpret,
        name="bottleneck_quant",
    )(x, w)
