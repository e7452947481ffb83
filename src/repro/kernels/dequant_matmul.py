"""Pallas TPU kernel: decoder-side fused dequantize + up-projection
(layer B receive path): y = (codes * scales) @ w_up.

The int8 codes arrive from the wire; dequantization happens in VMEM as the
operand is fed to the MXU, so no f32 copy of the code matrix is ever
materialized in HBM. Grid: (M/BM, D/BD); the bottleneck width N is small
(<= 2048) and rides whole in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(codes_ref, scales_ref, w_ref, out_ref, *, out_dtype):
    z = codes_ref[...].astype(jnp.float32) * scales_ref[...]
    y = jnp.dot(z, w_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    out_ref[...] = y.astype(out_dtype)


def dequant_matmul(codes, scales, w, *, out_dtype=jnp.bfloat16,
                   block_m: int = 128, block_d: int = 512,
                   interpret: bool = False):
    """codes: int8 [M, N], scales: f32 [M, 1], w: [N, D] -> [M, D]."""
    M, N = codes.shape
    N2, D = w.shape
    assert N == N2, (codes.shape, w.shape)
    assert M % block_m == 0 and D % block_d == 0, (M, D, block_m, block_d)

    grid = (M // block_m, D // block_d)
    return pl.pallas_call(
        functools.partial(_kernel, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, N), lambda m, d: (m, 0)),
            pl.BlockSpec((block_m, 1), lambda m, d: (m, 0)),
            pl.BlockSpec((N, block_d), lambda m, d: (0, d)),
        ],
        out_specs=pl.BlockSpec((block_m, block_d), lambda m, d: (m, d)),
        out_shape=jax.ShapeDtypeStruct((M, D), out_dtype),
        interpret=interpret,
        name="dequant_matmul",
    )(codes, scales, w)
