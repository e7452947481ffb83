"""Pallas TPU kernel: blocked RG-LRU linear recurrence
h_t = a_t * h_{t-1} + b_t  (recurrentgemma's temporal-mixing hot loop).

TPU adaptation: instead of the GPU pattern (one thread-block per channel
slice scanning global memory), time is tiled into VMEM-resident blocks of
``block_s`` steps; the carry h lives in a VMEM scratch that persists across
sequential grid steps, so HBM traffic is exactly one read of (a, b) and one
write of h — the memory-bound roofline optimum for a recurrence.

Grid: (B * D/BD, S/BS) with the time dimension innermost (TPU grid order is
sequential over the last axis, which is what makes the scratch carry legal).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h_ref, carry_ref, *, block_s: int):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _reset():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    def step(t, h):
        # one time step straight through the refs: a [1, BD] row load per
        # operand and a row store (Mosaic has no dynamic_slice of a loaded
        # value, so the loop index must address the ref)
        row = pl.ds(t, 1)
        h = a_ref[0, row, :] * h + b_ref[0, row, :]
        h_ref[0, row, :] = h
        return h

    carry_ref[...] = jax.lax.fori_loop(0, block_s, step, carry_ref[...])


def rglru_scan(a, b, *, block_s: int = 256, block_d: int = 512,
               interpret: bool = False):
    """a, b: [B, S, D] f32 -> h: [B, S, D] f32."""
    B, S, D = a.shape
    assert a.shape == b.shape
    assert S % block_s == 0 and D % block_d == 0, (S, D, block_s, block_d)
    n_d = D // block_d

    grid = (B * n_d, S // block_s)
    spec = pl.BlockSpec((1, block_s, block_d),
                        lambda i, s: (i // n_d, s, i % n_d))
    return pl.pallas_call(
        functools.partial(_kernel, block_s=block_s),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, S, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
        name="rglru_scan",
    )(a, b)
