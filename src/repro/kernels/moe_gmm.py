"""Pallas TPU kernel: the expert MLPs of the experts a chip holds, over token
rows sorted by expert — a grouped SwiGLU matmul (``moe_gmm``).

Rows ``x`` [M, d] arrive sorted by held expert: group ``g`` is rows
``offsets[g] .. offsets[g + 1]`` and is multiplied by expert ``g``'s
weights only, ``w_down(act(w_gate x) * w_up x)``. ``group_sizes`` has one
entry more than there are held experts: the last group is the rows routed
to experts this chip does not hold, sorted past every held group and never
multiplied (their output rows are zero). M is static (every candidate row
of the batch), the group sizes are data.

The grid is (group tile, f-tile). The group-tile axis is megablox's
(``jax.experimental.pallas.ops.tpu.megablox``): each ``tm``-row tile of
``x`` is visited once by every group that has rows in it, and only tiles
of held groups that have rows are visited, so an expert no row chose is
never read. Its length is data, set from the group sizes. For each visit
the f-axis streams the expert's ``[d, tf]`` gate and up columns and its
``[tf, d]`` down rows; the down-projection accumulates in f32 VMEM, and
the last f-step stores the rows of this group into the output tile, which
stays resident while consecutive visits share it.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.boundary_mixed import mxu_precision

# the module, not the ``gmm`` function the package re-exports under its name
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _act(x, kind: str):
    return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x)


def _kernel(offsets, gids, mids, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc,
            *, tm: int, nf: int, act: str):
    i = pl.program_id(0)
    fi = pl.program_id(1)
    dt = x_ref.dtype
    prec = mxu_precision(dt)

    @pl.when(fi == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]                                            # [tm, d]
    h = jnp.dot(x, wg_ref[...], precision=prec,
                preferred_element_type=jnp.float32)           # [tm, tf]
    u = jnp.dot(x, wu_ref[...], precision=prec,
                preferred_element_type=jnp.float32)
    a = (_act(h, act) * u).astype(dt)
    acc[...] += jnp.dot(a, wd_ref[...], precision=prec,
                        preferred_element_type=jnp.float32)   # [tm, d]

    @pl.when(fi == nf - 1)
    def _store():
        g = gids[i]
        row = mids[i] * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = jnp.logical_and(row >= offsets[g], row < offsets[g + 1])
        o_ref[...] = jnp.where(mine, acc[...], o_ref[...])


def moe_gmm(x, w_gate, w_up, w_down, group_sizes, *, act: str = "silu",
            tm: int = 128, tf: int = 128, name: str = "moe_gmm",
            interpret: bool = False):
    """Grouped expert MLP. x: [M, d] rows sorted by held expert;
    ``w_gate``/``w_up``: [G, d, f], ``w_down``: [G, f, d]; ``group_sizes``:
    [G + 1] int32, the last entry the rows of experts not held. Returns
    [M, d] float32: each held group's rows through its expert, zero for the
    rest. M must be a multiple of ``tm`` and f of ``tf``. ``name`` is the
    kernel's name in a profile."""
    M, d = x.shape
    G, _, f = w_gate.shape
    nf = f // tf
    (offsets, gids, mids), n_tiles = _megablox.make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=M, tm=tm,
        start_group=0, num_nonzero_groups=G, visit_empty_groups=False)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_tiles, nf),
        in_specs=[
            pl.BlockSpec((tm, d), lambda i, j, o, g, m: (m[i], 0)),
            pl.BlockSpec((None, d, tf), lambda i, j, o, g, m: (g[i], 0, j)),
            pl.BlockSpec((None, d, tf), lambda i, j, o, g, m: (g[i], 0, j)),
            pl.BlockSpec((None, tf, d), lambda i, j, o, g, m: (g[i], j, 0)),
        ],
        out_specs=pl.BlockSpec((tm, d), lambda i, j, o, g, m: (m[i], 0)),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, nf=nf, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name=name,
    )(offsets, gids, mids, x, w_gate, w_up, w_down)
    held = jnp.arange(M, dtype=jnp.int32) < offsets[G]
    return jnp.where(held[:, None], out, 0.0)
