"""Two-stage pod pipeline: the paper's UE -> edge link mapped onto the
inter-pod ICI axis.

``shard_map`` is manual over the ``pod`` axis only (data/model stay auto, so
GSPMD still applies TP/FSDP inside each stage). Stage 0 (= the UE encoder)
runs the first half of the layer stack on each microbatch, pushes the
boundary activation through the selected bottleneck mode (down-proj + int8
quantization for mode >= 1 — the paper's layer A + wire format), and
``ppermute``s the payload to stage 1 (= the edge decoder), which adapts it
back (layer B) and finishes the stack.

The collective-permute operand size in the compiled HLO IS the paper's
"transmission resource consumption" — mode m shrinks it by
(d_bneck/d_model) x (int8/bf16), which the roofline harness measures.

Split *learning* across the link uses straight-through-estimator semantics:
the forward wire carries int8 codes; the backward wire carries the gradient
of the boundary activation — float by default (what the paper implies), or
int8 with ``bwd_bits=8`` (beyond paper; ``tests/test_pipeline_pods.py``
pins the compressed-wire collective bytes). Implemented as a
``jax.custom_vjp`` around the
quantize -> ppermute -> dequantize segment.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import bottleneck, quant
from repro.models import sharding
from repro.models.layers import dense_apply, norm_apply
from repro.models import transformer as T


def stack_stages(params, cfg: ModelConfig, n_stages: int = 2):
    """Repack layer params into [n_stages, L/n_stages, ...] for P('pod')
    placement. Requires homogeneous (scan) archs and L % n_stages == 0."""
    if not cfg.homogeneous:
        raise ValueError("pod pipeline requires a homogeneous layer stack; "
                         "hybrid/ssm archs use the tensor-split path instead")
    if cfg.per_layer_attention:
        raise ValueError("pod pipeline stages share one attention setting; "
                         "per-layer windows or rotary tables are not staged")
    L = cfg.n_layers
    assert L % n_stages == 0, (L, n_stages)
    per = L // n_stages
    return jax.tree.map(
        lambda a: a.reshape((n_stages, per) + a.shape[1:]), params["layers"])


def _make_wire(bits: int, perm, axis: str = "pod", bwd_bits: int = 0):
    """Forward: quantize -> collective-permute (the uplink) -> dequantize.
    Backward: the boundary gradient rides the reverse link (STE through the
    quantizer, as in QAT split learning).

    ``bwd_bits``: ALSO quantize the backward boundary gradient (beyond
    paper — the f32 gradient dominates the wire once the forward is
    compressed; this closes the gap toward the theoretical 8x).
    Plain rowwise-absmax quantized gradients, no error feedback — the
    residual-error accumulator would live on the UE across steps and is
    noted as an open item in ROADMAP.md."""
    rev = [(d, s) for (s, d) in perm]

    @jax.custom_vjp
    def wire(z):
        if bits == 0:
            return jax.lax.ppermute(z, axis, perm)
        codes, scales = quant.quantize(z, bits)
        codes = jax.lax.ppermute(codes, axis, perm)
        scales = jax.lax.ppermute(scales, axis, perm)
        return quant.dequantize(codes, scales, bits).astype(z.dtype)

    def fwd(z):
        return wire(z), None

    def bwd(_, g):
        if bwd_bits == 0:
            return (jax.lax.ppermute(g, axis, rev),)
        codes, scales = quant.quantize(g, bwd_bits)
        codes = jax.lax.ppermute(codes, axis, rev)
        scales = jax.lax.ppermute(scales, axis, rev)
        return (quant.dequantize(codes, scales, bwd_bits).astype(g.dtype),)

    wire.defvjp(fwd, bwd)
    return wire


def pipeline_apply(stage_layers, bneck_head, x, positions,
                   cfg: ModelConfig, *, mesh, n_micro: int, mode: int,
                   train: bool = False, bwd_bits: int = 0):
    """Run the layer stack as a 2-stage pipeline over the ``pod`` axis.

    stage_layers: [2, L/2, ...] pytree (placed P('pod') by the caller's jit).
    x: embedded inputs [B, S, d]; B % n_micro == 0.
    Returns (hidden [B, S, d], aux).
    """
    B, S, d = x.shape
    n_data = mesh.shape.get("data", 1)
    assert B % (n_micro * n_data) == 0, (B, n_micro, n_data)
    n_stages = mesh.shape["pod"]
    dtype = x.dtype
    bits = 0 if mode == 0 else bottleneck.mode_widths(cfg.split)[mode - 1][1]
    perm = [(i, i + 1) for i in range(n_stages - 1)]
    wire = _make_wire(bits, perm, bwd_bits=bwd_bits)

    def inner(stage_ids, stage_layers, head_f32, x_f32, pos):
        # inside the manual `pod` region the outer mesh's NamedShardings are
        # invalid (pod axis is Manual here) — drop activation constraints for
        # the duration of this trace and let GSPMD keep propagating
        # data/model shardings from the operands
        with sharding.activation_rules(None, {}):
            return _inner_body(stage_ids, stage_layers, head_f32, x_f32, pos)

    def _inner_body(stage_ids, stage_f32, head_f32, x_f32, pos):
        # the stage id rides in as a P('pod')-sharded iota, so the stage
        # body needs no jax.lax.axis_index on the manual axis inside this
        # partially-auto shard_map region
        stage = stage_ids[0]
        # inputs (incl. the pod-replicated stage weights) enter in fp32 —
        # XLA CPU aborts on the bf16 psum their cotangents need; compute
        # stays in bf16. The batch dim is MANUALLY sharded over `data`
        # (replicating it — the first version — cost 63 GiB/device temp).
        my_layers = jax.tree.map(lambda a: a[0].astype(dtype)
                                 if jnp.issubdtype(a.dtype, jnp.floating)
                                 else a[0], stage_f32)           # [L/2, ...]
        xs = x_f32.astype(dtype)
        head = jax.tree.map(lambda a: a.astype(dtype), head_f32)
        B_loc = xs.shape[0]
        mb_l = B_loc // n_micro
        micro = xs.reshape(n_micro, mb_l, S, d)
        posm = pos[:mb_l]

        def run(h):
            return T.run_layers(my_layers, h, posm, cfg, train=train)

        def boundary_tx(h):
            """Sender-side bottleneck (layer A) + wire."""
            if mode == 0:
                return wire(h)
            z = dense_apply(head["down"],
                            norm_apply(head["norm"], h, "rmsnorm"))
            return wire(z)

        def boundary_rx(zq):
            """Receiver-side adapter (layer B)."""
            if mode == 0:
                return zq
            return dense_apply(head["up"], zq)

        def tick(carry, t):
            recv, out_buf, aux = carry
            inp0 = jnp.where(t < n_micro,
                             micro[jnp.minimum(t, n_micro - 1)], 0.0)
            inp = jnp.where(stage == 0, inp0, recv)
            h, a = run(inp)
            recv = boundary_rx(boundary_tx(h))
            j = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            upd = jax.lax.dynamic_update_slice_in_dim(
                out_buf, h[None], j, axis=0)
            out_buf = jnp.where((stage == n_stages - 1)
                                & (t >= n_stages - 1), upd, out_buf)
            return (recv, out_buf, aux + a), None

        carry0 = (jnp.zeros((mb_l, S, d), dtype),
                  jnp.zeros((n_micro, mb_l, S, d), dtype),
                  jnp.zeros((), jnp.float32))
        (recv, out_buf, aux), _ = jax.lax.scan(
            tick, carry0, jnp.arange(n_micro + n_stages - 1))
        # bring outputs from the last stage to every pod so unembed/loss can
        # run data-parallel (this return hop is the edge->UE feedback path);
        # fp32 reduce for the same XLA CPU reason as above
        out = out_buf.reshape(B_loc, S, d)
        out = jnp.where(stage == n_stages - 1, out, 0.0)
        out = jax.lax.psum(out.astype(jnp.float32), "pod")
        aux = jax.lax.psum(aux, "pod") / n_stages
        aux = jax.lax.pmean(aux, "data")
        return out, aux

    shmap = sharding.shard_map(
        inner, mesh=mesh,
        in_specs=(P("pod"), P("pod"), P(), P("data", None, None),
                  P("data", None)),
        out_specs=(P("data", None, None), P()),
        axis_names={"pod", "data"}, check=False)
    def f32(t):
        return jax.tree.map(lambda a: a.astype(jnp.float32)
                            if jnp.issubdtype(a.dtype, jnp.floating) else a,
                            t)
    head_f32 = f32(bneck_head if bneck_head is not None else {})
    out, aux = shmap(jnp.arange(n_stages, dtype=jnp.int32),
                     f32(stage_layers), head_f32, x.astype(jnp.float32),
                     positions)
    return out.astype(dtype), aux


def pipeline_forward(params, tokens, cfg: ModelConfig, *, mesh,
                     n_micro: int = 4, mode: int = 0, train: bool = False,
                     bwd_bits: int = 0, embeddings=None):
    """Embed -> pod pipeline -> unembed. Returns (logits, aux)."""
    x = T.embed_tokens(params, tokens, cfg, embeddings)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    stages = stack_stages(params, cfg, mesh.shape["pod"])
    modes = params.get("bneck_modes") or ()
    head = modes[mode - 1] if (mode >= 1 and modes) else (
        modes[0] if modes else None)
    h, aux = pipeline_apply(stages, head, x, positions, cfg, mesh=mesh,
                            n_micro=n_micro, mode=mode, train=train,
                            bwd_bits=bwd_bits)
    h = T.norm_apply_final(params, h, cfg)
    return T.lm_logits(params, h, cfg), aux
