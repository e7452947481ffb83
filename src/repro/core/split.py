"""Split-model wrapper: cut any assigned architecture at ``cfg.split.split_at``
into a UE-side encoder and an edge-side decoder, with the paper's selectable
bottleneck modes at the boundary.

``split_forward`` is numerically identical to running the full model when
``mode == 0`` (the boundary is transmitted raw); mode m >= 1 routes the
boundary through bottleneck head m (down-proj -> quantize -> wire ->
dequant -> up-proj adapter), which is the phase-2 network of Algorithm 1.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import bottleneck
from repro.models import sharding
from repro.models import transformer as T


# ---------------------------------------------------------------------------
# parameter plumbing
# ---------------------------------------------------------------------------

def init_split_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    """Full model params + the bottleneck mode bank."""
    k1, k2 = jax.random.split(key)
    params = T.init_params(k1, cfg)
    params["bneck_modes"] = bottleneck.bank_init(
        k2, cfg, dtype=T.model_dtype(cfg))
    return params


def slice_layers(layers, cfg: ModelConfig, split_at: Optional[int] = None):
    """(encoder_layers, decoder_layers) views of the layer params."""
    s = split_at if split_at is not None else cfg.split.split_at
    if cfg.homogeneous:
        enc = jax.tree.map(lambda a: a[:s], layers)
        dec = jax.tree.map(lambda a: a[s:], layers)
    else:
        enc, dec = layers[:s], layers[s:]
    return enc, dec


def _kinds(cfg: ModelConfig):
    return tuple(cfg.block_kind(i) for i in range(cfg.n_layers))


# ---------------------------------------------------------------------------
# full-sequence split forward (training / prefill)
# ---------------------------------------------------------------------------

def encoder_apply(params, tokens, cfg: ModelConfig, mode: int, *,
                  train: bool = False, embeddings=None):
    """UE side. Returns (payload, aux, info) where payload crosses the link.

    mode 0 payload: raw boundary activation (bf16).
    mode m payload: (int codes, scales) from bottleneck head m.
    """
    s = cfg.split.split_at
    x = T.embed_tokens(params, tokens, cfg, embeddings)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    enc, _ = slice_layers(params["layers"], cfg, s)
    x, aux = T.run_layers(enc, x, positions, cfg, train=train,
                          kinds=_kinds(cfg)[:s])
    if mode == 0:
        payload = (x, None)
        bits = 0
    else:
        _, bits = bottleneck.mode_widths(cfg.split)[mode - 1]
        payload = bottleneck.encode(params["bneck_modes"][mode - 1], x, bits,
                                    train=train)
    info = {"positions": positions,
            "payload_bytes": bottleneck.mode_payload_bytes(cfg, B, S, mode)}
    return payload, aux, info


def decoder_apply(params, payload, positions, cfg: ModelConfig, mode: int, *,
                  train: bool = False):
    """Edge side: adapter (mode >= 1) + remaining layers + head."""
    s = cfg.split.split_at
    codes, scales = payload
    if mode == 0:
        x = codes
    else:
        _, bits = bottleneck.mode_widths(cfg.split)[mode - 1]
        x = bottleneck.decode(params["bneck_modes"][mode - 1], codes, scales,
                              bits, dtype=T.model_dtype(cfg))
    _, dec = slice_layers(params["layers"], cfg, s)
    x, aux = T.run_layers(dec, x, positions, cfg, train=train,
                          kinds=_kinds(cfg)[s:], first=s)
    x = T.norm_apply_final(params, x, cfg)
    logits = sharding.constrain(T.lm_logits(params, x, cfg), "logits")
    return logits, aux


def split_forward(params, tokens, cfg: ModelConfig, mode: int = 0, *,
                  train: bool = False, embeddings=None):
    """End-to-end split forward (the wire is simulated as identity on values;
    byte accounting returned in info). Returns (logits, aux, info)."""
    payload, aux1, info = encoder_apply(params, tokens, cfg, mode,
                                        train=train, embeddings=embeddings)
    logits, aux2 = decoder_apply(params, payload, info["positions"], cfg,
                                 mode, train=train)
    return logits, aux1 + aux2, info


# ---------------------------------------------------------------------------
# decode-time split (one token across the link per step)
# ---------------------------------------------------------------------------

def split_decode_step(params, token, states, cur_pos, cfg: ModelConfig,
                      mode: int = 0, return_tokens: bool = False):
    """One-token decode with the boundary activation crossing the link.

    Encoder-side layer states stay on the UE; decoder-side states stay at the
    edge — only the (possibly bottlenecked) activation is transmitted.
    Returns (logits, new_states, payload_bytes); with ``return_tokens`` the
    fused decode tail (``T.decode_tail_tokens``) replaces the logits with
    argmax int32 tokens.
    """
    s = cfg.split.split_at
    x = T.embed_tokens(params, token, cfg, None)
    x, states, *_ = T.run_layers_decode(params["layers"], x, states,
                                        cur_pos, cfg, stop=s)
    B = x.shape[0]
    if mode == 0:
        payload = (x, None)
    else:
        _, bits = bottleneck.mode_widths(cfg.split)[mode - 1]
        payload = bottleneck.encode(params["bneck_modes"][mode - 1], x, bits)
        x = bottleneck.decode(params["bneck_modes"][mode - 1], *payload, bits,
                              dtype=T.model_dtype(cfg))
    x, states, *_ = T.run_layers_decode(params["layers"], x, states,
                                        cur_pos, cfg, start=s)
    pb = bottleneck.mode_payload_bytes(cfg, B, 1, mode)
    if return_tokens:
        return T.decode_tail_tokens(params, x, cfg), states, pb
    x = T.norm_apply_final(params, x, cfg)
    logits = T.lm_logits(params, x, cfg)
    return logits, states, pb


def split_decode_step_mixed(params, stacked_bank, token, states, positions,
                            cfg: ModelConfig, mode_idx, block_table=None,
                            mesh=None, return_tokens: bool = False,
                            with_stats: bool = False):
    """One decode step for a *mixed-mode* continuous batch.

    Unlike :func:`split_decode_step`, every batch slot decodes at its own
    sequence depth (``positions``: [B] int32 absolute positions) and through
    its own orchestrator-chosen bottleneck (``mode_idx``: [B] int32, 0 = raw
    code z, m >= 1 = head m-1 gathered from ``stacked_bank``; see
    ``bottleneck.bank_stack``). The whole step is one jittable function —
    mode selection is a gather, not a Python branch, so a single compiled
    executable serves any mode mixture.

    Per-slot wire bytes are host-side accounting (they depend only on the
    static mode table, not on traced values) — see
    ``bottleneck.mode_payload_bytes(cfg, 1, 1, mode)`` per slot.
    With ``block_table`` ([B, nb] int32, paged serving) the attention
    leaves of ``states`` are page arenas shared by both halves. Both halves
    run over layer ranges of the whole stack (``0..split_at`` and
    ``split_at..L``), so no half of the weights or state is copied.

    ``mesh``: serving ``('dp','mp')`` mesh for the sharded engine — the
    boundary runs in a replicated ``shard_map`` region (bit-identity with
    the unsharded step; see ``ops.boundary_mixed_sharded``) and the
    decoder-side activation is re-constrained batch-over-``dp`` so GSPMD
    keeps the slot sharding through the decoder half. Returns (logits,
    new_states); with ``return_tokens`` the fused decode tail
    (``T.decode_tail_tokens``) replaces the logits with argmax int32 tokens
    and the whole tick is two kernels on TPU — boundary + tail — with the
    f32 logits never touching HBM. ``with_stats`` (a model with experts)
    adds the MoE stats of both halves third (``T.run_layers_decode``).
    """
    s = cfg.split.split_at
    x = T.embed_tokens(params, token, cfg, None)
    x, states, *enc = T.run_layers_decode(
        params["layers"], x, states, positions, cfg, block_table=block_table,
        stop=s)
    x = bottleneck.boundary_mixed(stacked_bank, x, mode_idx,
                                  dtype=T.model_dtype(cfg), mesh=mesh)
    x = sharding.constrain_batch(x, mesh)
    x, states, *dec = T.run_layers_decode(
        params["layers"], x, states, positions, cfg, block_table=block_table,
        start=s)
    stats = [jax.tree.map(jnp.add, enc[0], dec[0])] if with_stats else []
    if return_tokens:
        return (T.decode_tail_tokens(params, x, cfg), states, *stats)
    x = T.norm_apply_final(params, x, cfg)
    logits = T.lm_logits(params, x, cfg)
    return (logits, states, *stats)


# ---------------------------------------------------------------------------
# batched full-sequence prefill (admission hot path)
# ---------------------------------------------------------------------------

def _prefill_through(params, tokens, cfg: ModelConfig, states, boundary,
                     lengths, block_table=None):
    """Shared whole-prompt prefill skeleton: encoder layers, ``boundary``
    (the wire crossing), decoder layers — populating every layer's decode
    state. Returns (last-real-position logits, new_states)."""
    s = cfg.split.split_at
    x = T.embed_tokens(params, tokens, cfg, None)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
    x, states = T.run_layers_prefill(params["layers"], x, positions, states,
                                     cfg, lengths=lengths,
                                     block_table=block_table, stop=s)
    x = boundary(x)
    x, states = T.run_layers_prefill(params["layers"], x, positions, states,
                                     cfg, lengths=lengths,
                                     block_table=block_table, start=s)
    last = (lengths - 1 if lengths is not None
            else jnp.full((B,), S - 1, jnp.int32))
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)
    x = T.norm_apply_final(params, x, cfg)
    return T.lm_logits(params, x, cfg), states


def split_prefill(params, tokens, cfg: ModelConfig, states, mode: int = 0, *,
                  lengths=None):
    """Whole-prompt split prefill in ONE forward pass: encoder layers,
    boundary through bottleneck ``mode`` (the single uplink transfer of the
    prompt's boundary representation), decoder layers — while populating
    every layer's decode state, instead of looping ``split_decode_step``
    per prompt token.

    tokens: [B, S] right-padded to a bucket; ``lengths``: optional [B] true
    prompt lengths. Returns (last-real-position logits, new_states,
    payload_bytes). The byte figure covers the full padded [B, S] bucket
    (it must stay a host-side int under jit); callers admitting ragged
    prompts account per row with ``mode_payload_bytes(cfg, 1, len_b, mode)``
    instead, as the serving engine does.
    """
    def boundary(x):
        if mode == 0:
            return x
        _, bits = bottleneck.mode_widths(cfg.split)[mode - 1]
        payload = bottleneck.encode(params["bneck_modes"][mode - 1], x, bits)
        return bottleneck.decode(params["bneck_modes"][mode - 1], *payload,
                                 bits, dtype=T.model_dtype(cfg))

    logits, new_states = _prefill_through(params, tokens, cfg, states,
                                          boundary, lengths)
    B, S = jnp.shape(tokens)[0], jnp.shape(tokens)[-1]
    pb = bottleneck.mode_payload_bytes(cfg, B, S, mode)
    return logits, new_states, pb


def split_prefill_mixed(params, stacked_bank, tokens, states,
                        cfg: ModelConfig, mode_idx, *, lengths=None,
                        block_table=None, mesh=None):
    """Batched multi-request prefill with per-row bottleneck modes: one
    forward over a right-padded prompt batch where row b's boundary
    activations cross the wire through its own admission-chosen mode
    (``mode_idx``: [B] int32, 0 = raw z, m >= 1 = head m-1 gathered from
    ``stacked_bank``). This is the admission analogue of
    :func:`split_decode_step_mixed` — quantization happens per boundary
    position with each row's own bit width, exactly as the per-mode path
    does. Returns (last-real-position logits, new_states).

    ``mesh``: serving mesh — the boundary runs replicated-per-shard like
    the decode step. Prefill inputs arrive replicated (a prompt batch is
    written into dp-sharded pool rows only afterwards), so no batch
    constraint is added here: fully-replicated prefill compute keeps the
    admission path bit-identical to the unsharded engine.
    """
    return _prefill_through(
        params, tokens, cfg, states,
        lambda x: bottleneck.boundary_mixed(stacked_bank, x, mode_idx,
                                            dtype=T.model_dtype(cfg),
                                            mesh=mesh),
        lengths, block_table)
