"""Unified decoder-only transformer covering all assigned architecture
families (dense / MoE / VLM / audio / hybrid RG-LRU / xLSTM).

Homogeneous attention stacks (dense, moe, vlm, audio) use stacked layer
params + ``jax.lax.scan`` with per-layer remat; heterogeneous block patterns
(recurrentgemma, xlstm) use an unrolled loop over per-layer param tuples.

The split-learning machinery in ``repro.core.split`` runs the same layer
params as encoder/decoder halves: training slices them, serving runs the
decode/prefill helpers over a layer range of the whole stack.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import sharding
from repro.models.attention import (attn_init, decode_attention, full_attention,
                                    init_cache, paged_decode_attention,
                                    paged_prefill_attention, prefill_attention)
from repro.models.layers import (dense_apply, dense_init, embed_apply,
                                 embed_init, mlp_apply, mlp_init, norm_apply,
                                 norm_init, rope_inv_freq,
                                 yarn_attention_factor)
from repro.models.moe import moe_apply, moe_held_apply, moe_init
from repro.models.moe_ep import moe_apply_ep, moe_supports_ep
from repro.models.rglru import (rglru_full, rglru_init, rglru_prefill,
                                rglru_state_init, rglru_step)
from repro.models.xlstm import (mlstm_full, mlstm_init, mlstm_prefill,
                                mlstm_state_init, mlstm_step, slstm_full,
                                slstm_init, slstm_prefill, slstm_state_init,
                                slstm_step)

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_dtype(cfg: ModelConfig):
    return _DTYPES[cfg.dtype]


# ---------------------------------------------------------------------------
# per-block init / apply
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, kind: str):
    dt = model_dtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    p: Dict[str, Any] = {"norm1": norm_init(cfg.d_model, cfg.norm, dtype=dt)}
    if kind == "attn":
        p["mix"] = attn_init(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                             cfg.head_dim, qkv_bias=cfg.qkv_bias, dtype=dt)
    elif kind == "rglru":
        p["mix"] = rglru_init(k1, cfg.d_model, cfg.d_rnn or cfg.d_model,
                              dtype=dt)
    elif kind == "mlstm":
        p["mix"] = mlstm_init(k1, cfg.d_model, cfg.n_heads, dtype=dt)
    elif kind == "slstm":
        p["mix"] = slstm_init(k1, cfg.d_model, cfg.n_heads, dtype=dt)
    else:
        raise ValueError(kind)
    if kind in ("attn", "rglru") and cfg.d_ff:
        p["norm2"] = norm_init(cfg.d_model, cfg.norm, dtype=dt)
        if cfg.is_moe:
            p["mlp"] = moe_init(k2, cfg.d_model, cfg.d_ff, cfg.n_experts,
                                dtype=dt, n_routed=cfg.routed_experts,
                                router_dtype=_DTYPES[cfg.router_dtype])
        else:
            p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, dtype=dt)
    return p


def _attn_window(cfg: ModelConfig) -> int:
    """The one window of every attention layer, which sizes a rolling
    cache; 0 where layers differ (``layer_types``): their windows then ride
    the layer scan as arrays (``layer_meta``) over caches that never wrap."""
    if cfg.layer_types:
        return 0
    return cfg.sliding_window or cfg.local_window


def full_attention_arch(cfg: ModelConfig) -> bool:
    """True if any layer attends the full context (no window): the KV cache
    is addressed by absolute position, so serving must keep
    ``prompt_len + max_new_tokens <= cache_len`` or the rolling write
    (``pos % cache_len``) silently evicts early prompt context."""
    return any(cfg.block_kind(i) == "attn" and not cfg.layer_window(i)
               for i in range(cfg.n_layers))


def layer_meta(cfg: ModelConfig):
    """Per-layer attention settings of a stack whose layers differ, as
    arrays the layer scan indexes (None when every layer is alike):
    ``window`` [L] int32 (0: full), and the rotary table, ``inv_freq``
    [L, head_dim // 2] float32 and ``rope_scale`` [L] float32 — YaRN's on
    full layers when ``yarn_factor`` is set, the plain ``rope_theta`` table
    elsewhere."""
    if not cfg.per_layer_attention:
        return None
    windows = [cfg.layer_window(i) for i in range(cfg.n_layers)]
    plain = rope_inv_freq(cfg.head_dim, cfg.rope_theta)
    yarn = plain if not cfg.yarn_factor else rope_inv_freq(
        cfg.head_dim, cfg.rope_theta, yarn_factor=cfg.yarn_factor,
        original_max=cfg.yarn_original_max_position,
        beta_fast=cfg.yarn_beta_fast, beta_slow=cfg.yarn_beta_slow)
    scale = 1.0 if not cfg.yarn_factor else yarn_attention_factor(
        cfg.yarn_factor, cfg.yarn_attention_factor)
    return {
        "window": np.asarray(windows, np.int32),
        "inv_freq": np.stack([yarn if w == 0 else plain for w in windows]
                             ).astype(np.float32),
        "rope_scale": np.asarray([scale if w == 0 else 1.0 for w in windows],
                                 np.float32),
    }


def _attn_settings(cfg: ModelConfig, meta):
    """(window, rotary table) of one layer: its ``layer_meta`` row, or the
    model's one window and the plain table."""
    if meta is None:
        return _attn_window(cfg), None
    return meta["window"], (meta["inv_freq"], meta["rope_scale"])


def _served_moe(p, h, cfg: ModelConfig, kernel: str = "moe_gmm"):
    """The served MoE layer (``moe_held_apply``): dropless, over the
    experts this model holds. ``kernel`` names the expert kernel in a
    profile: decode's ``moe_gmm``, and ``moe_gmm_prefill`` for whole
    sequences. Returns (y, stats)."""
    return moe_held_apply(p, h, k=cfg.experts_per_tok, e0=cfg.expert_offset,
                          act=cfg.act, kernel=kernel)


def check_cache_capacity(cfg: ModelConfig, pos: int, n: int, cache_len: int,
                         what: str = "generation") -> None:
    """The full-attention capacity rule, shared by every dense serving
    entry point (sync engine prefill / decode and the launcher loop):
    ``pos + n`` must not exceed ``cache_len`` or the rolling write would
    silently evict early prompt context. Windowed / recurrent archs wrap by
    design and always pass; the paged pool replaces this rule with
    page-budget admission. Raises ``ValueError`` with the offending spans.
    """
    if full_attention_arch(cfg) and pos + n > cache_len:
        raise ValueError(
            f"{what} of {n} tokens from position {pos} exceeds cache_len "
            f"{cache_len} for a full-attention arch (the rolling cache "
            f"would overwrite prompt context)")


def block_apply_full(p, x, positions, cfg: ModelConfig, kind: str,
                     train: bool = False, meta=None):
    """Full-sequence block. Returns (x, aux_loss). ``train`` keeps the
    recurrent families on their remat-friendly ``chunked_scan`` paths (the
    Pallas scan op has no VJP); eval routes them through
    ``ops.rglru_scan_op``. Training routes experts with the capacity layer
    (``moe_apply`` / ``moe_apply_ep``) for its load-balance loss; eval
    takes the served layer. ``meta``: the layer's ``layer_meta`` row."""
    aux = jnp.zeros((), jnp.float32)
    h = norm_apply(p["norm1"], x, cfg.norm)
    if kind == "attn":
        window, rope = _attn_settings(cfg, meta)
        mix = full_attention(p["mix"], h, positions, n_q=cfg.n_heads,
                             n_kv=cfg.n_kv_heads, hd=cfg.head_dim,
                             rope_theta=cfg.rope_theta, window=window,
                             rope=rope)
    elif kind == "rglru":
        mix = rglru_full(p["mix"], h, act=cfg.act, train=train)
    elif kind == "mlstm":
        mix = mlstm_full(p["mix"], h, cfg.n_heads, train=train)
    elif kind == "slstm":
        mix = slstm_full(p["mix"], h, cfg.n_heads)
    else:
        raise ValueError(kind)
    x = x + mix
    if "mlp" in p:
        h = norm_apply(p["norm2"], x, cfg.norm)
        if cfg.is_moe and not train:
            m, _ = _served_moe(p["mlp"], h, cfg, "moe_gmm_prefill")
        elif cfg.is_moe:
            mesh = sharding.ctx_mesh()
            if sharding.ctx_flag("moe_ep") and moe_supports_ep(
                    cfg.n_experts, mesh, h.shape[0], h.shape[1]):
                m, aux = moe_apply_ep(p["mlp"], h, k=cfg.experts_per_tok,
                                      act=cfg.act, mesh=mesh)
            else:
                m, aux = moe_apply(p["mlp"], h, k=cfg.experts_per_tok,
                                   act=cfg.act)
        else:
            m = mlp_apply(p["mlp"], h, cfg.act)
        x = x + m
    return x, aux


def block_apply_decode(p, x, state, cur_pos, cfg: ModelConfig, kind: str,
                       block_table=None, meta=None):
    """One-token decode. Returns (x, new_state), and in a model with experts
    the served MoE layer's stats third. With
    ``block_table`` the attention state is a paged arena indexed through the
    table instead of a dense per-slot rolling cache. ``meta``: the layer's
    ``layer_meta`` row."""
    h = norm_apply(p["norm1"], x, cfg.norm)
    window, rope = _attn_settings(cfg, meta)
    if kind == "attn" and block_table is not None:
        mix, new_state = paged_decode_attention(
            p["mix"], h, state, block_table, cur_pos, n_q=cfg.n_heads,
            n_kv=cfg.n_kv_heads, hd=cfg.head_dim, rope_theta=cfg.rope_theta,
            window=window, rope=rope)
    elif kind == "attn":
        mix, new_state = decode_attention(
            p["mix"], h, state, cur_pos, n_q=cfg.n_heads, n_kv=cfg.n_kv_heads,
            hd=cfg.head_dim, rope_theta=cfg.rope_theta, window=window,
            rope=rope)
    elif kind == "rglru":
        mix, new_state = rglru_step(p["mix"], h, state, act=cfg.act)
    elif kind == "mlstm":
        mix, new_state = mlstm_step(p["mix"], h, state, cfg.n_heads)
    elif kind == "slstm":
        mix, new_state = slstm_step(p["mix"], h, state, cfg.n_heads)
    else:
        raise ValueError(kind)
    x = x + mix
    stats = None
    if "mlp" in p:
        h = norm_apply(p["norm2"], x, cfg.norm)
        if cfg.is_moe:
            m, stats = _served_moe(p["mlp"], h, cfg)
        else:
            m = mlp_apply(p["mlp"], h, cfg.act)
        x = x + m
    return (x, new_state, stats) if cfg.is_moe else (x, new_state)


def block_apply_prefill(p, x, positions, state, cfg: ModelConfig, kind: str,
                        lengths=None, block_table=None, meta=None):
    """Full-sequence block that also populates the decode state (KV cache or
    recurrent carry) — one forward instead of S sequential decode steps.
    Returns (x, new_state). With ``block_table`` the attention rows scatter
    into a paged arena through the table. ``meta``: the layer's
    ``layer_meta`` row."""
    h = norm_apply(p["norm1"], x, cfg.norm)
    window, rope = _attn_settings(cfg, meta)
    if kind == "attn" and block_table is not None:
        mix, new_state = paged_prefill_attention(
            p["mix"], h, positions, state, block_table, n_q=cfg.n_heads,
            n_kv=cfg.n_kv_heads, hd=cfg.head_dim, rope_theta=cfg.rope_theta,
            lengths=lengths, window=window, rope=rope)
    elif kind == "attn":
        mix, new_state = prefill_attention(
            p["mix"], h, positions, state, n_q=cfg.n_heads,
            n_kv=cfg.n_kv_heads, hd=cfg.head_dim, rope_theta=cfg.rope_theta,
            window=window, lengths=lengths, rope=rope)
    elif kind == "rglru":
        mix, new_state = rglru_prefill(p["mix"], h, state, act=cfg.act,
                                       lengths=lengths)
    elif kind == "mlstm":
        mix, new_state = mlstm_prefill(p["mix"], h, state, cfg.n_heads,
                                       lengths=lengths)
    elif kind == "slstm":
        mix, new_state = slstm_prefill(p["mix"], h, state, cfg.n_heads,
                                       lengths=lengths)
    else:
        raise ValueError(kind)
    x = x + mix
    if "mlp" in p:
        h = norm_apply(p["norm2"], x, cfg.norm)
        if cfg.is_moe:
            # the served expert layer decode runs too: routing is per token
            # and nothing is dropped, so a token gets the same experts
            # whether it is prefilled or decoded
            m, _ = _served_moe(p["mlp"], h, cfg, "moe_gmm_prefill")
        else:
            m = mlp_apply(p["mlp"], h, cfg.act)
        x = x + m
    return x, new_state


def block_state_init(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                     kv_bits: int = 0):
    dt = model_dtype(cfg)
    if kind == "attn":
        w = _attn_window(cfg)
        clen = min(cache_len, w) if w else cache_len
        return init_cache(batch, cfg.n_kv_heads, cfg.head_dim, clen,
                          dtype=dt, kv_bits=kv_bits)
    if kind == "rglru":
        return rglru_state_init(batch, cfg.d_rnn or cfg.d_model, dtype=dt)
    if kind == "mlstm":
        return mlstm_state_init(batch, cfg.d_model, cfg.n_heads)
    if kind == "slstm":
        return slstm_state_init(batch, cfg.d_model)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dt = model_dtype(cfg)
    k_emb, k_layers, k_head, k_bneck = jax.random.split(key, 4)
    params: Dict[str, Any] = {}

    if cfg.frontend == "audio" and cfg.n_codebooks > 1:
        keys = jax.random.split(k_emb, cfg.n_codebooks)
        params["embed"] = {"table": jnp.stack(
            [embed_init(k, cfg.vocab_size, cfg.d_model, dtype=dt)["table"]
             for k in keys])}                      # [K, V, d]
    else:
        params["embed"] = embed_init(k_emb, cfg.vocab_size, cfg.d_model,
                                     dtype=dt)

    if cfg.homogeneous:
        keys = jax.random.split(k_layers, cfg.n_layers)
        params["layers"] = jax.vmap(
            lambda k: block_init(k, cfg, "attn"))(keys)   # stacked [L, ...]
    else:
        keys = jax.random.split(k_layers, cfg.n_layers)
        params["layers"] = tuple(
            block_init(keys[i], cfg, cfg.block_kind(i))
            for i in range(cfg.n_layers))

    params["final_norm"] = norm_init(cfg.d_model, cfg.norm, dtype=dt)
    if cfg.frontend == "audio" and cfg.n_codebooks > 1:
        keys = jax.random.split(k_head, cfg.n_codebooks)
        params["lm_head"] = {"w": jnp.stack(
            [dense_init(k, cfg.d_model, cfg.vocab_size, dtype=dt)["w"]
             for k in keys])}                      # [K, d, V]
    elif not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size,
                                       dtype=dt)
    return params


def init_decode_state(cfg: ModelConfig, batch: int, cache_len: int,
                      kv_bits: int = 0):
    """Per-layer decode state (stacked for homogeneous archs).
    ``kv_bits=8``: int8 KV cache (attention blocks only)."""
    if cfg.homogeneous:
        one = block_state_init(cfg, "attn", batch, cache_len, kv_bits)
        return jax.tree.map(
            lambda a: jnp.zeros((cfg.n_layers,) + a.shape, a.dtype), one)
    return tuple(block_state_init(cfg, cfg.block_kind(i), batch, cache_len,
                                  kv_bits if cfg.block_kind(i) == "attn"
                                  else 0)
                 for i in range(cfg.n_layers))


def init_paged_state(cfg: ModelConfig, n_pages: int, page_len: int):
    """Paged KV arena of a homogeneous full-attention arch: ``{"k","v"}``
    leaves ``[L, n_pages, n_kv, page_len, hd]``. One page of one kv head is
    one ``[page_len, hd]`` tile — the block ``kernels.paged_attention``
    streams — where dense caches keep ``[..., T, n_kv, hd]``."""
    shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_len, cfg.head_dim)
    dt = model_dtype(cfg)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg: ModelConfig,
                 embeddings: Optional[jnp.ndarray] = None):
    """tokens: [B,S] int32, or [B,K,S] for audio. ``embeddings`` is the
    stubbed modality-frontend output ([B,Nv,d] vision prefix)."""
    if cfg.frontend == "audio" and cfg.n_codebooks > 1:
        # sum codebook embeddings: table [K,V,d], tokens [B,K,S]
        x = jnp.sum(jnp.take_along_axis(
            params["embed"]["table"][None],            # [1,K,V,d]
            tokens[..., None].astype(jnp.int32), axis=2), axis=1)
    else:
        x = embed_apply(params["embed"], tokens)
    if cfg.tie_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.frontend == "vision" and embeddings is not None:
        x = jnp.concatenate([embeddings.astype(x.dtype), x], axis=1)
    return x


def norm_apply_final(params, x, cfg: ModelConfig):
    return norm_apply(params["final_norm"], x, cfg.norm)


def lm_logits(params, x, cfg: ModelConfig):
    if cfg.frontend == "audio" and cfg.n_codebooks > 1:
        return jnp.einsum("bsd,kdv->bksv", x.astype(jnp.float32),
                          params["lm_head"]["w"].astype(jnp.float32))
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                          params["embed"]["table"].astype(jnp.float32))
    return x.astype(jnp.float32) @ params["lm_head"]["w"].astype(jnp.float32)


def decode_tail_tokens(params, x, cfg: ModelConfig):
    """Fused decode tail: final norm -> LM head -> argmax in one kernel
    (``ops.decode_tail_op``), replacing the three separate HLO groups the
    legacy ``norm_apply + lm_logits + jnp.argmax`` chain emits per tick.
    On CPU the op's reference path is expression-identical to that chain,
    so tokens cannot move; multi-codebook audio heads keep the legacy chain
    (the per-codebook argmax is not a single head gather).

    x: [B, S, d] decoder output (pre final norm). Returns int32 tokens
    [B, S] ([B, K, S] audio)."""
    from repro.kernels import ops as kops

    if cfg.frontend == "audio" and cfg.n_codebooks > 1:
        xn = norm_apply(params["final_norm"], x, cfg.norm)
        return jnp.argmax(lm_logits(params, xn, cfg), axis=-1).astype(
            jnp.int32)
    fn = params["final_norm"]
    if cfg.tie_embeddings:
        heads, tied = params["embed"]["table"][None], True
    else:
        heads, tied = params["lm_head"]["w"][None], False
    return kops.decode_tail_op(x, fn["scale"], fn.get("bias"), heads,
                               norm_kind=cfg.norm, tied=tied)


# ---------------------------------------------------------------------------
# layer runners (shared by the full model and the split encoder/decoder)
# ---------------------------------------------------------------------------

def _meta_row(meta, i):
    """Layer ``i``'s row of ``layer_meta`` (None passes through)."""
    if meta is None:
        return None
    return {k: jnp.asarray(v)[i] for k, v in meta.items()}


def run_layers(layers, x, positions, cfg: ModelConfig, *, train: bool,
               kinds: Optional[Tuple[str, ...]] = None, first: int = 0):
    """Full-sequence pass through a group of layers.

    ``layers``: stacked pytree (homogeneous) or tuple of per-layer pytrees,
    the model's layers ``first ..`` (their per-layer attention settings).
    Returns (x, aux_loss_sum).
    """
    meta = layer_meta(cfg)
    if cfg.homogeneous:
        if meta is not None:
            n = jax.tree.leaves(layers)[0].shape[0]
            meta = {k: v[first:first + n] for k, v in meta.items()}

        def body(carry, xs):
            lp, m = xs
            h, aux = carry
            h = sharding.constrain(h, "resid")
            h, a = block_apply_full(lp, h, positions, cfg, "attn", train, m)
            return (h, aux + a), None
        f = jax.checkpoint(body) if train else body
        (x, aux), _ = jax.lax.scan(f, (x, jnp.zeros((), jnp.float32)),
                                   (layers, meta))
        return x, aux

    kinds = kinds or tuple(cfg.block_kind(i) for i in range(len(layers)))
    aux = jnp.zeros((), jnp.float32)
    for j, (lp, kind) in enumerate(zip(layers, kinds)):
        x = sharding.constrain(x, "resid")
        fn = functools.partial(block_apply_full, cfg=cfg, kind=kind,
                               train=train, meta=_meta_row(meta, first + j))
        if train:
            fn = jax.checkpoint(fn)
        x, a = fn(lp, x, positions)
        aux = aux + a
    return x, aux


def _run_layer_range(step, layers, x, states, cfg: ModelConfig, start: int,
                     stop: Optional[int], acc=None):
    """Run ``step(h, layer_params, layer_state, kind, meta) -> (h,
    new_state)`` over layers ``start..stop`` (default: to the end) of the
    WHOLE stack and return (x, states) with those layers' states replaced.
    ``meta`` is the layer's ``layer_meta`` row (None when layers are
    alike). With ``acc`` (a pytree of zeros) ``step`` returns a third value
    of that structure, summed over the layers and returned third.

    Homogeneous stacks scan over the layer indices: each iteration indexes
    layer ``i`` out of the full ``[L, ...]`` params the scan closes over and
    the full state stack it carries, and writes the new state back in place.
    Slicing the stacks at ``start``/``stop`` instead would make each half a
    buffer of its own — a copy of half the weights on every call. Tuples of
    per-layer pytrees (heterogeneous stacks) loop in Python, where a range
    of a tuple copies nothing."""
    stop = cfg.n_layers if stop is None else stop
    meta = layer_meta(cfg)

    def add(tot, out):
        if acc is None:
            return out[0], out[1], tot
        return out[0], out[1], jax.tree.map(jnp.add, tot, out[2])

    if cfg.homogeneous:
        def body(carry, i):
            h, sts, tot = carry
            at = functools.partial(jax.lax.dynamic_index_in_dim, index=i,
                                   keepdims=False)
            h, new, tot = add(tot, step(h, jax.tree.map(at, layers),
                                        jax.tree.map(at, sts), "attn",
                                        _meta_row(meta, i)))
            sts = jax.tree.map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, i, 0),
                sts, new)
            return (h, sts, tot), None
        (x, states, tot), _ = jax.lax.scan(
            body, (x, states, acc), jnp.arange(start, stop, dtype=jnp.int32))
        return (x, states) if acc is None else (x, states, tot)

    new_states = list(states)
    tot = acc
    for i in range(start, stop):
        x, new_states[i], tot = add(tot, step(
            x, layers[i], states[i], cfg.block_kind(i), _meta_row(meta, i)))
    return (x, tuple(new_states)) if acc is None \
        else (x, tuple(new_states), tot)


def run_layers_decode(layers, x, states, cur_pos, cfg: ModelConfig,
                      block_table=None, start: int = 0,
                      stop: Optional[int] = None):
    """One-token decode through layers ``start..stop`` of the whole stack.
    Returns (x, states with those layers updated); in a model with experts
    also the served MoE layer's stats summed over the layers, third: ``rows`` [B, 1] each token's rows routed to held experts and
    ``touched``, held experts that got a row. ``block_table`` (paged
    serving) is shared by every attention layer."""
    def step(h, lp, st, kind, meta):
        return block_apply_decode(lp, h, st, cur_pos, cfg, kind, block_table,
                                  meta)
    acc = ({"rows": jnp.zeros(x.shape[:-1], jnp.int32),
            "touched": jnp.zeros((), jnp.int32)} if cfg.is_moe else None)
    return _run_layer_range(step, layers, x, states, cfg, start, stop, acc)


def run_layers_prefill(layers, x, positions, states, cfg: ModelConfig,
                       lengths=None, block_table=None, start: int = 0,
                       stop: Optional[int] = None):
    """Full-sequence pass through layers ``start..stop`` of the whole stack
    that also populates their decode states. Returns (x, states with those
    layers updated)."""
    def step(h, lp, st, kind, meta):
        return block_apply_prefill(lp, h, positions, st, cfg, kind, lengths,
                                   block_table, meta)
    return _run_layer_range(step, layers, x, states, cfg, start, stop)


# ---------------------------------------------------------------------------
# top-level forwards
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: ModelConfig, *, train: bool = False,
            embeddings: Optional[jnp.ndarray] = None):
    """Full-sequence forward. Returns (logits, aux_loss)."""
    x = embed_tokens(params, tokens, cfg, embeddings)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x, aux = run_layers(params["layers"], x, positions, cfg, train=train)
    x = norm_apply(params["final_norm"], x, cfg.norm)
    logits = sharding.constrain(lm_logits(params, x, cfg), "logits")
    return logits, aux


def prefill(params, tokens, cfg: ModelConfig, states, lengths=None,
            embeddings: Optional[jnp.ndarray] = None, block_table=None):
    """Batched full-sequence prefill: run the whole prompt in ONE forward
    pass while populating ``states`` (KV caches scattered at their rolling
    slots, recurrent carries advanced to each row's last real token).

    tokens: [B, S] (or [B, K, S] audio), right-padded to a common bucket
    length; ``lengths``: optional [B] true prompt lengths (None: all S).
    With vision ``embeddings`` the prefix is concatenated exactly as in
    :func:`forward`, and ``lengths`` refer to the concatenated sequence.
    Returns (logits at each row's last real position, shaped like
    ``decode_step`` output, new_states).
    """
    x = embed_tokens(params, tokens, cfg, embeddings)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
    x, new_states = run_layers_prefill(params["layers"], x, positions,
                                       states, cfg, lengths=lengths,
                                       block_table=block_table)
    last = (lengths - 1 if lengths is not None
            else jnp.full((B,), S - 1, jnp.int32))
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)       # [B, 1, d]
    x = norm_apply(params["final_norm"], x, cfg.norm)
    return lm_logits(params, x, cfg), new_states


def decode_step(params, token, states, cur_pos, cfg: ModelConfig,
                embeddings: Optional[jnp.ndarray] = None, block_table=None,
                return_tokens: bool = False, with_stats: bool = False):
    """One new token against the decode state. token: [B,1] (or [B,K,1]
    audio). Returns (logits for the new position, new states); with
    ``return_tokens`` the fused decode tail replaces the logits with argmax
    int32 tokens (shaped like the token input) and the [B, V] logits never
    materialize. ``with_stats`` adds the MoE stats of
    ``run_layers_decode`` third (a model with experts)."""
    x = embed_tokens(params, token, cfg, None)
    x, new_states, *stats = run_layers_decode(
        params["layers"], x, states, cur_pos, cfg, block_table=block_table)
    if return_tokens:
        out = decode_tail_tokens(params, x, cfg)
    else:
        x = norm_apply(params["final_norm"], x, cfg.norm)
        out = lm_logits(params, x, cfg)
    return (out, new_states, *stats) if with_stats else (out, new_states)


def lm_loss(logits, labels, mask=None):
    """Cross-entropy over the vocab axis; labels int [B,S] or [B,K,S]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
