"""Mixture-of-experts layers.

``moe_apply`` is the GShard-style training layer (top-k router,
capacity-based dispatch/combine einsums): expert weights carry a leading E
dim that shards over the ``model`` mesh axis, so the dispatch einsum lowers
to the expert-parallel all-to-all.

``moe_held_apply`` is the served layer: dropless, and told which experts
it holds. It routes every token over all the router's experts and computes
the part of the result its own experts give, through the grouped expert
kernel (``ops.moe_gmm_op``) over the rows routed to them.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import sharding
from repro.models.layers import _act, _normal


def moe_init(key, d: int, d_ff: int, n_experts: int, *, dtype=jnp.bfloat16,
             n_routed: int = 0, router_dtype=jnp.float32):
    """Weights of ``n_experts`` held experts and a router over ``n_routed``
    (default: the held ones), stored in ``router_dtype``; its logits are
    computed in float32 either way."""
    kr, kg, ku, kd = jax.random.split(key, 4)
    return {
        "router": {"w": _normal(kr, (d, n_routed or n_experts), d ** -0.5,
                                router_dtype)},
        "w_gate": _normal(kg, (n_experts, d, d_ff), d ** -0.5, dtype),
        "w_up": _normal(ku, (n_experts, d, d_ff), d ** -0.5, dtype),
        "w_down": _normal(kd, (n_experts, d_ff, d), d_ff ** -0.5, dtype),
    }


def moe_apply(p, x, *, k: int, act: str = "silu",
              capacity_factor: float = 1.25,
              group_size: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, d]  ->  (y [B, S, d], aux_loss scalar).

    Tokens are routed within groups (default: one group per batch row;
    ``group_size`` splits rows further — smaller groups cut the quadratic
    dispatch-einsum cost, a hillclimb knob).
    """
    B, S, d = x.shape
    E = p["w_gate"].shape[0]
    if group_size and S % group_size == 0 and S > group_size:
        g = S // group_size
        xg = x.reshape(B * g, group_size, d)
    else:
        xg = x
    G, N, _ = xg.shape

    logits = xg.astype(jnp.float32) @ p["router"]["w"]          # [G,N,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                        # [G,N,k]
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)      # renormalize

    cap = max(int(capacity_factor * k * N / E), 1)

    # one-hot expert choice per (token, slot): [G, N, k, E]
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
    # position of each (token, slot) inside its expert buffer, priority by
    # (token index, slot index):
    flat = onehot.reshape(G, N * k, E)
    pos = jnp.cumsum(flat, axis=1) - flat                       # [G,N*k,E]
    pos = pos.reshape(G, N, k, E)
    keep = (pos < cap) & (onehot > 0)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
    # dispatch [G,N,E,C] (bool-ish), combine [G,N,E,C] (gate-weighted)
    dispatch = jnp.einsum("gnke,gnkec->gnec", onehot * keep, pos_oh)
    combine = jnp.einsum("gnk,gnke,gnkec->gnec", gates, onehot * keep, pos_oh)

    # NOTE: annotating xin/out with an expert-sharded constraint here was
    # tried and REFUTED (EXPERIMENTS.md §Perf pair B iter 2): GSPMD lowers
    # the combine side to a full expert-output all-gather (610 GiB/dev).
    # The einsum formulation is kept as the portable fallback; the fast
    # path is the explicit shard_map schedule in ``moe_ep.py``.
    xin = jnp.einsum("gnec,gnd->gecd", dispatch, xg.astype(jnp.float32))
    xin = xin.astype(p["w_gate"].dtype)                         # [G,E,C,d]
    h = _act(jnp.einsum("gecd,edf->gecf", xin, p["w_gate"]), act) \
        * jnp.einsum("gecd,edf->gecf", xin, p["w_up"])
    out = jnp.einsum("gecf,efd->gecd", h, p["w_down"])          # [G,E,C,d]
    y = jnp.einsum("gnec,gecd->gnd", combine, out.astype(jnp.float32))

    # Switch/GShard load-balance auxiliary loss
    frac_tokens = jnp.mean(onehot[..., 0, :] if k == 1 else
                           jnp.max(onehot, axis=2), axis=1)     # [G,E]
    frac_probs = jnp.mean(probs, axis=1)                        # [G,E]
    aux = E * jnp.mean(jnp.sum(frac_tokens * frac_probs, axis=-1))

    return y.reshape(B, S, d).astype(x.dtype), aux


#: candidate rows (tokens x k) the served layer sorts and multiplies at
#: once; longer blocks go through in chunks of this many rows, so that a
#: 16 x 4,096-token prefill holds 16,384 sorted rows at a time, not 524,288
CHUNK_ROWS = 16384


def route(router_w, x, k: int):
    """The router: float32 logits over every expert, softmax, top-k, and
    the k gates renormalised to sum to one. x: [N, d] -> (gates [N, k]
    float32, expert ids [N, k] int32)."""
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    return gates / jnp.sum(gates, axis=-1, keepdims=True), idx


def _held_rows(p, x, k: int, e0: int, act: str, kernel: str):
    """One block of tokens through the held experts. x: [N, d]. Returns
    (y [N, d] float32, rows routed here per token [N], held experts with at
    least one row)."""
    from repro.kernels import ops as K

    N, d = x.shape
    E = p["w_gate"].shape[0]
    gates, idx = route(p["router"]["w"], x, k)
    local = idx - e0
    held = (local >= 0) & (local < E)                          # [N, k]
    # experts not held sort past the last held group, and are never
    # multiplied; every candidate row keeps a place, so no row is dropped
    key = jnp.where(held, local, E).reshape(-1)                # [N*k]
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((E + 1,), jnp.int32).at[key].add(1)
    y = K.moe_gmm_op(x[order // k], p["w_gate"], p["w_up"], p["w_down"],
                     sizes[:E], act=act, name=kernel)          # [N*k, d]
    gate = jnp.where(held, gates, 0.0).reshape(-1)[order]
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(N * k, dtype=order.dtype))
    out = (y * gate[:, None])[inv].reshape(N, k, d).sum(axis=1)
    return (out, jnp.sum(held, axis=-1, dtype=jnp.int32),
            jnp.sum(sizes[:E] > 0, dtype=jnp.int32))


def moe_held_apply(p, x, *, k: int, e0: int = 0, act: str = "silu",
                   kernel: str = "moe_gmm"):
    """The served MoE layer: a dropless share of expert parallelism.

    ``p`` holds experts ``e0 .. e0 + E - 1`` (``w_*`` [E, ...]) and the
    router over all of them (``router.w`` [d, R]). Each token is routed over
    all R (``route``) and gets sum over its selected experts that are held
    here of ``gate * w_down(act(w_gate x) * w_up x)``; what experts held
    elsewhere would add is not computed. There is no capacity: the kernel
    sees all ``tokens * k`` candidate rows (a static shape), sorted by held
    expert with the rest past the last group.

    x: [..., d]. Blocks of more than ``CHUNK_ROWS // k`` tokens go through
    in chunks of that many, to bound the sorted copy of the rows. ``kernel``
    names the expert kernel's call in a profile. Returns
    (y shaped like x, stats): ``stats["rows"]`` [...] int32, the token's
    candidate rows that went to a held expert, and ``stats["touched"]``,
    the held experts that got at least one row (summed over chunks)."""
    lead, d = x.shape[:-1], x.shape[-1]
    xf = x.reshape(-1, d)
    N = xf.shape[0]
    chunk = max(CHUNK_ROWS // k, 1)
    if N > chunk and N % chunk == 0:
        y, rows, touched = jax.lax.map(
            lambda c: _held_rows(p, c, k, e0, act, kernel),
            xf.reshape(N // chunk, chunk, d))
        y, rows, touched = y.reshape(N, d), rows.reshape(N), jnp.sum(touched)
    else:
        y, rows, touched = _held_rows(p, xf, k, e0, act, kernel)
    return (y.astype(x.dtype).reshape(*lead, d),
            {"rows": rows.reshape(lead), "touched": touched})
