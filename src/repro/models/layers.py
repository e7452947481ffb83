"""Shared neural-network building blocks (pure JAX, functional style).

Every module follows the ``init(key, ...) -> params`` / ``apply(params, x)``
convention; params are plain dicts of jnp arrays so they compose into pytrees
that pjit/shard_map can shard.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def _normal(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape, dtype=jnp.float32)).astype(dtype)


def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.bfloat16, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": _normal(key, (d_in, d_out), scale, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype=dtype)
    return p


def dense_apply(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def embed_init(key, vocab: int, d: int, *, dtype=jnp.bfloat16):
    return {"table": _normal(key, (vocab, d), 1.0, dtype)}


def embed_apply(p, ids):
    return jnp.take(p["table"], ids, axis=0)


def embed_logits(p, x):
    """Tied read-out: x @ table^T (fp32 accumulation for the softmax)."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      p["table"].astype(jnp.float32))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_init(d: int, kind: str = "rmsnorm", *, dtype=jnp.bfloat16):
    p = {"scale": jnp.ones((d,), dtype=dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype=dtype)
    return p


def norm_apply(p, x, kind: str = "rmsnorm", eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    else:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# gated MLP (llama-style)
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, d_ff: int, *, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d, d_ff, dtype=dtype),
        "w_up": dense_init(k2, d, d_ff, dtype=dtype),
        "w_down": dense_init(k3, d_ff, d, dtype=dtype),
    }


def _act(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(f"unknown activation {kind!r}")


def mlp_apply(p, x, act: str = "silu"):
    g = _act(dense_apply(p["w_gate"], x), act)
    u = dense_apply(p["w_up"], x)
    return dense_apply(p["w_down"], g * u)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_inv_freq(hd: int, theta: float, *, yarn_factor: float = 0.0,
                  original_max: int = 0, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """Inverse rotary frequencies ``theta ** (-2i / hd)`` of the ``hd // 2``
    channel pairs, as float64 numpy. With ``yarn_factor`` they follow HF
    transformers' YaRN (``_compute_yarn_parameters``): pairs that turn
    fewer than ``beta_slow`` times over ``original_max`` positions are
    interpolated (frequency / factor), pairs that turn more than
    ``beta_fast`` times keep their frequency, and a linear ramp blends the
    pairs between, its ends rounded outwards (floor, ceil)."""
    import numpy as np
    half = hd // 2
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    if not yarn_factor:
        return inv

    def corr_dim(rotations):
        return (hd * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    extrapolate = 1.0 - ramp
    return inv / yarn_factor * (1.0 - extrapolate) + inv * extrapolate


def yarn_attention_factor(factor: float, given: float = 0.0) -> float:
    """YaRN's scale of cos and sin: ``given``, or HF's default
    0.1 ln(factor) + 1."""
    return given or (0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0)


def apply_rope(x, positions, theta: float = 10_000.0, table=None):
    """x: [..., S, H, hd]; positions: broadcastable to [..., S].

    ``table`` = (inv_freq [hd // 2], scale): the layer's own rotary table
    (arrays, so that it may differ between the layers of one scan); cos
    and sin are multiplied by ``scale``. None: plain ``theta`` table."""
    hd = x.shape[-1]
    half = hd // 2
    if table is None:
        freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                        * (math.log(theta) / half))
        scale = None
    else:
        freqs, scale = table
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]   # [..., S, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
                          axis=-1)
    return out.astype(x.dtype)
