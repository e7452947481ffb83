"""Plain reference of a llama-family split model, for the correctness check.

Straightforward ``jax.numpy`` in float32 with matmuls at ``HIGHEST``
precision: embedding, pre-norm blocks (RMSNorm, GQA attention with
half-split rotary embeddings and optional q/k/v biases, gated SiLU MLP),
the split boundary (mode 0 passes the activation; mode m >= 1 is RMSNorm,
down-projection, row-wise symmetric quantization at the mode's bits,
dequantization and up-projection), final RMSNorm and LM head. It imports
nothing of the program and reads the weights the benchmark made, by name.

It runs teacher-forced over a prompt and the tokens the program served,
one layer at a time and attention in blocks of queries, so that it fits
beside the weights once the program's state is freed.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 (rows of activations, columns of weights, each with its own
scale) before the float32 product, the nearest precision below the
configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


def _fq(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, prec):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if prec == "fp8":
        x, w = _fq(x, -1), _fq(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = pos[..., None].astype(jnp.float32) * freqs       # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, qb, prec):
    """Causal GQA attention. q [n, T, nq, hd]; k, v [n, T, nkv, hd]."""
    n, T, nq, hd = q.shape
    nkv = k.shape[2]
    g = nq // nkv
    if prec == "fp8":
        q, k, v = _fq(q, -1), _fq(k, -1), _fq(v, -1)
    qg = q.reshape(n, T // qb, qb, nkv, g, hd).swapaxes(0, 1)
    t_k = jnp.arange(T)

    def block(args):
        i, qi = args                              # qi [n, qb, nkv, g, hd]
        s = jnp.einsum("nqkgh,ntkh->nkgqt", qi, k, precision=HI)
        s = s / math.sqrt(hd)
        t_q = i * qb + jnp.arange(qb)
        s = jnp.where(t_k[None, :] <= t_q[:, None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        if prec == "fp8":
            p = _fq(p, -1)
        o = jnp.einsum("nkgqt,ntkh->nqkgh", p, v, precision=HI)
        return o.reshape(n, qb, nq * hd)

    out = jax.lax.map(block, (jnp.arange(T // qb), qg))
    return out.swapaxes(0, 1).reshape(n, T, nq * hd)


@functools.partial(jax.jit, static_argnames=("cfg", "prec", "qb"))
def _block(x, layers, li, *, cfg, prec, qb):
    c = dict(cfg)
    lp = jax.tree.map(lambda a: a[li], layers)
    n, T, _ = x.shape
    nq, nkv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    pos = jnp.arange(T)
    h = _rms(x, lp["norm1"]["scale"], c["norm_eps"])
    proj = {}
    for name, heads in (("wq", nq), ("wk", nkv), ("wv", nkv)):
        y = _mm(h, lp["mix"][name]["w"], prec)
        if "b" in lp["mix"][name]:
            y = y + lp["mix"][name]["b"].astype(jnp.float32)
        proj[name] = y.reshape(n, T, heads, hd)
    q = _rope(proj["wq"], pos, c["rope_theta"])
    k = _rope(proj["wk"], pos, c["rope_theta"])
    x = x + _mm(_attend(q, k, proj["wv"], qb, prec), lp["mix"]["wo"]["w"], prec)
    h = _rms(x, lp["norm2"]["scale"], c["norm_eps"])
    g = _mm(h, lp["mlp"]["w_gate"]["w"], prec)
    u = _mm(h, lp["mlp"]["w_up"]["w"], prec)
    return x + _mm(jax.nn.silu(g) * u, lp["mlp"]["w_down"]["w"], prec)


@functools.partial(jax.jit, static_argnames=("cfg", "prec"))
def _embed(table, tokens, *, cfg, prec):
    c = dict(cfg)
    x = jnp.take(table, tokens, axis=0).astype(jnp.float32)
    if c["tie_embeddings"]:
        x = x * math.sqrt(c["d_model"])
    return x


@functools.partial(jax.jit, static_argnames=("bits", "prec"))
def _boundary(x, modes, head, *, bits, prec):
    """Mode-1 bottleneck at every position whose mode is 1; the others
    pass through unchanged."""
    h = _rms(x, head["norm"]["scale"], 1e-6)
    z = _mm(h, head["down"]["w"], prec)
    qmax = max((1 << (bits - 1)) - 1, 1)
    scale = jnp.maximum(jnp.max(jnp.abs(z), axis=-1, keepdims=True), 1e-8) / qmax
    y = _mm(jnp.clip(jnp.round(z / scale), -qmax, qmax) * scale,
            head["up"]["w"], prec)
    return jnp.where((modes > 0)[..., None], y, x)


@functools.partial(jax.jit, static_argnames=("cfg", "prec", "tc"))
def _head_chunk(params, x, ci, served, probe, *, cfg, prec, tc):
    """(best logit, logit of ``served``, logit of ``probe``, argmax) over
    positions ``ci*tc ..`` of ``x`` (the last layer's output)."""
    c = dict(cfg)
    xs = jax.lax.dynamic_slice_in_dim(x, ci * tc, tc, axis=1)
    h = _rms(xs, params["final_norm"]["scale"], c["norm_eps"])
    w = (params["embed"]["table"].T if c["tie_embeddings"]
         else params["lm_head"]["w"])
    logits = _mm(h, w, prec)                              # [n, tc, V]
    pick = lambda t: jnp.take_along_axis(
        logits, jax.lax.dynamic_slice_in_dim(t, ci * tc, tc, 1)[..., None],
        axis=-1)[..., 0]
    return (jnp.max(logits, -1), pick(served), pick(probe),
            jnp.argmax(logits, -1).astype(jnp.int32))


def _cfg_key(cfg: dict):
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "rope_theta", "norm_eps", "tie_embeddings", "split_at",
            "quant_bits")
    return tuple((k, cfg[k]) for k in keys)


def run(params, cfg: dict, tokens, modes, served, probe=None,
        prec: str = "f32", qb: int = 256, tc: int = 256):
    """Teacher-forced forward of ``tokens`` [n, T] (T a multiple of ``qb``
    and ``tc``) with boundary ``modes`` [n, T]. Returns numpy arrays
    [n, T]: best logit, logit of ``served`` (the token the program put at
    each position), logit of ``probe`` (zeros when None) and argmax."""
    ck = _cfg_key(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    served = jnp.asarray(served, jnp.int32)
    probe = served if probe is None else jnp.asarray(probe, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"]["table"], tokens, cfg=ck, prec=prec)
        for li in range(cfg["n_layers"]):
            if li == cfg["split_at"] and cfg["d_bottleneck"]:
                x = _boundary(x, jnp.asarray(modes, jnp.int32),
                              params["bneck_modes"][0],
                              bits=cfg["quant_bits"], prec=prec)
            x = _block(x, params["layers"], jnp.int32(li), cfg=ck, prec=prec,
                       qb=qb)
        outs = [_head_chunk(params, x, jnp.int32(ci), served, probe, cfg=ck,
                            prec=prec, tc=tc)
                for ci in range(tokens.shape[1] // tc)]
    return tuple(np.concatenate([np.asarray(o[i]) for o in outs], axis=1)
                 for i in range(4))
