"""Plain reference of a Mellum2-style split model (sparse experts beside
sliding-window and full GQA layers), for the correctness check, with the
model's weight tree and the work a token does in it. A configuration names
this module by ``"reference": "mellum_split"``.

It reads the published config's own keys (``hidden_size``,
``layer_types``, ``rope_parameters``, ``num_experts`` ...), and from the
program's keys only the cut: which experts are held here (``n_experts``
from ``expert_offset``) and the split (``split_at``, ``d_bottleneck``,
``quant_bits``). It imports nothing of the program and reads the weights
the benchmark made, by name.

Straightforward ``jax.numpy`` in float32 with matmuls at ``HIGHEST``
precision: embedding, pre-norm blocks (RMSNorm; GQA attention with
half-split rotary embeddings, a causal mask, and on sliding layers a mask
that keeps keys ``q - k < sliding_window``; the rotary table of each
layer's ``rope_parameters`` entry, YaRN's on full layers with cos and sin
scaled by its attention factor), then the sparse MLP: float32 router
logits over all ``num_experts`` experts, softmax, top-``num_experts_per_tok``,
gates renormalised when ``norm_topk_prob``; every held expert is computed
for every token and weighted by the token's gate for it (zero unless the
router chose it). What experts held on other chips would add is left out,
as the program leaves it out. Then the split boundary (mode 0 passes the
activation; mode m >= 1 is RMSNorm, down-projection, row-wise symmetric
quantization, dequantization and up-projection), final RMSNorm and the LM
head.

It runs teacher-forced over a prompt and the tokens the program served,
one layer at a time and attention in blocks of queries, so that it fits
beside the weights once the program's state is freed.

``precision="fp8"`` is the control: every matmul operand (the router's
too) is rounded to float8 e4m3 (rows of activations, columns of weights,
each with its own scale) before the float32 product, the nearest precision
below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG = -1e30

#: configuration keys this module reads (or checks) that are not
#: ``ModelConfig`` fields: the published config's own
READS = ("attention_bias", "hidden_act", "hidden_size", "intermediate_size",
         "mlp_layer_types", "max_position_embeddings", "max_window_layers",
         "model_type", "moe_intermediate_size", "norm_topk_prob",
         "num_attention_heads", "num_experts", "num_experts_per_tok",
         "num_hidden_layers", "num_key_value_heads", "rms_norm_eps",
         "rope_parameters", "tie_word_embeddings", "use_sliding_window")


def _published(cfg: dict) -> dict:
    """The published sizes this module computes with, after checking the
    parts of the published config it does not implement are absent."""
    L = cfg["num_hidden_layers"]
    if cfg["model_type"] != "mellum" or cfg["hidden_act"] != "silu" \
            or cfg["attention_bias"] or cfg["tie_word_embeddings"] \
            or not cfg["use_sliding_window"] or cfg["max_window_layers"] \
            or list(cfg["mlp_layer_types"]) != ["sparse"] * L \
            or len(cfg["layer_types"]) != L:
        raise ValueError("not a Mellum2 config this reference implements: "
                         "silu, no attention bias, untied head, sparse MLP "
                         "in every layer, one layer type per layer")
    held = (cfg["expert_offset"], cfg["expert_offset"] + cfg["n_experts"])
    if not 0 <= held[0] < held[1] <= cfg["num_experts"]:
        raise ValueError(f"held experts {held} not among "
                         f"{cfg['num_experts']}")
    return {"L": L, "d": cfg["hidden_size"],
            "nq": cfg["num_attention_heads"],
            "nkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "V": cfg["vocab_size"], "f": cfg["moe_intermediate_size"],
            "R": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
            "held": held, "eps": cfg["rms_norm_eps"],
            "window": cfg["sliding_window"]}


def shapes(cfg: dict) -> dict:
    """The weight tree, by name: each leaf (kind, shape, scale) for
    ``weights.make``, laid out as the program's ``init_split_params``: the
    router over all experts, the held experts' weights.

    Scales: embedding N(0, 1); every projection, the router's too,
    N(0, 1/fan_in); norm scales 1 + N(0, 0.1^2)."""
    p = _published(cfg)
    L, d, V, hd, f = p["L"], p["d"], p["V"], p["hd"], p["f"]
    E = p["held"][1] - p["held"][0]

    def dense(i, o):
        return {"w": ("normal", (L, i, o), i ** -0.5)}

    return {
        "embed": {"table": ("normal", (V, d), 1.0)},
        "layers": {
            "norm1": {"scale": ("scale", (L, d), 0.1)},
            "mix": {"wq": dense(d, p["nq"] * hd), "wk": dense(d, p["nkv"] * hd),
                    "wv": dense(d, p["nkv"] * hd), "wo": dense(p["nq"] * hd, d)},
            "norm2": {"scale": ("scale", (L, d), 0.1)},
            "mlp": {"router": dense(d, p["R"]),
                    "w_gate": ("normal", (L, E, d, f), d ** -0.5),
                    "w_up": ("normal", (L, E, d, f), d ** -0.5),
                    "w_down": ("normal", (L, E, f, d), f ** -0.5)},
        },
        "final_norm": {"scale": ("scale", (d,), 0.1)},
        "bneck_modes": tuple(
            {"norm": {"scale": ("scale", (d,), 0.1)},
             "down": {"w": ("normal", (d, w), d ** -0.5)},
             "up": {"w": ("normal", (w, d), w ** -0.5)}}
            for w in ([cfg["d_bottleneck"]] if cfg["d_bottleneck"] else [])),
        "lm_head": {"w": ("normal", (d, V), d ** -0.5)},
    }


def matmul_params(cfg: dict) -> int:
    """Parameters a token's layers multiply by (no embedding, no head):
    attention, the router, and the experts a token uses here on average,
    ``k * held / num_experts`` of them."""
    p = _published(cfg)
    d, hd, f = p["d"], p["hd"], p["f"]
    E = p["held"][1] - p["held"][0]
    attn = d * p["nq"] * hd * 2 + 2 * d * p["nkv"] * hd
    experts = p["k"] * E * 3 * d * f // p["R"]
    return p["L"] * (attn + d * p["R"] + experts)


def attention_flops(cfg: dict, rows: int) -> int:
    """q.k and p.v of one query over ``rows`` keys in every layer: full
    layers read all of them, sliding layers at most their window."""
    p = _published(cfg)
    n_full = sum(t == "full_attention" for t in cfg["layer_types"])
    n_slide = p["L"] - n_full
    per_row = 4 * p["nq"] * p["hd"]
    return per_row * (n_full * rows + n_slide * min(rows, p["window"]))


def rope_table(cfg: dict, layer_type: str):
    """(inverse frequencies [hd / 2], cos/sin scale) of a layer type's
    ``rope_parameters`` entry, following HF transformers' default and YaRN
    rotary initialisation, in float64."""
    rp = cfg["rope_parameters"][layer_type]
    hd, base = cfg["head_dim"], float(rp["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if rp["rope_type"] == "default":
        return inv, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope type {rp['rope_type']!r}")
    factor, orig = float(rp["factor"]), rp["original_max_position_embeddings"]

    def dim_of(rotations):          # pair index that turns this many times
        return hd * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(rp["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rp["beta_slow"])), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp               # 1: extrapolate (keep), 0: interpolate
    inv = inv / factor * (1.0 - keep) + inv * keep
    scale = rp.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv, float(scale)


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


def _rope(x, pos, inv, scale):
    half = x.shape[-1] // 2
    ang = pos[..., None].astype(jnp.float32) * inv           # [T, half]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, window, qb, prec):
    """Causal GQA attention, keys ``t_q - t_k < window`` when ``window``.
    q [n, T, nq, hd]; k, v [n, T, nkv, hd]."""
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
        keep = t_k[None, :] <= t_q[:, None]
        if window:
            keep &= t_q[:, None] - t_k[None, :] < window
        s = jnp.where(keep, s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        if prec == "fp8":
            p = _fq(p, -1)
        o = jnp.einsum("nkgqt,ntkh->nqkgh", p, v, precision=HI)
        return o.reshape(n, qb, nq * hd)

    out = jax.lax.map(block, (jnp.arange(T // qb), qg))
    return out.swapaxes(0, 1).reshape(n, T, nq * hd)


def _experts(h, mlp, k, R, held, norm_topk, prec):
    """The sparse MLP's share here: every held expert on every token,
    weighted by the token's renormalised top-k gate for it."""
    logits = _mm(h, mlp["router"]["w"], prec)                # [n, T, R]
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate_all = jnp.sum(jax.nn.one_hot(idx, R, dtype=jnp.float32)
                       * top[..., None], axis=-2)            # [n, T, R]
    gates = jnp.moveaxis(gate_all[..., held[0]:held[1]], -1, 0)  # [E, n, T]

    def one(acc, args):
        g, wg, wu, wd = args
        a = jax.nn.silu(_mm(h, wg, prec)) * _mm(h, wu, prec)
        return acc + g[..., None] * _mm(a, wd, prec), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (gates, mlp["w_gate"], mlp["w_up"], mlp["w_down"]))
    return out


@functools.partial(jax.jit, static_argnames=("window", "cfg", "prec", "qb"))
def _block(x, layers, li, inv, scale, *, window, cfg, prec, qb):
    c = dict(cfg)
    lp = jax.tree.map(lambda a: a[li], layers)
    n, T, _ = x.shape
    nq, nkv, hd = c["nq"], c["nkv"], c["hd"]
    pos = jnp.arange(T)
    h = _rms(x, lp["norm1"]["scale"], c["eps"])
    q = _mm(h, lp["mix"]["wq"]["w"], prec).reshape(n, T, nq, hd)
    k = _mm(h, lp["mix"]["wk"]["w"], prec).reshape(n, T, nkv, hd)
    v = _mm(h, lp["mix"]["wv"]["w"], prec).reshape(n, T, nkv, hd)
    q, k = _rope(q, pos, inv, scale), _rope(k, pos, inv, scale)
    x = x + _mm(_attend(q, k, v, window, qb, prec), lp["mix"]["wo"]["w"],
                prec)
    h = _rms(x, lp["norm2"]["scale"], c["eps"])
    return x + _experts(h, lp["mlp"], c["k"], c["R"], c["held"],
                        c["norm_topk"], prec)


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


@functools.partial(jax.jit, static_argnames=("eps", "prec", "tc"))
def _head_chunk(params, x, ci, served, probe, *, eps, prec, tc):
    """(best logit, logit of ``served``, logit of ``probe``, argmax) over
    positions ``ci*tc ..`` of ``x`` (the last layer's output)."""
    xs = jax.lax.dynamic_slice_in_dim(x, ci * tc, tc, axis=1)
    h = _rms(xs, params["final_norm"]["scale"], eps)
    logits = _mm(h, params["lm_head"]["w"], prec)            # [n, tc, V]
    pick = lambda t: jnp.take_along_axis(
        logits, jax.lax.dynamic_slice_in_dim(t, ci * tc, tc, 1)[..., None],
        axis=-1)[..., 0]
    return (jnp.max(logits, -1), pick(served), pick(probe),
            jnp.argmax(logits, -1).astype(jnp.int32))


def run(params, cfg: dict, tokens, modes, served, probe=None,
        prec: str = "f32", qb: int = 256, tc: int = 256):
    """Teacher-forced forward of ``tokens`` [n, T] (T a multiple of ``qb``
    and ``tc``) with boundary ``modes`` [n, T]. Returns numpy arrays
    [n, T]: best logit, logit of ``served`` (the token the program put at
    each position), logit of ``probe`` (zeros when None) and argmax."""
    p = _published(cfg)
    ck = tuple(sorted({"nq": p["nq"], "nkv": p["nkv"], "hd": p["hd"],
                       "eps": p["eps"], "k": p["k"], "R": p["R"],
                       "held": p["held"],
                       "norm_topk": bool(cfg["norm_topk_prob"])}.items()))
    tables = {t: rope_table(cfg, t) for t in set(cfg["layer_types"])}
    tokens = jnp.asarray(tokens, jnp.int32)
    served = jnp.asarray(served, jnp.int32)
    probe = served if probe is None else jnp.asarray(probe, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(
            jnp.float32)
        for li, kind in enumerate(cfg["layer_types"]):
            if li == cfg["split_at"] and cfg["d_bottleneck"]:
                x = _boundary(x, jnp.asarray(modes, jnp.int32),
                              params["bneck_modes"][0],
                              bits=cfg["quant_bits"], prec=prec)
            inv, scale = tables[kind]
            window = p["window"] if kind == "sliding_attention" else 0
            x = _block(x, params["layers"], jnp.int32(li),
                       jnp.asarray(inv, jnp.float32), jnp.float32(scale),
                       window=window, cfg=ck, prec=prec, qb=qb)
        outs = [_head_chunk(params, x, jnp.int32(ci), served, probe,
                            eps=p["eps"], prec=prec, tc=tc)
                for ci in range(tokens.shape[1] // tc)]
    return tuple(np.concatenate([np.asarray(o[i]) for o in outs], axis=1)
                 for i in range(4))
