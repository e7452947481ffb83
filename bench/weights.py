"""Random weights of a llama-family split model, made by the benchmark from
the run's seed in one jitted call on the device, in the type they are
served in. The tree has the layout the program's ``init_split_params``
gives (``check_layout`` holds it to that), so the program serves these
weights and the reference reads the same ones by name.

Scales: embedding N(0, 1); every projection N(0, 1/fan_in); norm scales
1 + N(0, 0.1^2) and q/k/v biases N(0, 0.1^2), so that no term of the
forward pass is an identity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def key_of(seed: int):
    """A PRNG key from any whole seed (the driver's exceed 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (1 << 31)),
                              (seed >> 31) % (1 << 31))


def shapes(cfg: dict) -> dict:
    """Leaf shapes of the parameter tree, by name."""
    L, d, V = cfg["n_layers"], cfg["d_model"], cfg["vocab_size"]
    nq, nkv, hd, ff = (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
                       cfg["d_ff"])

    def dense(i, o, bias=False):
        p = {"w": ("normal", (L, i, o), i ** -0.5)}
        if bias:
            p["b"] = ("bias", (L, o), 0.1)
        return p

    bias = cfg["qkv_bias"]
    tree = {
        "embed": {"table": ("normal", (V, d), 1.0)},
        "layers": {
            "norm1": {"scale": ("scale", (L, d), 0.1)},
            "mix": {"wq": dense(d, nq * hd, bias), "wk": dense(d, nkv * hd, bias),
                    "wv": dense(d, nkv * hd, bias), "wo": dense(nq * hd, d)},
            "norm2": {"scale": ("scale", (L, d), 0.1)},
            "mlp": {"w_gate": dense(d, ff), "w_up": dense(d, ff),
                    "w_down": dense(ff, d)},
        },
        "final_norm": {"scale": ("scale", (d,), 0.1)},
        "bneck_modes": tuple(
            {"norm": {"scale": ("scale", (d,), 0.1)},
             "down": {"w": ("normal", (d, w), d ** -0.5)},
             "up": {"w": ("normal", (w, d), w ** -0.5)}}
            for w in ([cfg["d_bottleneck"]] if cfg["d_bottleneck"] else [])),
    }
    if not cfg["tie_embeddings"]:
        tree["lm_head"] = {"w": ("normal", (d, V), d ** -0.5)}
    return tree


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], str)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec_items):
    dt = jnp.bfloat16
    out = []
    for i, (kind, shape, s) in enumerate(spec_items):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if kind == "scale":
            out.append((1.0 + s * z).astype(dt))
        else:                               # normal, bias
            out.append((s * z).astype(dt))
    return out


def make(cfg: dict, seed: int):
    """The weight tree for ``cfg`` from ``seed``, bf16, on the device."""
    tree = shapes(cfg)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_spec)
    vals = _make(key_of(seed), tuple(leaves))
    return jax.tree.unflatten(treedef, vals)


def check_layout(params, program_shapes) -> None:
    """Raise unless ``params`` has the program's tree, shapes and dtypes
    (``program_shapes``: ``jax.eval_shape`` of its initializer)."""
    a = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    b = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), program_shapes)
    if a != b:
        raise ValueError(f"benchmark weights do not match the program's "
                         f"parameter layout:\n{a}\nvs\n{b}")
