"""Random weights of a split model, made by the benchmark from the run's
seed in one jitted call on the device, in the type they are served in.

The tree comes from the configuration's reference module
(``bench/references/<name>.py``, its ``shapes``): each leaf is a spec
(kind, shape, scale), and leaf ``i`` of the flattened tree is drawn from
``fold_in(key, i)``. The tree has the layout the program's
``init_split_params`` gives (``check_layout`` holds it to that), so the
program serves these weights and the reference reads the same ones by
name.
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


def make(tree: dict, seed: int):
    """The weights of a spec ``tree`` from ``seed``, bf16, on the device."""
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
