"""Sharding rules: parameter PartitionSpecs by pytree path + activation
constraints, with divisibility-aware fallback.

Conventions (single pod mesh = (data, model); multi-pod adds a leading pod
axis used for data parallelism by default):
  - FSDP: weight input dims shard over ``data``.
  - TP (megatron): head/ffn/expert output dims shard over ``model``.
  - Activations: batch over ``data`` (+ ``pod``), residual sequence over
    ``model`` (sequence parallelism, needed for the biggest archs' remat
    footprint).
"""
from __future__ import annotations

import contextlib
import re
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check: bool = False):
    """``jax.shard_map`` with this repo's defaults: ``axis_names`` is the
    set of *manual* axes (all of them when None; the rest stay automatic),
    and the varying-manual-axes check is off unless ``check``."""
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
              check_vma=check)
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)


# ---------------------------------------------------------------------------
# activation-constraint context
# ---------------------------------------------------------------------------

_ACTIVATION_CTX: Dict[str, Any] = {"mesh": None, "rules": {}}


@contextlib.contextmanager
def activation_rules(mesh: Optional[Mesh], rules: Dict[str, P]):
    """Install activation sharding constraints used by ``constrain``."""
    old = dict(_ACTIVATION_CTX)
    _ACTIVATION_CTX.update(mesh=mesh, rules=rules)
    try:
        yield
    finally:
        _ACTIVATION_CTX.update(old)


def constrain(x, name: str):
    mesh, rules = _ACTIVATION_CTX["mesh"], _ACTIVATION_CTX["rules"]
    if mesh is None or name not in rules:
        return x
    spec = _fit_spec(rules[name], x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def ctx_mesh():
    """Mesh of the installed activation rules (None outside a mesh ctx)."""
    return _ACTIVATION_CTX["mesh"]


def ctx_flag(name: str) -> bool:
    """Boolean feature flags riding the activation-rule context (e.g.
    ``moe_ep`` switches the MoE layer to the shard_map expert-parallel
    schedule)."""
    return bool(_ACTIVATION_CTX["rules"].get(name, False))


# ---------------------------------------------------------------------------
# divisibility-aware spec fitting
# ---------------------------------------------------------------------------

def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def _fit_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on dims the mesh axes don't divide (or that don't
    exist); pad the spec with None up to the rank. Tuple axes degrade by
    trimming trailing axes (e.g. batch 256 on a 512-chip ('pod','data',
    'model') spec falls back to ('pod','data') rather than replicating)."""
    out = []
    for i, dim in enumerate(shape):
        axis = spec[i] if i < len(spec) else None
        if isinstance(axis, (tuple, list)):
            axis = tuple(axis)
            while axis and dim % _axis_size(mesh, axis) != 0:
                axis = axis[:-1]
            axis = axis or None
        elif axis is not None and dim % _axis_size(mesh, axis) != 0:
            axis = None
        out.append(axis)
    return P(*out)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

# (regex on the '/'-joined tree path, spec WITHOUT the stacked-layer dim)
_PARAM_RULES = [
    # embeddings / heads
    (r"embed/table$", {2: P("model", None), 3: P(None, "model", None)}),
    (r"lm_head/w$", {2: P("data", "model"), 3: P(None, "data", "model")}),
    # attention
    (r"mix/wq/w$", P("data", "model")),
    (r"mix/wk/w$", P("data", "model")),
    (r"mix/wv/w$", P("data", "model")),
    (r"mix/wo/w$", P("model", "data")),
    (r"mix/w[qkv]/b$", P("model")),
    # dense MLP
    (r"mlp/w_gate/w$", P("data", "model")),
    (r"mlp/w_up/w$", P("data", "model")),
    (r"mlp/w_down/w$", P("model", "data")),
    # MoE — expert dim over model when divisible, else shard d/f dims
    (r"mlp/router/w$", P(None, None)),
    (r"mlp/w_gate$", P("model", "data", None)),
    (r"mlp/w_up$", P("model", "data", None)),
    (r"mlp/w_down$", P("model", None, "data")),
    # RG-LRU
    (r"mix/in_gate/w$", P("data", "model")),
    (r"mix/in_rec/w$", P("data", "model")),
    (r"mix/w_[ax]/w$", P("data", "model")),
    (r"mix/w_[ax]/b$", P("model")),
    (r"mix/conv$", P(None, "model")),
    (r"mix/lam$", P("model")),
    (r"mix/out/w$", P("model", "data")),
    # xLSTM
    (r"mix/up_[lr]/w$", P("data", "model")),
    (r"mix/up/w$", P("data", "model")),
    (r"mix/up_gate/w$", P("data", "model")),
    (r"mix/w[qkvifzo]/w$", P("data", "model")),
    (r"mix/w_[ifzo]/w$", P("data", "model")),
    (r"mix/down/w$", P("model", "data")),
    (r"mix/r_[ifzo]$", P(None, None, None)),
    # bottleneck heads (core/bottleneck.py)
    (r"down/w$", P("data", "model")),
    (r"up/w$", P("model", "data")),
    # paper LSTM PoC (tiny — replicate)
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _moe_alt_spec(name: str, shape, mesh: Mesh) -> Optional[P]:
    """MoE expert weights when E doesn't divide ``model``: shard d/f dims."""
    E = shape[-3] if len(shape) >= 3 else 0
    if E and E % _axis_size(mesh, "model") != 0:
        if name.endswith("w_down"):
            return P(*([None] * (len(shape) - 3)), None, "model", "data")
        return P(*([None] * (len(shape) - 3)), None, "data", "model")
    return None


def param_pspecs(params, mesh: Mesh, *, stacked_layers: bool = True,
                 tp_scope: str = "all"):
    """Pytree of PartitionSpecs matching ``params``.

    ``stacked_layers``: params under 'layers/' carry a leading L dim
    (homogeneous scan archs) that stays unsharded.
    ``tp_scope``: 'all' (megatron TP everywhere) or 'ffn' (attention/mixer
    weights replicated over ``model`` — removes the attention TP all-reduce
    at the cost of replicated attention-weight storage; a §Perf hillclimb
    knob, best for archs whose attention weights are small relative to FFN).
    """
    def rule_for(path, leaf):
        name = _path_str(path)
        in_layers = name.startswith("layers/")
        stacked = stacked_layers and in_layers and not re.match(
            r"layers/\d", name)
        shape = leaf.shape
        base_rank = len(shape) - (1 if stacked else 0)
        for pat, spec in _PARAM_RULES:
            if re.search(pat, name):
                if isinstance(spec, dict):
                    spec = spec.get(base_rank, P())
                if "mlp/w_" in name and not name.endswith("/w"):
                    alt = _moe_alt_spec(name, shape, mesh)
                    if alt is not None:
                        spec = P(*alt[-base_rank:])
                if tp_scope == "ffn" and "mix/" in name:
                    spec = P(*(None if a == "model" else a for a in spec))
                if stacked:
                    spec = P(None, *spec)
                return _fit_spec(spec, shape, mesh)
        return P()  # replicate (norms, small params, LSTM PoC)

    return jax.tree_util.tree_map_with_path(rule_for, params)


# ---------------------------------------------------------------------------
# activation / batch rules
# ---------------------------------------------------------------------------

def dp_axes(mesh: Mesh):
    """Mesh axes used for data parallelism (pod folds into data if present)."""
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def all_axes(mesh: Mesh):
    """Every mesh axis, for fully-data-parallel (ZeRO-3-style) activations."""
    return tuple(mesh.shape.keys())


def batch_pspec(mesh: Mesh, rank: int, batch_size: int,
                act_policy: str = "seq") -> P:
    dp = all_axes(mesh) if act_policy == "batch2d" else dp_axes(mesh)
    while dp and batch_size % _axis_size(mesh, dp) != 0:
        # long_500k has batch 1 (and batch2d needs batch % chips == 0):
        # drop trailing axes until the batch divides, else replicate
        dp = dp[:-1] or None
    return P(dp, *([None] * (rank - 1)))


def default_activation_rules(mesh: Mesh, *, seq_shard: bool = True,
                             act_policy: Optional[str] = None,
                             moe_ep: bool = False):
    """Residual stream + logits constraints.

    Policies (see EXPERIMENTS.md §Perf for the derivation):
      ``seq``     batch over dp axes + sequence over ``model`` (sequence
                  parallelism: bounds the per-chip remat footprint, but XLA
                  inserts relayout all-gathers/all-to-alls at every
                  seq<->head-sharded transition — collective-heavy).
      ``batch``   batch over dp axes only; weights stay 2D-sharded (ZeRO-3):
                  per-layer weight all-gathers replace activation relayouts.
      ``batch2d`` batch over ALL mesh axes (pure FSDP at chip granularity) —
                  the relayout-free layout when global_batch % chips == 0.
    ``seq_shard=False`` is back-compat for ``batch``.
    """
    policy = act_policy or ("seq" if seq_shard else "batch")
    dp = dp_axes(mesh)
    rules = {"logits": P(dp, None, "model")}
    if policy == "seq":
        rules["resid"] = P(dp, "model", None)
    elif policy == "batch":
        rules["resid"] = P(dp, None, None)
    elif policy == "batch2d":
        axes = all_axes(mesh)
        rules["resid"] = P(axes, None, None)
        rules["logits"] = P(axes, None, None)
    else:
        raise ValueError(f"unknown act_policy {policy!r}")
    if moe_ep:
        rules["moe_ep"] = True
    return rules


# ---------------------------------------------------------------------------
# serving mesh — ('dp', 'mp') data plane for the continuous-batching engine
# ---------------------------------------------------------------------------
#
# Serving shards differently from training: the batch dim IS the slot pool
# (thousands of concurrent sessions), so slots shard over ``dp`` while
# parameters replicate across it; ``mp`` carries megatron tensor parallelism
# (params + KV head dim). dp-only meshes are bit-identical to single-device
# execution (slot sharding is pure data placement); mp > 1 reassociates
# head-dim reductions and is numerically equivalent but not bit-exact — see
# docs/sharding.md.

#: leaf names (last pytree-path component) holding KV caches shaped
#: ``[..., slots, T, n_kv, head_dim]``. Exact-component match on purpose:
#: ``endswith`` would also catch e.g. the rglru ``conv`` state.
_KV_LEAF_NAMES = frozenset({"k", "v", "k_s", "v_s"})


def serving_mesh(dp: int, mp: int = 1, *, devices=None) -> Mesh:
    """Build the serving ``('dp', 'mp')`` mesh from the first ``dp * mp``
    devices (or an explicit device subset, e.g. an EdgeCluster replica's
    slice)."""
    devices = list(jax.devices() if devices is None else devices)
    if dp < 1 or mp < 1:
        raise ValueError(f"mesh axes must be >= 1, got dp={dp} mp={mp}")
    need = dp * mp
    if need > len(devices):
        raise ValueError(
            f"mesh ({dp} x {mp}) needs {need} devices, only "
            f"{len(devices)} available — on CPU, set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return make_mesh((dp, mp), ("dp", "mp"), devices=devices[:need])


def _rename_spec(spec: P, mapping: Dict[Optional[str], Optional[str]]) -> P:
    out = []
    for axis in spec:
        if isinstance(axis, (tuple, list)):
            renamed = tuple(mapping.get(a, a) for a in axis)
            renamed = tuple(a for a in renamed if a is not None)
            axis = renamed if len(renamed) > 1 else (
                renamed[0] if renamed else None)
        else:
            axis = mapping.get(axis, axis)
        out.append(axis)
    return P(*out)


def serving_param_pspecs(params, mesh: Mesh, **kwargs):
    """Parameter specs on the serving mesh: TP dims over ``mp``, FSDP dims
    replicated (every dp row serves every slot, so weights replicate over
    ``dp``). Reuses the training ``_PARAM_RULES`` via a proxy mesh with the
    training axis names, then renames ``model -> mp`` / drops ``data``."""
    proxy = make_mesh(mesh.devices.shape, ("data", "model"),
                      devices=mesh.devices.flat)
    specs = param_pspecs(params, proxy, **kwargs)
    ren = {"data": None, "model": "mp"}
    return jax.tree.map(lambda s: _rename_spec(s, ren), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _pool_spec(path, shape, mesh: Mesh, slot_axis: int,
               paged: bool = False) -> P:
    spec = [None] * len(shape)
    if len(shape) > slot_axis:
        spec[slot_axis] = "dp"
    last = _path_str(path).split("/")[-1]
    if last in _KV_LEAF_NAMES and len(shape) == slot_axis + 4:
        # dense [..., slots, T, n_kv, head_dim] or paged
        # [..., pages, n_kv, page_len, head_dim] — head groups over mp
        spec[slot_axis + (1 if paged else 2)] = "mp"
    return _fit_spec(P(*spec), shape, mesh)


def pool_pspecs(states, mesh: Mesh, *, slot_axis: int, paged: bool = False):
    """Slot-pool specs: slot axis over ``dp``, KV head groups over ``mp``;
    non-dividing dims fall back to replicated (``_fit_spec``). ``slot_axis``
    is 1 for stacked homogeneous states ``[L, S, ...]`` and the paged arena
    ``[L, pages, ...]`` (pages are that pool's slot axis; ``paged`` puts
    its head axis right after them), 0 for heterogeneous per-layer states
    ``[S, ...]``."""
    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: _pool_spec(p, leaf.shape, mesh, slot_axis, paged),
        states)


def shard_pool(states, mesh: Mesh, *, slot_axis: int, paged: bool = False):
    """Place a slot-pool state tree onto the serving mesh."""
    specs = pool_pspecs(states, mesh, slot_axis=slot_axis, paged=paged)
    return jax.tree.map(
        lambda leaf, s: jax.device_put(leaf, NamedSharding(mesh, s)),
        states, specs)


def constrain_batch(x, mesh: Optional[Mesh], *, axis: int = 0):
    """Constrain one array's batch/slot ``axis`` over ``dp`` (no-op when
    unsharded or non-dividing)."""
    if mesh is None:
        return x
    spec = [None] * x.ndim
    spec[axis] = "dp"
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, _fit_spec(P(*spec), x.shape, mesh)))


def shard_batch(x, mesh: Optional[Mesh], *, axis: int = 0):
    """``device_put`` one array with its batch/slot ``axis`` over ``dp``
    (the committed-placement counterpart of :func:`constrain_batch`;
    no-op when unsharded or non-dividing)."""
    if mesh is None:
        return x
    spec = [None] * x.ndim
    spec[axis] = "dp"
    return jax.device_put(
        x, NamedSharding(mesh, _fit_spec(P(*spec), x.shape, mesh)))


def shard_params(params, mesh: Optional[Mesh], **kwargs):
    """Place a parameter tree with :func:`serving_param_pspecs` shardings
    (no-op without a mesh)."""
    if mesh is None:
        return params
    specs = serving_param_pspecs(params, mesh, **kwargs)
    return jax.tree.map(
        lambda leaf, s: jax.device_put(leaf, NamedSharding(mesh, s)),
        params, specs)


def replicate(tree, mesh: Optional[Mesh]):
    """Place every leaf fully replicated on the mesh (params/bank in the
    serving engine; no-op without a mesh)."""
    if mesh is None:
        return tree
    sh = NamedSharding(mesh, P())
    return jax.tree.map(lambda leaf: jax.device_put(leaf, sh), tree)


def state_pspecs(states, mesh: Mesh, batch: int, *, stacked: bool) -> Any:
    """Decode-state (KV cache / recurrent state) specs: batch over data; KV
    heads over model when divisible, else cache time dim over model."""
    dp = dp_axes(mesh)
    bdp = dp if batch % _axis_size(mesh, dp) == 0 else None

    def rule(path, leaf):
        name = _path_str(path)
        shape = leaf.shape
        off = 1 if stacked else 0           # leading L dim
        spec = [None] * len(shape)
        if len(shape) - off >= 1:
            spec[off] = bdp                 # batch dim
        if name.endswith(("k", "v", "k_s", "v_s")) and len(shape) - off == 4:
            # [*,B,T,n_kv,hd]
            n_kv, T = shape[off + 2], shape[off + 1]
            m = _axis_size(mesh, "model")
            if n_kv % m == 0:
                spec[off + 2] = "model"
            elif T % m == 0:
                spec[off + 1] = "model"
        elif name.endswith("C") and len(shape) - off == 4:
            spec[off + 1] = "model" if shape[off + 1] % _axis_size(
                mesh, "model") == 0 else None
        return _fit_spec(P(*spec), shape, mesh)

    return jax.tree_util.tree_map_with_path(rule, states)
