import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent without real
hardware.

For every (architecture x input shape x mesh) combination this lowers and
compiles the appropriate step program against ShapeDtypeStruct stand-ins
(no allocation), then records memory_analysis / cost_analysis / the
collective schedule parsed from the post-SPMD HLO into
``results/dryrun/<arch>__<shape>__<mesh>[__<variant>].json``.

Variants:
  baseline   - standard pjit step (TP over 'model', DP/FSDP over 'data'(+pod))
  pipeline0  - 2-stage pod pipeline, raw bf16 boundary (paper mode z)
  pipeline1  - 2-stage pod pipeline, bottleneck+int8 boundary (paper mode z')
  pipeline2  - pipeline1 + int8 BACKWARD wire (beyond paper, §Perf pair C)
  qtp0/qtp8  - manual Megatron-SP prefill, bf16 / int8-quantized gathers
               (beyond paper, §Perf pair A)
The pipeline variants exist only for multi-pod train/prefill of homogeneous
archs — they are the paper's technique at pod scale. Placement knobs:
--act-policy seq|batch|batch2d, --tp-scope all|ffn, --moe-ep.

NOTE: the XLA_FLAGS line above must run before ANY other import (jax locks
the device count on first init). Do not set this flag globally.
"""
import argparse
import dataclasses
import json
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config, get_shape
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.core import split as SP
from repro.data.tokens import token_batch_shapes
from repro.launch import analytic, roofline
from repro.launch.mesh import make_production_mesh
from repro.models import sharding
from repro.models import transformer as T
from repro.training import loop as train_loop
from repro.training import optimizer as opt

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# full-attention archs skip long_500k (sub-quadratic required); see DESIGN.md
LONG_CTX_ARCHS = ("mixtral-8x7b", "recurrentgemma-2b", "xlstm-125m")


def pair_supported(arch: str, shape: str) -> bool:
    if shape == "long_500k":
        return arch in LONG_CTX_ARCHS
    return True


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------

def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def input_specs(cfg: ModelConfig, sc: ShapeConfig, mesh,
                act_policy: str = "seq") -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for one batch (weak-type-correct,
    shardable, no device allocation)."""
    out = {}
    for name, shape in token_batch_shapes(cfg, sc.global_batch, sc.seq_len,
                                          sc.kind).items():
        dtype = jnp.float32 if name == "embeddings" else jnp.int32
        spec = sharding.batch_pspec(mesh, len(shape), sc.global_batch,
                                    act_policy)
        out[name] = _sds(shape, dtype, mesh, spec)
    return out


def abstract_params(cfg: ModelConfig, mesh, tp_scope: str = "all"):
    shapes = jax.eval_shape(
        lambda k: SP.init_split_params(k, cfg), jax.random.PRNGKey(0))
    specs = sharding.param_pspecs(shapes, mesh,
                                  stacked_layers=cfg.homogeneous,
                                  tp_scope=tp_scope)
    return jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, mesh, sp), shapes, specs), specs


def abstract_opt_state(params_abs, mesh):
    def f32_like(s):
        return _sds(s.shape, jnp.float32, mesh, s.sharding.spec)
    m = jax.tree.map(f32_like, params_abs)
    v = jax.tree.map(f32_like, params_abs)
    step = _sds((), jnp.int32, mesh, P())
    return opt.AdamState(step=step, m=m, v=v)


def abstract_decode_state(cfg: ModelConfig, sc: ShapeConfig, mesh,
                          kv_bits: int = 0):
    shapes = jax.eval_shape(
        lambda: T.init_decode_state(cfg, sc.global_batch, sc.seq_len,
                                    kv_bits))
    specs = sharding.state_pspecs(shapes, mesh, sc.global_batch,
                                  stacked=cfg.homogeneous)
    return jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, mesh, sp), shapes, specs)


# ---------------------------------------------------------------------------
# step builders per shape kind
# ---------------------------------------------------------------------------

def build_step(cfg: ModelConfig, sc: ShapeConfig, mesh, variant: str,
               seq_shard: bool = True, act_policy: Optional[str] = None,
               tp_scope: str = "all", moe_ep: bool = False,
               kv_bits: int = 0):
    tcfg = TrainConfig()
    policy = act_policy or ("seq" if seq_shard else "batch")
    if sc.kind == "train":
        use_pipe = variant.startswith("pipeline")
        mode = int(variant[-1]) if use_pipe else None
        bwd_bits = 0
        if use_pipe and mode == 2:        # pipeline2 = mode-1 + int8 bwd wire
            mode, bwd_bits = 1, 8
        step = train_loop.make_train_step(
            cfg, tcfg, mode=mode, mesh=mesh, use_pipeline=use_pipe,
            n_micro=4, act_policy=policy, moe_ep=moe_ep, bwd_bits=bwd_bits)
        params_abs, _ = abstract_params(cfg, mesh, tp_scope)
        opt_abs = abstract_opt_state(params_abs, mesh)
        batch_abs = input_specs(cfg, sc, mesh, policy)
        return jax.jit(step), (params_abs, opt_abs, batch_abs)

    if sc.kind == "prefill":
        use_pipe = variant.startswith("pipeline")
        use_qtp = variant.startswith("qtp")
        mode = int(variant[-1]) if (use_pipe or use_qtp) else None
        rules = sharding.default_activation_rules(mesh, act_policy=policy,
                                                   moe_ep=moe_ep)

        def prefill(params, batch):
            with sharding.activation_rules(mesh, rules):
                if use_pipe:
                    from repro.core import pipeline as PL
                    logits, _ = PL.pipeline_forward(
                        params, batch["tokens"], cfg, mesh=mesh, n_micro=4,
                        mode=mode, embeddings=batch.get("embeddings"))
                elif use_qtp:
                    from repro.core import qtp as QTP
                    logits = QTP.qtp_forward(
                        params, batch["tokens"], cfg, mesh=mesh, bits=mode,
                        embeddings=batch.get("embeddings"))
                else:
                    logits, _ = T.forward(
                        params, batch["tokens"], cfg,
                        embeddings=batch.get("embeddings"))
            return logits

        params_abs, _ = abstract_params(cfg, mesh, tp_scope)
        batch_abs = input_specs(cfg, sc, mesh, policy)
        return jax.jit(prefill), (params_abs, batch_abs)

    # decode: ONE new token against a seq_len-deep state
    def serve_step(params, token, states, cur_pos):
        logits, new_states = T.decode_step(params, token, states, cur_pos,
                                           cfg)
        return logits, new_states

    params_abs, _ = abstract_params(cfg, mesh, tp_scope)
    tok_shapes = token_batch_shapes(cfg, sc.global_batch, sc.seq_len, "decode")
    tok_abs = _sds(tok_shapes["tokens"], jnp.int32, mesh,
                   sharding.batch_pspec(mesh, len(tok_shapes["tokens"]),
                                        sc.global_batch))
    states_abs = abstract_decode_state(cfg, sc, mesh, kv_bits)
    pos_abs = _sds((), jnp.int32, mesh, P())
    return jax.jit(serve_step), (params_abs, tok_abs, states_abs, pos_abs)


# ---------------------------------------------------------------------------
# run one combination
# ---------------------------------------------------------------------------

def run_one(arch: str, shape: str, *, multi_pod: bool = False,
            variant: str = "baseline", seq_shard: bool = True,
            act_policy: Optional[str] = None, tp_scope: str = "all",
            moe_ep: bool = False, kv_bits: int = 0,
            save: bool = True, verbose: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    sc = get_shape(shape)
    if not pair_supported(arch, shape):
        return {"arch": arch, "shape": shape, "skipped": True,
                "reason": "full-attention arch; long_500k requires "
                          "sub-quadratic decode (DESIGN.md)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    step, args = build_step(cfg, sc, mesh, variant, seq_shard, act_policy,
                            tp_scope, moe_ep, kv_bits)
    with jax.set_mesh(mesh):
        lowered = step.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = roofline.parse_collectives(hlo)
    coll_bytes = int(sum(v["bytes"] for v in coll.values()))

    # analytic FLOPs/bytes (XLA's cost_analysis counts while-loop bodies
    # once, undercounting everything under lax.scan — see launch/analytic.py)
    flops_dev = analytic.step_flops(cfg, sc) / chips
    bytes_model = analytic.step_hbm_bytes(cfg, sc, chips,
                                          kv_bits=kv_bits)
    hbm_bytes = bytes_model.total
    terms = roofline.roofline_terms(flops_dev, hbm_bytes, coll_bytes, chips)

    toks = sc.global_batch * (1 if sc.kind == "decode" else sc.seq_len)
    n_active = cfg.active_param_count()
    mf = roofline.model_flops_per_step(
        n_active, toks, "train" if sc.kind == "train" else "inference")
    policy = act_policy or ("seq" if seq_shard else "batch")
    result = {
        "arch": arch, "shape": shape, "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "seq_shard": policy == "seq", "act_policy": policy,
        "tp_scope": tp_scope, "moe_ep": moe_ep, "kv_bits": kv_bits,
        "flops_per_device": flops_dev,
        "hbm_bytes_per_device": hbm_bytes,
        "hbm_bytes_breakdown": dataclasses.asdict(bytes_model),
        "collective_bytes_per_device": coll_bytes,
        "collectives": coll,
        "roofline": terms,
        "model_flops": mf,
        "useful_ratio": roofline.useful_ratio(mf, flops_dev, chips),
        "raw_cost_analysis": {k: float(v) for k, v in cost.items()
                              if k in ("flops", "bytes accessed")},
        "memory_analysis": _mem_dict(mem),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }
    if verbose:
        ma = result["memory_analysis"]
        print(f"[dryrun] {arch} x {shape} x {result['mesh']} ({variant}): "
              f"compute {terms['compute_s']*1e3:.2f}ms "
              f"memory {terms['memory_s']*1e3:.2f}ms "
              f"collective {terms['collective_s']*1e3:.2f}ms "
              f"-> {terms['dominant']}  "
              f"useful {result['useful_ratio']:.2f}  "
              f"argbytes/dev {ma.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
              f"temp {ma.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        tag = f"{arch}__{shape}__{result['mesh'].replace('x','_')}"
        if variant != "baseline":
            tag += f"__{variant}"
        if policy == "batch":
            tag += "__noseqshard"
        elif policy != "seq":
            tag += f"__{policy}"
        if tp_scope != "all":
            tag += f"__tp{tp_scope}"
        if moe_ep:
            tag += "__ep"
        if kv_bits:
            tag += f"__kv{kv_bits}"
        with open(os.path.join(RESULTS_DIR, tag + ".json"), "w") as f:
            json.dump(result, f, indent=1, default=str)
    return result


def _mem_dict(mem) -> Dict[str, float]:
    if mem is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + ["all"])
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + ["all"])
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "pipeline0", "pipeline1",
                             "pipeline2", "qtp0", "qtp8"])
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--act-policy", default=None,
                    choices=["seq", "batch", "batch2d"])
    ap.add_argument("--tp-scope", default="all", choices=["all", "ffn"])
    ap.add_argument("--kv-bits", type=int, default=0, choices=[0, 8],
                    help="int8 KV cache for decode shapes")
    ap.add_argument("--moe-ep", action="store_true",
                    help="shard_map expert-parallel MoE (requires "
                         "E %% model == 0 and batch %% chips == 0)")
    ap.add_argument("--no-save", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch in (None, "all") else [args.arch]
    shapes = list(SHAPES) if args.shape in (None, "all") else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multipod]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    run_one(arch, shape, multi_pod=mp, variant=args.variant,
                            seq_shard=not args.no_seq_shard,
                            act_policy=args.act_policy,
                            tp_scope=args.tp_scope, moe_ep=args.moe_ep,
                            kv_bits=args.kv_bits,
                            save=not args.no_save)
                except Exception as e:  # noqa: BLE001 - report and continue
                    failures.append((arch, shape, mp, repr(e)[:200]))
                    print(f"[dryrun] FAIL {arch} x {shape} "
                          f"multipod={mp}: {e!r}")
    if failures:
        print(f"\n{len(failures)} failures:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()
