"""Production training launcher.

Builds a mesh over the *actual* devices of the host (degrading gracefully to
1 CPU device), shards params/optimizer with the same rules the multi-pod
dry-run proves out, and runs the (optionally split-cascade) training loop
with checkpointing.

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --reduced \
        --steps 50 --batch 4 --seq 128
    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --reduced \
        --cascade --steps 40            # Algorithm 1: phase-1 then phase-2

On a real TPU slice the same entry point runs the full configs: the mesh is
shaped from ``jax.device_count()`` (data x model), params are initialized
directly into their shards via ``jax.jit`` out_shardings, and the step is
donated to keep HBM flat.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.configs.base import ModelConfig, TrainConfig
from repro.core import cascade as CC
from repro.core import split as SP
from repro.data import tokens
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import sharding
from repro.training import checkpoint
from repro.training import loop as L
from repro.training import optimizer as opt


def sharded_init(cfg: ModelConfig, mesh, seed: int = 0):
    """Initialize params directly into their shards (no host round-trip)."""
    abstract = jax.eval_shape(
        lambda k: SP.init_split_params(k, cfg), jax.random.PRNGKey(seed))
    specs = sharding.param_pspecs(abstract, mesh,
                                  stacked_layers=cfg.homogeneous)
    out_sh = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs)
    init = jax.jit(lambda k: SP.init_split_params(k, cfg),
                   out_shardings=out_sh)
    with jax.set_mesh(mesh):
        return init(jax.random.PRNGKey(seed)), specs


def run_phase(params, cfg, tcfg, mesh, specs, data_fn, *, steps, mode,
              log_every=10, donate=True):
    """One monolithic/split training phase on a mesh."""
    step_fn = L.make_train_step(cfg, tcfg, mode=mode, mesh=mesh)
    opt_state = opt.init(params)
    jitted = jax.jit(step_fn, donate_argnums=(0, 1) if donate else ())
    hist = []
    t0 = time.time()
    with jax.set_mesh(mesh):
        for s in range(steps):
            batch = {k: jnp.asarray(v) for k, v in data_fn(s).items()}
            params, opt_state, m = jitted(params, opt_state, batch)
            if s % log_every == 0 or s == steps - 1:
                rec = {k: float(v) for k, v in m.items()}
                rec.update(step=s, wall=round(time.time() - t0, 1))
                hist.append(rec)
                print(f"[launch.train] step {s:4d} loss {rec['loss']:.4f} "
                      f"({rec['wall']}s)")
    return params, hist


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-scale smoke)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mode", type=int, default=None,
                    help="split bottleneck mode (None = monolithic)")
    ap.add_argument("--cascade", action="store_true",
                    help="run Algorithm 1: phase-1 (mode 0) then phase-2 "
                         "(frozen backbone, train bottleneck head)")
    ap.add_argument("--mp", type=int, default=1, help="model-parallel size")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="results/ckpt")
    ap.add_argument("--resume", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_host_mesh(args.mp)
    print(f"== launch.train {args.arch} ({'reduced' if args.reduced else 'FULL'}) "
          f"on mesh {dict(mesh.shape)} — {cfg.param_count()/1e6:.1f}M params ==")

    params, specs = sharded_init(cfg, mesh, args.seed)
    if args.resume:
        params = checkpoint.restore(args.resume, params)
        print(f"resumed from {args.resume}")

    tcfg = TrainConfig(learning_rate=args.lr, warmup_steps=max(args.steps // 10, 1),
                       total_steps=max(args.steps, 100), seed=args.seed)
    src = tokens.MarkovTokenSource(cfg, seed=args.seed)
    data_fn = lambda s: src.batch(args.batch, args.seq, s)  # noqa: E731

    os.makedirs(args.ckpt_dir, exist_ok=True)
    history = {}
    if args.cascade:
        # Algorithm 1 over all configured modes, sharded on the host mesh.
        def loss_fn(p, batch, mode):
            return L.make_loss_fn(cfg, mode=mode)(p, batch)

        def eval_fn(p, mode):
            b = {k: jnp.asarray(v) for k, v in data_fn(10_001).items()}
            return L.make_eval_step(cfg, mode=mode)(p, b)

        n_modes = cfg.split.n_modes
        with jax.set_mesh(mesh):
            params, hist = CC.train_cascade(
                params, loss_fn,
                lambda s: {k: jnp.asarray(v) for k, v in data_fn(s).items()},
                tcfg, n_modes=n_modes, steps_per_phase=args.steps,
                eval_fn=eval_fn, log_every=max(args.steps // 4, 1))
        history["cascade"] = hist["ensure"]
        print(f"[cascade] mode losses {hist['ensure']['losses']} "
              f"ordered={hist['ensure']['ordered']}")
    else:
        params, h = run_phase(params, cfg, tcfg, mesh, specs, data_fn,
                              steps=args.steps, mode=args.mode)
        history["phase1"] = h

    ck = os.path.join(args.ckpt_dir, f"{args.arch.replace('.', '_')}.npz")
    checkpoint.save(ck, params, {"arch": args.arch, "steps": args.steps,
                                 "reduced": args.reduced})
    with open(ck.replace(".npz", "_history.json"), "w") as f:
        json.dump(history, f, indent=1)
    print(f"checkpoint -> {ck}")
    return history


if __name__ == "__main__":
    main()
