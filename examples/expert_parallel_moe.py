"""Expert-parallel MoE training end to end — the §Perf pair-B configuration
at CPU scale.

Spawns 8 host devices, builds the (2 data, 4 model) mesh, and trains the
reduced phi3.5-moe config twice for the same steps/seed: once with the
einsum MoE (GSPMD picks the collectives) and once with the explicit
shard_map expert-parallel all-to-all schedule (`--moe-ep` in the dry-run,
`moe_ep=True` here). Losses must track each other — the EP schedule is a
placement change, not a model change — while the compiled HLO shows
all-to-alls instead of expert-weight all-gathers.

    PYTHONPATH=src python examples/expert_parallel_moe.py [--steps 12]

NOTE: sets XLA_FLAGS before importing jax — run standalone, not from a
process that already initialized jax.
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import argparse      # noqa: E402
import re            # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_reduced                    # noqa: E402
from repro.configs.base import TrainConfig               # noqa: E402
from repro.core import split as SP                       # noqa: E402
from repro.data import tokens                            # noqa: E402
from repro.launch.mesh import make_mesh                  # noqa: E402
from repro.training import loop as L                     # noqa: E402
from repro.training import optimizer as opt              # noqa: E402


def run(cfg, mesh, *, moe_ep: bool, steps: int, batch: int, seq: int):
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=2,
                       total_steps=max(steps, 10))
    step = jax.jit(L.make_train_step(cfg, tcfg, mesh=mesh,
                                     act_policy="batch", moe_ep=moe_ep))
    src = tokens.MarkovTokenSource(cfg, seed=3)
    opt_state = opt.init(params)
    losses = []
    with jax.set_mesh(mesh):
        lowered = step.lower(params, opt_state, {
            k: jnp.asarray(v) for k, v in src.batch(batch, seq, 0).items()})
        hlo = lowered.compile().as_text()
        for s in range(steps):
            b = {k: jnp.asarray(v) for k, v in src.batch(batch, seq, s).items()}
            params, opt_state, m = step(params, opt_state, b)
            losses.append(float(m["loss"]))
    n_a2a = len(re.findall(r"all-to-all", hlo))
    return losses, n_a2a


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_reduced("phi3.5-moe-42b-a6.6b")
    print(f"== reduced phi3.5-moe ({cfg.n_experts} experts, top-"
          f"{cfg.experts_per_tok}) on mesh {dict(mesh.shape)} ==")

    ref_losses, ref_a2a = run(cfg, mesh, moe_ep=False, steps=args.steps,
                              batch=args.batch, seq=args.seq)
    ep_losses, ep_a2a = run(cfg, mesh, moe_ep=True, steps=args.steps,
                            batch=args.batch, seq=args.seq)
    print(f"einsum MoE: loss {ref_losses[0]:.4f} -> {ref_losses[-1]:.4f} "
          f"(a2a ops in HLO: {ref_a2a})")
    print(f"EP MoE:     loss {ep_losses[0]:.4f} -> {ep_losses[-1]:.4f} "
          f"(a2a ops in HLO: {ep_a2a})")
    gap = max(abs(a - b) for a, b in zip(ref_losses, ep_losses))
    print(f"max per-step loss gap: {gap:.4f}")
    assert ep_a2a > 0, "EP path must lower to all-to-all"
    assert gap < 0.5, "EP and einsum training must track each other"
    assert ep_losses[-1] < ep_losses[0], "loss must decrease"
    print("OK — expert-parallel schedule trains identically")


if __name__ == "__main__":
    main()
