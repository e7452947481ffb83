"""Production serving launcher: requests through the split engine with the
orchestrator picking the transmit mode from simulated mmWave channels (the
paper's Fig. 3/5 loop, runnable end to end).

    # synchronous static batch (legacy engine)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --requests 4 --prompt-len 16 --gen 32
    # continuous batching: per-request channels, per-slot bottleneck modes
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --engine continuous --requests 16 --n-slots 4 --arrival-every 2
    # edge cluster: N replicas, mobility traces, live migration on handover
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --engine cluster --replicas 2 --placement best-channel \
        --handover migrate --requests 8 --n-slots 2
    # mesh-sharded serving: slot pools over dp, decoder heads over mp
    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --engine continuous --requests 16 --n-slots 8 --dp 4 --mp 2

Policies (sync engine):
  orchestrator  paper's dynamic policy (channel + loss feedback, hysteresis)
  static0       always mode 0 (raw boundary, most informative)
  static1       always mode 1 (bottleneck z', cheapest)
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.core import bottleneck
from repro.core import split as SP
from repro.core.channel import (Channel, ChannelConfig, FleetChannel,
                                MobilityChannel, channel_fleet)
from repro.data.lumos5g import capacity_traces_bps
from repro.core.orchestrator import AppRequirement, ModeProfile, Orchestrator
from repro.data import tokens
from repro.launch.cache import enable_compile_cache
from repro.models import transformer as T
from repro.models.sharding import serving_mesh
from repro.serving import (HANDOVER_POLICIES, PLACEMENTS,
                           Autoscaler, AutoscalerConfig,
                           ContinuousBatchingEngine, ControllerConfig,
                           EdgeCluster, FleetLoadConfig, ModeController,
                           Request, SLOAdmission, SLOAdmissionConfig,
                           ServingEngine, Telemetry, fleet_requests,
                           profile_capture)
from repro.serving.telemetry import Stopwatch
from repro.training import checkpoint


def build_orchestrator(cfg, batch: int, latency_budget_s: float,
                       *, hysteresis: float = 0.85):
    """Mode profiles from the analytic payload model (calibration stands in
    for the cascade validation losses on untrained smoke weights)."""
    profiles = []
    for m in range(cfg.split.n_modes):
        pb = bottleneck.mode_payload_bytes(cfg, batch, 1, m)
        profiles.append(ModeProfile(mode=m, payload_bytes=pb,
                                    expected_loss=float(m)))  # DPI ordering
    return Orchestrator(profiles,
                        AppRequirement(latency_budget_s=latency_budget_s),
                        hysteresis=hysteresis)


def _build_mesh(args):
    """``('dp','mp')`` serving mesh from --dp/--mp, or None (single-device
    semantics, bit-identical to builds without the flags)."""
    if not (args.dp or args.mp):
        return None
    dp, mp = args.dp or 1, args.mp or 1
    n_dev = len(jax.devices())
    if dp * mp > n_dev:
        raise SystemExit(
            f"--dp {dp} x --mp {mp} needs {dp * mp} devices but only "
            f"{n_dev} visible (on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return serving_mesh(dp, mp)


def _latency_section(tel) -> dict:
    """Millisecond percentile summary of the run's latency histograms
    (empty without --telemetry)."""
    if tel is None:
        return {}
    return {"latency": tel.registry.latency_summary(
        "engine.ttft_s", "engine.intertoken_s",
        "engine.admit_to_first_token_s", "cluster.migration_backhaul_s")}


def run_continuous(args, cfg, params, tel=None):
    orch = build_orchestrator(cfg, 1, args.latency_budget_ms / 1e3,
                              hysteresis=1.0)
    chans = channel_fleet(
        args.requests,
        ChannelConfig(mean_mbps=args.mean_mbps, std_mbps=args.mean_mbps / 2,
                      blockage_prob=0.06, recovery_prob=0.2,
                      seed=args.channel_seed),
        seed=args.channel_seed, mean_spread=0.9)
    src = tokens.MarkovTokenSource(cfg, seed=7)
    batch = src.batch(args.requests, args.prompt_len)["tokens"]
    reqs = [Request(rid=i, prompt=np.asarray(batch[i]),
                    max_new_tokens=args.gen, channel=chans[i],
                    arrival_tick=i * args.arrival_every)
            for i in range(args.requests)]
    kw = {}
    if args.mode_policy == "adaptive":
        kw["controller"] = ModeController(
            orch, ControllerConfig(dwell_ticks=args.dwell_ticks))
    else:
        kw["orchestrator"] = orch
        kw["freeze_modes"] = args.mode_policy == "frozen"
    eng = ContinuousBatchingEngine(params, cfg, n_slots=args.n_slots,
                                   cache_len=args.cache_len,
                                   mesh=_build_mesh(args), telemetry=tel,
                                   **kw)
    # warm the compiled prefill/decode paths (every prefill batch bucket)
    # so decode_tok_per_s measures steady-state serving — the sync engine
    # likewise excludes its one-time prefill/trace cost from the decode rate
    eng.warm(np.asarray(batch[0]))

    with Stopwatch() as sw:
        done = eng.run(reqs)
    st = eng.stats()
    eng.close()
    return {
        "engine": "continuous",
        "n_slots": args.n_slots,
        "decode_tok_per_s": round(
            st["decode_tokens"] / max(sw.seconds, 1e-9), 1),
        "per_request": [s.result() for s in done],
        **_latency_section(tel),
        **st,
    }


def run_cluster(args, cfg, params, tel=None):
    """Multi-replica edge cluster on scripted mobility: each UE starts in
    its home cell and crosses into the next cell partway through its
    generation, so every session exercises the configured handover policy
    (migrate / stay / drop) under the chosen placement."""
    n_rep = args.replicas
    cap_bps = args.mean_mbps * 1e6 / 8.0
    rng = np.random.default_rng(args.channel_seed)
    src = tokens.MarkovTokenSource(cfg, seed=7)
    batch = src.batch(args.requests, args.prompt_len)["tokens"]
    reqs = []
    for i in range(args.requests):
        home = i % n_rep
        cross = int(rng.integers(2, max(args.gen - 2, 3)))
        cells = [home] * cross + [(home + 1) % n_rep] * (args.gen + 8)
        ch = MobilityChannel(cells, [cap_bps] * n_rep,
                             detach_factor=args.detach_factor)
        reqs.append(Request(rid=i, prompt=np.asarray(batch[i]),
                            max_new_tokens=args.gen, channel=ch,
                            arrival_tick=i * args.arrival_every))
    cluster = EdgeCluster(
        params, cfg, n_replicas=n_rep, n_slots=args.n_slots,
        cache_len=args.cache_len, placement=args.placement,
        handover=args.handover, snapshot_bits=args.snapshot_bits,
        backhaul_bps=args.backhaul_mbps * 1e6 / 8.0,
        latency_budget_s=args.latency_budget_ms / 1e3,
        telemetry=tel, dp=args.dp, mp=args.mp)
    # warm every replica's compiled paths so decode_tok_per_s measures
    # steady-state serving, same as the continuous-engine path
    cluster.warm(np.asarray(batch[0]))
    with Stopwatch() as sw:
        done = cluster.run(reqs)
    st = cluster.stats()
    cluster.close()
    return {
        "engine": "cluster",
        "decode_tok_per_s": round(
            st["decode_tokens"] / max(sw.seconds, 1e-9), 1),
        "per_request": [s.result() for s in done[:4]],
        **_latency_section(tel),
        **st,
    }


def run_fleet(args, cfg, params, tel=None):
    """City-fleet serving: every UE rides one lane of a single vectorized
    ``FleetChannel`` replaying Lumos5G-resampled capacity traces (no
    per-UE Python channel objects), arrivals come from a Poisson or
    heavy-tail renewal process, and the elastic ``EdgeCluster`` applies
    SLO-driven admission plus replica autoscaling."""
    n = args.requests
    traces = capacity_traces_bps(n, 512, seed=args.channel_seed)
    fleet = FleetChannel(n, traces_bps=traces, cycle=True)
    load = FleetLoadConfig(arrival=args.arrival,
                           mean_interarrival_ticks=args.arrival_every,
                           prompt_len=args.prompt_len,
                           max_new_tokens=args.gen,
                           vocab=cfg.vocab_size,
                           slo_ticks=args.slo_ticks,
                           seed=args.channel_seed)
    reqs = fleet_requests(fleet, load)
    min_payload = min(bottleneck.mode_payload_bytes(cfg, 1, 1, m)
                      for m in range(cfg.split.n_modes))
    autoscaler = (Autoscaler(AutoscalerConfig(
        max_replicas=args.max_replicas)) if args.autoscale else None)
    cluster = EdgeCluster(
        params, cfg, n_replicas=args.replicas, n_slots=args.n_slots,
        cache_len=args.cache_len, placement="least-loaded",
        latency_budget_s=args.latency_budget_ms / 1e3,
        admission=SLOAdmission(min_payload, SLOAdmissionConfig(
            latency_budget_s=args.latency_budget_ms / 1e3)),
        autoscaler=autoscaler,
        telemetry=tel,
        max_pending=max(n, 64))
    cluster.warm(reqs[0].prompt)
    with Stopwatch() as sw:
        done = cluster.run_paced(reqs)
    st = cluster.stats()
    cluster.close()
    return {
        "engine": "fleet",
        "n_ues": n,
        "arrival": args.arrival,
        "autoscale": bool(args.autoscale),
        "decode_tok_per_s": round(
            st["decode_tokens"] / max(sw.seconds, 1e-9), 1),
        "admission": cluster.admission.stats(),
        "per_request": [s.result() for s in done[:2]],
        **_latency_section(tel),
        **st,
    }


def run_sync(args, cfg, params, tel=None):
    orch = None
    if args.policy == "orchestrator":
        orch = build_orchestrator(cfg, args.requests,
                                  args.latency_budget_ms / 1e3)
    eng = ServingEngine(params, cfg, cache_len=args.cache_len,
                        batch=args.requests, orchestrator=orch,
                        mesh=_build_mesh(args), telemetry=tel)

    # batched request prompts
    src = tokens.MarkovTokenSource(cfg, seed=7)
    prompt = jnp.asarray(
        src.batch(args.requests, args.prompt_len)["tokens"])
    chan = Channel(ChannelConfig(seed=args.channel_seed))

    with Stopwatch() as sw:
        logits = eng.prefill(prompt)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t_prefill = sw.lap()

        if args.policy.startswith("static"):
            # same cache-wraparound guard ServingEngine.decode_tokens
            # applies on the orchestrator path
            T.check_cache_capacity(cfg, eng.pos, args.gen, args.cache_len,
                                   what="--gen")
            mode = int(args.policy[-1])
            out, wire = [], 0
            tok = first
            for _ in range(args.gen):
                logits, eng.states, pb = SP.split_decode_step(
                    params, tok, eng.states, jnp.int32(eng.pos), cfg,
                    mode=mode)
                eng.pos += 1
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                out.append(np.asarray(tok))
                wire += int(pb)
            gen = np.concatenate(out, axis=-1)
            stats = {"tokens": int(gen.size), "wire_bytes": wire,
                     "mode_counts": {mode: args.gen}}
        else:
            gen = eng.decode_tokens(first, args.gen,
                                    capacity_bps_fn=chan.step)
            stats = {"tokens": eng.stats.tokens,
                     "wire_bytes": eng.stats.wire_bytes,
                     "mode_counts": eng.stats.mode_counts,
                     "mode_switches": orch.state.switches}
    t_total = sw.seconds

    toks = args.requests * args.gen
    return {
        "engine": "sync", "policy": args.policy,
        "prefill_s": round(t_prefill, 2),
        "decode_tok_per_s": round(toks / max(t_total - t_prefill, 1e-9), 1),
        "wire_bytes_per_token": stats["wire_bytes"] / max(toks, 1),
        **stats,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", default="sync",
                    choices=["sync", "continuous", "cluster", "fleet"])
    ap.add_argument("--requests", type=int, default=4,
                    help="number of requests (sync: the batch size)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--policy", default="orchestrator",
                    choices=["orchestrator", "static0", "static1"])
    ap.add_argument("--latency-budget-ms", type=float, default=5.0)
    ap.add_argument("--channel-seed", type=int, default=0)
    ap.add_argument("--n-slots", type=int, default=4,
                    help="continuous engine: decode slot pool size")
    ap.add_argument("--arrival-every", type=int, default=2,
                    help="continuous engine: ticks between request arrivals")
    ap.add_argument("--mode-policy", default="pertick",
                    choices=["pertick", "adaptive", "frozen"],
                    help="continuous engine: per-tick orchestrator loop "
                         "(legacy), adaptive ModeController (dwell + "
                         "deadline escalation), or admission-frozen modes")
    ap.add_argument("--dwell-ticks", type=int, default=2,
                    help="adaptive policy: min ticks between mode switches")
    ap.add_argument("--mean-mbps", type=float, default=40.0,
                    help="continuous engine: fleet mean uplink")
    ap.add_argument("--replicas", type=int, default=2,
                    help="cluster engine: decoder replicas (one per cell)")
    ap.add_argument("--placement", default="least-loaded",
                    choices=list(PLACEMENTS),
                    help="cluster engine: new-request routing policy")
    ap.add_argument("--handover", default="migrate",
                    choices=list(HANDOVER_POLICIES),
                    help="cluster engine: what to do when a UE crosses "
                         "cells mid-generation")
    ap.add_argument("--snapshot-bits", type=int, default=0,
                    help="cluster engine: quantize migration snapshots at "
                         "this bit width (0 = raw, bit-exact)")
    ap.add_argument("--backhaul-mbps", type=float, default=10000.0,
                    help="cluster engine: inter-replica backhaul for "
                         "migration snapshots")
    ap.add_argument("--detach-factor", type=float, default=0.05,
                    help="cluster engine: capacity multiplier while a UE "
                         "is served from the wrong cell")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "heavy-tail", "burst"],
                    help="fleet engine: arrival process for the load "
                         "generator")
    ap.add_argument("--slo-ticks", type=int, default=96,
                    help="fleet engine: session SLO in engine ticks "
                         "(arrival -> finish, queue wait included)")
    ap.add_argument("--autoscale", action="store_true",
                    help="fleet engine: attach the replica autoscaler")
    ap.add_argument("--max-replicas", type=int, default=8,
                    help="fleet engine: autoscaler ceiling")
    ap.add_argument("--dp", type=int, default=None,
                    help="serving mesh: data-parallel axis — slot/page "
                         "pools shard over dp (must divide n_slots; "
                         "cluster engine: per-replica, replicas get "
                         "disjoint device subsets)")
    ap.add_argument("--mp", type=int, default=None,
                    help="serving mesh: tensor-parallel axis — decoder "
                         "heads/FFN shard over mp (reassociates "
                         "reductions; dp alone stays bit-identical)")
    ap.add_argument("--telemetry", action="store_true",
                    help="attach the metrics registry + trace recorder "
                         "(latency percentiles land in the summary)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Perfetto-loadable Chrome trace JSON "
                         "here (implies --telemetry)")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of the run into "
                         "this directory")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    print(f"== launch.serve {args.arch} "
          f"({'reduced' if args.reduced else 'FULL'}) "
          f"engine={args.engine} requests={args.requests} "
          f"prompt={args.prompt_len} gen={args.gen} ==")
    # one jitted init: eager init would materialize each stacked weight in
    # f32 (and its random bits) before the cast to the model dtype
    params = jax.jit(SP.init_split_params, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)
    if args.ckpt:
        params = checkpoint.restore(args.ckpt, params)
        print(f"loaded weights from {args.ckpt}")

    tel = (Telemetry() if (args.telemetry or args.trace_out) else None)
    runner = {"sync": run_sync, "continuous": run_continuous,
              "cluster": run_cluster, "fleet": run_fleet}[args.engine]
    with profile_capture(args.profile_dir):
        summary = runner(args, cfg, params, tel)
    summary = {"arch": args.arch, **summary}
    if args.trace_out and tel is not None:
        tel.trace.export(args.trace_out)
        summary["trace_out"] = args.trace_out
        summary["trace_events"] = len(tel.trace.events())
    print(json.dumps(summary, indent=1, default=str))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(summary, f, indent=1, default=str)
    return summary


if __name__ == "__main__":
    main()
