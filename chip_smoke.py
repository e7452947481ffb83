#!/usr/bin/env python3
"""Run the split-serving system once on a TPU, through its own entry points.

    python3 chip_smoke.py               # one chip: every phase below
    python3 chip_smoke.py --four-chips  # four chips: the cluster phase only

One process, one chip (or four with ``--four-chips``); every phase is fatal.

  device     the first JAX device is a TPU; prints its kind and count
  serve      ``launch.serve.main``: qwen2.5-3b at its full config, continuous
             engine, adaptive modes, paged pool; every request finishes its
             budget in-vocabulary, at least two modes run, and the lowered
             decode window holds the boundary, decode-tail and paged-attention
             kernels
  recurrent  ``launch.serve.main``: recurrentgemma-2b at its full config; the
             lowered prefill holds the rglru_scan kernel
  kernels    the four served kernels against their blocked jnp oracles
             (``kernels/ref.py``) at served shapes
  train      ``launch.train.main``: xlstm-125m at its full config, 3 steps,
             finite loss
  cluster    (``--four-chips`` only) an EdgeCluster of four one-chip qwen2.5-3b
             replicas with live migration and raw snapshots; each replica's
             params on its own chip, and every migrated stream
             token-identical to one replica serving it without handover

Weights are random (seeded). The launchers' own output goes to
``chiprun_out/chip_smoke/``; dumped IR and the training checkpoint go to
``results/chip_smoke/`` (gitignored) and are removed once checked. Times printed are set-up and compile times of
one cold process, not device metrics. The last stdout line is one JSON
object, ``{"ok": ..., "device": {"platform", "kind", "count"}}``; the exit
code is 0 only if every phase passed. Without a TPU, or without the repo's
``src/`` beside this file, it exits non-zero before any phase and prints no
result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import glob
import json
import os
import shutil
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")      # launcher logs
WORK = os.path.join(REPO, "results", "chip_smoke")         # IR, checkpoint
IR_DIR = os.path.join(WORK, "ir")

SERVE_ARGS = ["--engine", "continuous", "--mode-policy", "adaptive",
              "--prompt-len", "64", "--cache-len", "128"]


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg: str):
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def launcher_output(name: str):
    """Send a launcher's stdout to ``chiprun_out/chip_smoke/<name>.log``."""
    path = os.path.join(OUT, f"{name}.log")
    with open(path, "w") as f, contextlib.redirect_stdout(f):
        yield
    log(f"{name}: launcher output in {os.path.relpath(path, REPO)}")


@contextlib.contextmanager
def dump_ir(tag: str):
    """Dump every module lowered inside the block to ``IR_DIR/<tag>``."""
    import jax
    path = os.path.join(IR_DIR, tag)
    shutil.rmtree(path, ignore_errors=True)
    jax.config.update("jax_dump_ir_to", path)
    try:
        yield path
    finally:
        jax.config.update("jax_dump_ir_to", "")


def kernels_in(ir_path: str, jit_name: str, kernels) -> dict:
    """For each lowered module of ``jit_name``: the kernels among
    ``kernels`` that appear as a ``tpu_custom_call``."""
    found = {}
    for fn in sorted(glob.glob(os.path.join(ir_path,
                                            f"*_jit_{jit_name}_*.mlir"))):
        with open(fn) as f:
            calls = [ln for ln in f if "tpu_custom_call" in ln]
        found[os.path.basename(fn)] = sorted(
            k for k in kernels
            if any(f'kernel_name = "{k}"' in c for c in calls))
    return found


def check_kernels(ir_path: str, jit_name: str, kernels):
    found = kernels_in(ir_path, jit_name, kernels)
    shutil.rmtree(ir_path, ignore_errors=True)
    check(found, f"no lowered {jit_name} module in {ir_path}")
    for mod, ks in found.items():
        log(f"  {mod}: custom calls {ks}")
        check(set(ks) == set(kernels),
              f"{mod} lacks {sorted(set(kernels) - set(ks))}")


def check_no_fallbacks():
    from repro.kernels import ops
    check(not ops.FALLBACKS,
          f"dispatchers took the jnp reference on TPU: {dict(ops.FALLBACKS)}")


def check_served(summary: dict, *, n_requests: int, gen: int, vocab: int):
    check(summary["requests_finished"] == n_requests,
          f"{summary['requests_finished']}/{n_requests} requests finished")
    reqs = summary["per_request"]
    check(len(reqs) == n_requests, f"{len(reqs)} per-request results")
    for r in reqs:
        check(r["n_tokens"] == gen,
              f"request {r['rid']}: {r['n_tokens']} tokens, budget {gen}")
        check(all(0 <= t < vocab for t in r["tokens"]),
              f"request {r['rid']}: token outside [0, {vocab})")


def serve(arch: str, extra: list, tag: str) -> dict:
    from repro.launch import serve as serve_launch
    t0 = time.time()
    with dump_ir(tag) as ir, launcher_output(tag):
        summary = serve_launch.main(["--arch", arch] + SERVE_ARGS + extra)
    log(f"{tag}: {arch} served in {time.time() - t0:.1f} s wall "
        f"(init, compiles, warm-up and run of one cold process)")
    summary["_ir"] = ir
    return summary


def max_abs(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_serve():
    from repro.configs import get_config
    cfg = get_config("qwen2.5-3b")
    n, gen = 8, 16
    s = serve(cfg.name, ["--requests", str(n), "--n-slots", "8",
                         "--gen", str(gen), "--arrival-every", "1"],
              "serve_qwen")
    check_served(s, n_requests=n, gen=gen, vocab=cfg.vocab_size)
    check(s["paged"] is True and s["peak_pages_in_use"] > 0,
          f"paged pool unused (paged={s['paged']}, "
          f"peak_pages_in_use={s['peak_pages_in_use']})")
    modes = sorted(int(m) for m, c in s["mode_counts"].items() if c)
    log(f"serve: {n} requests x {gen} tokens finished; modes {modes}; "
        f"page_len {s['page_len']}, peak pages {s['peak_pages_in_use']}")
    check(len(modes) >= 2, f"only modes {modes} ran")
    check_kernels(s["_ir"], "mixed_step_dev",
                  ("boundary_mixed", "decode_tail", "paged_attention"))
    check_no_fallbacks()


def phase_recurrent():
    from repro.configs import get_config
    cfg = get_config("recurrentgemma-2b")
    n, gen = 2, 8
    s = serve(cfg.name, ["--requests", str(n), "--n-slots", "2",
                         "--gen", str(gen)], "serve_recurrentgemma")
    check_served(s, n_requests=n, gen=gen, vocab=cfg.vocab_size)
    log(f"recurrent: {n} requests x {gen} tokens finished")
    check_kernels(s["_ir"], "mixed_prefill", ("boundary_mixed", "rglru_scan"))
    check_no_fallbacks()


def phase_kernels():
    """Each served kernel, compiled, against its blocked jnp oracle run
    eagerly on the same chip, at the widths the serve phases use."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import bottleneck
    from repro.kernels import boundary_mixed as BM
    from repro.kernels import ops, ref
    from repro.kernels import paged_attention as PA
    from repro.kernels import rglru_scan as RS

    qwen = get_config("qwen2.5-3b")
    rg = get_config("recurrentgemma-2b")
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 16))
    B, d, bf16 = 8, qwen.d_model, jnp.bfloat16
    failed = []

    def report(name, err, tol, what="max abs err"):
        ok = err <= tol
        log(f"kernels: {name:15s} {what} {err:.6g} (tol {tol:.6g}) "
            f"{'ok' if ok else 'FAIL'}")
        if not ok:
            failed.append(name)

    # boundary: 8 decode rows, random modes over the qwen bank
    stacked = bottleneck.bank_stack(bottleneck.bank_init(next(keys), qwen),
                                    qwen.split)
    x = jax.random.normal(next(keys), (B, d)).astype(bf16)
    modes = jnp.arange(B, dtype=jnp.int32) % (stacked["width"].shape[0] + 1)
    block_r = 16
    dest, t = ops.group_layout(stacked, modes, block_r, 128)
    xp = jnp.zeros((t["P"], d), bf16).at[dest].set(x)
    tables = (t["hid"], t["nchunk"], t["width"], t["bits"])
    yk = BM.boundary_mixed_grouped(
        xp, stacked["down_w"], stacked["up_w"], stacked["norm_scale"],
        *tables, block_r=block_r)
    yr = ref.boundary_mixed_grouped_ref(
        xp, stacked["down_w"], stacked["up_w"], stacked["norm_scale"],
        *tables, block_r=block_r)
    # kernel and oracle round z to bf16 after MXU accumulations whose order
    # may differ; one flipped bf16 ulp can move an 8-bit wire code by one
    # step (1/127 of the row's absmax), which moves an output element by
    # about 1% of the output scale. 5% of max|ref| admits a few such flips
    # and still catches a wrong head, width, bit path or mode grouping,
    # which are errors of order max|ref|.
    err = max_abs(yk[dest], yr[dest])
    report("boundary_mixed", err, 0.05 * float(jnp.max(jnp.abs(yr))))

    # decode tail: the full qwen vocab, one LM head
    heads = (jax.random.normal(next(keys), (1, d, qwen.vocab_size))
             * d ** -0.5).astype(bf16)
    scale = (1.0 + 0.1 * jax.random.normal(next(keys), (d,))).astype(bf16)
    bias = jnp.zeros((d,), bf16)
    hdest, hid_g, P = ops.head_layout(jnp.zeros(B, jnp.int32), 1, block_r)
    xt = jnp.zeros((P, d), bf16).at[hdest].set(x)
    block_v = ops._pick_block(qwen.vocab_size, 512)
    tk = BM.decode_tail_grouped(xt, heads, scale, bias, hid_g,
                                block_r=block_r, block_v=block_v)
    tr = ref.decode_tail_grouped_ref(xt, heads, scale, bias, hid_g,
                                     block_r=block_r, block_v=block_v)
    # tokens are argmaxes: exact unless two logits tie within f32
    # accumulation noise, which random Gaussian logits do not
    mism = int(np.sum(np.asarray(tk)[hdest, 0] != np.asarray(tr)[hdest, 0]))
    report("decode_tail", mism, 0, f"tokens differing of {B}")

    # paged attention: qwen heads, page_len 16, 8 pages per sequence
    nq, nkv, hd, plen, nb = qwen.n_heads, qwen.n_kv_heads, qwen.head_dim, \
        16, 8
    n_pages = B * nb + 1
    q = jax.random.normal(next(keys), (B, nq, hd)).astype(bf16)
    kp = jax.random.normal(next(keys), (n_pages, nkv, plen, hd)).astype(bf16)
    vp = jax.random.normal(next(keys), (n_pages, nkv, plen, hd)).astype(bf16)
    rng = np.random.default_rng(5)
    pos = rng.integers(0, nb * plen, size=B).astype(np.int32)
    bt = np.zeros((B, nb), np.int32)
    free = list(rng.permutation(np.arange(1, n_pages)))
    for b in range(B):
        for j in range(pos[b] // plen + 1):
            bt[b, j] = free.pop()
    ak = PA.paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos))
    ar = ref.paged_attention_ref(q, kp, vp, jnp.asarray(bt), pos)
    # both round to bf16 at the same five hand-offs (score, probability,
    # correction, denominator, context); a different accumulation order
    # can flip one of them by one ulp (2^-8 relative), and a flipped score
    # of magnitude <= 8 moves its softmax weight by at most 3.2%, so the
    # context by at most 2^-5 * max|v|
    report("paged_attention", max_abs(ak, ar),
           2.0 ** -5 * float(jnp.max(jnp.abs(vp.astype(jnp.float32)))))

    # rglru scan: recurrentgemma's d_rnn over one 64-token prompt bucket
    D, S = rg.d_rnn, 64
    a = jax.nn.sigmoid(3.0 + jax.random.normal(next(keys), (2, S, D)))
    bb = jax.random.normal(next(keys), (2, S, D))
    hk = RS.rglru_scan(a, bb, block_s=ops._pick_block(S, 256, align=8),
                       block_d=ops._pick_block(D, 512))
    hr = ref.rglru_scan_ref(a, bb)
    # f32 multiply-adds in the same order; the compiler may or may not
    # fuse each into an FMA, one rounding per step, and |a| < 1 damps the
    # carried difference: 1e-4 of max|h| is ~800 f32 ulps of slack
    report("rglru_scan", max_abs(hk, hr),
           1e-4 * float(jnp.max(jnp.abs(hr))))
    check(not failed, f"kernels outside tolerance: {failed}")


def phase_train():
    import math

    from repro.launch import train as train_launch
    t0 = time.time()
    ckpt = os.path.join(WORK, "ckpt")
    with launcher_output("train_xlstm"):
        hist = train_launch.main([
            "--arch", "xlstm-125m", "--steps", "3", "--batch", "4",
            "--seq", "128", "--ckpt-dir", ckpt])
    shutil.rmtree(ckpt, ignore_errors=True)
    losses = [h["loss"] for h in hist["phase1"]]
    log(f"train: xlstm-125m 3 steps in {time.time() - t0:.1f} s wall "
        f"(init, compile and steps); logged losses {losses}")
    check(losses and all(math.isfinite(v) for v in losses),
          f"non-finite loss {losses}")


def phase_cluster():
    """Four one-chip replicas with live migration against one replica that
    serves the same requests without handover."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import split as SP
    from repro.core.channel import MobilityChannel
    from repro.data import tokens
    from repro.models.sharding import serving_mesh
    from repro.serving import (ContinuousBatchingEngine, EdgeCluster,
                               Request, default_orchestrator)

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    cfg = get_config("qwen2.5-3b")
    n_rep, gen, spacing = 4, 16, 24
    t0 = time.time()
    params = jax.jit(SP.init_split_params, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)
    prompts = np.asarray(tokens.MarkovTokenSource(cfg, seed=7).batch(
        n_rep, 64)["tokens"])

    def reqs():
        # UE i starts in cell i, crosses into cell i+1 after `cross` ticks
        # and arrives alone (spacing > its lifetime), so both runs decode
        # every session in the same one-live-row batch shapes;
        # detach_factor=1.0 keeps both capacity sequences identical
        out = []
        for i in range(n_rep):
            cross = 4 + 2 * i
            cells = [i] * cross + [(i + 1) % n_rep] * (gen + 8)
            ch = MobilityChannel(cells, [8e6] * n_rep, detach_factor=1.0)
            out.append(Request(rid=i, prompt=prompts[i], max_new_tokens=gen,
                               channel=ch, arrival_tick=i * spacing))
        return out

    kw = dict(n_slots=2, cache_len=128, max_window=1)
    cluster = EdgeCluster(params, cfg, n_replicas=n_rep,
                          placement="best-channel", handover="migrate",
                          snapshot_bits=0, **kw)
    homes = []
    for i, r in enumerate(cluster.replicas):
        ds = {d for leaf in jax.tree.leaves(r.params) for d in leaf.devices()}
        check(len(ds) == 1, f"replica {i} params span {ds}")
        homes.append(ds.pop())
    log(f"cluster: replica devices {[str(d) for d in homes]}")
    check(len(set(homes)) == n_rep, "replicas share a device")
    got = {s.request.rid: s for s in cluster.run(reqs())}
    st = cluster.stats()
    cluster.close()
    log(f"cluster: {st['requests_finished']} finished, "
        f"{st['migrations']} migrations, {st['migration_bytes']} snapshot "
        f"bytes; {time.time() - t0:.1f} s wall incl. init and compiles")

    ref = ContinuousBatchingEngine(
        params, cfg, orchestrator=default_orchestrator(cfg),
        mesh=serving_mesh(1, 1, devices=[devs[0]]), **kw)
    base = {s.request.rid: s for s in ref.run(reqs())}
    ref.close()
    check(st["migrations"] == n_rep, f"{st['migrations']} migrations")
    check(sorted(got) == sorted(base) == list(range(n_rep)),
          f"finished {sorted(got)} vs reference {sorted(base)}")
    for rid in base:
        g, b = got[rid], base[rid]
        check(len(g.migrations) == 1, f"request {rid}: {g.migrations}")
        check(g.tokens == b.tokens,
              f"request {rid}: migrated {g.tokens} != reference {b.tokens}")
        check(g.mode_counts == b.mode_counts,
              f"request {rid}: modes {g.mode_counts} != {b.mode_counts}")
    log(f"cluster: {n_rep} migrated streams token-identical to the "
        f"one-replica reference")
    check_no_fallbacks()


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica cluster phase")
    args = ap.parse_args(argv)

    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: no src/repro beside this script; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    from repro.launch.cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the first JAX device is {dev.platform!r}, not a "
              "TPU; nothing was run", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    os.makedirs(OUT, exist_ok=True)
    log(f"device: {device['kind']} x {device['count']} "
        f"(jax {jax.__version__}); compile cache {enable_compile_cache()}")

    phases = ([phase_cluster] if args.four_chips else
              [phase_serve, phase_recurrent, phase_kernels, phase_train])
    failed = []
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        t0 = time.time()
        try:
            phase()
            log(f"{name}: PASS ({time.time() - t0:.1f} s)")
        except Exception:
            failed.append(name)
            traceback.print_exc()
            log(f"{name}: FAIL ({time.time() - t0:.1f} s)")
        gc.collect()
    if failed:
        log(f"failed phases: {failed}")
    print(json.dumps({"ok": not failed, "device": device}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
