"""One run of one cell: set-up, the measured window, the traced stretch and
the correctness check. ``run.py`` is the command; tests drive ``run_cell``
directly at small sizes on the CPU.

The window drives the program's served path as ``launch/serve.py --engine
continuous --mode-policy adaptive`` builds it: ``ContinuousBatchingEngine``
over the paged pool with the fused decode tail and a ``ModeController`` on
``default_orchestrator``. The harness owns the clock: it submits each
request when it is due, calls ``step()``, and after each step notes which
tokens have become visible on the host.

The model comes from the configuration's file and the reference module it
names (``bench/references/<name>.py``): its weight tree, its work counts
and the plain reference the check compares with.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time

import jax
import numpy as np

from bench import loadgen, references, trace_reduce, weights

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
clock = time.monotonic          # the serving engine's clock (telemetry.now)


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------

def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _load_file(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return _load_file(os.path.join(HERE, "metrics", name + ".py"),
                      "bench_metric_" + name.replace(".", "_"))


def kernel_work(name: str):
    return _load_file(os.path.join(HERE, "kernels", name + ".py"),
                      "bench_kernel_" + name).work


#: keys of a configuration file that describe it and reach no program
DESCRIPTIVE = ("name", "source", "deployment", "reference", "reduced",
               "assumed", "departures")
#: keys that make the program's ``SplitConfig``
SPLIT = ("split_at", "d_bottleneck", "quant_bits")


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file: every key
    that names one of its fields (JSON lists become tuples), and the
    ``SplitConfig`` from ``split_at``, ``d_bottleneck`` and ``quant_bits``.
    Any key that is neither such a field, nor descriptive, nor read by the
    configuration's reference module raises: a misspelt key is never served
    as the default it would fall back to."""
    from repro.configs.base import ModelConfig, SplitConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)} - {"split"}
    known = fields | set(SPLIT) | set(DESCRIPTIVE) \
        | set(references.load(c["reference"]).READS)
    unknown = sorted(set(c) - known)
    if unknown:
        raise ValueError(f"configuration {c.get('name')!r}: unknown keys "
                         f"{unknown}")
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in c.items() if k in fields}
    return ModelConfig(**kw, split=SplitConfig(
        split_at=c["split_at"], d_bottleneck=c["d_bottleneck"],
        quant_bits=c["quant_bits"]))


# ---------------------------------------------------------------------------
# compilations inside the window
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts JAX traces, lowerings and backend compiles while ``on``."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
              "/jax/core/compile/backend_compile_duration": "compiles"}
    _installed = None

    def __init__(self):
        self.on = False
        self.counts = {v: 0 for v in self.EVENTS.values()}
        CompileCounter._installed = self
        if not getattr(CompileCounter, "_hooked", False):
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._listen)
            CompileCounter._hooked = True

    @staticmethod
    def _listen(event, _secs, **_kw):
        self = CompileCounter._installed
        if self is not None and self.on and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


# ---------------------------------------------------------------------------
# warm-up: every shape this cell's traffic can reach, and no other
# ---------------------------------------------------------------------------

def _bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b <<= 1
    return b


def _table_width(pages: int, n_pages: int) -> int:
    b = 1
    while b < max(pages, 1):
        b <<= 1
    return min(b, n_pages)


def decode_plan(mix: dict, n_pages: int):
    """How to reach every decode window (table width, K) the mix can: a
    window of K ticks needs a live row with K tokens to come, and its table
    is as wide as the longest live row. Each entry (w, K, P_A, parts, B_A):
    request A (prompt P_A, budget B_A) first decodes windows of the sizes
    in ``parts`` (each forced by a helper request that ends with it), then
    a request with budget K + 1 joins, so that the next window has K ticks
    while A's rows fill a table of width w."""
    e = mix["engine"]
    plen, kmax = e["page_len"], e["max_window"]
    pmin, pmax = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    omax = mix["output_len"]["max"]
    top = pmax + omax - 1                     # the longest row a request has
    span = {}                                 # width -> (lo rows, hi rows]
    for pages in range(1, -(-top // plen) + 1):
        w = _table_width(pages, n_pages)
        lo, hi = span.get(w, (math.inf, 0))
        span[w] = (min(lo, (pages - 1) * plen), max(hi, pages * plen))
    plan = []
    k = 1
    while k <= min(kmax, omax - 1):
        for w, (lo, hi) in sorted(span.items()):
            hi = min(hi, top)
            p_a = min(pmax, hi - k)
            d = max(0, lo + 1 - k - p_a)      # tokens A decodes first
            if p_a < pmin or 1 + d + k > omax:
                continue                      # no row reaches it with K
            parts = [1 << i for i in range(d.bit_length() - 1, -1, -1)
                     if d >> i & 1]
            while parts and parts[0] > kmax:  # windows are at most kmax
                parts[0:1] = [parts[0] // 2] * 2
            plan.append((w, k, p_a, parts, 1 + d + k))
        k <<= 1
    return plan


def warm(eng, mix: dict, vocab: int, n_pages: int, log=print):
    """Run every prefill (length bucket x batch bucket, and each admitted
    count) and every decode window (K x table width) this mix can reach,
    through the engine's own submit/step, then zero its counters."""
    from repro.serving import Request
    rng = np.random.default_rng(0)
    pmin, pmax = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    n_slots = mix["engine"]["n_slots"]
    rid = [0]

    def req(p, b):
        rid[0] -= 1
        return Request(rid=rid[0], prompt=rng.integers(0, vocab, p,
                                                        dtype=np.int32),
                       max_new_tokens=b, arrival_tick=eng.tick)

    t = clock()
    blens = sorted({_bucket(n) for n in range(pmin, pmax + 1)})
    for n in range(1, n_slots + 1):               # each admitted count
        eng.run([req(pmin, 1) for _ in range(n)])
    log(f"warm-up: {n_slots} admitted counts {clock() - t:.1f} s")
    for blen in blens:                            # each length bucket
        t = clock()
        p = min(max(blen, pmin), pmax)
        bp = 1
        while bp <= n_slots:
            if blen != _bucket(pmin):
                eng.run([req(p, 1) for _ in range(bp)])
            bp <<= 1
        log(f"warm-up: prefill bucket {blen} {clock() - t:.1f} s")
    t = clock()
    plan = decode_plan(mix, n_pages)
    for _w, k, p_a, parts, b_a in plan:
        eng.submit(req(p_a, b_a))
        for part in parts:
            eng.submit(req(pmin, part + 1))
            eng.step()
        eng.run([req(pmin, k + 1)])
    log(f"warm-up: {len(plan)} decode windows {clock() - t:.1f} s")
    eng.reset_counters()


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _q(values, q):
    """The q-quantile (0..1) of ``values`` by linear interpolation."""
    return float(np.quantile(np.asarray(values, np.float64), q))


class _Tracker:
    """What the host has seen of each session, noted after every step."""

    def __init__(self, eng):
        self.eng = eng
        self.sessions = {}        # rid -> Session
        self.t_last = {}          # rid -> time its last token was visible
        self._n_fin = 0

    def note(self, now):
        for s in self.eng.active.values():
            self.sessions[s.request.rid] = s
        fin = self.eng.finished
        for s in fin[self._n_fin:]:
            self.sessions[s.request.rid] = s
            self.t_last[s.request.rid] = now
        self._n_fin = len(fin)

    def decoded(self):
        return sum(max(len(s.tokens) - 1, 0) for s in self.sessions.values())

    def uplink(self):
        b = sum(s.wire_bytes - s.prefill_wire_bytes
                for s in self.sessions.values())
        t = sum(sum(s.mode_counts.values()) for s in self.sessions.values())
        return b, t


def _device_info(devs, peak_bytes):
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak_bytes)}


def _peak_bytes(devs):
    out = 0
    for d in devs:
        st = d.memory_stats() or {}
        out = max(out, int(st.get("peak_bytes_in_use", 0)))
    return out


def setup(cfgd: dict, mix: dict, seed: int, *, warm_up: bool = True,
          log=print) -> dict:
    """Weights from the seed, the engine, and the warm-up of every shape
    the mix can reach."""
    from repro.core import split as SP
    from repro.serving import ContinuousBatchingEngine, ModeController
    from repro.serving.cluster import default_orchestrator

    t0 = clock()
    cfg = model_config(cfgd)
    e = mix["engine"]
    max_ctx = loadgen.max_context(mix)
    n_pages = e.get("n_pages") or e["n_slots"] * -(-max_ctx // e["page_len"])
    params = weights.make(references.load(cfgd["reference"]).shapes(cfgd),
                          seed)
    weights.check_layout(params, jax.eval_shape(
        lambda k: SP.init_split_params(k, cfg), jax.random.PRNGKey(0)))
    jax.block_until_ready(params)
    t_weights = clock()
    orch = default_orchestrator(cfg, mix["latency_budget_ms"] / 1e3)
    eng = ContinuousBatchingEngine(
        params, cfg, n_slots=e["n_slots"], cache_len=max_ctx,
        controller=ModeController(orch), max_pending=e["max_pending"],
        max_window=e["max_window"], paged=True, page_len=e["page_len"],
        n_pages=n_pages)
    if warm_up:
        warm(eng, mix, cfgd["vocab_size"], n_pages, log)
    log(f"set-up: weights {t_weights - t0:.1f} s, warm-up "
        f"{clock() - t_weights:.1f} s, arena {n_pages} pages")
    return {"params": params, "eng": eng, "cfg": cfgd}


def run_cell(cell: dict, cfgd: dict, mix: dict, seed: int, seconds: float,
             trace: bool, *, t_start: float, bench: dict,
             trace_dir: str | None = None, log=print) -> dict:
    """Set up, measure for ``seconds``, check, and return the result line
    (a dict). ``t_start`` is the process's start on ``clock``."""
    st = setup(cfgd, mix, seed, log=log)
    res = measure(st, mix, seed, seconds, trace, t_start=t_start,
                  trace_dir=trace_dir, log=log)
    params = st.pop("params")
    st.clear()                  # free the program's state before the check
    gc.collect()
    return finish(cell, res, params, trace, bench, False, seed, log)[0]


def measure(st: dict, mix: dict, seed: int, seconds: float, trace: bool, *,
            t_start: float, trace_dir: str | None = None, log=print) -> dict:
    """Lead-in, then the measured window of ``seconds``; with ``trace`` a
    profiled stretch inside it. Returns the window's raw records."""
    from repro.core.channel import Channel
    from repro.serving import Request

    eng, cfgd = st["eng"], st["cfg"]
    e = mix["engine"]
    devs = jax.devices()
    items = loadgen.build(mix, seed, seconds, cfgd["vocab_size"])
    tr = loadgen.traces(mix, seconds)
    chan = loadgen.channel_class(Channel)
    tick_s = mix["channel"]["tick_s"]
    trk = _Tracker(eng)
    counter = CompileCounter()
    lateness, rejected = [], []
    due = {}                                  # rid -> due time (abs clock)
    reqs = {}
    ann = (jax.profiler.TraceAnnotation if trace
           else lambda *_a, **_k: contextlib.nullcontext())

    def submit(it, t_due, now):
        req = Request(rid=it.rid, prompt=it.prompt, max_new_tokens=it.max_new,
                      channel=chan(tr[it.ue], tick_s, t_due, clock),
                      arrival_tick=eng.tick)
        due[it.rid], reqs[it.rid] = t_due, req
        lateness.append(now - t_due)
        with ann("bench.submit"):
            if not eng.submit(req):
                rejected.append(it.rid)

    queue_samples = []

    def step():
        with ann("engine.step"):
            eng.step()
        now = clock()
        trk.note(now)
        queue_samples.append((now, len(eng.queue)))

    closed = mix["loop"] == "closed"
    if closed:
        # the first context of each client is prefilled in set-up
        nxt = 0
        for _ in range(mix["clients"]):
            submit(items[nxt], clock(), clock())
            nxt += 1
        while len(eng.queue):
            step()
        t0 = clock()
    else:
        t0 = clock() + mix["lead_in_s"]       # lead-in is traffic set-up
        nxt = 0
    t_end = t0 + seconds
    stretch = None
    if trace:
        tspan = min(4.0, 0.3 * seconds)
        stretch = {"t_on": t0 + 0.4 * seconds}
        stretch["t_off"] = stretch["t_on"] + tspan
        tdir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
    base = None
    in_flight = {}                            # closed loop: client -> rid
    if closed:
        for c in range(mix["clients"]):
            in_flight[c] = items[c].rid
    while True:
        now = clock()
        if base is None and now >= t0:
            counter.on = True
            base = {"t": now, "decoded": trk.decoded(),
                    "uplink": trk.uplink(),
                    "decode_ticks": eng.decode_ticks,
                    "slot_ticks": eng.decoded_slot_ticks,
                    "setup_s": now - t_start}
        if now >= t_end:
            break
        if stretch is not None and "on" not in stretch and now >= stretch["t_on"]:
            stretch["pre"] = _stretch_mark(eng)
            eng.close()                       # land in-flight work: the
            trk.note(clock())                 # stretch holds whole windows
            stretch["on"] = _stretch_mark(eng)
            jax.profiler.start_trace(tdir)
            stretch["ann"] = jax.profiler.TraceAnnotation("bench.stretch")
            stretch["ann"].__enter__()
        if stretch is not None and "on" in stretch and "off" not in stretch \
                and now >= stretch["t_off"]:
            eng.close()
            trk.note(clock())
            stretch["off"] = _stretch_mark(eng)
            stretch["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
        if closed:
            for c, rid in in_flight.items():
                if rid in trk.t_last and nxt < len(items):
                    submit(items[nxt], now, now)   # the client's next
                    in_flight[c] = items[nxt].rid
                    nxt += 1
        else:
            while nxt < len(items) and t0 + items[nxt].t_due <= now:
                submit(items[nxt], t0 + items[nxt].t_due, now)
                nxt += 1
        if eng.active or len(eng.queue):
            step()
        else:
            t_next = (t0 + items[nxt].t_due if not closed and nxt < len(items)
                      else t_end)
            with ann("bench.wait"):
                time.sleep(max(0.0, min(t_next, t_end) - clock()))
    t_close = clock()
    counter.on = False
    win = {"t": t_close, "decoded": trk.decoded(), "uplink": trk.uplink(),
           "decode_ticks": eng.decode_ticks,
           "slot_ticks": eng.decoded_slot_ticks}
    if stretch is not None and "on" in stretch and "off" not in stretch:
        eng.close()
        stretch["off"] = _stretch_mark(eng)
        stretch["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    eng.close()
    trk.note(clock())
    peak = _peak_bytes(devs)

    sessions = list(trk.sessions.values())
    result = {
        "window": win, "base": base, "sessions": sessions, "due": due,
        "reqs": reqs, "t0": t0, "n_slots": e["n_slots"],
        "lateness": lateness, "rejected": rejected, "mix": mix,
        "cfg": cfgd, "compile_counts": dict(counter.counts),
        "queue": queue_samples,
        "stretch": stretch, "devs": devs, "peak": peak,
        "setup_s": base["setup_s"], "trace_dir": tdir if trace else None,
        "trace_dir_owned": trace and trace_dir is None,
        "engine_stats": eng.stats(), "t_last": trk.t_last,
    }
    log(f"window: {win['t'] - base['t']:.2f} s, compiles in window "
        f"{counter.counts}, generator lateness p99 "
        f"{_q(lateness, 0.99) if lateness else 0.0:.4f} s")
    return result


def _stretch_mark(eng):
    return {"t": clock(), "tick": eng.tick, "decode_ticks": eng.decode_ticks,
            "slot_ticks": eng.decoded_slot_ticks,
            "prefill_tokens": eng.prefill_tokens}


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def end_to_end(res: dict) -> dict:
    """Every end-to-end metric this run's traffic supports."""
    base, win = res["base"], res["window"]
    span = win["t"] - base["t"]
    out = {"setup_s": (res["setup_s"], "s"),
           "decode_tok_s": ((win["decoded"] - base["decoded"]) / span,
                            "tokens/s")}
    b = win["uplink"][0] - base["uplink"][0]
    t = win["uplink"][1] - base["uplink"][1]
    if t:
        out["uplink_B_per_tok"] = (b / t, "B/token")
    if res["mix"]["loop"] == "open":
        tpot = []
        for s in res["sessions"]:
            t_last = res["t_last"].get(s.request.rid)
            if t_last is None or not base["t"] <= t_last <= win["t"] \
                    or len(s.tokens) < 2:
                continue
            t_first = s.request.t_submit + s.ttft_s
            tpot.append((t_last - t_first) / (len(s.tokens) - 1))
        if tpot:
            out["tpot_p50_ms"] = (_q(tpot, 0.5) * 1e3, "ms")
    return out


def layer_context(res: dict, red: dict | None) -> dict:
    """What per-layer readers read: the window's counters and spans, the
    traced stretch's ticks and device times, the configuration and peaks."""
    st = res["stretch"]
    ctx = {"cfg": res["cfg"], "mix": res["mix"], "n_slots": res["n_slots"],
           "base": res["base"], "window": res["window"],
           "sessions": res["sessions"], "due": res["due"],
           "device_kind": res["devs"][0].device_kind,
           "n_devices": len(res["devs"]), "trace": red, "stretch": None,
           "kernel_work": kernel_work}
    if st is not None and "on" in st and "off" in st:
        ctx["stretch"] = {k: st[k] for k in ("pre", "on", "off")}
    return ctx


def per_layer(bench: dict, cell_name: str, ctx: dict) -> dict:
    """Each per-layer metric of the cell that its reader finds."""
    out = {}
    e2e_cells = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is None:
            cells = e2e_cells.get(m["moves"])
        if cells is not None and cell_name not in cells:
            continue
        v = metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def _modes_at(s, n_pos):
    """The boundary mode of each position 0..n_pos-1 of a session: the
    admission mode over the prompt, then the mode of each decode tick."""
    P = s.request.prompt_len
    modes = np.full(n_pos, s.admission_mode, np.int32)
    for j in range(n_pos - P):
        tick = s.admitted_tick + j
        m = s.admission_mode
        for tk, mm in s.mode_trace:
            if tk <= tick:
                m = mm
        modes[P + j] = m
    return modes


def sample(res: dict, n: int, seed: int):
    """Finished sessions to check: the longest, and others drawn from the
    seed."""
    fin = [s for s in res["sessions"]
           if len(s.tokens) >= (s.gen_budget or s.request.max_new_tokens)]
    if not fin:
        return []
    fin.sort(key=lambda s: (s.request.prompt_len + len(s.tokens),
                            s.request.rid))
    rest = fin[:-1]
    rng = np.random.default_rng(seed ^ 0x5EED)
    pick = list(rng.choice(len(rest), min(n - 1, len(rest)), replace=False)) \
        if rest else []
    return [fin[-1]] + [rest[i] for i in sorted(pick)]


def load_limits(cell_name: str) -> dict:
    """The cell's limits on the numbers the check compares
    (``bench/limits/<cell>.json``)."""
    with open(os.path.join(HERE, "limits", cell_name + ".json")) as f:
        return json.load(f)


def check(params, cfgd: dict, sessions, control: bool = False) -> dict:
    """Compare the served tokens with the configuration's reference. At
    each served position, the gap is how far the served token's logit lies
    below the reference's best. Returns the number of compared positions
    and, under ``program``, the widest gap and the mean gap over them; with
    ``control``, the same two numbers under ``control`` for the token the
    float8 reference puts first at each position."""
    none = {"max_logit_gap": math.inf, "mean_logit_gap": math.inf}
    if not sessions:
        return {"tokens": 0, "program": none,
                **({"control": none} if control else {})}
    lens = [s.request.prompt_len + len(s.tokens) - 1 for s in sessions]
    T = -(-max(lens) // 512) * 512
    n = len(sessions)
    toks = np.zeros((n, T), np.int32)
    modes = np.zeros((n, T), np.int32)
    served = np.zeros((n, T), np.int32)
    mask = np.zeros((n, T), bool)
    for i, s in enumerate(sessions):
        P, out = s.request.prompt_len, np.asarray(s.tokens, np.int32)
        seq = np.concatenate([np.asarray(s.request.prompt).reshape(-1),
                              out[:-1]])
        toks[i, :len(seq)] = seq
        modes[i, :len(seq)] = _modes_at(s, len(seq))
        served[i, P - 1:P - 1 + len(out)] = out
        mask[i, P - 1:P - 1 + len(out)] = True
    qb = 512 if cfgd["n_heads"] * T * n <= 16 * 8192 * 4 else 256
    ref = references.load(cfgd["reference"])
    probe = None
    if control:
        _, _, _, probe = ref.run(params, cfgd, toks, modes, served,
                                 prec="fp8", qb=qb)
    best, got, probed, _ = ref.run(params, cfgd, toks, modes, served, probe,
                                   qb=qb)

    def gaps(logit):
        gap = (best - logit)[mask]
        return {"max_logit_gap": float(np.max(gap)),
                "mean_logit_gap": float(np.mean(gap))}
    out = {"tokens": int(mask.sum()), "program": gaps(got)}
    if control:
        out["control"] = gaps(probed)
    return out


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def finish(cell, res, params, trace, bench, control, seed, log,
           limits: dict | None = None) -> list:
    """Metrics of the run, then the correctness check once the program's
    state is freed; returns the result line in a list. With ``control`` a
    second line follows: the same run judged by the same limits with the
    float8 reference's tokens in the program's place, which has to come
    out as not correct."""
    name, cfgd, mix = cell["name"], res["cfg"], res["mix"]
    red = None
    if trace:
        red = trace_reduce.reduce(res["trace_dir"])
        if res["trace_dir_owned"]:
            shutil.rmtree(res["trace_dir"], ignore_errors=True)
        metrics = per_layer(bench, name, layer_context(res, red))
    else:
        have = end_to_end(res)
        metrics = {}
        for m in bench["end_to_end"]:
            cells = m.get("workloads")
            if (cells is None or name in cells) and m["name"] in have:
                metrics[m["name"]] = {"value": have[m["name"]][0],
                                      "unit": m["unit"]}
    sess = sample(res, mix["check"]["requests"], seed)
    t_chk = clock()
    chk = check(params, cfgd, sess, control)
    log(f"check: {len(sess)} requests, {chk['tokens']} served tokens, "
        f"reference {clock() - t_chk:.1f} s")
    limits = limits if limits is not None else load_limits(name)
    base_t, end_t = res["base"]["t"], res["window"]["t"]
    attempted = sum(1 for t in res["due"].values() if base_t <= t < end_t)
    failed = sum(1 for r in res["rejected"]
                 if base_t <= res["due"][r] < end_t)
    failed += res["engine_stats"]["requests_over_capacity"]

    def line(who):
        gaps = chk[who]
        checks = {k: {"value": gaps[k], "limit": v}
                  for k, v in limits.items()}
        # the one lower limit: a run must have served something to compare
        checks["served_tokens_compared"] = {"value": chk["tokens"],
                                            "limit": 1}
        correct = bool(all(gaps[k] <= v for k, v in limits.items())
                       and chk["tokens"] >= 1)
        out = {"correct": correct, "attempted": attempted, "failed": failed,
               "metrics": metrics,
               "device": _device_info(res["devs"], res["peak"])}
        if red is not None:
            out["device"]["busy_s"] = red["busy_s"]
            out["device"]["window_s"] = red["window_s"]
            out["breakdown"] = red["breakdown"]
        out["compiles_in_window"] = res["compile_counts"]["compiles"]
        out["logit_gaps"] = gaps
        if who == "control":
            out["control"] = "float8 reference in the program's place"
        out["checks"] = checks
        return out
    return [line("program")] + ([line("control")] if control else [])
