"""Serving telemetry: registry oracles, trace round-trip, the engine's
phase spans, and the no-behavior-change contract.

The telemetry subsystem (``repro.serving.telemetry``) must be purely
additive: attaching a ``Telemetry`` to an engine may not change a single
decoded token bit, on either the host loop or the device-resident
windowed loop, for any decode-state family, nor compile a program the
plain engine lacks. These tests pin that, plus the registry's percentile
math against a ``np.quantile`` oracle, the Chrome-trace JSON round-trip
Perfetto relies on, the registry's wire counters against the host's
accounting, the phase spans in a ``jax.profiler`` trace, and the cluster
timeline's per-replica lanes with admission/migration/autoscale events.
"""
import glob
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import bottleneck as BN
from repro.core import split as SP
from repro.core.channel import (ChannelConfig, MobilityChannel,
                                channel_fleet)
from repro.core.orchestrator import (AppRequirement, ModeProfile,
                                     Orchestrator)
from repro.serving import (Autoscaler, AutoscalerConfig,
                           ContinuousBatchingEngine, EdgeCluster,
                           MetricsRegistry, Request, SLOAdmission,
                           SLOAdmissionConfig, Telemetry, TraceRecorder)
from repro.serving.telemetry import Histogram, span as phase_span

ARCHS = ["qwen2.5-3b", "recurrentgemma-2b", "xlstm-125m"]


# ---------------------------------------------------------------------------
# registry / histogram oracles
# ---------------------------------------------------------------------------

def test_histogram_quantiles_match_numpy_oracle():
    """A log-bucketed quantile is the upper edge of the rank's bucket, so
    it must bracket the exact sample quantile from above within one
    bucket ratio."""
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-5.0, sigma=1.5, size=5000)
    h = Histogram("t", lo=1e-6, hi=100.0, n_buckets=96)
    for s in samples:
        h.observe(s)
    ratio = (100.0 / 1e-6) ** (1 / 95)        # adjacent-edge ratio ~1.21x
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(samples, q))
        est = h.quantile(q)
        assert exact <= est <= exact * ratio * 1.0001, (q, exact, est)
    assert h.count == 5000
    assert h.summary()["max"] == pytest.approx(samples.max())
    assert h.summary()["mean"] == pytest.approx(samples.mean(), rel=1e-9)


def test_histogram_weighted_observe_and_overflow():
    h = Histogram("t", lo=1e-3, hi=1.0, n_buckets=16)
    h.observe(0.01, n=7)
    h.observe(50.0)                            # past hi -> overflow bucket
    assert h.count == 8
    assert h.quantile(0.5) >= 0.01
    assert h.quantile(1.0) == 50.0             # overflow reports true max
    h.reset()
    assert h.count == 0 and h.quantile(0.5) == 0.0


def test_registry_snapshot_prometheus_and_reset():
    reg = MetricsRegistry()
    reg.inc("a.events", 3)
    reg.set("a.depth", 2.5)
    reg.observe("a.lat_s", 0.02, n=4)
    snap = reg.snapshot()
    assert snap["a.events"] == 3 and snap["a.depth"] == 2.5
    assert snap["a.lat_s"]["count"] == 4
    prom = reg.prometheus()
    assert "# TYPE a_events counter" in prom
    assert "# TYPE a_lat_s histogram" in prom
    assert 'a_lat_s_bucket{le="+Inf"} 4' in prom
    lat = reg.latency_summary("a.lat_s", "missing")
    assert set(lat) == {"a.lat_s"}
    assert lat["a.lat_s"]["p50"] >= 20.0       # ms
    with pytest.raises(TypeError):
        reg.inc("a.depth")                     # kind mismatch must be loud
    reg.ingest("st", {"x": 1, "nested": {"y": 2.0}, "skip": [1, 2]})
    assert reg.snapshot()["st.nested.y"] == 2.0
    reg.reset()
    snap = reg.snapshot()
    assert snap["a.events"] == 0 and snap["a.lat_s"]["count"] == 0


# ---------------------------------------------------------------------------
# trace recorder round-trip
# ---------------------------------------------------------------------------

def test_trace_chrome_json_round_trip(tmp_path):
    tel = Telemetry(trace_capacity=64, lane=0, lane_name="cluster")
    tr = tel.trace
    tr.instant("admit", lane=0, cat="admission", rid=1)
    with phase_span("window", tel.for_lane(1, "replica0"), "window_s",
              cat="window", ticks=4):
        pass
    assert tel.registry.snapshot()["window_s"]["count"] == 1
    path = tr.export(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["pid"]: m["args"]["name"] for m in meta} == {
        0: "cluster", 1: "replica0"}
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["name"] == "admit" and inst["pid"] == 0
    assert inst["cat"] == "admission" and inst["args"]["rid"] == 1
    span = next(e for e in evs if e["ph"] == "X")
    assert span["pid"] == 1 and span["dur"] >= 0
    assert span["args"]["ticks"] == 4
    ts = [e["ts"] for e in evs if e["ph"] != "M"]
    assert all(t >= 0 for t in ts)


def test_trace_ring_buffer_drops_oldest():
    tr = TraceRecorder(capacity=8)
    for i in range(20):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 8 and tr.dropped == 12
    assert tr.events()[0]["name"] == "e12"     # oldest evicted first


def test_telemetry_lane_views_share_registry_and_trace():
    tel = Telemetry(lane=0, lane_name="cluster")
    view = tel.for_lane(2, "replica1")
    view.inc("x", 5)
    view.instant("ev")
    assert tel.registry.snapshot()["x"] == 5
    assert tel.trace.events()[0]["pid"] == 2
    assert tel.trace._lanes[2] == "replica1"


# ---------------------------------------------------------------------------
# engine instrumentation: zero behavior change
# ---------------------------------------------------------------------------

def _requests(cfg, n, *, seed=3):
    chans = channel_fleet(
        n, ChannelConfig(mean_mbps=8.0, std_mbps=3.0, blockage_prob=0.08,
                         recovery_prob=0.15),
        seed=11, mean_spread=0.95)
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        size=4).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, 8)),
                    channel=chans[i], arrival_tick=i // 2)
            for i in range(n)]


def _orch(cfg):
    return Orchestrator(
        [ModeProfile(m, BN.mode_payload_bytes(cfg, 1, 1, m), float(m))
         for m in range(cfg.split.n_modes)],
        AppRequirement(latency_budget_s=0.006), ema=0.5, hysteresis=1.0)


def _run(params, cfg, *, host_loop, telemetry):
    tel = Telemetry() if telemetry else None
    eng = ContinuousBatchingEngine(params, cfg, n_slots=3, cache_len=32,
                                   orchestrator=_orch(cfg),
                                   host_loop=host_loop, telemetry=tel)
    done = eng.run(_requests(cfg, 10))
    st = eng.stats()
    assert eng.pool.n_free == eng.pool.n_slots
    return done, st, tel, eng


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("host_loop", [False, True])
def test_telemetry_changes_no_token_bits(arch, host_loop):
    """The no-behavior-change contract: the instrumented engine decodes
    the exact streams the plain engine decodes — tokens, modes, wire,
    lifecycle ticks — on both the host loop and the device windowed
    loop (where the engine runs the plain engine's compiled programs)."""
    cfg = get_reduced(arch)
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    plain_done, plain_st, _, _ = _run(params, cfg, host_loop=host_loop,
                                      telemetry=False)
    tel_done, tel_st, tel, _ = _run(params, cfg, host_loop=host_loop,
                                    telemetry=True)

    plain = {s.request.rid: s for s in plain_done}
    instr = {s.request.rid: s for s in tel_done}
    assert plain.keys() == instr.keys() and len(plain) == 10
    for rid in plain:
        assert plain[rid].tokens == instr[rid].tokens, rid
        assert plain[rid].mode_counts == instr[rid].mode_counts, rid
        assert plain[rid].wire_bytes == instr[rid].wire_bytes, rid
        assert plain[rid].admitted_tick == instr[rid].admitted_tick, rid
        assert plain[rid].finished_tick == instr[rid].finished_tick, rid
    # stats() parity — mean_ttft_s is wall-clock and run-dependent
    for k in plain_st:
        if k == "mean_ttft_s":
            continue
        assert plain_st[k] == tel_st[k], k

    # the registry saw real work
    snap = tel.registry.snapshot()
    assert snap["engine.ttft_s"]["count"] == 10
    assert snap["engine.decode_wire_bytes"] == tel_st["decode_wire_bytes"]
    # the registry's decode counters are the host's accounting: wire
    # bytes charged per session, one token per live slot per tick, and
    # one mode per decoded token
    assert snap["engine.decode_wire_bytes"] == sum(
        s.wire_bytes - s.prefill_wire_bytes for s in tel_done)
    assert snap["engine.decode_tokens"] == sum(
        len(s.tokens) - 1 for s in tel_done)
    assert snap["engine.decode_tokens"] == tel_st["decoded_slot_ticks"]
    assert sum(tel_st["mode_counts"].values()) \
        == snap["engine.decode_tokens"]


def test_reset_counters_clears_registry():
    cfg = get_reduced("qwen2.5-3b")
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    tel = Telemetry()
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, cache_len=32,
                                   orchestrator=_orch(cfg), telemetry=tel)
    eng.warm(np.array([1, 2, 3], np.int32))    # ends in reset_counters
    snap = tel.registry.snapshot()
    assert snap["engine.ttft_s"]["count"] == 0
    assert snap["engine.decode_wire_bytes"] == 0


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "xlstm-125m"])
def test_telemetry_compiles_nothing_new(arch):
    """Attaching a Telemetry selects no other program: once a plain
    engine has run a workload, an instrumented engine runs the same
    workload without a single backend compile (paged and dense pools)."""
    cfg = get_reduced(arch)
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    _run(params, cfg, host_loop=False, telemetry=False)
    compiles = []

    def listen(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        _run(params, cfg, host_loop=False, telemetry=True)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []


# the serving thread's spans, each with the span it must lie inside
# (None: outermost among the program's spans). The pipeline also lands
# outside admission: when the pool drains, and at close().
SERVING_SPANS = {
    "engine.admit": None,
    "engine.sync_wait": ("engine.admit", None),
    "engine.collect_admits": "engine.admit",
    "engine.prefill": "engine.admit",
    "engine.prefill_wait": "engine.admit",
    "engine.plan": None,
    "engine.choose_modes": None,
    "engine.dispatch": None,
    "engine.retire": None,
    "engine.materialize": None,
    "engine.materialize_wait": "engine.materialize",
}


def test_phase_spans_in_the_profiler_trace(tmp_path):
    """Every phase span reaches the ``jax.profiler`` trace under its own
    name, nested as the engine nests the work: admission's parts inside
    ``engine.admit``, the host's waits inside the phase that waits, the
    window's phases outermost, and ``engine.launch`` alone on the
    pipeline worker's thread. The attached Telemetry records the same
    spans into its timeline and histograms."""
    from jax.profiler import ProfileData
    cfg = get_reduced("qwen2.5-3b")
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    tel = Telemetry()
    eng = ContinuousBatchingEngine(params, cfg, n_slots=3, cache_len=32,
                                   orchestrator=_orch(cfg), telemetry=tel)
    with jax.profiler.trace(str(tmp_path)):
        eng.run(_requests(cfg, 10))
        eng.close()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    host = [p for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")]
    lines = []
    for p in host:
        for line in p.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("engine.")]
            if evs:
                lines.append(evs)
    serving = [evs for evs in lines
               if any(n != "engine.launch" for n, _, _ in evs)]
    worker = [evs for evs in lines
              if all(n == "engine.launch" for n, _, _ in evs)]
    assert len(serving) == 1 and len(worker) == 1
    serving, worker = serving[0], worker[0]
    assert len(worker) >= 2                  # one launch per window
    names = {n for n, _, _ in serving}
    assert names == set(SERVING_SPANS)
    for name, parent in SERVING_SPANS.items():
        for _, s, e in (ev for ev in serving if ev[0] == name):
            holders = [p for p, ps, pe in serving
                       if (ps, pe) != (s, e) and ps <= s and e <= pe]
            allowed = parent if isinstance(parent, tuple) else (parent,)
            assert holders in [[] if p is None else [p] for p in allowed], \
                (name, holders)
    admit = [(s, e) for n, s, e in serving if n == "engine.admit"]
    assert any(ps <= s and e <= pe for n, s, e in serving
               if n == "engine.sync_wait" for ps, pe in admit)
    # the prefill's dispatch ends before the host blocks on its tokens
    pre = sorted((s, e) for n, s, e in serving if n == "engine.prefill")
    wait = sorted((s, e) for n, s, e in serving if n == "engine.prefill_wait")
    assert len(pre) == len(wait) and all(
        p[1] <= w[0] for p, w in zip(pre, wait))

    recorded = {e["name"] for e in tel.trace.events() if e["ph"] == "X"}
    assert recorded == set(SERVING_SPANS)
    prefill = next(e for e in tel.trace.events()
                   if e["name"] == "engine.prefill")
    assert prefill["args"]["rids"] and "bucket" in prefill["args"]
    snap = tel.registry.snapshot()
    for hist, name in (("engine.prefill_s", "engine.prefill"),
                       ("engine.window_dispatch_s", "engine.dispatch"),
                       ("engine.window_materialize_s",
                        "engine.materialize")):
        assert snap[hist]["count"] == sum(
            1 for e in tel.trace.events() if e["name"] == name)


# ---------------------------------------------------------------------------
# SLO admission structured events
# ---------------------------------------------------------------------------

def test_slo_admission_records_decisions_with_margin():
    gate = SLOAdmission(64, SLOAdmissionConfig(latency_budget_s=0.05,
                                               hopeless_factor=4.0,
                                               park_queue_per_slot=2.0))
    assert gate.decide(slo_ticks=100, predicted_wait_ticks=10,
                       service_ticks=20, queue_per_slot=0.5,
                       rid=7) == "admit"
    assert gate.decide(slo_ticks=25, predicted_wait_ticks=10,
                       service_ticks=20, rid=8) == "reject"
    assert gate.decide(slo_ticks=None, predicted_wait_ticks=0,
                       service_ticks=1, queue_per_slot=9.0,
                       rid=9) == "park"
    assert gate.decide(slo_ticks=100, predicted_wait_ticks=0,
                       service_ticks=1, capacity_bps=1.0,
                       rid=10) == "reject"
    evs = list(gate.events)
    assert [e["reason"] for e in evs] == ["ok", "deadline", "backlog",
                                          "link_hopeless"]
    assert evs[0] == {"rid": 7, "verdict": "admit", "reason": "ok",
                      "margin_ticks": 70, "predicted_wait_ticks": 10,
                      "service_ticks": 20, "queue_per_slot": 0.5}
    assert evs[1]["margin_ticks"] == -5
    assert evs[2]["margin_ticks"] is None
    tel = Telemetry()
    gate.telemetry = tel
    gate.decide(slo_ticks=100, predicted_wait_ticks=1, service_ticks=1,
                rid=11)
    ev = tel.trace.events()[-1]
    assert ev["name"] == "slo_admission" and ev["cat"] == "admission"
    assert ev["args"]["rid"] == 11 and ev["args"]["margin_ticks"] == 98


# ---------------------------------------------------------------------------
# cluster timeline: lanes + admission/migration/autoscale events
# ---------------------------------------------------------------------------

def _mobility(cross_at, n, cap=2e6):
    return MobilityChannel([0] * cross_at + [1] * n, [cap, cap],
                           detach_factor=1.0)


def test_cluster_trace_has_lanes_and_lifecycle_events(tmp_path):
    """One exported cluster trace must carry per-replica lanes plus the
    control-plane story: SLO admission verdicts, migration send/inject
    and autoscale decisions, all loadable as Chrome trace JSON."""
    cfg = get_reduced("qwen2.5-3b")
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    gen = 10
    tel = Telemetry()
    cluster = EdgeCluster(
        params, cfg, n_replicas=2, n_slots=2, cache_len=48,
        placement="best-channel", handover="migrate",
        admission=SLOAdmission(64, SLOAdmissionConfig()),
        autoscaler=Autoscaler(AutoscalerConfig(
            min_replicas=1, max_replicas=4, high_occupancy=0.5,
            sustain_ticks=1, cooldown_ticks=2)),
        telemetry=tel)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        size=4).astype(np.int32),
                    max_new_tokens=gen,
                    channel=_mobility(5 if i == 0 else gen + 60,
                                      gen + 60),
                    slo_ticks=400)
            for i in range(4)]
    cluster.run(reqs)
    st = cluster.stats()
    cluster.close()

    names = {e["name"] for e in tel.trace.events()}
    assert "slo_admission" in names
    if st["migrations"]:
        assert {"migrate_send", "migrate_inject"} & names
    lanes = {e["pid"] for e in tel.trace.events()}
    assert 0 in lanes and len(lanes) >= 2      # cluster + >=1 replica lane
    assert tel.trace._lanes[0] == "cluster"
    assert tel.trace._lanes[1] == "replica0"
    # registry mirrors the cluster stats() totals
    snap = tel.registry.snapshot()
    assert snap["cluster.migrations"] == st["migrations"]
    assert "cluster.stats.requests_finished" in snap
    # and the whole timeline survives a JSON round-trip
    path = tel.trace.export(str(tmp_path / "cluster_trace.json"))
    doc = json.load(open(path))
    assert any(e.get("ph") == "M" for e in doc["traceEvents"])
