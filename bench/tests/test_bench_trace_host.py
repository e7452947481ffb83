"""The reduction of the engine's phase spans (``bench/trace_host.py``)
and the numbers it gives, against a hand-written trace counted by hand.

Device TPU:0, "XLA Ops": [1000, 3000), [5000, 8000), [9000, 10000).
The window is ``bench.stretch`` [0, 12000), so the device idles in
[0, 1000), [3000, 5000), [8000, 9000) and [10000, 12000): 6,000 ns.

Serving thread (one host line):
  engine.step [500, 6000) holds
    engine.admit [600, 3500): sync_wait [600, 900), collect_admits
      [900, 1200), prefill [1200, 2000), prefill_wait [2000, 3200)
    plan [3500, 3800), choose_modes [3800, 4600), dispatch [4600, 4900),
    retire [4900, 5000), materialize [5000, 5900) holding
      materialize_wait [5000, 5800)
  engine.step [7000, 11000) holds admit [7000, 7100), plan [7100, 7300),
    choose_modes [7300, 8500), dispatch [8500, 8800), retire [8800, 8900)
  bench.wait [11000, 12000); engine.sync_wait [11800, 12300) (a close()
    at the stretch's end), clipped to 200 ns.
Worker thread: engine.launch [4700, 5000) and [8700, 9000).

Idle by phase: [0, 1000) is admit 400, engine.step 100, no host span
500; [3000, 5000) is admit 500, plan 300, choose_modes 800, dispatch 300,
retire 100; [8000, 9000) is choose_modes 500, dispatch 300, retire 100,
engine.step 100; [10000, 12000) is engine.step 1000, bench.wait 800,
sync_wait 200.
Busy: the program's spans hold [600, 5900) and [7000, 8900) and the
clipped [11800, 12000): 7,400 ns; less the waits 300 + 1,200 + 800 + 200:
4,900 ns.
"""
import gzip
import json
import os

import pytest
from jax.profiler import ProfileData

from bench import trace_host, trace_reduce

SERVING = [
    ("bench.stretch", 0, 12000), ("engine.step", 500, 6000),
    ("engine.admit", 600, 3500), ("engine.sync_wait", 600, 900),
    ("engine.collect_admits", 900, 1200), ("engine.prefill", 1200, 2000),
    ("engine.prefill_wait", 2000, 3200), ("engine.plan", 3500, 3800),
    ("engine.choose_modes", 3800, 4600), ("engine.dispatch", 4600, 4900),
    ("engine.retire", 4900, 5000), ("engine.materialize", 5000, 5900),
    ("engine.materialize_wait", 5000, 5800), ("engine.step", 7000, 11000),
    ("engine.admit", 7000, 7100), ("engine.plan", 7100, 7300),
    ("engine.choose_modes", 7300, 8500), ("engine.dispatch", 8500, 8800),
    ("engine.retire", 8800, 8900), ("bench.wait", 11000, 12000),
    ("engine.sync_wait", 11800, 12300),
]
WORKER = [("engine.launch", 4700, 5000), ("engine.launch", 8700, 9000)]
OPS = [(1000, 3000), (5000, 8000), (9000, 10000)]


def _text():
    names = sorted({n for n, _, _ in SERVING + WORKER})
    mid = {n: i + 1 for i, n in enumerate(names)}

    def line(i, evs):
        body = "".join(
            f"    events {{ metadata_id: {mid[n]} offset_ps: {s * 1000} "
            f"duration_ps: {(e - s) * 1000} }}\n" for n, s, e in evs)
        return f'  lines {{\n    id: {i} name: "python" timestamp_ns: 0\n' \
            f"{body}  }}\n"
    meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in mid.items())
    ops = "".join(f"    events {{ metadata_id: 1 offset_ps: {s * 1000} "
                  f"duration_ps: {(e - s) * 1000} }}\n" for s, e in OPS)
    return (
        'planes {\n  id: 1 name: "/device:TPU:0"\n'
        '  lines {\n    id: 1 name: "XLA Ops" timestamp_ns: 0\n'
        f"{ops}  }}\n"
        '  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = '
        'bf16[8] fusion(bf16[8] %p)" } }\n}\n'
        'planes {\n  id: 2 name: "/host:CPU"\n'
        + line(7, SERVING) + line(8, WORKER) + meta + "}\n")


def _pd():
    return ProfileData.from_text_proto(_text())


def test_host_phases_counted_by_hand():
    pd = _pd()
    r, h = trace_reduce.reduce_data(pd), trace_host.reduce_data(pd)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(12000 * ns)
    assert h["window_s"] == pytest.approx(12000 * ns)
    assert r["busy_s"] == pytest.approx(6000 * ns)
    assert h["span_s"] == pytest.approx({
        "engine.admit": 3000 * ns, "engine.sync_wait": 500 * ns,
        "engine.collect_admits": 300 * ns, "engine.prefill": 800 * ns,
        "engine.prefill_wait": 1200 * ns, "engine.plan": 500 * ns,
        "engine.choose_modes": 2000 * ns, "engine.dispatch": 600 * ns,
        "engine.retire": 200 * ns, "engine.materialize": 900 * ns,
        "engine.materialize_wait": 800 * ns, "engine.launch": 600 * ns})
    assert h["busy_s"] == pytest.approx(4900 * ns)
    assert h["idle_by_phase"] == pytest.approx({
        "engine.admit": 900 * ns, "engine.plan": 300 * ns,
        "engine.choose_modes": 1300 * ns, "engine.dispatch": 600 * ns,
        "engine.retire": 200 * ns, "engine.sync_wait": 200 * ns,
        "engine.step": 1200 * ns, "bench.wait": 800 * ns,
        "no host span": 500 * ns})
    assert sum(h["idle_by_phase"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    # the trace reduction names the longest gaps by the innermost span at
    # their middle: program phases where the program opens them
    gaps = r["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["engine.choose_modes", "bench.wait",
                                    "engine.step", "engine.dispatch"]


def test_phase_readers_on_the_hand_written_trace():
    m = trace_host.metrics(trace_host.reduce_data(_pd()), slot_ticks=40)
    assert m["host_busy_share"] == pytest.approx(100 * 4900 / 12000)
    # 2,000 ns of engine.choose_modes over 40 slot-ticks: 50 ns = 0.05 us
    assert m["controller_us_per_slot_tick"] == pytest.approx(0.05)
    assert m["admission_idle_share"] == pytest.approx(100 * 900 / 12000)


def test_phase_readers_on_a_hand_built_context():
    host = {"window_s": 4.0,
            "span_s": {"engine.admit": 0.5, "engine.choose_modes": 0.03},
            "busy_s": 0.2,
            "idle_by_phase": {"engine.admit": 0.02, "engine.step": 0.08}}
    m = trace_host.metrics(host, slot_ticks=40)
    assert m["host_busy_share"] == pytest.approx(5.0)
    assert m["controller_us_per_slot_tick"] == pytest.approx(750.0)
    assert m["admission_idle_share"] == pytest.approx(0.5)
    # admission spans with no device idle under them read 0, not nothing
    del host["idle_by_phase"]["engine.admit"]
    assert trace_host.metrics(host, 40)["admission_idle_share"] == 0.0
    # no decoded slot-ticks in the stretch: no controller reading
    assert "controller_us_per_slot_tick" not in trace_host.metrics(host, 0)
    assert "controller_us_per_slot_tick" not in trace_host.metrics(host)


CHIP = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "chip_trace_chat.textproto.gz")


def test_a_program_without_phase_spans_reads_nothing():
    """The recorded chip trace predates the program's phase spans: no
    program span is found, its idle time falls to the harness's spans,
    and none of the three numbers is given (nothing raises)."""
    with gzip.open(CHIP, "rt") as f:
        pd = ProfileData.from_text_proto(f.read())
    r, h = trace_reduce.reduce_data(pd), trace_host.reduce_data(pd)
    assert h["span_s"] == {} and h["busy_s"] == 0
    assert h["window_s"] == pytest.approx(r["window_s"])
    assert set(h["idle_by_phase"]) <= {"engine.step", "bench.submit",
                                       "bench.wait", "no host span"}
    assert sum(h["idle_by_phase"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert trace_host.metrics(h, slot_ticks=100) == {}


def test_command_line_prints_the_reduction(tmp_path, capsys):
    """``python3 -m bench.trace_host DIR --slot-ticks N`` on a directory
    that holds a trace, as ``bench/run.py --trace-dir`` leaves it."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_text()))
    assert trace_host.main([str(tmp_path), "--slot-ticks", "40"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["busy_s"] == pytest.approx(4900e-9)
    assert out["metrics"] == pytest.approx(
        trace_host.metrics(trace_host.reduce_data(_pd()), 40))
