"""Trace reduction against a hand-written trace whose busy time, idle
time, per-name device time and gap owners are counted by hand."""
import gzip
import os
import re

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce

# Device TPU:0, "XLA Ops" from t = 1,000 ns (names as the TPU profiler
# gives them, whole HLO instructions):
#   while.1            [1000, 6000)   holds the next three
#   paged_attention.3  [1000, 3000)   2,000 ns
#   fusion.12          [4000, 5000)   1,000 ns; its operand names
#                                     paged_attention.3, which must not count
#   decode_tail.2      [5000, 6000)   1,000 ns
#   paged_attention.4  [9000, 12000)  3,000 ns, clipped at 10,500 to 1,500
# "XLA Modules": jit_mixed_step_dev [1000, 6000), jit_mixed_prefill
#   [9000, 12000) clipped to 1,500.
# Host: bench.stretch [500, 10500) is the window (10,000 ns); engine.step
#   [300, 1200); bench.submit [6000, 8000); bench.wait [8000, 9000).
# Busy: [1000, 6000) + [9000, 10500) = 6,500 ns. The while's own time:
#   5,000 - 2,000 - 1,000 - 1,000 = 1,000 ns.
# Gaps: [500, 1000) 500 ns, mid 750 in engine.step; [6000, 9000)
#   3,000 ns, mid 7,500 in bench.submit.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 3000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 6 offset_ps: 8000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%paged_attention.3 = bf16[16,2,8,128] custom-call(s32[16,8] %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.12 = bf16[16,2048] fusion(bf16[16,2,8,128] %paged_attention.3)" } }
  event_metadata { key: 3 value { id: 3 name: "%decode_tail.2 = s32[32,128] custom-call(bf16[32,2048] %x)" } }
  event_metadata { key: 4 value { id: 4 name: "%paged_attention.4 = bf16[16,2,8,128] custom-call(s32[16,8] %q)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_mixed_step_dev(1234)" } }
  event_metadata { key: 6 value { id: 6 name: "jit_mixed_prefill(5678)" } }
  event_metadata { key: 7 value { id: 7 name: "%while.1 = (s32[]) while(s32[] %t), body=%region_0" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 7 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 300000 duration_ps: 900000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.stretch" } }
  event_metadata { key: 2 value { id: 2 name: "engine.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.submit" } }
  event_metadata { key: 4 value { id: 4 name: "bench.wait" } }
}
"""


def test_hand_counted_trace():
    r = trace_reduce.reduce_data(ProfileData.from_text_proto(TRACE))
    ns = 1e-9
    assert r["window_s"] == pytest.approx(10000 * ns)
    assert r["busy_s"] == pytest.approx(6500 * ns)
    assert r["kernels"]["paged_attention"] == pytest.approx(3500 * ns)
    assert r["kernels"]["decode_tail"] == pytest.approx(1000 * ns)
    assert "boundary_mixed" not in r["kernels"]
    assert r["modules"]["mixed_step_dev"] == pytest.approx(5000 * ns)
    assert r["modules"]["mixed_prefill"] == pytest.approx(1500 * ns)
    gaps = r["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.submit", "engine.step"]
    assert [g[1] for g in gaps] == pytest.approx([3000 * ns, 500 * ns])
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == pytest.approx({"paged_attention": 3500 * ns,
                                 "fusion": 1000 * ns, "while": 1000 * ns,
                                 "decode_tail": 1000 * ns})


def test_a_trace_without_a_device_is_refused():
    host_only = TRACE[TRACE.index("planes {\n  id: 2"):]
    with pytest.raises(ValueError):
        trace_reduce.reduce_data(ProfileData.from_text_proto(host_only))


CHIP = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "chip_trace_chat.textproto.gz")


def _chip():
    with gzip.open(CHIP, "rt") as f:
        return ProfileData.from_text_proto(f.read())


def test_recorded_chip_trace():
    """120 ms of a traced qwen2.5-3b chat run on a TPU v5e, trimmed to the
    device's op and module lines and the harness's host spans. Each number
    is counted here a second way: busy time by a sweep over start and end
    points, kernel time by the instruction's own name."""
    pd = _chip()
    r = trace_reduce.reduce_data(pd)
    dev = [p for p in pd.planes if p.name == "/device:TPU:0"][0]
    ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for l in dev.lines if l.name == "XLA Ops" for e in l.events]
    mods = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for l in dev.lines if l.name == "XLA Modules" for e in l.events]
    host = [e for p in pd.planes if p.name.startswith("/host")
            for l in p.lines for e in l.events]
    st = [e for e in host if e.name == "bench.stretch"][0]
    w0, w1 = st.start_ns, st.start_ns + st.duration_ns
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    points = sorted([(max(s, w0), 1) for _, s, e in ops if e > w0 and s < w1]
                    + [(min(e, w1), -1) for _, s, e in ops
                       if e > w0 and s < w1])
    depth, busy, last = 0, 0.0, None
    for t, dlt in points:
        if depth > 0:
            busy += t - last
        depth, last = depth + dlt, t
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]

    def own(prefix):
        pat = re.compile(r"%" + prefix + r"(\.\d+)* = ")
        return sum(min(e, w1) - max(s, w0) for n, s, e in ops
                   if pat.match(n) and e > w0 and s < w1) * 1e-9
    for k in ("paged_attention", "decode_tail"):
        assert own(k) > 0
        assert r["kernels"][k] == pytest.approx(own(k))
    step = sum(min(e, w1) - max(s, w0) for n, s, e in mods
               if n.startswith("jit_mixed_step_dev(") and e > w0
               and s < w1) * 1e-9
    assert r["modules"]["mixed_step_dev"] == pytest.approx(step)
    assert sum(r["kernels"].values()) <= r["busy_s"]
    for name, secs in r["breakdown"]["device_ops"]:
        assert " " not in name and secs > 0
    for owner, secs in r["breakdown"]["idle_gaps"]:
        assert owner in ("engine.step", "bench.submit", "bench.wait",
                         "no host span") and secs > 0
