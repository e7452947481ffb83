"""The serving engine's phase spans in a ``jax.profiler`` trace.

``serving/batcher.py`` opens ``engine.<phase>`` spans on the profiler's
clock (``docs/observability.md`` lists them). This module reads them from
the ``.xplane.pb`` inside the same window as ``trace_reduce`` (the
harness's ``bench.stretch`` span, else the first to the last device
operation) and returns:

* ``window_s``: the window;
* ``span_s``: seconds per program span name (every ``engine.*`` span but
  the harness's own ``engine.step``);
* ``busy_s``: the union of the serving thread's program spans less its
  ``*_wait`` spans, in which the host is blocked on the device or on the
  pipeline worker. The serving thread is every host line with a program
  span other than the pipeline worker's ``engine.launch``;
* ``idle_by_phase``: device-idle seconds by the outermost serving-thread
  span over them, else the harness span (``engine.step``,
  ``bench.submit``, ``bench.wait``), else ``no host span``; by interval
  overlap, averaged over the devices.

``span_s`` is empty, and ``busy_s`` 0, where the program opens no spans.
``metrics`` turns these into three per-layer numbers:

* ``host_busy_share``: ``busy_s / window_s``, in %;
* ``controller_us_per_slot_tick``: seconds of ``engine.choose_modes`` per
  live slot-tick decoded in the window, in us;
* ``admission_idle_share``: device-idle seconds under ``engine.admit`` /
  ``window_s``, in %.

The benchmark's traced runs do not report them yet. For a trace kept by
``bench/run.py --trace 1 --trace-dir DIR``::

    python3 -m bench.trace_host DIR [--slot-ticks N]

prints them as one JSON object (the controller's number only given the
slot-ticks decoded in the traced stretch).
"""
from __future__ import annotations

import argparse
import bisect
import json
import sys
from typing import Dict, Optional

from bench import trace_reduce

HARNESS = ("engine.step", "bench.submit", "bench.wait")
WORKER = "engine.launch"
NO_SPAN = "no host span"


def _outermost(spans):
    """The spans that no other span holds, sorted and disjoint."""
    out, end = [], None
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        if end is None or s >= end:
            out.append((name, s, e))
            end = e
    return out


def _cover(pieces, spans):
    """Split each (start, end) piece by sorted, disjoint named spans:
    nanoseconds per name, and the pieces no span covers."""
    starts = [s for _, s, _ in spans]
    got, left = {}, []
    for s, e in pieces:
        t = s
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(spans) and spans[i][1] < e:
            name, a, b = spans[i]
            lo, hi = max(a, t), min(b, e)
            if hi > lo:
                if lo > t:
                    left.append((t, lo))
                got[name] = got.get(name, 0) + hi - lo
                t = hi
            i += 1
        if e > t:
            left.append((t, e))
    return got, left


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def reduce_data(pd) -> Dict:
    host_lines = [[ev for ev in _events(line)
                   if ev[0].startswith("engine.")
                   or ev[0].startswith("bench.")]
                  for p in pd.planes if p.name.startswith("/host:")
                  for line in p.lines]
    devices = [[ev for line in p.lines if line.name == trace_reduce.OPS_LINE
                for ev in _events(line)]
               for p in pd.planes if trace_reduce._is_device(p)]
    if not devices:
        raise ValueError("the trace holds no device plane")
    stretch = [ev for evs in host_lines for ev in evs
               if ev[0] == trace_reduce.STRETCH]
    if stretch:
        w0, w1 = stretch[0][1], stretch[0][2]
    else:
        w0 = min(s for ops in devices for _, s, _ in ops)
        w1 = max(e for ops in devices for _, _, e in ops)

    def clip(iv):
        return [(max(s, w0), min(e, w1)) for s, e in iv if e > w0 and s < w1]

    gaps = []
    for ops in devices:
        u = trace_reduce._union(clip([(s, e) for _, s, e in ops]))
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])

    span_ns, serving, harness = {}, [], []
    for evs in host_lines:
        own = [ev for ev in evs
               if ev[0].startswith("engine.") and ev[0] not in HARNESS]
        for name, s, e in own:
            d = min(e, w1) - max(s, w0)
            if d > 0:
                span_ns[name] = span_ns.get(name, 0) + d
        if any(name != WORKER for name, _, _ in own):
            serving.extend(own)
        harness.extend(ev for ev in evs if ev[0] in HARNESS)
    held = trace_reduce._union(clip([(s, e) for _, s, e in serving]))
    waits = trace_reduce._union(clip([(s, e) for name, s, e in serving
                                      if name.endswith("_wait")]))
    busy = sum(e - s for s, e in held) - sum(e - s for s, e in waits)
    idle, left = _cover(gaps, _outermost(serving))
    by_harness, left = _cover(left, _outermost(harness))
    for name, ns in by_harness.items():
        idle[name] = idle.get(name, 0) + ns
    if left:
        idle[NO_SPAN] = idle.get(NO_SPAN, 0) + sum(e - s for s, e in left)
    n = len(devices)
    return {"window_s": (w1 - w0) / 1e9,
            "span_s": {k: v / 1e9 for k, v in span_ns.items()},
            "busy_s": busy / 1e9,
            "idle_by_phase": {k: v / n / 1e9 for k, v in idle.items()}}


def reduce(path: str) -> Dict:
    from jax.profiler import ProfileData
    return reduce_data(ProfileData.from_file(trace_reduce.find(path)))


def metrics(host: Dict, slot_ticks: Optional[int] = None) -> Dict:
    """The per-layer numbers of a reduction; each left out where the trace
    holds nothing to read (no program spans, no decoded slot-ticks)."""
    out = {}
    span, window = host["span_s"], host["window_s"]
    if not span or window <= 0:
        return out
    out["host_busy_share"] = 100.0 * host["busy_s"] / window
    t = span.get("engine.choose_modes")
    if t is not None and slot_ticks:
        out["controller_us_per_slot_tick"] = 1e6 * t / slot_ticks
    if "engine.admit" in span:
        out["admission_idle_share"] = \
            100.0 * host["idle_by_phase"].get("engine.admit", 0.0) / window
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--slot-ticks", type=int, default=None,
                    help="live slot-ticks decoded in the traced stretch")
    args = ap.parse_args(argv)
    host = reduce(args.trace_dir)
    print(json.dumps({**host, "metrics": metrics(host, args.slot_ticks)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
