"""Reduce a ``jax.profiler`` trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and returns:

* ``window_s``: the traced stretch, bounded by the harness's
  ``bench.stretch`` host span (or, without it, by the first and last
  device operation);
* ``busy_s``: the union of the intervals in which an operation ran on a
  device inside the window, averaged over the devices;
* ``kernels`` and ``modules``: device seconds of the stable names
  (``paged_attention``, ``decode_tail``, ``boundary_mixed`` among the
  operations; ``mixed_step_dev``, ``mixed_prefill`` among the jitted
  programs);
* ``breakdown``: the device operations that took most time (their own
  time, less what is nested in them, by name without XLA's suffix), and the
  longest idle gaps, each named by the innermost host span of the harness
  (``bench.submit``, ``engine.step``, ``bench.wait``) that covers it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

KERNELS = ("paged_attention", "decode_tail", "boundary_mixed")
MODULES = ("mixed_step_dev", "mixed_prefill")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STRETCH = "bench.stretch"


def find(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir`` (or the file itself)."""
    if trace_dir.endswith(".xplane.pb"):
        return trace_dir
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def op_name(event_name: str) -> str:
    """An operation's own name: the trace may give the whole HLO
    instruction (``%fusion.12 = bf16[..] fusion(..%paged_attention.3..)``),
    whose operands name other operations."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _base(name: str) -> str:
    """An operation's name without XLA's numeric suffix (``fusion.12``)."""
    return re.sub(r"(\.\d+)+$", "", name)


def _self_times(ops):
    """Each event's duration less that of the events nested in it (a
    ``while`` holds its body's operations on the same line)."""
    out, stack = [], []
    for i, (name, s, e) in sorted(enumerate(ops),
                                  key=lambda x: (x[1][1], -x[1][2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        out.append([name, s, e, e - s])
        if stack:
            out[stack[-1][0]][3] -= e - s
        stack.append((len(out) - 1, e))
    return out


def _is_device(plane) -> bool:
    return plane.name.startswith("/device:") and "TPU" in plane.name \
        and "SparseCore" not in plane.name


def reduce(path: str) -> Dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find(path))
    return reduce_data(pd)


def reduce_data(pd) -> Dict:
    host_spans = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith("bench.") or ev.name.startswith(
                        "engine."):
                    host_spans.append((ev.name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns))
    devices = [p for p in pd.planes if _is_device(p)]
    if not devices:
        raise ValueError("the trace holds no device plane")
    per_dev = []
    for p in devices:
        ops, mods = [], []
        for line in p.lines:
            if line.name == OPS_LINE:
                ops.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in line.events)
            elif line.name == MODULES_LINE:
                mods.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events)
        per_dev.append((ops, mods))
    stretch = [s for s in host_spans if s[0] == STRETCH]
    if stretch:
        w0, w1 = stretch[0][1], stretch[0][2]
    else:
        starts = [s for ops, _ in per_dev for _, s, _ in ops]
        ends = [e for ops, _ in per_dev for _, _, e in ops]
        w0, w1 = min(starts), max(ends)

    def clip(iv):
        return [(max(s, w0), min(e, w1)) for s, e in iv if e > w0 and s < w1]

    busy, kern, mod, by_op, gaps = [], {}, {}, {}, []
    for ops, mods in per_dev:
        u = _union(clip([(s, e) for _, s, e in ops]))
        busy.append(sum(e - s for s, e in u))
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        for name, s, e, own in _self_times(ops):
            d = max(0.0, min(e, w1) - max(s, w0))
            b = _base(op_name(name))
            by_op[b] = by_op.get(b, 0.0) + own * (d / (e - s) if e > s else 0)
            if b in KERNELS:
                kern[b] = kern.get(b, 0.0) + d
        for name, s, e in mods:
            d = max(0.0, min(e, w1) - max(s, w0))
            for k in MODULES:
                if k in name:
                    mod[k] = mod.get(k, 0.0) + d
    n = len(per_dev)
    spans = [s for s in host_spans if s[0] != STRETCH]

    def owner(t):
        inside = [s for s in spans if s[1] <= t <= s[2]]
        return min(inside, key=lambda s: s[2] - s[1])[0] if inside \
            else "no host span"

    gaps.sort(key=lambda g: g[0] - g[1])
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "kernels": {k: v / n / 1e9 for k, v in kern.items()},
        "modules": {k: v / n / 1e9 for k, v in mod.items()},
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in top_ops],
            "idle_gaps": [[owner((s + e) / 2), (e - s) / 1e9]
                          for s, e in gaps[:10]],
        },
    }
