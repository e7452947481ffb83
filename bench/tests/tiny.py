"""A tiny cell for the benchmark's tests on the CPU: a 2-layer split model
and small mixes, run through the same harness as the chip cells."""
import copy
import json
import os
import time

from bench import harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHAT, DOCS = "tiny.chat", "tiny.docs"


def config(name="tiny"):
    with open(os.path.join(DATA, "tiny.json")) as f:
        c = json.load(f)
    c["name"] = name            # a fresh name compiles fresh programs
    return c


def mix(name):
    with open(os.path.join(DATA, name + ".json")) as f:
        m = json.load(f)
    m["name"] = name
    return m


def bench():
    """BENCHMARK.json with the tiny cells added wherever the chat and
    long-context cells are named."""
    b = copy.deepcopy(harness.load_benchmark())
    names = {c["name"]: c for c in b["workloads"]}
    alias = {"qwen2.5-3b.chat-mmwave": CHAT,
             "qwen2.5-3b.longctx-static": DOCS}
    for m in b["end_to_end"] + b["per_layer"]:
        if m.get("workloads"):
            m["workloads"] += [alias[w] for w in m["workloads"] if w in alias]
    b["workloads"] += [dict(names["qwen2.5-3b.chat-mmwave"], name=CHAT,
                            config="tiny", traffic="tiny-chat"),
                       dict(names["qwen2.5-3b.longctx-static"], name=DOCS,
                            config="tiny", traffic="tiny-docs")]
    return b


#: the tiny cells' limit (see ``test_bench_harness.py`` for its readings)
LIMITS = {"max_logit_gap": 0.05}


def run(cell, cfg, m, seed, seconds=3.0, control=False, warm_up=True,
        limits=None):
    """Set-up, window and check of a tiny cell; returns the result lines
    (the program's, then with ``control`` the float8 control's)."""
    b = bench()
    c = {w["name"]: w for w in b["workloads"]}[cell]
    t0 = time.monotonic()
    st = harness.setup(cfg, m, seed, warm_up=warm_up, log=lambda *_: None)
    res = harness.measure(st, m, seed, seconds, False, t_start=t0,
                          log=lambda *_: None)
    params = st.pop("params")
    st.clear()
    return harness.finish(c, res, params, False, b, control, seed,
                          lambda *_: None, limits=limits or LIMITS)
