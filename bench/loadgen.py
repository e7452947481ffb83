"""The one traffic generator: reads a mix file from ``bench/traffic/`` and
turns it, with the run's seed, into the requests of a run.

The run's ``--seed`` draws the schedule: the arrival times of an open
loop, the order in which the mix's lengths are sent, which channel trace
each user replays, and the prompt tokens (and, in the harness, the
weights). Every seed sends the same amount of work: an open loop has
``rate_per_s`` times the window's seconds arrivals in the window, each
placed by the seed; the lengths are the lognormal's quantiles at evenly
spaced probabilities, clipped, so each seed gets the same set of sizes in
another order. A closed loop replays such a set to a fixed number of
clients, in the seed's order.

An open loop's lead-in, traffic sent in set-up, replays the window's own
last ``lead_in_s`` seconds (arrival offsets, lengths and links; fresh
prompt tokens) just before the window: the schedule wraps around, so the
tokens that the window's last requests would decode after its end are
decoded, by their copies, at its start. With a lead-in longer than the
longest request lasts, the window decodes the tokens of its own requests
whatever order the seed gives them, and not a share that depends on which
lengths the seed puts last.

Open-loop arrivals are a Poisson process conditioned on its count: the
times are uniform over the span, or, with ``burst``, drawn from an
intensity that is ``factor`` times higher for ``seconds`` in bursts that
themselves arrive as a Poisson process, one per ``every_s`` on average.
"""
from __future__ import annotations

import dataclasses
import json
import os
import statistics
import time
from typing import List, Optional

import numpy as np

from bench import lumos

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


@dataclasses.dataclass
class Item:
    """One request of the schedule."""
    rid: int
    prompt: np.ndarray
    max_new: int
    t_due: Optional[float]     # seconds from the window's start (open loop)
    ue: int                    # channel trace row


def lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` lengths of a lognormal (median, sigma) at the probabilities
    (i + 1/2) / n, rounded and clipped to [min, max], in ascending order."""
    p = (np.arange(n) + 0.5) / max(n, 1)
    z = np.array([statistics.NormalDist().inv_cdf(float(q)) for q in p])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrivals(rate: float, burst: Optional[dict], t0: float, t1: float,
             rng) -> np.ndarray:
    """``rate * (t1 - t0)`` arrival times in [t0, t1), rounded to whole
    requests: uniform, or with ``burst`` drawn from an intensity ``factor``
    times higher inside bursts (by rejection)."""
    n = int(round(rate * (t1 - t0)))
    if not burst:
        return np.sort(rng.uniform(t0, t1, n))
    f, blen = burst["factor"], burst["seconds"]
    nb = rng.poisson((t1 - t0 + blen) / burst["every_s"])
    starts = rng.uniform(t0 - blen, t1, nb)
    out = np.empty(0)
    while len(out) < n:
        t = rng.uniform(t0, t1, 4 * n)
        inside = ((t[:, None] >= starts[None, :])
                  & (t[:, None] < starts[None, :] + blen)).any(axis=1)
        keep = rng.uniform(size=len(t)) < np.where(inside, 1.0, 1.0 / f)
        out = np.concatenate([out, t[keep]])
    return np.sort(out[:n])


def max_context(mix: dict) -> int:
    """Rows the longest request of the mix can occupy."""
    return mix["prompt_len"]["max"] + mix["output_len"]["max"] - 1


def build(mix: dict, seed: int, seconds: float, vocab: int) -> List[Item]:
    """The run's requests. Open loop: every arrival from the lead-in's
    start to the window's end, with its due time; the lead-in is a copy of
    the window's last ``lead_in_s`` seconds, shifted to just before it.
    Closed loop: the replay set, in the order the clients take it."""
    rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        t = arrivals(mix["rate_per_s"], mix.get("burst"), 0.0,
                     float(seconds), rng)
    else:
        t = [None] * mix["replay_size"]
    n = len(t)
    plen = rng.permutation(lengths(mix["prompt_len"], n))
    olen = rng.permutation(lengths(mix["output_len"], n))
    n_traces = mix["channel"].get("n_traces", 1)
    ues = rng.permutation(max(n, n_traces)) % n_traces
    src = list(range(n))
    if mix["loop"] == "open":
        wrap = [i for i in range(n) if t[i] >= seconds - mix["lead_in_s"]]
        t = [float(t[i]) - seconds for i in wrap] + [float(x) for x in t]
        src = wrap + src
    return [Item(rid=r, prompt=rng.integers(0, vocab, int(plen[i]),
                                            dtype=np.int32),
                 max_new=int(olen[i]), t_due=t[r], ue=int(ues[i]))
            for r, i in enumerate(src)]


class WallClockChannel:
    """A user's uplink whose capacity follows the wall clock: ``step()``
    returns the trace value at the time elapsed since the request was due,
    however often the engine asks, so the mode mix follows time and not the
    engine's speed. Built on the program's ``Channel`` by ``channel_class``
    so that this module imports nothing of the program."""

    def __init__(self, trace_bps: np.ndarray, tick_s: float, t0: float,
                 clock=time.perf_counter):
        self._trace = np.asarray(trace_bps, np.float64)
        self._tick_s = tick_s
        self._t0 = t0
        self._clock = clock

    def step(self) -> float:
        i = int((self._clock() - self._t0) / self._tick_s)
        return float(self._trace[min(max(i, 0), len(self._trace) - 1)])


def channel_class(base):
    """``WallClockChannel`` as a subclass of the program's ``Channel``."""
    class _Chan(WallClockChannel, base):
        def __init__(self, trace_bps, tick_s, t0, clock=time.perf_counter):
            base.__init__(self)
            WallClockChannel.__init__(self, trace_bps, tick_s, t0, clock)
    return _Chan


def traces(mix: dict, seconds: float) -> np.ndarray:
    """The mix's channel traces [n_traces, ticks] in bytes/second, long
    enough to outlast any request of the run."""
    ch = mix["channel"]
    ticks = int((mix.get("lead_in_s", 0) + seconds + 120) / ch["tick_s"])
    if ch["kind"] == "static":
        return np.full((1, 1), ch["mbps"] * 1e6 / 8.0)
    if ch["kind"] == "lumos5g":
        return lumos.capacity_traces_bps(ch["n_traces"], ticks,
                                         tick_seconds=ch["tick_s"],
                                         seed=ch["trace_seed"])
    raise ValueError(f"unknown channel kind {ch['kind']!r}")
