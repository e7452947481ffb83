"""The traffic generator: seeds, clipping, the same amount of work for
every seed, the Lumos5G-shaped traces and the wall-clock channel."""
import statistics

import numpy as np
import pytest

from bench import loadgen, lumos


def _mix(name):
    return loadgen.load_mix(name)


@pytest.mark.parametrize("name", ["chat-mmwave", "longctx-static"])
def test_same_seed_same_schedule(name):
    mix = _mix(name)
    a = loadgen.build(mix, 2 ** 31 + 5, 30, 151936)
    b = loadgen.build(mix, 2 ** 31 + 5, 30, 151936)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.t_due, x.max_new, x.ue) == (y.t_due, y.max_new, y.ue)
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", ["chat-mmwave", "longctx-static"])
def test_seeds_reorder_the_same_work(name):
    mix = _mix(name)
    win = lambda items: [i for i in items
                         if i.t_due is None or 0 <= i.t_due < 51]
    a = win(loadgen.build(mix, 1, 51, 151936))
    b = win(loadgen.build(mix, 2 ** 31 + 7, 51, 151936))
    assert len(a) == len(b)
    sizes = lambda items, k: sorted((len(i.prompt), i.max_new)[k]
                                    for i in items)
    for k in (0, 1):                    # the same set of lengths ...
        assert sizes(a, k) == sizes(b, k)
    key = lambda items: [(i.t_due, len(i.prompt), i.max_new, i.ue)
                         for i in items]
    assert key(a) != key(b)             # ... in another order
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    if mix["loop"] == "open":           # the same count in the window
        assert len(a) == round(mix["rate_per_s"] * 51)


def test_lead_in_replays_the_end_of_the_window():
    mix = _mix("chat-mmwave")
    items = loadgen.build(mix, 2 ** 31 + 9, 51, 151936)
    lead = [i for i in items if i.t_due < 0]
    tail = [i for i in items if i.t_due >= 51 - mix["lead_in_s"]]
    assert lead and len(lead) == len(tail)
    assert [i.rid for i in items] == list(range(len(items)))
    for x, y in zip(lead, tail):        # the same offsets, sizes and links
        assert x.t_due == pytest.approx(y.t_due - 51)
        assert (len(x.prompt), x.max_new, x.ue) == \
            (len(y.prompt), y.max_new, y.ue)
        assert not np.array_equal(x.prompt, y.prompt)
    assert min(i.t_due for i in items) >= -mix["lead_in_s"]


@pytest.mark.parametrize("name", ["chat-mmwave", "longctx-static"])
def test_lengths_are_clipped(name):
    mix = _mix(name)
    items = loadgen.build(mix, 7, 60, 151936)
    p = np.array([len(i.prompt) for i in items])
    o = np.array([i.max_new for i in items])
    assert p.min() >= mix["prompt_len"]["min"]
    assert p.max() <= mix["prompt_len"]["max"]
    assert o.min() >= mix["output_len"]["min"]
    assert o.max() <= mix["output_len"]["max"]
    assert all(0 <= int(i.prompt.max()) < 151936 for i in items)
    many = loadgen.lengths(mix["prompt_len"], 20000)
    assert many.min() == mix["prompt_len"]["min"]    # the clip binds
    assert many.max() == mix["prompt_len"]["max"]


def test_lengths_are_the_lognormal_quantiles():
    spec = {"median": 100, "sigma": 0.5, "min": 1, "max": 10 ** 6}
    z = statistics.NormalDist().inv_cdf(1 / 6)
    want = [round(100 * np.exp(0.5 * z)), 100, round(100 * np.exp(-0.5 * z))]
    assert list(loadgen.lengths(spec, 3)) == want
    assert np.median(loadgen.lengths(spec, 1001)) == 100


def test_open_loop_rate_and_bursts():
    rng = np.random.default_rng(0)
    t = loadgen.arrivals(2.0, None, 0.0, 5000.0, rng)
    assert len(t) == 10000
    assert np.all(np.diff(t) >= 0) and 0 <= t[0] and t[-1] < 5000.0
    counts = np.histogram(t, bins=2500, range=(0, 5000))[0]
    assert 0.9 < counts.var() / counts.mean() < 1.1        # Poisson
    burst = {"factor": 3.0, "seconds": 2.0, "every_s": 20.0}
    t = loadgen.arrivals(2.0, burst, 0.0, 5000.0, rng)
    assert len(t) == 10000
    assert np.all(np.diff(t) >= 0) and 0 <= t[0] and t[-1] < 5000.0
    counts = np.histogram(t, bins=2500, range=(0, 5000))[0]
    assert counts.var() / counts.mean() > 1.3              # bursty


def test_lumos_copy_matches_the_program():
    from repro.data import lumos5g
    np.testing.assert_array_equal(
        lumos.capacity_traces_bps(20, 300, seed=3),
        lumos5g.capacity_traces_bps(20, 300, seed=3))


def test_mode0_miss_share_matches_the_6ms_arithmetic():
    # qwen2.5-3b mode 0 sends 4,096 B a token; with a 4 ms round trip it
    # misses a 6 ms budget where the link carries less than 4096 / 2 ms
    caps = lumos.capacity_traces_bps(200, 600, seed=0)
    tx = 4096 / caps + 0.004
    share = float(np.mean(tx > 0.006))
    assert share == pytest.approx(float(np.mean(caps < 4096 / 0.002)))
    assert 0.02 < share < 0.07                       # 4.3% at this seed


def test_wall_clock_channel_follows_time():
    now = [100.0]
    ch = loadgen.WallClockChannel(np.array([1.0, 2.0, 3.0]), 0.1, 100.0,
                                  clock=lambda: now[0])
    assert ch.step() == ch.step() == 1.0        # asking twice: same time
    now[0] = 100.15
    assert ch.step() == 2.0
    now[0] = 500.0
    assert ch.step() == 3.0                     # the trace holds its end
    from repro.core.channel import Channel
    sub = loadgen.channel_class(Channel)(np.array([5.0]), 0.1, 0.0)
    assert isinstance(sub, Channel) and sub.step() == 5.0


def test_static_channel_is_one_gbit():
    tr = loadgen.traces(_mix("longctx-static"), 30)
    assert tr.shape == (1, 1) and tr[0, 0] == 125e6
