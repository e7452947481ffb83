"""Lumos5G-shaped mmWave uplink capacity traces.

A copy of the arithmetic of ``repro.data.lumos5g`` (the synthetic twin of
the Lumos5G throughput walk: beam-coverage zones on a 1300 m loop, a
LoS/NLoS blockage chain and TCP-like smoothing, resampled per UE), so that
the benchmark's traffic cannot move when the program's data module does.
The draws are made in the same order, so the same seed gives the same
series as the original.
"""
from __future__ import annotations

import numpy as np

SAMPLE_SECONDS = 1.0


def _smooth_field(n_knots: int, length: int, rng, amp: float = 1.0):
    knots = rng.normal(0, amp, n_knots)
    xs = np.linspace(0, 1, length, endpoint=False)
    field = np.zeros(length)
    for k, a in enumerate(knots):
        field += a * np.cos(2 * np.pi * (k + 1) * xs
                            + rng.uniform(0, 2 * np.pi))
    return field / np.sqrt(n_knots)


def throughput_series_mbps(n_seconds: int, seed: int = 0) -> np.ndarray:
    """Perceived mmWave throughput walk [n_seconds] in Mbps at 1 Hz."""
    rng = np.random.default_rng(seed)
    total = n_seconds + 1 + 1          # n_samples + seq_len + 1, seq_len 1
    speed = np.clip(1.4 + 0.6 * _smooth_field(8, total, rng)
                    + 0.2 * rng.normal(0, 1, total), 0.0, 4.0)
    frac = (np.cumsum(speed) % 1300.0) / 1300.0
    rng.normal(0, 1, total)            # longitude jitter
    rng.normal(0, 1, total)            # latitude jitter
    beam = _smooth_field(12, 4096, rng, amp=1.2)
    beam_at = beam[(frac * 4096).astype(int) % 4096]
    blocked = np.zeros(total, bool)
    b = False
    for t in range(total):
        b = (rng.random() < 0.25) if b else (rng.random() < 0.02)
        blocked[t] = b
    rng.normal(0, 2, total)            # NR RSRP noise
    rng.normal(0, 1, total)            # NR RSRQ noise
    nr_snr = 18 + 8 * beam_at - 18 * blocked + rng.normal(0, 1.5, total)
    for _ in range(3):                 # LTE RSRP / RSRQ / SNR
        _smooth_field(6, total, rng)
        rng.normal(0, 1, total)
    tput = np.clip(
        900 + 550 * beam_at - 820 * blocked - 60 * (speed - 1.4)
        + 12 * (nr_snr - 18) + 80 * rng.normal(0, 1, total), 1.0, 2200.0)
    for t in range(1, total):
        tput[t] = 0.7 * tput[t - 1] + 0.3 * tput[t]
    return tput[:n_seconds].astype(np.float32).astype(np.float64)


def capacity_traces_bps(n_ues: int, n_ticks: int, *,
                        tick_seconds: float = 0.1, seed: int = 0,
                        stagger_seconds: float = 30.0) -> np.ndarray:
    """Per-UE capacity traces [n_ues, n_ticks] in bytes/second: windows of
    one long walk at random start times, linearly interpolated to ticks."""
    need = int(np.ceil(n_ticks * tick_seconds / SAMPLE_SECONDS)) + 2
    total = max(2 * need, int(np.ceil(stagger_seconds / SAMPLE_SECONDS))
                * min(n_ues, 128) + need)
    series = throughput_series_mbps(total, seed=seed)
    rng = np.random.default_rng(seed + 1)
    offsets = rng.uniform(0.0, (total - need) * SAMPLE_SECONDS, size=n_ues)
    t = offsets[:, None] + np.arange(n_ticks) * tick_seconds
    mbps = np.interp(t.ravel(), np.arange(total) * SAMPLE_SECONDS, series)
    return mbps.reshape(n_ues, n_ticks) * 1e6 / 8.0
