"""Arithmetic shared by the per-layer readers: what the traced stretch
processed, tick by tick, worked out from the sessions' own records.

A session admitted at engine tick ``a`` with prompt length ``P`` decodes
its j-th token (j = 0, 1, ..) at tick ``a + j``, attending ``P + j + 1``
rows: sessions are never preempted, so their decode ticks are consecutive.
"""
from __future__ import annotations

from collections import defaultdict

from bench import peaks


def stretch_ticks(ctx):
    """{tick: [context length of each live row]} over the traced stretch's
    ticks, or None without a stretch."""
    st = ctx["stretch"]
    if st is None:
        return None
    t0, t1 = st["on"]["tick"], st["off"]["tick"]
    ticks = defaultdict(list)
    for s in ctx["sessions"]:
        a, P = s.admitted_tick, s.request.prompt_len
        n_dec = len(s.tokens) - 1
        for j in range(max(0, t0 - a), min(n_dec, t1 - a)):
            ticks[a + j].append(P + j + 1)
    return ticks


def stretch_prompts(ctx):
    """Prompt lengths of the sessions prefilled inside the stretch."""
    st = ctx["stretch"]
    t0, t1 = st["on"]["tick"], st["off"]["tick"]
    return [s.request.prompt_len for s in ctx["sessions"]
            if t0 <= s.admitted_tick < t1]


def matmul_params(cfg):
    """Parameters a token's layers multiply by (no embedding, no head)."""
    d, hd, ff = cfg["d_model"], cfg["head_dim"], cfg["d_ff"]
    nq, nkv = cfg["n_heads"], cfg["n_kv_heads"]
    per_layer = d * nq * hd * 2 + 2 * d * nkv * hd + 3 * d * ff
    return cfg["n_layers"] * per_layer


def attention_flops(cfg, rows):
    """q.k and p.v of one query over ``rows`` keys, in every layer."""
    return 4 * cfg["n_heads"] * cfg["head_dim"] * rows * cfg["n_layers"]


def kernel_share(ctx, kernel, calls):
    """Roofline share (%) of ``kernel`` over the stretch: the least time its
    ``calls`` [(flops, bytes)] need at the chip's peaks, over the device
    time the trace gives it. None where it did not run."""
    red = ctx["trace"]
    t = (red or {}).get("kernels", {}).get(kernel, 0.0)
    if not t or not calls:
        return None
    need = sum(peaks.roofline_seconds(f, b, ctx["device_kind"])[0]
               for f, b in calls)
    return 100.0 * need / t
