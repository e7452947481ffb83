"""What the served expert layer did in the traced stretch, tick by tick,
from the sessions' own records, and the expert kernel's device time.

The engine records on each session, per decoded token, the token's rows
routed to held experts (summed over layers) and the tick's count of held
experts that got a row (``Session.moe_rows`` / ``moe_touched``). A
session admitted at tick ``a`` decodes its j-th token at tick ``a + j``
(``layerstats``). A program that records neither gives nothing here.
"""
from __future__ import annotations

from collections import defaultdict

#: the decode window's expert kernel (prefill's is ``moe_gmm_prefill``)
KERNEL = "moe_gmm"


def stretch_ticks(ctx):
    """{tick: (rows routed to held experts, held experts touched)} over
    the traced stretch, or None where there is nothing to read."""
    st = ctx["stretch"]
    if st is None:
        return None
    t0, t1 = st["on"]["tick"], st["off"]["tick"]
    rows, touched = defaultdict(int), {}
    for s in ctx["sessions"]:
        mr, mt = getattr(s, "moe_rows", None), getattr(s, "moe_touched", None)
        if not mr or not mt:
            continue
        a = s.admitted_tick
        for j in range(max(0, t0 - a), min(len(s.tokens) - 1, t1 - a,
                                           len(mr))):
            rows[a + j] += mr[j]
            touched[a + j] = mt[j]
    return {t: (rows[t], touched[t]) for t in touched} or None


def kernel_seconds(red, name: str = KERNEL) -> float:
    """Device seconds of an operation in the stretch: the reduced trace's
    kernel time where it names the kernel, else its entry among the
    longest operations (0.0 when absent)."""
    if not red:
        return 0.0
    t = red.get("kernels", {}).get(name)
    if t:
        return t
    return sum(v for n, v in red.get("breakdown", {}).get("device_ops", [])
               if n == name)
