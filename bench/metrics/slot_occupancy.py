"""engine: live decode slots per decode tick, as a share of the pool
(``decoded_slot_ticks / (decode_ticks * n_slots)``, differences of the
engine's counters), over the part of the window before the profiler
starts, which stalls the host."""


def read(ctx):
    b = ctx["base"]
    w = (ctx["stretch"] or {}).get("pre", ctx["window"])
    ticks = w["decode_ticks"] - b["decode_ticks"]
    if ticks <= 0:
        return None
    return 100.0 * (w["slot_ticks"] - b["slot_ticks"]) / (ticks
                                                          * ctx["n_slots"])
