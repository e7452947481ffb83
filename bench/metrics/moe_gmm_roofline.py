"""kernels: share of its roofline that the grouped expert kernel
(``moe_gmm``) reached in the traced stretch's decode windows: each tick
reads the held experts that got a row and multiplies the rows routed to
them (``bench/kernels/moe_gmm.py``), counted from the sessions' records."""
from bench import moe_stats, peaks


def read(ctx):
    ticks = moe_stats.stretch_ticks(ctx)
    t = moe_stats.kernel_seconds(ctx["trace"])
    if not ticks or not t:
        return None
    c = ctx["cfg"]
    work = ctx["kernel_work"]("moe_gmm")
    need = sum(peaks.roofline_seconds(
        *work(rows, touched, d_model=c["d_model"], d_ff=c["d_ff"]),
        ctx["device_kind"])[0] for rows, touched in ticks.values())
    return 100.0 * need / t
