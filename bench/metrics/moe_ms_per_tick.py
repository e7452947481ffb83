"""model step: device time of the grouped expert kernel (``moe_gmm``, all
layers) per decode tick in the traced stretch."""
from bench import moe_stats


def read(ctx):
    red, st = ctx["trace"], ctx["stretch"]
    if not red or st is None:
        return None
    ticks = st["off"]["decode_ticks"] - st["on"]["decode_ticks"]
    t = moe_stats.kernel_seconds(red)
    if ticks <= 0 or not t:
        return None
    return 1e3 * t / ticks
