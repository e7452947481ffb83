"""device: share of the traced stretch in which no operation ran on the
device (1 - busy / window, from the profiler's trace)."""


def read(ctx):
    red = ctx["trace"]
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
