"""model step: device time of the decode window program
(``mixed_step_dev``) per decode tick in the traced stretch."""


def read(ctx):
    red, st = ctx["trace"], ctx["stretch"]
    if not red or st is None:
        return None
    ticks = st["off"]["decode_ticks"] - st["on"]["decode_ticks"]
    t = red["modules"].get("mixed_step_dev", 0.0)
    if ticks <= 0 or not t:
        return None
    return 1e3 * t / ticks
