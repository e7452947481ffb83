"""model step: device time of the jitted prefill (``mixed_prefill``) per
1,000 true prompt tokens prefilled in the traced stretch."""


def read(ctx):
    red, st = ctx["trace"], ctx["stretch"]
    if not red or st is None:
        return None
    toks = st["off"]["prefill_tokens"] - st["on"]["prefill_tokens"]
    t = red["modules"].get("mixed_prefill", 0.0)
    if toks <= 0 or not t:
        return None
    return 1e3 * t / (toks / 1e3)
