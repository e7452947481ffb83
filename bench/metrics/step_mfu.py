"""whole step: model FLOPs of the tokens the traced stretch processed, over
its seconds times the chips' peak. A decoded token costs 2 x (layer and
head parameters) plus attention over its live context; a prefilled prompt
costs 2 x layer parameters per token plus causal attention, and one head
row. Recomputed or padded work does not count."""
from bench import layerstats, peaks


def read(ctx):
    red = ctx["trace"]
    ticks = layerstats.stretch_ticks(ctx)
    if not red or ticks is None:
        return None
    c = ctx["cfg"]
    n_mm = layerstats.matmul_params(c)
    head = c["d_model"] * c["vocab_size"]
    flops = 0.0
    for lens in ticks.values():
        for rows in lens:
            flops += 2 * (n_mm + head) + layerstats.attention_flops(c, rows)
    for P in layerstats.stretch_prompts(ctx):
        flops += 2 * n_mm * P + 2 * head
        flops += layerstats.attention_flops(c, P * (P + 1) // 2)
    if flops <= 0:
        return None
    peak = peaks.peak(ctx["device_kind"])["flops_bf16"] * ctx["n_devices"]
    return 100.0 * flops / (red["window_s"] * peak)
