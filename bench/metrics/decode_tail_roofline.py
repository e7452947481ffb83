"""kernels: share of its roofline that the fused ``decode_tail`` (final
norm, LM head, argmax) reached in the traced stretch; one call per tick
over the live rows (``bench/kernels/decode_tail.py``)."""
from bench import layerstats


def read(ctx):
    ticks = layerstats.stretch_ticks(ctx)
    if not ticks:
        return None
    c = ctx["cfg"]
    work = ctx["kernel_work"]("decode_tail")
    calls = [work(len(lens), d_model=c["d_model"], vocab=c["vocab_size"])
             for lens in ticks.values()]
    return layerstats.kernel_share(ctx, "decode_tail", calls)
