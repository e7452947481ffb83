"""kernels: share of its roofline that ``paged_attention`` reached in the
traced stretch. The work is the algorithm's (live rows only, see
``bench/kernels/paged_attention.py``), one call per layer per tick."""
from bench import layerstats


def read(ctx):
    ticks = layerstats.stretch_ticks(ctx)
    if not ticks:
        return None
    c = ctx["cfg"]
    work = ctx["kernel_work"]("paged_attention")
    calls = [work(lens, n_q=c["n_heads"], n_kv=c["n_kv_heads"],
                  head_dim=c["head_dim"])
             for lens in ticks.values()] * c["n_layers"]
    return layerstats.kernel_share(ctx, "paged_attention", calls)
