"""Work the one-token paged GQA decode attention needs, from its shapes.

Counted from the algorithm, not from the pages the kernel's grid walks:
each live sequence reads the K and V rows of its live positions once, reads
its query and writes its context. Free slots of the pool need nothing.
"""
from __future__ import annotations


def work(ctx_lens, *, n_q: int, n_kv: int, head_dim: int,
         dtype_bytes: int = 2):
    """(flops, bytes) of one call over live rows with context lengths
    ``ctx_lens`` (positions + 1, the new token's row included)."""
    rows = sum(int(n) for n in ctx_lens)
    n_seq = len(ctx_lens)
    # q.k over every live row and p.v back: 2 flops per multiply-add
    flops = 4 * n_q * head_dim * rows
    kv = 2 * rows * n_kv * head_dim * dtype_bytes
    q_o = 2 * n_seq * n_q * head_dim * dtype_bytes
    return flops, kv + q_o
