"""Work the fused decode tail (final norm, LM head, argmax) needs, from its
shapes: the head is read once per call, each live row's activation is read
and one int32 token is written per row."""
from __future__ import annotations


def work(n_rows: int, *, d_model: int, vocab: int, dtype_bytes: int = 2):
    """(flops, bytes) of one call over ``n_rows`` live rows."""
    flops = 2 * n_rows * d_model * vocab
    head = d_model * vocab * dtype_bytes
    rows = n_rows * (d_model * dtype_bytes + 4)
    return flops, head + rows + d_model * dtype_bytes
