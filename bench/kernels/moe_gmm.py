"""Work the grouped expert kernel (``moe_gmm``) needs in one decode tick,
from its shapes: each row routed to a held expert goes through that
expert's gated MLP (three ``d x f`` matmuls), each held expert that got a
row is read once, and each row's activation is read in the model dtype and
its output written in float32."""
from __future__ import annotations


def work(rows_here: int, touched: int, *, d_model: int, d_ff: int,
         dtype_bytes: int = 2):
    """(flops, bytes) of one tick's calls, summed over the layers:
    ``rows_here`` rows routed to held experts, ``touched`` held experts
    that got at least one."""
    flops = 6 * d_model * d_ff * rows_here
    weights = touched * 3 * d_model * d_ff * dtype_bytes
    acts = rows_here * d_model * (dtype_bytes + 4)
    return flops, weights + acts
