"""Plain references, one module per model family. A configuration names
its module by its ``"reference"`` key; ``load`` finds it here by that name.

Each module gives ``shapes(cfg)`` (the weight tree ``weights.make`` draws),
``run(...)`` (the forward pass the correctness check compares with),
``matmul_params(cfg)`` and ``attention_flops(cfg, rows)`` (the work a token
does in it) and ``READS`` (the configuration keys it reads that are not the
program's ``ModelConfig`` fields).
"""
from __future__ import annotations

import importlib
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str):
    """The reference module ``bench/references/<name>.py``; raises on a
    name that has none. Imported once, so its jitted functions compile
    once."""
    if not os.path.exists(os.path.join(HERE, name + ".py")) \
            or not name.isidentifier():
        raise ValueError(f"unknown reference {name!r}: no "
                         f"bench/references/{name}.py")
    return importlib.import_module(f"{__name__}.{name}")
