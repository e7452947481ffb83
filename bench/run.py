"""Run one benchmark cell on the chips of this machine and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the repository root. The cell, its configuration and its traffic
mix are found by name (``BENCHMARK.json``, ``bench/configs/``,
``bench/traffic/``). The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device`` and, when
traced, ``breakdown``; the numbers the correctness check compared, each
with its limit (``bench/limits/<cell>.json``; ``served_tokens_compared``
is a lower limit, the others upper ones), come last (``checks``) and are
repeated as the last lines of standard error. The run fails, printing no result, when JAX finds no
TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed once read)")
    args = ap.parse_args(argv)

    import repro  # noqa: F401  (the system under test must be present)
    import jax
    from bench import harness

    bench = harness.load_benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        log(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
        return 2
    cell = cells[args.workload]
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        log(f"needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 3
    from repro.launch.cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfgd = harness.load_config(cell["config"])
    from bench import loadgen
    mix = loadgen.load_mix(cell["traffic"])
    line = harness.run_cell(cell, cfgd, mix, args.seed, args.seconds,
                            bool(args.trace), t_start=T_START, bench=bench,
                            trace_dir=args.trace_dir, log=log)
    for k, v in line["checks"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
