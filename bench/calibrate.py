"""Calibration runs of one cell that share one process's set-up: the sweep
of offered rates that finds an open-loop mix's knee, and the readings of the
correctness check over many seeds: the program's line and, with
``--control``, the float8 control's line judged by the same limits. Not
part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        (--rates r1,r2,.. --seed <n> | --seeds s1,s2,.. [--control]) \
        --out <file.json>
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def backlog(res):
    """Queue depth at the window's end and its least-squares slope
    (requests per second) over the window."""
    import numpy as np
    t0 = res["base"]["t"]
    q = [(t - t0, n) for t, n in res["queue"] if t >= t0]
    if len(q) < 2:
        return {"queue_end": q[-1][1] if q else 0, "queue_slope": 0.0}
    t, n = np.array(q, float).T
    return {"queue_end": int(n[-1]), "queue_mean": float(n.mean()),
            "queue_slope": float(np.polyfit(t, n, 1)[0])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    from bench import harness, loadgen
    from repro.launch.cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        log("calibration runs only on a TPU")
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = harness.load_benchmark()
    cell = {c["name"]: c for c in bench["workloads"]}[args.workload]
    cfgd = harness.load_config(cell["config"])
    mix = loadgen.load_mix(cell["traffic"])
    out = []
    warm = True
    if args.rates:
        for r in [float(x) for x in args.rates.split(",")]:
            m = dict(mix, rate_per_s=r)
            st = harness.setup(cfgd, m, args.seed, warm_up=warm, log=log)
            warm = False
            res = harness.measure(st, m, args.seed, args.seconds, False,
                                  t_start=time.monotonic(), log=log)
            rec = {"rate_per_s": r, **backlog(res),
                   "engine": res["engine_stats"],
                   "metrics": {k: v[0] for k, v in
                               harness.end_to_end(res).items()},
                   "due": sum(1 for t in res["due"].values()
                              if res["base"]["t"] <= t < res["window"]["t"])}
            rec["engine"].pop("mode_counts", None)
            log(json.dumps({k: rec[k] for k in rec if k != "engine"}))
            out.append(rec)
            st.clear()
            del res
            gc.collect()
    for seed in [int(x) for x in args.seeds.split(",") if x]:
        t_s = time.monotonic()
        st = harness.setup(cfgd, mix, seed, warm_up=warm, log=log)
        warm = False
        res = harness.measure(st, mix, seed, args.seconds, False,
                              t_start=t_s, log=log)
        params = st.pop("params")
        st.clear()
        gc.collect()
        for line in harness.finish(cell, res, params, False, bench,
                                   args.control, seed, log):
            line["seed"] = seed
            line["parked"] = res["engine_stats"].get("requests_parked")
            log(json.dumps(line))
            out.append(line)
        del params, res
        gc.collect()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
