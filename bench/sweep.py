"""Offered-rate sweep of an open-loop cell, to find the highest rate the
system sustains (run once when a cell's rate is chosen; the benchmark's
runs never search).

    python3 bench/sweep.py --workload <open-loop cell> --seed <n> \
        --seconds 20 --rates 3,4,5,6

One set-up, then one window per rate; prints per rate the offered and
completed requests, latency p50/p95 (s), the queue left at the close and
how long draining it took.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import run


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    c = run.load_cell(run.ROOT, args.workload)
    run.find_device(c["cell"]["chips"])
    run.enable_cache(run.ROOT)
    sys.path.insert(1, os.path.join(run.ROOT, "src"))
    import numpy as np
    from harness import traffic
    from harness.serve import Window
    spec, mix = c["spec"], c["mix"]
    family = run.load_module(os.path.join(c["bench_dir"], "configs",
                                          spec["family"] + ".py"), "family")
    prog = run.Program(c, args.seed, family)
    engine = prog.engine(mix["max_batch"])
    run.warm_up(engine, family, mix, spec, args.seed,
                traffic.requests(mix, spec, args.seed, 1))
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        due = traffic.arrivals(m, args.seconds)
        reqs = traffic.requests(m, spec, args.seed + i, len(due),
                                rid0=100000 * (i + 1))
        win = Window(engine, family.request, reqs, due, args.seconds)
        win.run()
        backlog = len(engine.queue)
        t = time.perf_counter()
        win.drain(True)
        lat = np.array([r["ready"] - r["due"] for r in win.rec.values()
                        if "ready" in r])
        print(json.dumps({"rate_per_s": rate, "offered": len(reqs),
                          "completed": len(lat), "failed": win.failed,
                          "p50_s": float(np.percentile(lat, 50)),
                          "p95_s": float(np.percentile(lat, 95)),
                          "backlog_at_close": backlog,
                          "drain_s": time.perf_counter() - t,
                          "batches": len(win.batches)}), flush=True)


if __name__ == "__main__":
    main()
