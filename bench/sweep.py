#!/usr/bin/env python3
"""Find the knee of a cell's mix on this machine's chip, once.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --outstanding 128 --rates 100,200,300

Builds the cell's deployment once, warms up, then serves the cell's mix
closed-loop at each `--outstanding` depth (saturated throughput), and
open-loop at each offered rate in `--rates` (latency against load).
Prints one JSON line per setting. The rate a cell offers is written into
its traffic file as a number; this only finds it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--outstanding", default="128")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.lib import harness, manifest
    from bench.lib.traffic import TrafficGen, gaps

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    harness.enable_compile_cache(ROOT)
    cell = manifest.load_cell(args.workload, ROOT)
    w = harness.build_world(cell.config)
    gen = TrafficGen(cell.traffic, w.dep)
    harness.warm_up(w, gen, cell.traffic, cell.config["deployment_seed"],
                    args.seed)
    rng = np.random.default_rng([args.seed, 3])
    for depth in [int(x) for x in args.outstanding.split(",") if x]:
        n = depth + int(50 * args.seconds)
        _, _, done_at = harness.serve_closed(
            harness.new_scheduler(w), gen.batch(n, rng), depth, args.seconds)
        qps = float((done_at <= args.seconds).sum() / args.seconds)
        print(json.dumps({"loop": "closed", "outstanding": depth,
                          "qps": qps}), flush=True)
    for rate in [float(x) for x in args.rates.split(",") if x]:
        n = int(round(rate * args.seconds))
        due = np.cumsum(gaps(n, rate, rng))
        reqs = harness.make_requests(gen.batch(n, rng))
        done_at, late = harness.serve_open(harness.new_scheduler(w), reqs,
                                           due, args.seconds)
        lat = np.where(np.isfinite(done_at), done_at - due, np.inf)
        print(json.dumps({
            "loop": "open", "rate_qps": rate,
            "qps": float((done_at <= args.seconds).sum() / args.seconds),
            "p50_ms": 1e3 * float(np.quantile(lat, 0.5)),
            "p95_ms": 1e3 * float(np.quantile(lat, 0.95)),
            "late_max_ms": 1e3 * float(late.max(initial=0))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
