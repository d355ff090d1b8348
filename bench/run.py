#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's deployment from its configuration's fixed seed, warms
up, serves the window through the program's `CostAwareScheduler` with
the deployment's requests in the order `--seed` draws, checks every
completed request against the plain reference, and prints one JSON
object as the last line of standard output. With `--trace 0` its metrics are the
cell's end-to-end metrics, with `--trace 1` its per-layer metrics.
Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib import harness

    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), root=ROOT)
    except harness.NoDevice as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
