#!/usr/bin/env python3
"""Readings that set a cell's correctness limits from above, on the chip.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \
        --seconds 51 --requests 400

For each seed: the cell's deployment and the first `--requests` requests
its window would submit, in the order the seed draws as a run draws it;
then,
put in the program's place and judged by the cell's comparison:

  control    the reference computed one precision below the
             configuration's (`"control"` in its config file)
  truncated  the reference's own answers with the second half of every
             row dropped (-1 padded): an answer altered where it is made

Prints one JSON line per seed with both sets of numbers and whether each
passes the cell's limits. The program is not run: its own readings come
from the benchmark's runs. The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seed: int, seconds: float, n_req: int) -> dict:
    from bench.lib import compare, data, harness
    from bench.lib.reference import Reference
    from bench.lib.traffic import TrafficGen

    cfg, k = cell.config, cell.config["search"]["k"]
    dep = data.generate(cfg["data"], cfg["deployment_seed"])
    batch, _ = harness.window_requests(cell.traffic, TrafficGen(
        cell.traffic, dep), cfg["deployment_seed"], seed, seconds)
    take = np.arange(min(n_req, len(batch.exprs)))
    q, filt = batch.queries[take], batch.filters.take(take)
    ref = Reference(dep.vectors, dep.labels_packed, dep.values)
    ref_ids, ref_d = ref.search(q, filt, k)
    cut = ref_ids.copy()
    cut[:, k // 2:] = -1
    cut_d = np.where(cut >= 0, ref_d, np.inf)
    c_ids, c_d = ref.search(q, filt, k, mode=cfg["control"])
    out = {}
    for name, (ids, dist) in (("control", (c_ids, c_d)),
                              ("truncated", (cut, cut_d))):
        true_d, ok = ref.score(q, ids, filt)
        nums, _ = compare.numbers(ids, dist, true_d, ok, ref_ids, ref_d,
                                  dep.n, 0)
        passes, _ = compare.judge(nums, cell.limits)
        out[name] = dict(nums, passes_limits=passes)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--requests", type=int, default=400)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.lib import manifest

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    cell = manifest.load_cell(args.workload, ROOT)
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        print(json.dumps({"seed": seed, "control_mode": cell.config[
            "control"], **readings(cell, seed, args.seconds,
                                   args.requests)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
