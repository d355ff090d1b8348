"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = measured
per-query wall time where the benchmark is timed; 0 for accuracy-only
tables). Full JSON dumps land in experiments/bench_results.json.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _row(name, us, **derived):
    d = ";".join(f"{k}={v}" for k, v in derived.items())
    print(f"{name},{us:.1f},{d}", flush=True)


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig3,fig56,table3,fig7,fig8,fig910")
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: backend-throughput benchmark only "
                         "(N=100k, B=64, warmup + best-of-3 timing)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    if args.quick:
        from benchmarks import quick

        rows = quick.run()
        for r in rows:
            _row(r["name"], r["latency_us_per_query"],
                 **{k: (round(v, 4) if isinstance(v, float) else v)
                    for k, v in r.items()
                    if k not in ("name", "latency_us_per_query")})
        out = os.path.join(os.path.dirname(__file__), "..", "experiments",
                           "bench_quick.json")
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(rows, f, indent=2, default=str)
        return

    def want(x):
        return only is None or x in only

    from benchmarks import (fig3_misalignment, fig56_tradeoff, fig7_equality,
                            fig8_importance, fig910_acorn, table3_estimator)
    from benchmarks.common import get_bench

    all_results = []
    t_start = time.time()

    if want("fig3"):
        for r in fig3_misalignment.run():
            _row(r["name"], 0.0,
                 spearman_rho_sigma=round(r["spearman_rho_sigma"], 3),
                 mean_abs_log_ratio=round(r["mean_abs_log_ratio"], 3),
                 frac_gt_10x_off=round(r["frac_gt_10x_off"], 3))
            all_results.append({k: v for k, v in r.items()
                                if k not in ("rho_local", "sigma_global")})

    bench_specs = [("tripclick-s", "contain"), ("tripclick-s", "equal"),
                   ("msmarco-s", "range")]
    benches = {}
    for preset, kind in bench_specs:
        benches[(preset, kind)] = get_bench(preset, kind)

    if want("fig56"):
        for key, bench in benches.items():
            curves = fig56_tradeoff.run(bench)
            for c in curves:
                _row(c["name"], c["latency_ms_per_query"] * 1e3,
                     recall=round(c["recall"], 4), ndc=round(c["ndc"], 1))
            all_results.extend(curves)
            for variant in ("e2e", "e2e_quantile"):
                sp = fig56_tradeoff.speedup_at_matched_recall(curves, variant)
                if sp:
                    best = max(sp.values())
                    _row(f"fig56_{key[0]}_{key[1]}_{variant}_speedup", 0.0,
                         max_ndc_speedup_vs_naive=round(best, 2),
                         at_recalls=";".join(f"{r}:{round(s,2)}"
                                             for r, s in sorted(sp.items())))

    if want("table3"):
        for key, bench in benches.items():
            for r in table3_estimator.run(bench):
                _row(r["name"], 0.0, log_rmse=r["log_rmse"], r2=r["r2"],
                     spearman=r["spearman"])
                all_results.append(r)

    if want("fig7"):
        for r in fig7_equality.run():
            _row(r["name"], 0.0, **{k: round(v, 3) for k, v in r.items()
                                    if k != "name"})
            all_results.append(r)

    if want("fig8"):
        for key, bench in benches.items():
            for r in fig8_importance.run(bench):
                _row(r["name"], 0.0,
                     filter_features_in_top8=r["filter_features_in_top8"],
                     top3=";".join(f"{n}:{round(v,2)}" for n, v in r["top8"][:3]))
                all_results.append(r)

    if want("fig910"):
        for r in fig910_acorn.run():
            _row(r["name"], 0.0, **{k: round(v, 3) for k, v in r.items()
                                    if k != "name"})
            all_results.append(r)

    out = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "bench_results.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(all_results, f, indent=2, default=str)
    print(f"# total benchmark wall time: {time.time()-t_start:.0f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
