"""Planner benchmark: adaptive per-query routing across filter-execution
plans, swept over filter selectivity.

For each target global selectivity σ (conjunction workloads, 0.005 → 0.5)
this runs every single-plan strategy and the planner:

  scan       pre-filter: bitmap + masked exact top-k over the valid set —
             recall 1.0 by construction, NDC = σ_q·N exactly
  traverse   the standard E2E pipeline (probe → GBDT budget → resume)
  widen      filtered-expansion traversal (1-hop ∪ strided 2-hop frontier)
  planner    two-stage per-lane routing: exact-σ stage 0 (free scan
             dispatch), shared probe + cost heads for the rest

and reports per-plan recall (vs the brute-force oracle), mean NDC, and the
planner's chosen-plan histogram per sweep point.

Acceptance bars (recorded under "checks" in BENCH_planner.json):
  * at every swept selectivity, planner mean NDC ≤ 1.05 × the best
    single plan's (routing never costs more than 5% over the per-workload
    winner it is supposed to find);
  * on the σ ≈ 0.009 conjunction workload (the filter-algebra bench's
    "and" shape), planner NDC is ≥ 10× below standard traversal at
    recall ≥ 0.93 (the crossover the planning layer exists to exploit —
    stage 0 routes these lanes to scan with zero probe overhead).

    PYTHONPATH=src python -m benchmarks.planner_bench [--quick]

--quick shrinks the world for the ci.sh smoke and does not overwrite
BENCH_planner.json (the bars are printed but only enforced at full scale —
at N=3000 the scan/traversal crossover itself shrinks below 10×).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

PLAN_NAMES = ("scan", "traverse", "widen")


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--corpus", type=int, default=12000)
    ap.add_argument("--train-queries", type=int, default=384)
    ap.add_argument("--eval-queries", type=int, default=96)
    ap.add_argument("--queue-size", type=int, default=512)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--probe", type=int, default=64)
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--sweep", default="0.005,0.01,0.05,0.1,0.2,0.5",
                    help="target global selectivities (conjunctions)")
    ap.add_argument("--quick", action="store_true",
                    help="small world for the ci.sh smoke run")
    ap.add_argument("--out", default=None,
                    help="explicit output JSON path — written even with "
                         "--quick (an explicit path never clobbers the "
                         "committed artifact)")
    args = ap.parse_args()
    if args.quick:
        args.corpus, args.train_queries = 3000, 96
        args.eval_queries, args.queue_size = 24, 128
        args.sweep = "0.01,0.1,0.5"

    from repro.core import (SearchConfig, SearchEngine, fit_planner,
                            generate_plan_training_data, planned_search,
                            run_plan)
    from repro.data import make_composite_workload, make_dataset
    from repro.index import build_graph_index, filtered_knn_exact
    from repro.index.bruteforce import recall_at_k

    backend = os.environ.get("REPRO_BACKEND", "dense")
    print(f"# bring-up: corpus={args.corpus} backend={backend}")
    ds = make_dataset(n=args.corpus, dim=48, n_clusters=16, alphabet_size=48,
                      seed=0)
    graph = build_graph_index(ds.vectors, degree=24, seed=0)
    engine = SearchEngine.build(ds, graph, backend=backend)
    cfg = SearchConfig(k=args.k, queue_size=args.queue_size)

    # One planner for the whole sweep: cost heads trained on a
    # mixed-structure workload (dual-exhaustion labels for traverse AND
    # widen from one shared probe per query)
    print("# plan training data (dual exhaustion) + planner fit")
    t0 = time.time()
    wl_tr = make_composite_workload(ds, batch=args.train_queries,
                                    structure="mixed", seed=10)
    td = generate_plan_training_data(engine, ds, wl_tr, cfg,
                                     probe_budget=args.probe, chunk=96)
    planner = fit_planner(td, probe_budget=args.probe, n_trees=150, depth=5)
    print(f"#   {time.time()-t0:.0f}s, converged: "
          f"traverse={td.converged_t.mean():.2f} "
          f"widen={td.converged_w.mean():.2f}")

    def evaluate(queries, filters, gt_idx):
        """Planner + every single plan on one workload → result row."""
        auto = planned_search(engine, planner, cfg, queries, filters,
                              probe_budget=args.probe, alpha=args.alpha)
        hist = np.bincount(np.asarray(auto.plan), minlength=3)
        singles = {}
        for p in PLAN_NAMES:
            st = run_plan(engine, planner, p, cfg, queries, filters,
                          probe_budget=args.probe, alpha=args.alpha)
            singles[p] = dict(
                recall=float(recall_at_k(
                    np.asarray(st.res_idx), gt_idx).mean()),
                mean_ndc=float(np.asarray(st.cnt, np.int64).mean()))
        auto_row = dict(
            recall=float(recall_at_k(
                np.asarray(auto.state.res_idx), gt_idx).mean()),
            mean_ndc=float(np.asarray(auto.state.cnt, np.int64).mean()),
            plan_hist={PLAN_NAMES[i]: int(hist[i]) for i in range(3)},
            pre_probe_frac=float(np.asarray(auto.pre_probe).mean()))
        return dict(planner=auto_row, singles=singles)

    def range_workload(target, seed):
        """Queries from the corpus + per-query Range windows of exact width
        `target` on the empirical value CDF — selectivity controlled
        directly, which composite label leaves cannot reach at the high end
        (their σ saturates near the label marginals)."""
        from repro.filters import Range

        rng = np.random.default_rng(seed)
        src = rng.integers(0, ds.n, size=args.eval_queries)
        queries = (ds.vectors[src]
                   + 0.05 * rng.standard_normal(
                       (args.eval_queries, ds.dim)).astype(np.float32))
        vals = np.sort(ds.value_matrix[:, 0])
        exprs = []
        for _ in range(args.eval_queries):
            lo_q = rng.uniform(0.0, 1.0 - target)
            lo = float(np.quantile(vals, lo_q))
            hi = float(np.quantile(vals, lo_q + target))
            exprs.append(Range(lo, hi))
        sigma = float(np.mean([((ds.value_matrix[:, 0] >= e.lo)
                                & (ds.value_matrix[:, 0] <= e.hi)).mean()
                               for e in exprs]))
        return queries.astype(np.float32), exprs, sigma

    # ---------------------------------------------------- selectivity sweep
    sweep = tuple(float(x) for x in args.sweep.split(","))
    sweep_rows = []
    for si, target in enumerate(sweep):
        queries, exprs, sigma = range_workload(target, seed=100 + si)
        gt_idx, _ = filtered_knn_exact(queries, ds.vectors, exprs,
                                       ds.labels_packed, ds.value_matrix,
                                       args.k)
        row = dict(target_sigma=target, sigma_global_mean=sigma,
                   **evaluate(queries, exprs, gt_idx))
        best_p = min(row["singles"], key=lambda p: row["singles"][p]["mean_ndc"])
        best = row["singles"][best_p]["mean_ndc"]
        row["best_single"] = best_p
        row["planner_vs_best_ndc"] = row["planner"]["mean_ndc"] / max(best, 1.0)
        sweep_rows.append(row)
        h = row["planner"]["plan_hist"]
        print(f"σ≈{row['sigma_global_mean']:.4f} (target {target}): "
              f"planner NDC={row['planner']['mean_ndc']:.0f} "
              f"recall={row['planner']['recall']:.3f} "
              f"best single={best_p}({best:.0f}) "
              f"ratio={row['planner_vs_best_ndc']:.3f} "
              f"hist scan/trav/widen={h['scan']}/{h['traverse']}/{h['widen']}")

    # ------------------------- selective-conjunction bar (σ ≈ 0.009 shape)
    wl_sel = make_composite_workload(ds, batch=args.eval_queries,
                                     structure="and", seed=99)
    gt_sel, _ = filtered_knn_exact(wl_sel.queries, ds.vectors, wl_sel.exprs,
                                   ds.labels_packed, ds.value_matrix, args.k)
    sel = dict(sigma_global_mean=float(np.mean(wl_sel.sigma_global)),
               **evaluate(wl_sel.queries, wl_sel.filters, gt_sel))
    trav = sel["singles"]["traverse"]
    speedup = trav["mean_ndc"] / max(sel["planner"]["mean_ndc"], 1.0)
    print(f"selective conjunctions σ≈{sel['sigma_global_mean']:.4f}: "
          f"planner NDC={sel['planner']['mean_ndc']:.0f} "
          f"recall={sel['planner']['recall']:.3f} vs standard traversal "
          f"NDC={trav['mean_ndc']:.0f} → {speedup:.1f}× reduction")

    checks = dict(
        within_5pct_of_best_single=bool(
            all(r["planner_vs_best_ndc"] <= 1.05 for r in sweep_rows)),
        worst_ratio_vs_best_single=float(
            max(r["planner_vs_best_ndc"] for r in sweep_rows)),
        selective_sigma=sel["sigma_global_mean"],
        selective_speedup_vs_traverse=float(speedup),
        selective_recall=sel["planner"]["recall"],
        selective_bar_ok=bool(speedup >= 10.0
                              and sel["planner"]["recall"] >= 0.93),
    )
    print(f"# checks: {checks}")

    out = dict(
        protocol=dict(corpus=args.corpus, dim=48,
                      train_queries=args.train_queries,
                      eval_queries=args.eval_queries,
                      queue_size=args.queue_size, k=args.k,
                      probe_budget=args.probe, alpha=args.alpha,
                      backend=backend, sweep=list(sweep),
                      quick=bool(args.quick),
                      ndc_accounting="cnt includes probe distances for "
                                     "traverse/widen and for planner lanes "
                                     "that probed; scan pays none"),
        planner=dict(n_train=int(td.features.shape[0]),
                     converged_traverse=float(td.converged_t.mean()),
                     converged_widen=float(td.converged_w.mean()),
                     scan_floor=planner.scan_floor),
        sweep=sweep_rows,
        selective_conjunctions=sel,
        checks=checks,
    )
    path = args.out or os.path.join(os.path.dirname(__file__), "..",
                                    "BENCH_planner.json")
    if args.out or not args.quick:  # smoke must not clobber the artifact
        with open(path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {os.path.normpath(path)}")
    if not args.quick:
        if not (checks["within_5pct_of_best_single"]
                and checks["selective_bar_ok"]):
            raise SystemExit("planner acceptance bars FAILED (see checks)")


if __name__ == "__main__":
    main()
