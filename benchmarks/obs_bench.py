"""Observability benchmark: the tracing-changes-nothing contract at scale.

Serves a mixed-plan request stream (plan="auto": the router sends lanes to
scan / traverse / widen) through the cost-aware scheduler twice — once bare,
once with full observability (lifecycle tracer + calibration telemetry) —
and verifies the contract the obs subsystem is built on:

  1. **bit-identity**: every request's (top-k ids, distances, NDC) is
     byte-equal between the two runs — tracing must never perturb the
     search, only watch it;
  2. **calibration telemetry**: the traced run yields a calibration report
     over ≥ --requests completed queries (predicted-vs-actual quantiles,
     per-plan routing shares and win rates) and a window that survives a
     save/load round trip;
  3. **valid scrape**: `scheduler.prometheus()` passes the strict
     exposition-format validator (no NaN samples, labels well-formed);
  4. **overhead**: interleaved repeated sweeps (U,T,U,T,...) on a smaller
     fixed stream, min-of-N wall time each — the container's noisy-timing
     discipline — must show tracing+calibration total-time overhead under
     5% (and the per-request p99 ratio is recorded alongside);
  5. **sharded**: the same bit-identity + zero-added-dispatch + ≤1.05x
     overhead contract on a 2-shard engine, plus the per-shard EXPLAIN
     sum invariant (section counters == merged counters, exactly) and the
     scheduler's per-shard NDC accounting (gauge totals == stream NDC);
  6. **drift**: the estimator drift monitor stays quiet on a stationary
     continuation of the serve stream and alarms on an injected
     selectivity shift.

Writes `BENCH_obs.json` at the repo root.

    PYTHONPATH=src python -m benchmarks.obs_bench [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

#: total-wall-time overhead gate for the full protocol (min-of-N damps the
#: container's timing noise; quick mode records but does not gate)
OVERHEAD_GATE = 1.05


def serve_stream(mk_sched, reqs):
    """One full serve sweep on fresh request clones; returns
    (scheduler, served requests, wall seconds)."""
    from benchmarks.serve_bench import clone_requests

    sched = mk_sched()
    reqs = clone_requests(reqs)
    t0 = time.perf_counter()
    for r in reqs:
        sched.submit(r, time.perf_counter() - t0)
    sched.run_until_idle(time.perf_counter() - t0)
    return sched, reqs, time.perf_counter() - t0


def assert_bit_identical(a, b):
    by_rid = {r.rid: r for r in a}
    for r in b:
        o = by_rid[r.rid]
        assert np.array_equal(o.res_idx, r.res_idx), f"rid {r.rid}: ids"
        assert np.array_equal(o.res_dist, r.res_dist), f"rid {r.rid}: dists"
        assert o.ndc == r.ndc, f"rid {r.rid}: ndc {o.ndc} != {r.ndc}"


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=512,
                    help="mixed-plan queries for the telemetry run")
    ap.add_argument("--overhead-requests", type=int, default=96,
                    help="stream size for the interleaved overhead timing")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved timing repetitions per arm")
    ap.add_argument("--corpus", type=int, default=6000)
    ap.add_argument("--train-queries", type=int, default=256)
    ap.add_argument("--queue-size", type=int, default=128)
    ap.add_argument("--lane-width", type=int, default=16)
    ap.add_argument("--probe", type=int, default=48)
    ap.add_argument("--alpha", type=float, default=1.5)
    ap.add_argument("--quick", action="store_true",
                    help="small world smoke run (overhead recorded, not "
                         "gated — tiny streams are timing noise)")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: repo-root "
                         "BENCH_obs.json)")
    args = ap.parse_args()
    if args.quick:
        args.requests, args.corpus = 96, 3000
        args.train_queries, args.overhead_requests, args.reps = 128, 48, 1

    from repro.core import fit_planner, generate_plan_training_data
    from repro.data import make_composite_workload
    from repro.launch.serve import build_world
    from repro.obs import CalibrationMonitor, Tracer, validate_prometheus
    from repro.serve import (CostAwareScheduler, ServeConfig,
                             requests_from_workload)

    print("# bring-up (index + graph + estimator + plan router)")
    backend = os.environ.get("REPRO_BACKEND", "dense")
    ds, graph, engine, cfg, est = build_world(
        args.corpus, args.train_queries, args.queue_size, k=10,
        probe=args.probe, backend=backend)
    wl_pl = make_composite_workload(ds, batch=args.train_queries, seed=11,
                                    structure="mixed",
                                    selectivities=(0.01, 0.1, 0.3))
    data = generate_plan_training_data(engine, ds, wl_pl, cfg,
                                       probe_budget=args.probe, chunk=64)
    planner = fit_planner(data, probe_budget=args.probe, n_trees=60, depth=4)

    # composite filters across a selectivity spread keep all three plans in
    # play; the cache is off so every request produces a calibration record
    wl = make_composite_workload(ds, batch=args.requests, seed=500,
                                 structure="mixed",
                                 selectivities=(0.005, 0.05, 0.2, 0.5))
    reqs = requests_from_workload(wl)
    for i, r in enumerate(reqs):
        r.rid = i
    scfg = ServeConfig(lane_width=args.lane_width, buckets=(256, 1024, None),
                       probe_budget=args.probe, alpha=args.alpha,
                       plan="auto", cache_capacity=0,
                       queue_capacity=10 * args.requests)

    def make(tracer=None, calibration=False):
        return lambda: CostAwareScheduler(engine, est, cfg, scfg,
                                          planner=planner, tracer=tracer,
                                          calibration=calibration)

    # -- telemetry sweep: bare vs fully observed, bit-identical ----------
    print(f"# serving {args.requests} mixed-plan requests (bare)")
    s_bare, done_bare, _ = serve_stream(make(), reqs)
    tracer = Tracer()
    print(f"# serving {args.requests} mixed-plan requests (traced)")
    s_obs, done_obs, _ = serve_stream(
        make(tracer=tracer, calibration=True), reqs)
    assert_bit_identical(done_bare, done_obs)
    print(f"# results bit-identical over {len(reqs)} requests")

    calib = s_obs.calibration_report()
    assert calib["n_records"] == len(reqs), (calib["n_records"], len(reqs))
    plans = calib["per_plan"]
    assert len(plans) >= 2, f"stream not mixed-plan: {list(plans)}"
    print("# calibration: log_rmse=%.3f over/under=%.2f/%.2f  plans: %s" % (
        calib["log_rmse"], calib["overprediction_rate"],
        calib["underprediction_rate"],
        " ".join(f"{k}:{v['n']}(win={v['win_rate']:.2f})"
                 for k, v in plans.items())))

    # the frozen-schema window survives persistence (what the future
    # online-recalibration trainer will consume)
    with tempfile.TemporaryDirectory() as tmp:
        path = s_obs.calibration.save(tmp)
        mon2, manifest = CalibrationMonitor.load(path)
        assert len(mon2) == len(reqs) and manifest["sha256"]

    scrape = s_obs.prometheus()
    names = validate_prometheus(scrape)
    print(f"# prometheus scrape: {sum(names.values())} samples / "
          f"{len(names)} metrics — valid")

    span_names = {}
    for sp in tracer.spans():
        span_names[sp.name] = span_names.get(sp.name, 0) + 1
    for needed in ("admit", "probe", "plan-select", "complete"):
        assert needed in span_names, (needed, span_names)
    assert span_names["complete"] == len(reqs)

    # -- sharded arm: the same contract on an index-axis-sharded engine --
    # 2-shard loop-path engine; per-shard EXPLAIN sections must sum
    # EXACTLY to the merged counters, tracing must stay bit-identical with
    # zero added dispatches, and the scheduler's per-shard NDC gauges must
    # account for every distance computation the stream paid.
    print("# sharded arm: 2-shard engine, traced vs bare")
    import dataclasses as _dc

    from repro.core import e2e_search
    from repro.core.search import dispatch_counters
    from repro.core.sharded import ShardedSearchEngine
    from repro.index.builder import build_sharded_graph_index

    sgraph = build_sharded_graph_index(np.asarray(ds.vectors), 2, degree=24,
                                       seed=0)
    eng_s = ShardedSearchEngine.build(ds, sgraph, backend=backend, mesh=None)
    scfg_s = _dc.replace(scfg, plan="traverse")

    def make_s(tracer=None, calibration=False):
        return lambda: CostAwareScheduler(eng_s, est, cfg, scfg_s,
                                          tracer=tracer,
                                          calibration=calibration)

    reqs_s = reqs[: args.overhead_requests]
    d0 = dispatch_counters()
    _, done_s_bare, _ = serve_stream(make_s(), reqs_s)
    d1 = dispatch_counters()
    tr_s = Tracer()
    ss_obs, done_s_obs, _ = serve_stream(
        make_s(tracer=tr_s, calibration=True), reqs_s)
    d2 = dispatch_counters()
    assert_bit_identical(done_s_bare, done_s_obs)
    zero_added = (d2["launches"] - d1["launches"]
                  == d1["launches"] - d0["launches"])
    assert zero_added, (d0, d1, d2)
    sh = ss_obs.summary()["shards"]
    assert sum(sh["ndc_by_shard"]) == sum(r.ndc for r in done_s_obs), (
        sh["ndc_by_shard"], sum(r.ndc for r in done_s_obs))
    assert {sp.attrs["shard"] for sp in tr_s.spans(name="shard-search")} \
        == {0, 1}
    assert tr_s.spans(name="shard-merge")
    validate_prometheus(ss_obs.prometheus())
    print(f"# sharded: bit-identical, ndc_by_shard={sh['ndc_by_shard']} "
          f"(sums to stream NDC), balance={sh['work_balance']:.3f}")

    # per-shard EXPLAIN attribution: every section counter sums exactly to
    # its merged counterpart (the PR-8 accounting contract, surfaced)
    exprs_x = (list(wl.exprs[:8]) if getattr(wl, "exprs", None) is not None
               else wl.spec)
    r_x = e2e_search(eng_s, est, cfg, wl.queries[:8], exprs_x,
                     probe_budget=args.probe, alpha=args.alpha, explain=True)
    hops_x = np.asarray(r_x.state.hops)
    sections_exact = bool(all(
        len(rep.shards) == 2
        and sum(sec.ndc for sec in rep.shards) == rep.actual_ndc
        and sum(sec.hops for sec in rep.shards) == int(hops_x[i])
        for i, rep in enumerate(r_x.reports)))
    assert sections_exact
    print("# sharded: EXPLAIN sections sum exactly to merged counters")

    # interleaved min-of-N overhead on the sharded engine (same protocol
    # and gate as the unsharded arm below)
    sb_t, so_t = [], []
    for _ in range(args.reps):
        _, _, dt = serve_stream(make_s(), reqs_s)
        sb_t.append(dt)
        _, _, dt = serve_stream(make_s(tracer=Tracer(), calibration=True),
                                reqs_s)
        so_t.append(dt)
    sharded_ratio = min(so_t) / max(min(sb_t), 1e-9)
    print(f"# sharded overhead (min of {args.reps}): {sharded_ratio:.3f}x")
    if not args.quick:
        assert sharded_ratio < OVERHEAD_GATE, (
            f"sharded tracing overhead {sharded_ratio:.3f}x exceeds "
            f"{OVERHEAD_GATE}x gate")

    # -- drift arm: stationary continuation quiet, injected shift alarms --
    # Hosted on a fresh traverse-plan sharded scheduler: PSI watches the
    # probe feature distribution, and only traverse/widen records carry
    # probe features (scan lanes never probe — on the auto scheduler both
    # windows would be dominated by feature-less scan rows and PSI would
    # be blind to the shift). The workloads are AND-conjunctions because
    # the per-leaf selectivity band is the controlled knob the per-clause
    # rho features observe directly; the shift collapses the leaf band
    # from σ ∈ 0.2–0.4 to σ ∈ 0.005–0.01 (measured separation: stationary
    # psi_max ≈ 0.2, shifted ≈ 7 — an order of magnitude on each side of
    # the threshold). psi_bins=4 cuts the small-window sampling noise
    # (~bins·(1/n_ref + 1/n_cur)); quick mode compares ~100-row windows.
    print("# drift arm: stationary continuation vs injected selectivity "
          "shift")
    from repro.obs import DriftConfig, DriftMonitor

    dmon = DriftMonitor(DriftConfig(psi_bins=4, psi_threshold=0.5,
                                    win_rate_shift=0.35, rmse_ratio=2.0,
                                    rmse_margin=0.25, min_ref=32,
                                    min_cur=24))
    s_drift = make_s(calibration=True)()
    n_drift = 2 * args.overhead_requests

    def drift_serve(seed, sel, start_rid):
        more = requests_from_workload(
            make_composite_workload(ds, batch=n_drift, seed=seed,
                                    structure="and", selectivities=sel),
            start_rid=start_rid)
        for r in more:
            s_drift.submit(r, 0.0)
        s_drift.run_until_idle(0.0)

    drift_serve(501, (0.2, 0.3, 0.4), 100_000)
    assert dmon.set_reference(s_drift.calibration)
    drift_serve(503, (0.2, 0.3, 0.4), 150_000)
    rep_q = dmon.observe(s_drift.calibration)
    quiet = bool(rep_q["ready"] and not rep_q["alarm"])
    assert quiet, rep_q
    print(f"# drift stationary: quiet (psi_max={rep_q['psi_max']:.3f}, "
          f"n_cur={rep_q['n_cur']})")

    dmon.advance(s_drift.calibration)
    drift_serve(502, (0.005, 0.01), 200_000)
    rep_a = dmon.report(s_drift.calibration)
    alarm = bool(rep_a["alarm"])
    assert alarm, rep_a
    print(f"# drift shifted: ALARM {rep_a['alarms']} "
          f"(psi_max={rep_a['psi_max']:.3f})")
    from repro.obs import prometheus_text
    validate_prometheus(prometheus_text(s_drift.summary(),
                                        s_drift.calibration_report(), rep_a))

    # -- overhead: interleaved min-of-N on a fixed smaller stream --------
    reqs_oh = reqs[: args.overhead_requests]
    bare_t, obs_t = [], []
    bare_p99, obs_p99 = [], []
    for rep in range(args.reps):
        s, _, dt = serve_stream(make(), reqs_oh)
        bare_t.append(dt)
        bare_p99.append(s.summary()["latency"]["p99"])
        s, _, dt = serve_stream(make(tracer=Tracer(), calibration=True),
                                reqs_oh)
        obs_t.append(dt)
        obs_p99.append(s.summary()["latency"]["p99"])
    ratio = min(obs_t) / max(min(bare_t), 1e-9)
    p99_ratio = min(obs_p99) / max(min(bare_p99), 1e-9)
    print(f"# overhead (min of {args.reps}): total {ratio:.3f}x  "
          f"p99 {p99_ratio:.3f}x")
    if not args.quick:
        assert ratio < OVERHEAD_GATE, (
            f"tracing overhead {ratio:.3f}x exceeds {OVERHEAD_GATE}x gate")

    out = dict(
        protocol=dict(requests=args.requests, corpus=args.corpus,
                      lane_width=args.lane_width, probe_budget=args.probe,
                      alpha=args.alpha, backend=backend, plan="auto",
                      queue_size=args.queue_size, quick=bool(args.quick),
                      overhead_requests=args.overhead_requests,
                      reps=args.reps,
                      timing="interleaved min-of-N wall time per arm"),
        results_bit_identical=True,
        calibration=dict(
            n_records=calib["n_records"], log_rmse=calib["log_rmse"],
            mean_log_ratio=calib["mean_log_ratio"],
            overprediction_rate=calib["overprediction_rate"],
            underprediction_rate=calib["underprediction_rate"],
            predicted=calib["predicted"], actual=calib["actual"],
            ratio=calib["ratio"], per_plan=calib["per_plan"]),
        prometheus=dict(valid=True, n_metrics=len(names),
                        n_samples=int(sum(names.values()))),
        spans=dict(n_emitted=tracer.n_emitted, by_name=span_names),
        overhead=dict(total_ratio=ratio, p99_ratio=p99_ratio,
                      gate=OVERHEAD_GATE, gated=not args.quick),
        sharded=dict(
            n_shards=2, bit_identical=True,
            sections_sum_exact=sections_exact,
            zero_added_dispatches=bool(zero_added),
            ndc_by_shard=sh["ndc_by_shard"], ndc_skew=sh["ndc_skew"],
            bitmap_by_shard=sh["bitmap_by_shard"],
            work_balance=sh["work_balance"],
            overhead_ratio=sharded_ratio, gate=OVERHEAD_GATE,
            gated=not args.quick),
        drift=dict(
            quiet_on_stationary=quiet, alarm_on_shift=alarm,
            psi_max_stationary=rep_q["psi_max"],
            psi_max_shift=rep_a["psi_max"], alarms_on_shift=rep_a["alarms"],
            log_rmse_ref=rep_a["log_rmse_ref"],
            log_rmse_shift=rep_a["log_rmse_cur"],
            n_ref=rep_a["n_ref"], n_cur=rep_a["n_cur"],
            window=n_drift),
    )
    path = args.out or os.path.join(os.path.dirname(__file__), "..",
                                    "BENCH_obs.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {os.path.normpath(path)}")


if __name__ == "__main__":
    main()
