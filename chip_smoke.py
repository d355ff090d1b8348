#!/usr/bin/env python3
"""On-chip smoke run of the filtered-search serving path.

    python chip_smoke.py               # one TPU chip: phases 0-2
    python chip_smoke.py --four-chips  # four chips: sharded mesh vs loop

Runs in one process from start to end (a chip belongs to one process).

  phase 0  the device: a TPU, or exit non-zero before anything runs.
  phase 1  the served pipeline at the paper's YouTube shape (128-d, audio
           tags): host NN-descent graph, W_q labels, GBDT estimator and
           plan router, then `CostAwareScheduler(plan="auto")` serving
           mixed contain/range requests on `pallas` and `pallas_persistent`
           at float32 and int8. Checked against the exact filtered k-NN
           oracle and against the `dense` backend on the same chip.
  phase 2  the paper's YouTube scale resident on the chip: 1,048,576 x
           128 with the preset's labels and a random-regular R=32 graph,
           traversal on every backend and one scan-routed batch.

Earlier lines report versions, seconds (cold = compile + run, warm = run
again), device bytes and agreement numbers. The last line is one JSON
object naming the device, printed only when every check passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

K, QUEUE, DEGREE, DIM = 10, 128, 32, 128
# phase 1 corpus rows: as many as the host NN-descent builder takes in
# about two minutes on the chip host's shared cores
SERVED_N = 8192
# agreement with the dense backend on the same chip, judged per lane on
# the lanes a traversal kernel served (scan lanes run the same scan kernel
# on every backend, so they would only compare it with itself): every such
# lane returns dense's top-10 ids. The MXU contraction and the XLA einsum
# may round distances differently, but every distance is computed at
# Precision.HIGHEST, and every chip run so far agreed on every lane.
MIN_LANE_OVERLAP = 1.0
MAX_RECALL_GAP = 0.01
SERVED_SELECTIVITIES = (0.05, 0.2, 0.5)
# the routed pipeline on the NN-descent graph (0.995 on the CPU at N=8k)
SERVED_MIN_RECALL = 0.9


def log(msg):
    print(msg, flush=True)


def check(ok, what):
    """Every check is fatal: no phase's failure is passed over."""
    if not ok:
        log(f"FAIL {what}")
        sys.exit(1)
    log(f"pass {what}")


def timed(fn):
    t = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t


def device_bytes(devices):
    stats = [d.memory_stats() or {} for d in devices]
    return ([s.get("bytes_in_use", 0) for s in stats],
            [s.get("peak_bytes_in_use", 0) for s in stats])


def lane_overlap(ids, ref):
    """Per lane, the fraction of the reference's valid top-k ids that
    `ids` holds (1.0 where the reference holds none)."""
    out = np.ones(len(ref))
    for i, (a, b) in enumerate(zip(ids, ref)):
        want = {int(x) for x in b if x >= 0}
        if want:
            out[i] = len(want & {int(x) for x in a}) / len(want)
    return out


def agreement(name, ids, ref_ids, gt, lanes=None, ref_name="dense",
              min_recall=None):
    """Recall against the oracle `gt`, and per-lane top-k overlap with
    `ref_ids` on the lanes selected by the boolean mask `lanes` (all
    lanes by default)."""
    from repro.index.bruteforce import recall_at_k

    rec = float(recall_at_k(ids, gt).mean())
    rec_ref = float(recall_at_k(ref_ids, gt).mean())
    ov = lane_overlap(ids, ref_ids)
    if lanes is not None:
        ov = ov[lanes]
    log(f"  {name}: recall@{K}={rec:.4f} ({ref_name} {rec_ref:.4f}) "
        f"top-{K} overlap with {ref_name} on {ov.size} lanes: min "
        f"{ov.min(initial=1.0):.4f} mean {ov.mean() if ov.size else 1.0:.4f}")
    check(ov.size > 0 and ov.min() >= MIN_LANE_OVERLAP
          and abs(rec - rec_ref) <= MAX_RECALL_GAP,
          f"{name} agrees with {ref_name} (overlap >= {MIN_LANE_OVERLAP} "
          f"on each of {ov.size} lanes, |recall gap| <= {MAX_RECALL_GAP})")
    if min_recall is not None:
        check(rec >= min_recall and rec_ref >= min_recall,
              f"{name} recall@{K} >= {min_recall} against the oracle")


def exact_topk(ds, queries, filters):
    from repro.index import filtered_knn_exact

    gt, _ = filtered_knn_exact(queries, np.asarray(ds.vectors), filters,
                               np.asarray(ds.labels_packed),
                               np.asarray(ds.value_matrix), K)
    return gt


# --------------------------------------------------------------- phase 1 ----

def serve_once(engine, est, cfg, planner, reqs, scfg):
    from repro.core.search import dispatch_counters
    from repro.serve import CostAwareScheduler

    sched = CostAwareScheduler(engine, est, cfg, scfg, planner=planner)
    d0 = dispatch_counters()["bodies"]
    t0 = time.perf_counter()
    for r in reqs:
        sched.submit(r, time.perf_counter() - t0)
    sched.run_until_idle(time.perf_counter() - t0)
    dt = time.perf_counter() - t0
    d1 = dispatch_counters()["bodies"]
    bodies = {k: v - d0.get(k, 0) for k, v in d1.items() if v > d0.get(k, 0)}
    done = sorted(reqs, key=lambda r: r.rid)
    ids = np.stack([r.res_idx for r in done])
    lane_plans = np.array([r.plan or "traverse" for r in done])
    return ids, lane_plans, bodies, dt


def plan_mix(lane_plans):
    names, counts = np.unique(lane_plans, return_counts=True)
    return dict(zip(names.tolist(), counts.tolist()))


def phase_served(n, n_requests, train_queries, probe, seed):
    from repro.core import fit_planner, generate_plan_training_data
    from repro.data import make_composite_workload, make_preset
    from repro.index import build_graph_index
    from repro.kernels.ops import interpret_mode
    from repro.launch.serve import build_world, mixed_requests
    from repro.serve import ServeConfig

    log(f"== phase 1: served pipeline, youtube-s at d={DIM}, N={n}")
    ds, dt = timed(lambda: make_preset("youtube-s", dim=DIM, n=n))
    log(f"  dataset {n} x {ds.dim}, {ds.alphabet_size} tags: {dt:.1f}s")
    log(f"  N={n} is the host NN-descent builder's limit in a smoke run: "
        "its time grows faster than N (23 s at 4,096 rows, 54 s at 8,192, "
        "133 s at 16,384 on 8 idle cores; about twice that on a chip "
        "host's shared cores)")
    graph, dt = timed(lambda: build_graph_index(ds.vectors, degree=DEGREE,
                                                seed=seed))
    log(f"  NN-descent graph R={DEGREE}: {dt:.1f}s on the host "
        f"({os.cpu_count()} cores)")

    # range windows wide enough that the router keeps some lanes on the
    # graph: at this N it scans every lane whose sigma*N is below ~3k
    def requests():
        return mixed_requests(ds, n_requests, seed=100,
                              selectivities=SERVED_SELECTIVITIES)

    reqs0 = sorted(requests(), key=lambda r: r.rid)   # serve_once's order
    q = np.stack([r.query for r in reqs0])
    gt = exact_topk(ds, q, [r.get_expr() for r in reqs0])
    auto = ServeConfig(lane_width=16, buckets=(256, 1024, 4096, None),
                       probe_budget=probe, alpha=1.5, plan="auto",
                       cache_capacity=0, queue_capacity=4 * n_requests)
    widen = dataclasses.replace(auto, plan="widen")

    for precision in ("float32", "int8"):
        log(f" -- {precision}")
        (_, _, engine, cfg, est), dt = timed(lambda: build_world(
            n, train_queries, QUEUE, K, probe, backend="pallas",
            seed=seed, precision=precision, ds=ds, graph=graph))
        log(f"  engine + W_q labels + GBDT estimator: {dt:.1f}s")

        def fit():
            wl = make_composite_workload(ds, batch=train_queries, seed=11,
                                         structure="mixed",
                                         selectivities=(0.005, 0.05, 0.2))
            data = generate_plan_training_data(engine, ds, wl, cfg,
                                               probe_budget=probe, chunk=64)
            log("  plan labels: W_traverse p10/p50/p90 "
                f"{np.percentile(data.w_traverse, [10, 50, 90])}, W_widen "
                f"{np.percentile(data.w_widen, [10, 50, 90])}")
            return fit_planner(data, probe_budget=probe, n_trees=60,
                               depth=4)

        planner, dt = timed(fit)
        log(f"  plan router (traverse / widen / scan heads): {dt:.1f}s")

        ids, ids_w, routed = {}, {}, {}
        for backend in ("dense", "pallas", "pallas_persistent"):
            eng = dataclasses.replace(engine, backend=backend)
            cold = serve_once(eng, est, cfg, planner, requests(), auto)
            warm = serve_once(eng, est, cfg, planner, requests(), auto)
            ids[backend], routed[backend], bodies, _ = warm
            plans = plan_mix(routed[backend])
            log(f"  {backend} plan=auto: {n_requests} requests cold "
                f"{cold[3]:.2f}s warm {warm[3]:.2f}s plans={plans}")
            # the router picks widen only where its head predicts it
            # cheaper than traversal; the forced widen plan runs that body
            ids_w[backend], plans_w, bodies_w, t_w = serve_once(
                eng, est, cfg, planner, requests(), widen)
            plans_w = plan_mix(plans_w)
            log(f"  {backend} plan=widen: {n_requests} requests cold "
                f"{t_w:.2f}s plans={plans_w}")
            check(set(plans) | set(plans_w) == {"scan", "traverse", "widen"},
                  f"phase 1 {precision} {backend}: scan, traverse and widen "
                  "all served")
            if backend == "pallas_persistent":
                # the persistent driver counts its launches per body
                launches = {**bodies, **bodies_w}
                log(f"    bodies counted by dispatch_counters: {launches}")
                check(bodies.get("post:kernel", 0) > 0
                      and bodies_w.get("widen:xla", 0) > 0,
                      f"phase 1 {precision} {backend}: traverse ran the "
                      "persistent kernel, widen the launch loop")
            else:
                # not counted: they follow from interpret_mode(), which is
                # False on a TPU (pallas: fused_step kernel per step)
                step = ("fused_step kernel" if backend == "pallas"
                        else "XLA step loop")
                log(f"    bodies, not counted (interpret_mode() "
                    f"{interpret_mode()}): traverse and widen {step}, "
                    "scan sqdist_masked kernel")
        for backend in ("pallas", "pallas_persistent"):
            graph_lanes = routed[backend] != "scan"
            log(f"  {backend}: {int((routed[backend] != routed['dense']).sum())}"
                " lanes routed differently from dense")
            agreement(f"phase 1 {precision} {backend} plan=auto, "
                      "traversal lanes", ids[backend], ids["dense"], gt,
                      lanes=graph_lanes, min_recall=SERVED_MIN_RECALL)
            agreement(f"phase 1 {precision} {backend} plan=widen",
                      ids_w[backend], ids_w["dense"], gt)


# --------------------------------------------------------------- phase 2 ----

def phase_resident(n, batch, budget, seed):
    import jax

    from repro.core import SearchConfig, SearchEngine
    from repro.core.plans import scan_search, scan_stats
    from repro.data import make_preset, make_range_workload
    from repro.index.graph import GraphIndex, random_regular_neighbors
    from repro.launch.serve import mixed_requests

    log(f"== phase 2: resident state, youtube-s at N={n} x d={DIM}")
    ds, dt = timed(lambda: make_preset("youtube-s", dim=DIM, n=n))
    log(f"  dataset with preset labels: {dt:.1f}s")
    graph = GraphIndex(neighbors=random_regular_neighbors(
        n, DEGREE, np.random.default_rng(seed)), entry_point=0, dim=DIM)
    log(f"  graph: random-regular R={DEGREE} (the host NN-descent builder "
        "cannot build 1M rows inside a smoke run; recall here measures "
        "agreement, not the index)")
    nw = (n + 31) // 32
    log(f"  visited bitset per lane: {nw} words ({4 * nw} B); persistent "
        f"kernel VMEM per 8-lane block: {8 * 4 * (-(-nw // 128) * 128)} B "
        "per buffer")

    reqs = sorted(mixed_requests(ds, batch, seed=100), key=lambda r: r.rid)
    q = np.stack([r.query for r in reqs])
    filt = [r.get_expr() for r in reqs]
    gt, dt = timed(lambda: exact_topk(ds, q, filt))
    log(f"  exact filtered top-{K} oracle for {batch} queries: {dt:.1f}s")

    dev = jax.devices()[:1]
    for precision in ("float32", "int8"):
        engine, dt = timed(lambda: SearchEngine.build(
            ds, graph, backend="dense", precision=precision))
        jax.block_until_ready(engine.neighbors)
        used, peak = device_bytes(dev)
        log(f" -- {precision}: engine build {dt:.1f}s, device bytes in use "
            f"{used[0]} (peak {peak[0]})")
        cfg = SearchConfig(k=K, queue_size=QUEUE)
        ids = {}
        for backend in ("dense", "pallas", "pallas_persistent"):
            c = dataclasses.replace(cfg, backend=backend)

            def run():
                st = engine.search(c, q, filt, budget)
                st = engine.rerank(c, q, st)
                return np.asarray(st.res_idx), np.asarray(st.cnt)

            (_, _), t_cold = timed(run)
            (ids[backend], cnt), t_warm = timed(run)
            log(f"  {backend}: B={batch} budget={budget} cold {t_cold:.2f}s "
                f"warm {t_warm:.2f}s mean NDC {cnt.mean():.0f}")
            check(bool((cnt > 0).all()), f"phase 2 {precision} {backend} "
                  "traversal ran on every lane")
        for backend in ("pallas", "pallas_persistent"):
            agreement(f"phase 2 {precision} {backend}", ids[backend],
                      ids["dense"], gt)
        del engine

    # one scan-routed batch: sigma ~ 0.005, about 5,000 gathered rows a lane
    engine = SearchEngine.build(ds, graph, backend="pallas")
    wl = make_range_workload(ds, batch=16, selectivities=(0.005,), seed=7)
    prog = engine.compile(wl.spec)
    stats = scan_stats(engine, prog)
    cfg = SearchConfig(k=K, queue_size=QUEUE)

    def scan():
        st = scan_search(engine, cfg, wl.queries, wl.spec, stats=stats)
        return np.asarray(st.res_idx)

    _, t_cold = timed(scan)
    got, t_warm = timed(scan)
    log(f"  scan: sigma*N per lane {int(stats.counts.min())}.."
        f"{int(stats.counts.max())}, cold {t_cold:.2f}s warm {t_warm:.2f}s")
    gt_s = exact_topk(ds, wl.queries, wl.spec)
    agreement("phase 2 scan (sqdist_masked)", got, gt_s, gt_s,
              ref_name="exact oracle")
    used, peak = device_bytes(dev)
    log(f"  device bytes in use {used[0]}, peak {peak[0]}")


# ------------------------------------------------------------ four chips ----

def phase_four_chips(ns, batch, budget, seed):
    import jax

    from repro.core import SearchConfig
    from repro.core.sharded import ShardedSearchEngine
    from repro.data.synthetic import AttributedDataset
    from repro.filters.predicates import PRED_RANGE, FilterSpec
    from repro.index.graph import (GraphIndex, ShardedGraphIndex,
                                   random_regular_neighbors)

    devs = jax.devices()
    s = 4
    n = s * ns
    log(f"== four chips: {s} shards of {ns} x {DIM}, mesh vs loop path")
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    vectors = rng.standard_normal((n, DIM), dtype=np.float32)
    vectors /= np.linalg.norm(vectors, axis=1, keepdims=True)
    ds = AttributedDataset(
        name="four_chips", vectors=vectors,
        labels_packed=np.zeros((n, 1), np.uint32), label_sets=[],
        values=rng.random(n).astype(np.float32), alphabet_size=1,
        cluster_ids=np.zeros(n, np.int32))
    sg = ShardedGraphIndex(shards=[
        GraphIndex(neighbors=random_regular_neighbors(ns, DEGREE, rng),
                   entry_point=0, dim=DIM, shard=i, offset=i * ns)
        for i in range(s)])
    log(f"  random vectors, range values and random-regular R={DEGREE} "
        f"graphs: {time.perf_counter() - t0:.1f}s")
    q = (vectors[rng.integers(0, n, batch)]
         + 0.05 * rng.standard_normal((batch, DIM)).astype(np.float32))
    spec = FilterSpec(PRED_RANGE, None, np.full(batch, 0.2, np.float32),
                      np.full(batch, 0.8, np.float32))
    cfg = SearchConfig(k=K, queue_size=QUEUE, backend="pallas")

    for precision in ("float32", "int8"):
        log(f" -- {precision}")
        out = {}
        for path, mesh in (("loop", None), ("mesh", "auto")):
            eng, dt = timed(lambda: ShardedSearchEngine.build(
                ds, sg, mesh=mesh, precision=precision))
            homes = sorted({d.id for e in eng.shards
                            for d in e.neighbors.devices()})
            log(f"  {path}: per-shard engines on device(s) {homes}")
            if eng.mesh is not None:
                eng._stacked_arrays()
                log(f"  mesh axes {dict(eng.mesh.shape)}")
            _, t_cold = timed(lambda: jax.block_until_ready(
                eng.search(cfg, q, spec, budget).merged))
            st, t_warm = timed(lambda: jax.block_until_ready(
                eng.search(cfg, q, spec, budget).merged))
            used, _ = device_bytes(devs)
            log(f"  {path}: build {dt:.1f}s search cold {t_cold:.2f}s warm "
                f"{t_warm:.2f}s; bytes in use per device {used}")
            out[path] = st
            del eng
        a, b = out["loop"], out["mesh"]
        for f in ("res_idx", "cand_idx", "cnt", "n_inspected", "hops"):
            check(np.array_equal(np.asarray(getattr(a, f)),
                                 np.asarray(getattr(b, f))),
                  f"four chips {precision}: mesh {f} == loop {f}")
        for f in ("res_dist", "cand_dist"):
            x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
            fin = np.isfinite(x)
            ulps = np.abs(x[fin].view(np.int32).astype(np.int64)
                          - y[fin].view(np.int32))
            log(f"  {f}: max |mesh - loop| = {int(ulps.max(initial=0))} ulp")
            check(np.array_equal(fin, np.isfinite(y))
                  and np.all(np.abs(x[fin] - y[fin]) <= np.spacing(x[fin])),
                  f"four chips {precision}: {f} within 1 ulp")


# ------------------------------------------------------------------ main ----

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded mesh-vs-loop comparison on "
                         "four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    # phase 0: a TPU, or stop before anything runs
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"FAIL phase 0: no TPU: JAX found {devs[0].platform!r} devices "
            f"({len(devs)}); this smoke run measures nothing elsewhere")
        sys.exit(2)
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        log(f"FAIL phase 0: {want} TPU chips needed, JAX found {len(devs)}")
        sys.exit(2)

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jaxlib
    log(f"jax {jax.__version__} jaxlib {jaxlib.__version__} python "
        f"{sys.version.split()[0]}; {len(devs)} x {devs[0].device_kind}; "
        f"compile cache {cache}")
    t_all = time.perf_counter()
    if args.four_chips:
        phase_four_chips(ns=1 << 20, batch=64, budget=4000, seed=args.seed)
    else:
        phase_served(SERVED_N, n_requests=64, train_queries=256,
                     probe=64, seed=args.seed)
        phase_resident(1 << 20, batch=64, budget=4000, seed=args.seed)
    log(f"all phases passed in {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
