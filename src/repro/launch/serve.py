"""Production serving launcher — thin client of the `repro.serve` subsystem.

Mixed (contain + range) filtered-AKNN requests flow through the cost-aware
scheduler: admission with backpressure → shared probe → GBDT cost estimate →
budget-bucketed micro-batches → resume/requeue on the carried SearchState.
Easy queries complete in short-budget batches instead of waiting on the
hardest lane of a fixed batch; hard queries are routed (or time-sliced) into
long-budget batches. This replaces the old fixed-batch loop whose
`clamp_budgets` call ran *after* the search had already finished — its
output was computed and discarded; budget bounding now happens where it
belongs, in the scheduler's bucket routing, before any resume work runs.

Optionally (--gen-len > 0) the retrieved doc ids condition a tiny decoder LM,
the paper's filtered-RAG deployment story.

    PYTHONPATH=src python -m repro.launch.serve --requests 64 --batch 16
"""
from __future__ import annotations

import argparse
import collections
import os
import time

import numpy as np


def build_world(corpus: int, train_queries: int, queue_size: int, k: int,
                probe: int, backend: str | None, seed: int = 0,
                precision: str = "float32", n_shards: int = 1,
                ds=None, graph=None):
    """Index + graph + engine + a single estimator trained on a *mixed*
    contain/range workload (features are predicate-agnostic, so one GBDT
    serves both request kinds). `precision` deploys the engine with a
    compressed vector store (int8 / pq) — the estimator is then trained on
    the same engine, so its cost model sees compressed-domain probes, and
    the scheduler reranks every finished lane with exact float32.
    `n_shards > 1` deploys an index-axis-sharded engine (core.sharded)
    with one independent graph per corpus slice; the estimator is trained
    on that same sharded engine, so it models the ⌈W/S⌉-split cost.
    `ds` / `graph` deploy a given dataset (e.g. a `data.make_preset`) and
    an already-built graph instead of building them here; `corpus` is then
    ignored."""
    import dataclasses

    from repro.core import (CostEstimator, SearchConfig, SearchEngine,
                            generate_training_data)
    from repro.data import make_dataset, make_label_workload, make_range_workload
    from repro.filters.predicates import PRED_CONTAIN, PRED_RANGE
    from repro.index import build_graph_index

    # equal contiguous slices require S | N
    corpus = -(-corpus // max(n_shards, 1)) * max(n_shards, 1)
    if ds is None:
        ds = make_dataset(n=corpus, dim=48, n_clusters=16, alphabet_size=48,
                          seed=seed)
    if n_shards > 1:
        from repro.core.sharded import ShardedSearchEngine
        from repro.index.builder import build_sharded_graph_index

        if graph is None:
            graph = build_sharded_graph_index(np.asarray(ds.vectors),
                                              n_shards, degree=24, seed=seed)
        engine = ShardedSearchEngine.build(ds, graph, backend=backend,
                                           mesh=None, precision=precision)
    else:
        if graph is None:
            graph = build_graph_index(ds.vectors, degree=24, seed=seed)
        engine = SearchEngine.build(ds, graph, backend=backend,
                                    precision=precision)
    cfg = SearchConfig(k=k, queue_size=queue_size, pred_kind=PRED_CONTAIN)

    half = train_queries // 2
    feats, w_q = [], []
    for kind, pred in (("contain", PRED_CONTAIN), ("range", PRED_RANGE)):
        wl = (make_label_workload(ds, batch=half, kind=kind, seed=7)
              if kind == "contain" else
              make_range_workload(ds, batch=half, seed=8))
        td = generate_training_data(
            engine, ds, wl, dataclasses.replace(cfg, pred_kind=pred),
            probe_budget=probe, chunk=128)
        feats.append(td.features)
        w_q.append(td.w_q)
    est = CostEstimator.fit(np.concatenate(feats), np.concatenate(w_q),
                            n_trees=120, depth=5)
    return ds, graph, engine, cfg, est


def mixed_requests(ds, n: int, seed: int = 100, hard_fraction: float = 0.5,
                   selectivities: tuple = (0.01, 0.05, 0.10, 0.20)):
    """Interleaved contain/range requests (heterogeneous difficulty);
    `selectivities` are the range windows' global selectivities."""
    from repro.data import make_label_workload, make_range_workload
    from repro.serve import requests_from_workload

    wl_c = make_label_workload(ds, batch=(n + 1) // 2, kind="contain",
                               hard_fraction=hard_fraction, seed=seed)
    wl_r = make_range_workload(ds, batch=n // 2, selectivities=selectivities,
                               hard_fraction=hard_fraction, seed=seed + 1)
    reqs = (requests_from_workload(wl_c, start_rid=0)
            + requests_from_workload(wl_r, start_rid=wl_c.batch))
    rng = np.random.default_rng(seed)
    rng.shuffle(reqs)
    return reqs


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16,
                    help="micro-batch lane width")
    ap.add_argument("--alpha", type=float, default=1.5)
    ap.add_argument("--buckets", default="256,1024,4096",
                    help="ascending NDC bucket caps (a final unbounded "
                         "bucket is always appended)")
    ap.add_argument("--policy", default="direct",
                    choices=["direct", "escalate"])
    ap.add_argument("--probe", type=int, default=64)
    ap.add_argument("--queue-capacity", type=int, default=None,
                    help="admission bound; default admits the whole "
                         "--requests stream (pass a smaller value to "
                         "demonstrate load shedding)")
    ap.add_argument("--corpus", type=int, default=6000)
    ap.add_argument("--train-queries", type=int, default=256)
    ap.add_argument("--queue-size", type=int, default=128,
                    help="search beam width M")
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--gen-len", type=int, default=0,
                    help="decode this many tokens per request with a tiny "
                         "LM over the retrieved ids (0 = retrieval only)")
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--precision", default="float32",
                    choices=["float32", "int8", "pq"],
                    help="engine vector-store precision: compressed-domain "
                         "traversal + exact float32 rerank on completion")
    ap.add_argument("--shards", type=int, default=1,
                    help="index-axis shards (>1 deploys core.sharded: "
                         "per-shard traversal at ceil(W/S) budgets + "
                         "cross-shard merge; per-shard skew telemetry "
                         "shows up in --status and --prometheus)")
    ap.add_argument("--status", action="store_true",
                    help="print the structured JSON health report (queue, "
                         "shard skew, calibration, drift alarms) after "
                         "the run")
    ap.add_argument("--explain", type=int, default=0, metavar="N",
                    help="trace request lifecycles and print the first N "
                         "served timelines (admit → queued → probe → "
                         "resume slices → complete), the programs "
                         "compiled while serving, by span, and the host "
                         "seconds of each span name")
    ap.add_argument("--trace-out", default=None,
                    help="stream lifecycle spans to this JSONL file")
    ap.add_argument("--prometheus", action="store_true",
                    help="print a Prometheus text-format scrape (serving + "
                         "calibration metrics) after the run")
    args = ap.parse_args()

    from repro.obs import Tracer
    from repro.serve import CostAwareScheduler, ServeConfig

    print("== index + estimator bring-up")
    ds, graph, engine, cfg, est = build_world(
        args.corpus, args.train_queries, args.queue_size, args.k, args.probe,
        backend=os.environ.get("REPRO_BACKEND", "pallas"),
        precision=args.precision, n_shards=args.shards)
    if args.shards > 1:
        print(f"   index-axis sharded: {engine.n_shards} shards x "
              f"{engine.shard_size} rows")
    if args.precision != "float32":
        from repro.quant import store_ratio

        print(f"   quantized store ({engine.codec_key()}): "
              f"{store_ratio(engine.quant, engine.base_vectors):.1f}x "
              "smaller than float32")

    buckets = tuple(int(x) for x in args.buckets.split(",") if x) + (None,)
    # the launcher submits the whole stream before pumping, so the default
    # admission bound must cover it — otherwise an idle system sheds load
    capacity = (args.queue_capacity if args.queue_capacity is not None
                else max(512, args.requests))
    scfg = ServeConfig(lane_width=args.batch, buckets=buckets,
                       policy=args.policy, probe_budget=args.probe,
                       alpha=args.alpha, queue_capacity=capacity)
    t0 = time.perf_counter()
    # the tracer shares the launcher's relative clock, so span timestamps
    # line up with request arrival/completion times in the timelines below
    tracer = (Tracer(clock=lambda: time.perf_counter() - t0,
                     sink=args.trace_out)
              if (args.explain or args.trace_out) else None)
    sched = CostAwareScheduler(engine, est, cfg, scfg, tracer=tracer)

    print(f"== serving {args.requests} mixed contain/range requests "
          f"(lanes={args.batch}, buckets={buckets}, policy={args.policy})")
    reqs = mixed_requests(ds, args.requests)
    for r in reqs:
        sched.submit(r, time.perf_counter() - t0)
    sched.run_until_idle(time.perf_counter() - t0)

    s = sched.summary()
    lat, ndc = s["latency"], s["ndc"]
    print(f"retrieval: p50/p95/p99 = {1e3*lat['p50']:.1f}/"
          f"{1e3*lat['p95']:.1f}/{1e3*lat['p99']:.1f} ms  "
          f"NDC p50/p95/p99 = {ndc['p50']:.0f}/{ndc['p95']:.0f}/"
          f"{ndc['p99']:.0f}")
    print(f"batches={s['n_batches']} requeues={s['n_requeues']} "
          f"shed={s['n_shed']} cache_hit_rate="
          f"{s['cache']['hit_rate']:.2f} queue_depth_max="
          f"{s['queue_depth_max']} launches={s['launches_total']}")

    rep = sched.calibration_report()
    if rep and rep["n_records"]:
        plans = " ".join(f"{k}:{v['n']}(win={v['win_rate']:.2f})"
                         for k, v in rep["per_plan"].items())
        print(f"calibration: n={rep['n_records']} "
              f"log_rmse={rep['log_rmse']:.3f} over/under="
              f"{rep['overprediction_rate']:.2f}/"
              f"{rep['underprediction_rate']:.2f}  {plans}")

    if args.explain:
        print(f"== lifecycle timelines (first {args.explain} requests)")
        for r in reqs[: args.explain]:
            print(f"request {r.rid} [{r.trace_id}] "
                  f"plan={r.plan or 'traverse'} budget={r.budget} "
                  f"ndc={r.ndc} probe_ndc={r.probe_ndc} "
                  f"slices={r.n_slices} cache_hit={r.cache_hit}")
            for sp in tracer.spans(trace_id=r.trace_id):
                extras = "".join(f"  {k}={v}" for k, v in sp.attrs.items()
                                 if k != "rid")
                t = (f" (+{1e3 * sp.duration:.1f}ms)"
                     if sp.duration > 0 else "")
                print(f"  {1e3 * (sp.t0 - (r.arrival or 0.0)):8.1f}ms "
                      f"{sp.name}{t}{extras}")
        comp = tracer.spans(name="compile")
        by_span = collections.Counter(sp.attrs["inside"] or "-"
                                      for sp in comp)
        print(f"== programs compiled while serving: {tracer.n_compiles} "
              f"({sum(sp.attrs['seconds'] for sp in comp):.3f} s); by "
              "span: " + (", ".join(f"{k} {v}" for k, v in
                                    by_span.most_common()) or "none"))
        secs, n = collections.Counter(), collections.Counter()
        for sp in tracer.spans():
            if sp.duration > 0:
                secs[sp.name] += sp.duration
                n[sp.name] += 1
        print("== host seconds by span over the run (nested spans overlap):"
              " " + ", ".join(f"{k} {v:.3f} s/{n[k]}"
                              for k, v in secs.most_common()))
    if tracer is not None:
        tracer.close()

    if args.status:
        import json

        print("== serving health")
        print(json.dumps(sched.status(), indent=2, sort_keys=True))

    if args.prometheus:
        print("== prometheus scrape")
        print(sched.prometheus(), end="")

    if args.gen_len > 0:
        _generate(args, reqs)


def _generate(args, reqs):
    """Filtered-RAG tail: retrieved ids condition a tiny decoder LM."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.models import build_model, split_tree
    from repro.models.transformer import _pad_cache_seq

    mcfg = get_arch(args.arch).tiny()
    model = build_model(mcfg)
    prm, _ = split_tree(model.init_params(jax.random.key(0)))
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)

    done = [r for r in reqs if r.res_idx is not None]
    if not done:
        print("generation: skipped (no served requests)")
        return
    b = len(done)
    doc_ids = np.stack([np.abs(r.res_idx) % mcfg.vocab_size for r in done])
    prompts = np.random.default_rng(0).integers(0, mcfg.vocab_size, (b, 8))
    tokens = jnp.asarray(np.concatenate([doc_ids, prompts], axis=1),
                         jnp.int32)
    t0 = time.perf_counter()
    logits, part = prefill(prm, {"tokens": tokens})
    cache, _ = split_tree(model.init_cache(b, tokens.shape[1] + args.gen_len))
    cache = _pad_cache_seq(cache, part)
    cur = jnp.argmax(logits[:, -1, :], -1)[:, None].astype(jnp.int32)
    pos = jnp.full((b,), tokens.shape[1], jnp.int32)
    for t in range(args.gen_len - 1):
        logits, cache = decode(prm, cache, cur, pos + t, None)
        cur = jnp.argmax(logits[:, -1, :], -1)[:, None].astype(jnp.int32)
    jax.block_until_ready(cur)
    dt = time.perf_counter() - t0
    print(f"generation: {1e3*dt/b:.1f} ms/req ({args.gen_len} tokens)")


if __name__ == "__main__":
    main()
