"""One run of one cell: build the deployment from its configuration's
fixed seed, warm up, serve the window with the requests in the order the
run's seed draws, check every answer against the plain reference, and
report.

This is the only module of the benchmark that imports the program, and
only what is under test: `SearchEngine`'s build (through `build_world`'s
training path), the planner's fitting, the filter expressions, and the
`CostAwareScheduler` that serves the window.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench.lib import compare, data, graph, manifest, peaks
from bench.lib import trace as tracelib
from bench.lib.reference import Reference
from bench.lib.traffic import (TrafficGen, block_order, closed_pool, gaps,
                                recall_requests)

DRAIN_S = 60.0
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def deep_merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = (deep_merge(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path:
    JAX_COMPILATION_CACHE_DIR if set, else `<checkout>/.jax_cache`."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts programs lowered and compiled while `on`."""

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event in COMPILE_EVENTS:
            self.count += 1
            self.seconds += duration


@dataclasses.dataclass
class World:
    dep: data.Deployment
    engine: object
    cfg: object            # SearchConfig
    est: object            # CostEstimator
    planner: object
    scfg: object           # ServeConfig
    split: dict            # set-up seconds per part


def build_world(cfg: dict) -> World:
    """Data, graph, engine, estimator and planner of one configuration,
    drawn from its fixed `deployment_seed`."""
    from repro.core import fit_planner, generate_plan_training_data
    from repro.data import make_composite_workload
    from repro.data.synthetic import AttributedDataset
    from repro.index.graph import GraphIndex
    from repro.launch.serve import build_world as program_world
    from repro.serve import ServeConfig

    split = {}
    seed = cfg["deployment_seed"]
    t = time.perf_counter()
    dep = data.generate(cfg["data"], seed)
    split["data"] = time.perf_counter() - t

    t = time.perf_counter()
    g = cfg["graph"]
    nbrs, entry = graph.build(dep.vectors, g["degree"], g["alpha"],
                              g["random"], data.key_from_seed(seed, 1))
    split["graph"] = time.perf_counter() - t

    t = time.perf_counter()
    ds = AttributedDataset(
        name=cfg["name"], vectors=dep.vectors,
        labels_packed=dep.labels_packed,
        label_sets=data.LabelSets(dep.labels_packed), values=dep.values,
        alphabet_size=dep.alphabet_size, cluster_ids=dep.cluster_ids)
    gi = GraphIndex(neighbors=nbrs, entry_point=entry, dim=dep.vectors.shape[1])
    s, tr = cfg["search"], cfg["training"]
    _, _, engine, scfg_search, est = program_world(
        dep.n, tr["estimator_queries"], s["queue_size"], s["k"],
        cfg["serve"]["probe_budget"], backend=tr["backend"],
        precision=cfg["precision"], ds=ds, graph=gi)
    split["estimator"] = time.perf_counter() - t

    t = time.perf_counter()
    wl = make_composite_workload(ds, batch=tr["planner_queries"], seed=11,
                                 structure="mixed",
                                 selectivities=tuple(
                                     tr["planner_selectivities"]))
    pdata = generate_plan_training_data(
        engine, ds, wl, scfg_search,
        probe_budget=cfg["serve"]["probe_budget"], chunk=tr["chunk"])
    planner = fit_planner(pdata, probe_budget=cfg["serve"]["probe_budget"],
                          n_trees=tr["planner_trees"],
                          depth=tr["planner_depth"])
    split["planner"] = time.perf_counter() - t

    engine = dataclasses.replace(engine, backend=s["backend"])
    sv = dict(cfg["serve"])
    sv["buckets"] = tuple(sv["buckets"])
    return World(dep=dep, engine=engine, cfg=scfg_search, est=est,
                 planner=planner, scfg=ServeConfig(**sv), split=split)


def make_requests(batch, start_rid: int = 0):
    from repro.serve import Request

    return [Request(rid=start_rid + i, query=batch.queries[i],
                    expr=batch.exprs[i]) for i in range(len(batch.exprs))]


def new_scheduler(w: World):
    from repro.serve import CostAwareScheduler

    return CostAwareScheduler(w.engine, w.est, w.cfg, w.scfg,
                              planner=w.planner)


def _close(on_close) -> float:
    """Run `on_close` (None: nothing); returns the seconds it took, which
    the drain's limit does not count."""
    if on_close is None:
        return 0.0
    t = time.perf_counter()
    on_close()
    return time.perf_counter() - t


def serve_closed(sched, batch, outstanding: int, until: float | None,
                 annotate=None, on_close=None, finish: int = 0,
                 start_rid: int = 0):
    """Closed loop over the P requests of `batch`: `outstanding` in
    flight, the next submitted as one completes, until `until` seconds,
    then `on_close()` once, between pumps, and the drain of what is in
    flight. A timed loop replays the batch: submission j serves entry
    j mod P, as a new request with its own rid, so the loop ends at the
    close and never because the batch ran out. During the drain it still
    submits, up to the first `finish` submissions and only those. An
    untimed one (`until` None) serves the batch once. Returns (the
    requests submitted, in order; the seconds each was submitted at; the
    seconds each completed at, nan where it did not)."""
    from repro.serve import Request

    ann = annotate or _no_annotation
    n = len(batch.exprs)
    subs, sent_at, done_at = [], [], []

    def wanted(open_):
        j = len(subs)
        return j < finish or (open_ and (until is not None or j < n))

    t0 = time.perf_counter()
    inflight = 0
    grace = 0.0
    while True:
        now = time.perf_counter() - t0
        open_ = until is None or now < until
        if not open_:
            grace += _close(on_close)
            on_close = None
        while inflight < outstanding and wanted(open_):
            i = len(subs) % n
            req = Request(rid=start_rid + len(subs), query=batch.queries[i],
                          expr=batch.exprs[i])
            with ann("bench.submit"):
                sched.submit(req, now)
            subs.append(req)
            sent_at.append(now)
            done_at.append(np.nan)
            inflight += 1
        if sched.has_work():
            with ann("bench.pump"):
                done, _ = sched.pump(now)
            t = time.perf_counter() - t0
            for r in done:
                done_at[r.rid - start_rid] = t
            inflight -= len(done)
        elif not wanted(open_):
            break
        if until is not None and now > until + DRAIN_S + grace:
            break
    return subs, np.asarray(sent_at), np.asarray(done_at, np.float64)


def serve_open(sched, reqs, due, seconds: float, annotate=None,
               on_close=None):
    """Open loop: each request submitted when it falls due (by the wall
    clock), stamped with its due time; `on_close()` once, between pumps,
    when `seconds` have passed. Returns (completion seconds, lateness of
    each submission)."""
    ann = annotate or _no_annotation
    done_at = np.full(len(reqs), np.nan)
    late = np.zeros(len(reqs))
    t0 = time.perf_counter()
    i = 0
    grace = 0.0
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            grace += _close(on_close)
            on_close = None
        while i < len(reqs) and due[i] <= now:
            with ann("bench.submit"):
                sched.submit(reqs[i], float(due[i]))
            late[i] = now - due[i]
            i += 1
        if sched.has_work():
            with ann("bench.pump"):
                done, _ = sched.pump(now)
            t = time.perf_counter() - t0
            for r in done:
                done_at[r.rid - reqs[0].rid] = t
        elif i < len(reqs):
            wait = due[i] - (time.perf_counter() - t0)
            if wait > 2e-3:
                time.sleep(wait - 1e-3)
        else:
            break
        if now > seconds + DRAIN_S + grace:
            break
    return done_at, late


def _no_annotation(name):
    return contextlib.nullcontext()


def ordered(gen: TrafficGen, n: int, pool_rng, rng):
    """n requests drawn from the deployment's `pool_rng`, in the order
    that the run's `rng` draws within blocks of the mix's
    `order_block`."""
    return gen.batch(n, pool_rng).take(
        block_order(n, int(gen.mix["order_block"]), rng))


def warm_up(w: World, gen: TrafficGen, mix: dict, pool_seed: int,
            seed: int) -> int:
    """Serve the mix's warm-up requests closed-loop at each [depth, count]
    of `warmup`, then, for an open mix, `warmup_open_seconds` of open-loop
    arrivals at its rate, so the lane widths, program shapes and scan
    widths the window meets are compiled first. The requests come from
    `pool_seed`, their order from `seed`."""
    pool = np.random.default_rng([pool_seed, 1])
    rng = np.random.default_rng([seed, 1])
    total = 0
    for depth, count in mix["warmup"]:
        serve_closed(new_scheduler(w), ordered(gen, int(count), pool, rng),
                     int(depth), None, start_rid=10_000_000 + total)
        total += int(count)
    sec = float(mix.get("warmup_open_seconds", 0))
    if sec > 0:
        n = int(round(mix["rate_qps"] * sec))
        reqs = make_requests(ordered(gen, n, pool, rng),
                             start_rid=10_000_000 + total)
        serve_open(new_scheduler(w), reqs,
                   np.cumsum(gaps(n, mix["rate_qps"], rng)), sec)
        total += n
    return total


def device_info(jax, memory_peak: int) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(memory_peak)}


def memory_peak(jax) -> int:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in jax.devices()]
    return max(peaks_) if peaks_ else 0


def window_requests(mix: dict, gen: TrafficGen, pool_seed: int, seed: int,
                    seconds: float):
    """(requests the window may submit, their due times or None): the
    deployment's requests, drawn from `pool_seed`, in the order and with
    the arrival gaps that `seed` draws."""
    pool = np.random.default_rng([pool_seed, 2])
    rng = np.random.default_rng([seed, 2])
    due = None
    if mix["loop"] == "open":
        n = int(round(mix["rate_qps"] * seconds))
        due = np.cumsum(gaps(n, mix["rate_qps"], rng))
    else:
        n = closed_pool(mix, seconds)
    return ordered(gen, n, pool, rng), due


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        root: Path = manifest.ROOT, allow_cpu: bool = False,
        overrides: dict | None = None) -> dict:
    """One run; returns the result object (the last line of stdout)."""
    t_start = time.perf_counter()
    cell = manifest.load_cell(cell_name, root)
    cfg = deep_merge(cell.config, (overrides or {}).get("config", {}))
    mix = deep_merge(cell.traffic, (overrides or {}).get("traffic", {}))
    closed = mix["loop"] == "closed"
    n_recall = recall_requests(mix, seconds) if closed else None
    if closed and cfg["serve"].get("cache_capacity") != 0:
        raise ValueError("a closed loop replays its pool, so the result "
                         "cache, keyed on the query, has to be off "
                         "(serve.cache_capacity 0)")
    import jax

    devs = jax.devices()
    if not allow_cpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {len(devs)} "
                       f"{devs[0].platform!r} device(s)")
    if len(devs) < cell.chips:
        raise NoDevice(f"{cell.chips} chips needed, JAX found {len(devs)}")
    cache = enable_compile_cache(root) if not allow_cpu else "off"
    kind = devs[0].device_kind
    if not allow_cpu:
        peaks.peaks_for(kind)          # an unknown chip is an error
    log(f"cell {cell_name} seed {seed} seconds {seconds} trace {int(trace)}"
        f" on {len(devs)} x {kind}; jax {jax.__version__}; compile cache "
        f"{cache}")
    counter = CompileCounter()

    w = build_world(cfg)
    gen = TrafficGen(mix, w.dep)
    t = time.perf_counter()
    n_warm = warm_up(w, gen, mix, cfg["deployment_seed"], seed)
    w.split["warmup"] = time.perf_counter() - t

    t = time.perf_counter()
    batch, due = window_requests(mix, gen, cfg["deployment_seed"], seed,
                                 seconds)
    n = len(batch.exprs)
    reqs = None if closed else make_requests(batch)
    sched = new_scheduler(w)
    w.split["requests"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    log("set-up split (s): " + json.dumps(
        {k: round(v, 3) for k, v in w.split.items()})
        + f"; warm-up requests {n_warm}; setup_s {setup_s:.3f}")

    from repro.core.search import dispatch_counters

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    annotate = None
    if trace:
        annotate = jax.profiler.TraceAnnotation
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    bodies0 = dispatch_counters()["bodies"]
    counter.on = True
    # At the window's close the window's span ends and the trace stops:
    # the drain that follows is outside the window, and tracing it only
    # makes the trace longer to write and read.
    stopped = {}

    def stop_trace():
        stopped["s"] = _close(jax.profiler.stop_trace)

    closing = contextlib.ExitStack()
    if trace:
        closing.callback(stop_trace)
    closing.enter_context((annotate or _no_annotation)("bench.window"))
    if closed:
        reqs, sent_at, done_at = serve_closed(
            sched, batch, int(mix["outstanding"]), seconds, annotate,
            closing.close, finish=n_recall)
    else:
        done_at, late = serve_open(sched, reqs, due, seconds, annotate,
                                   closing.close)
    closing.close()
    counter.on = False
    bodies1 = dispatch_counters()["bodies"]
    mem = memory_peak(jax)

    # `entry`: the pool entry each submission served; `in_window`: the
    # submissions judged for `correct`; `recall_set`: those a closed
    # loop's recall_at_10 averages over
    n_sub = len(reqs)
    missing = 0
    if closed:
        entry = np.arange(n_sub) % n
        in_window = np.arange(n_sub)
        recall_set = np.arange(min(n_recall, n_sub))
        missing = n_recall - len(recall_set)
        after = int((sent_at[recall_set] >= seconds).sum())
        lat = None
        log(f"closed loop: pool of {n} distinct requests, {n_sub} "
            f"submissions, {max(0, n_sub - n)} replays; recall set: the "
            f"first {n_recall}, {after} of them submitted after the close, "
            f"{missing} never submitted")
    else:
        entry = np.arange(n)
        in_window = np.arange(n)[due <= seconds]
        lat = done_at[in_window] - due[in_window]
        log(f"generator lateness: mean {1e3 * late.mean():.3f} ms, max "
            f"{1e3 * late.max(initial=0):.3f} ms over {n} submissions")
    completed = in_window[np.isfinite(done_at[in_window])]
    n_done_window = int((done_at[in_window] <= seconds).sum())
    unfinished = len(in_window) - len(completed) + missing
    log(f"window: {len(in_window)} requests due, {len(completed)} "
        f"completed ({n_done_window} inside {seconds} s); programs "
        f"compiled inside the window: {counter.count} "
        f"({counter.seconds:.3f} s)")

    done_reqs = [reqs[i] for i in completed]
    plans = [r.plan or "traverse" for r in done_reqs]
    summary = None
    if trace:
        t = time.perf_counter()
        summary = tracelib.reduce(tracelib.load(tdir), seconds)
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace: stopped in {stopped['s']:.3f} s, read in "
            f"{time.perf_counter() - t:.3f} s")

    # the program's state goes before the reference runs
    rec_meta = [dict(plan=p, ndc=int(r.ndc or 0),
                     probe_ndc=int(r.probe_ndc or 0))
                for p, r in zip(plans, done_reqs)]
    ids = np.stack([np.asarray(r.res_idx, np.int64) for r in done_reqs]) \
        if done_reqs else np.zeros((0, cfg["search"]["k"]), np.int64)
    dist = np.stack([np.asarray(r.res_dist, np.float32)
                     for r in done_reqs]) if done_reqs else np.zeros(
        (0, cfg["search"]["k"]), np.float32)
    del sched, reqs, done_reqs
    engine_meta = dict(dim=w.dep.vectors.shape[1],
                       label_words=int(w.dep.labels_packed.shape[1]),
                       value_attrs=1, degree=cfg["graph"]["degree"],
                       precision=cfg["precision"])
    dep = w.dep
    del w
    gc.collect()

    # the exact search once per distinct pool entry, so its cost stays
    # bounded by the pool whatever the rate; each answer scored apart
    t = time.perf_counter()
    ref = Reference(dep.vectors, dep.labels_packed, dep.values)
    served = entry[completed]
    uniq, inv = np.unique(served, return_inverse=True)
    ref_ids, ref_d = ref.search(batch.queries[uniq],
                                batch.filters.take(uniq), cfg["search"]["k"])
    true_d, ok = ref.score(batch.queries[served], ids,
                           batch.filters.take(served))
    nums, rec = compare.numbers(ids, dist, true_d, ok, ref_ids[inv],
                                ref_d[inv], dep.n, unfinished)
    correct, checks = compare.judge(nums, cell.limits)
    log(f"reference: exact search over {len(uniq)} distinct requests, "
        f"{len(completed)} answers scored: {time.perf_counter() - t:.3f} "
        f"s; numbers (those with a limit are compared): {json.dumps(nums)}")
    if closed:
        # a request of the recall set that never completed counts as 0
        recall_at_10 = float(rec[np.isin(completed, recall_set)].sum()
                             / n_recall)
    else:
        recall_at_10 = float(rec.mean()) if rec.size else 0.0

    metrics = {}
    if not trace:
        values = {"setup_s": setup_s,
                  "qps": n_done_window / seconds,
                  "recall_at_10": recall_at_10}
        if lat is not None:
            full = np.where(np.isfinite(lat), lat, np.inf)
            values["p50_ms"] = 1e3 * float(np.quantile(full, 0.5))
            values["p95_ms"] = 1e3 * float(np.quantile(full, 0.95))
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        by_close = [m for m, i in zip(rec_meta, completed)
                    if done_at[i] <= seconds]
        ctx = dict(requests=rec_meta, n_completed=len(completed),
                   traced_requests=by_close, window_s=seconds, trace=summary,
                   bodies={k: v - bodies0.get(k, 0)
                           for k, v in bodies1.items()},
                   engine=engine_meta, peaks=None if allow_cpu
                   else peaks.peaks_for(kind))
        for m in cell.per_layer:
            v = manifest.load_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    out = {"correct": bool(correct), "attempted": int(len(in_window)),
           "failed": int(unfinished), "metrics": metrics,
           "device": device_info(jax, mem)}
    if trace:
        out["device"]["busy_s"] = summary.busy_s
        out["device"]["window_s"] = summary.window_s
        out["breakdown"] = tracelib.breakdown(summary)
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    out["checks"] = checks
    return out
