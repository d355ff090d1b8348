"""Cost-aware serving subsystem: batcher invariants, bucket routing,
scheduled-vs-oneshot bit-identity (incl. mixed-boolean-structure batches),
cache correctness, admission control."""
import dataclasses
import json

import numpy as np
import pytest

from repro.core import (CostEstimator, SearchConfig, SearchEngine, e2e_search,
                        generate_training_data)
from repro.data import (make_composite_workload, make_dataset,
                        make_label_workload, make_range_workload)
from repro.filters import And, Contain, Not, Or, Range
from repro.filters.predicates import PRED_CONTAIN, PRED_EQUAL, PRED_RANGE
from repro.index import build_graph_index
from repro.serve import (AdmissionQueue, CostAwareScheduler, MicroBatcher,
                         Request, ServeConfig, requests_from_workload)
from repro.serve.cache import request_key


@pytest.fixture(scope="module")
def world():
    ds = make_dataset(n=2500, dim=24, n_clusters=6, alphabet_size=32, seed=0)
    graph = build_graph_index(ds.vectors, degree=16, seed=0)
    engine = SearchEngine.build(ds, graph)
    cfg = SearchConfig(k=5, queue_size=64, pred_kind=PRED_CONTAIN)
    wl_tr = make_label_workload(ds, batch=192, kind="contain", seed=7)
    td = generate_training_data(engine, ds, wl_tr, cfg, probe_budget=48,
                                chunk=96)
    est = CostEstimator.fit(td.features, td.w_q, n_trees=60, depth=4)
    return ds, engine, cfg, est


def _req(rid, kind=PRED_CONTAIN, budget=None, arrival=0.0, dim=4, words=1):
    r = Request(rid=rid, query=np.full(dim, rid, np.float32), kind=kind,
                arrival=arrival)
    if kind == PRED_RANGE:
        r.range_lo, r.range_hi = 0.0, 1.0
    else:
        r.label_mask = np.asarray([rid + 1] * words, np.uint32)
    r.budget = budget
    return r


# -------------------------------------------------------------- batcher ----
def test_batcher_padding_invariants():
    b = MicroBatcher(lane_width=8, buckets=(100, None), n_words=1, n_values=1)
    reqs = [_req(i, budget=50, arrival=i) for i in range(3)]
    q = np.asarray(b.pad_queries(reqs))
    assert q.shape == (8, 4)
    assert (q[3:] == 0).all()                       # pad lanes zeroed
    prog = b.pad_program(reqs)
    assert prog.masks.shape == (8, 1, 1)            # 3 single-clause + pads
    assert (np.asarray(prog.masks)[3:] == 0).all()
    # pad lanes are match-nothing: no active term → valid ≡ False
    assert not np.asarray(prog.term_active)[3:].any()
    assert np.asarray(prog.term_active)[:3].all()
    budgets = np.asarray(b.pad_budgets(reqs, cap=None))
    assert budgets.shape == (8,)
    assert (budgets[:3] == 50).all() and (budgets[3:] == 0).all()


def test_batcher_mixes_filter_structures():
    """Compiled programs erase the same-kind restriction: one FIFO batch
    carries label, range, and composite filters together."""
    b = MicroBatcher(lane_width=4, buckets=(100, None), fill=True,
                     n_words=1, n_values=1)
    b.enqueue(_req(0, kind=PRED_CONTAIN, budget=50, arrival=0.0))
    b.enqueue(_req(1, kind=PRED_RANGE, budget=50, arrival=1.0))
    r2 = Request(rid=2, query=np.zeros(4, np.float32), arrival=2.0,
                 expr=And(Contain([1]), Range(0.1, 0.9)))
    r2.budget = 50
    b.enqueue(r2)
    _, reqs, _ = b.form_batch()
    assert [r.rid for r in reqs] == [0, 1, 2]        # strict FIFO, one batch
    prog = b.pad_program(reqs, width=4)
    # slot shape covers the widest program (the 2-clause conjunction),
    # rounded to a power of two
    assert prog.n_slots == 2 and prog.batch == 4
    active = np.asarray(prog.active)
    assert active.sum(axis=1).tolist() == [1, 1, 2, 0]


def test_bucket_routing_deterministic():
    b = MicroBatcher(lane_width=4, buckets=(100, 400, None))
    assert b.bucket_of(1) == 0
    assert b.bucket_of(100) == 0                     # cap is inclusive
    assert b.bucket_of(101) == 1
    assert b.bucket_of(400) == 1
    assert b.bucket_of(401) == 2
    assert b.bucket_of(10**9) == 2
    # same inputs → same batches, twice
    def fill(bb):
        for i, w in enumerate([50, 500, 90, 120, 10**6]):
            bb.enqueue(_req(i, budget=w, arrival=i))
        out = []
        while bb.depth():
            idx, reqs, cap = bb.form_batch()
            out.append((idx, [r.rid for r in reqs], cap))
        return out
    b2 = MicroBatcher(lane_width=4, buckets=(100, 400, None))
    assert fill(b) == fill(b2)


def test_opportunistic_fill_rides_spare_lanes():
    b = MicroBatcher(lane_width=4, buckets=(100, None), fill=True)
    for i in range(3):
        b.enqueue(_req(i, budget=50 + i, arrival=float(i)))
    for i in (3, 4):
        b.enqueue(_req(i, budget=5000, arrival=3.0 + i))
    (plan, idx), reqs, cap = b.form_batch()
    assert plan == "traverse" and idx == 0 and cap == 100
    # 3 residents → natural width 4 → exactly one free pad lane for a rider
    assert [r.rid for r in reqs] == [0, 1, 2, 3]
    # the rider runs a bounded slice: its lane budget is clamped to the cap
    budgets = np.asarray(b.pad_budgets(reqs, cap, width=4))
    assert budgets.tolist() == [50, 51, 52, 100]


def test_fill_never_widens_past_natural_width():
    """Riders must not push a batch to a wider (costlier) lane shape."""
    b = MicroBatcher(lane_width=16, buckets=(100, None), fill=True)
    for i in range(3):                              # natural width 4
        b.enqueue(_req(i, budget=50, arrival=float(i)))
    for i in range(10, 22):                         # plenty of riders
        b.enqueue(_req(i, budget=5000, arrival=float(i)))
    _, reqs, _ = b.form_batch()
    assert len(reqs) == 4 == b.width_for(3)         # 1 rider, not 13


def test_batcher_rejects_unordered_buckets():
    with pytest.raises(ValueError, match="ascending"):
        MicroBatcher(buckets=(400, 100, None))


def test_form_batch_on_empty_named_bucket():
    b = MicroBatcher(lane_width=4, buckets=(100, None), fill=True)
    b.enqueue(_req(0, budget=5000, arrival=0.0))     # lives in bucket 1
    key, reqs, cap = b.form_batch(bucket=("traverse", 0))  # bucket 0 empty
    assert (key, reqs, cap) == (("traverse", 0), [], 100)
    assert b.depth() == 1                            # nothing was lost


# ------------------------------------------------- scheduled == one-shot ----
@pytest.mark.parametrize("policy", ["direct", "escalate"])
def test_scheduled_equals_oneshot(world, policy):
    """The acceptance bar: scheduling (micro-batching, bucket routing,
    resume-requeue slicing, lane padding) is bit-invisible in the results."""
    ds, engine, cfg, est = world
    wl = make_label_workload(ds, batch=24, kind="contain", seed=42)
    one = e2e_search(engine, est, cfg, wl.queries, wl.spec, probe_budget=48,
                     alpha=1.5)
    scfg = ServeConfig(lane_width=8, buckets=(128, 512, None), policy=policy,
                       probe_budget=48, alpha=1.5, cache_capacity=0)
    sched = CostAwareScheduler(engine, est, cfg, scfg)
    reqs = requests_from_workload(wl)
    for r in reqs:
        assert sched.submit(r, 0.0) == "queued"
    sched.run_until_idle(0.0)
    reqs.sort(key=lambda r: r.rid)
    np.testing.assert_array_equal(
        np.stack([r.res_idx for r in reqs]), np.asarray(one.state.res_idx))
    np.testing.assert_array_equal(
        np.stack([r.res_dist for r in reqs]), np.asarray(one.state.res_dist))
    np.testing.assert_array_equal(
        np.asarray([r.ndc for r in reqs]), np.asarray(one.state.cnt))
    np.testing.assert_array_equal(
        np.asarray([r.budget for r in reqs]), one.predicted_budget)
    if policy == "escalate":
        # the preemption path must actually have been exercised
        assert any(r.n_slices >= 2 for r in reqs)


def test_scheduled_equals_oneshot_with_padding(world):
    """5 requests through 8-wide lanes: pad lanes must be inert."""
    ds, engine, cfg, est = world
    wl = make_label_workload(ds, batch=5, kind="contain", seed=13)
    one = e2e_search(engine, est, cfg, wl.queries, wl.spec, probe_budget=48,
                     alpha=1.5)
    sched = CostAwareScheduler(engine, est, cfg, ServeConfig(
        lane_width=8, buckets=(128, 512, None), probe_budget=48, alpha=1.5,
        cache_capacity=0))
    reqs = requests_from_workload(wl)
    for r in reqs:
        sched.submit(r, 0.0)
    sched.run_until_idle(0.0)
    reqs.sort(key=lambda r: r.rid)
    np.testing.assert_array_equal(
        np.stack([r.res_idx for r in reqs]), np.asarray(one.state.res_idx))


def test_scheduled_mixed_kinds_equal_per_kind_oneshot(world):
    """Interleaved contain/range requests: each kind matches its one-shot."""
    ds, engine, cfg, est = world
    wl_c = make_label_workload(ds, batch=8, kind="contain", seed=5)
    wl_r = make_range_workload(ds, batch=8, seed=6)
    cfg_r = dataclasses.replace(cfg, pred_kind=PRED_RANGE)
    one_c = e2e_search(engine, est, cfg, wl_c.queries, wl_c.spec,
                       probe_budget=48, alpha=1.5)
    one_r = e2e_search(engine, est, cfg_r, wl_r.queries, wl_r.spec,
                       probe_budget=48, alpha=1.5)
    sched = CostAwareScheduler(engine, est, cfg, ServeConfig(
        lane_width=8, buckets=(128, 512, None), probe_budget=48, alpha=1.5,
        cache_capacity=0))
    rc = requests_from_workload(wl_c, start_rid=0)
    rr = requests_from_workload(wl_r, start_rid=100)
    inter = [r for pair in zip(rc, rr) for r in pair]
    for r in inter:
        sched.submit(r, 0.0)
    sched.run_until_idle(0.0)
    np.testing.assert_array_equal(np.stack([r.res_idx for r in rc]),
                                  np.asarray(one_c.state.res_idx))
    np.testing.assert_array_equal(np.stack([r.res_idx for r in rr]),
                                  np.asarray(one_r.state.res_idx))


def test_scheduled_mixed_structures_equal_oneshot(world):
    """Mixed-boolean-structure batch (And/Or/Not composites + bare leaves
    interleaved): the scheduler batches them into shared lanes and the
    results stay bit-identical to one-shot `e2e_search` over the same
    workload — the compiled-program generalization of the serving
    subsystem's core guarantee."""
    ds, engine, cfg, est = world
    wl = make_composite_workload(ds, batch=20, structure="mixed", seed=77)
    one = e2e_search(engine, est, cfg, wl.queries, wl.exprs, probe_budget=48,
                     alpha=1.5)
    sched = CostAwareScheduler(engine, est, cfg, ServeConfig(
        lane_width=8, buckets=(128, 512, None), probe_budget=48, alpha=1.5,
        cache_capacity=0))
    reqs = requests_from_workload(wl)
    for r in reqs:
        assert sched.submit(r, 0.0) == "queued"
    sched.run_until_idle(0.0)
    reqs.sort(key=lambda r: r.rid)
    # probe batches mixed at least two different program structures
    assert len({r.program.n_slots for r in reqs}) > 1
    np.testing.assert_array_equal(
        np.stack([r.res_idx for r in reqs]), np.asarray(one.state.res_idx))
    np.testing.assert_array_equal(
        np.stack([r.res_dist for r in reqs]), np.asarray(one.state.res_dist))
    np.testing.assert_array_equal(
        np.asarray([r.ndc for r in reqs]), np.asarray(one.state.cnt))
    np.testing.assert_array_equal(
        np.asarray([r.budget for r in reqs]), one.predicted_budget)


# ---------------------------------------------------------------- cache ----
def test_cache_hit_returns_identical_result(world):
    ds, engine, cfg, est = world
    wl = make_label_workload(ds, batch=4, kind="contain", seed=3)
    sched = CostAwareScheduler(engine, est, cfg, ServeConfig(
        lane_width=4, buckets=(128, None), probe_budget=48, alpha=1.5))
    reqs = requests_from_workload(wl)
    for r in reqs:
        assert sched.submit(r, 0.0) == "queued"
    sched.run_until_idle(0.0)
    again = requests_from_workload(wl)
    for r in again:
        assert sched.submit(r, 1.0) == "hit"
    for a, b in zip(reqs, again):
        assert b.cache_hit and b.completed == 1.0
        np.testing.assert_array_equal(a.res_idx, b.res_idx)
        assert a.ndc == b.ndc
    assert sched.cache.hit_rate == 0.5  # 4 misses then 4 hits


def test_cache_keys_distinguish_filter_spec_collisions():
    q = np.ones(8, np.float32)
    base = dict(k=5, queue_size=64, alpha=1.5, probe_budget=48)
    contain = Request(0, q, PRED_CONTAIN, label_mask=np.asarray([7], np.uint32))
    equal = Request(1, q, PRED_EQUAL, label_mask=np.asarray([7], np.uint32))
    # same query, same mask *bytes*, different predicate kind
    assert request_key(contain, **base) != request_key(equal, **base)
    # same kind, different mask
    other = Request(2, q, PRED_CONTAIN, label_mask=np.asarray([9], np.uint32))
    assert request_key(contain, **base) != request_key(other, **base)
    # a range whose float bytes shadow the mask bytes still differs
    lo, hi = np.frombuffer(np.asarray([7, 7], np.uint32).tobytes(),
                           np.float32)[:2]
    rng_req = Request(3, q, PRED_RANGE, range_lo=float(lo), range_hi=float(hi))
    assert request_key(contain, **base) != request_key(rng_req, **base)
    # search parameters are part of the key — every answer-changing one
    assert (request_key(contain, 5, 64, 1.5, 48)
            != request_key(contain, 5, 64, 2.0, 48))
    assert (request_key(contain, **base)
            != request_key(contain, **base, min_budget=64))
    assert (request_key(contain, **base)
            != request_key(contain, **base, n_probes=1))
    assert (request_key(contain, **base)
            != request_key(contain, **base, ablate_filter=True))
    # the engine's codec identity is answer-changing: compressed-domain
    # traversal keeps a different candidate pool, and a retrained codebook
    # (different digest) changes the pool again — neither may share entries
    # with float32 or with each other
    assert (request_key(contain, **base)
            != request_key(contain, **base, codec="int8:aabbccddeeff"))
    assert (request_key(contain, **base, codec="int8:aabbccddeeff")
            != request_key(contain, **base, codec="pq:aabbccddeeff"))
    assert (request_key(contain, **base, codec="pq:aabbccddeeff")
            != request_key(contain, **base, codec="pq:001122334455"))
    assert (request_key(contain, **base, codec="float32")
            == request_key(contain, **base))          # explicit default collides
    # identical requests collide on purpose
    twin = Request(4, q.copy(), PRED_CONTAIN,
                   label_mask=np.asarray([7], np.uint32))
    assert request_key(contain, **base) == request_key(twin, **base)


def test_cache_keys_canonicalize_composite_filters():
    """And(a,b) vs Or(a,b) must differ; And(a,b) vs And(b,a) must collide
    (same canonical program → same traversal → same answer)."""
    q = np.ones(8, np.float32)
    base = dict(k=5, queue_size=64, alpha=1.5, probe_budget=48)
    a, b = Contain([3]), Range(0.25, 0.75)

    def key(expr):
        return request_key(Request(0, q, expr=expr), **base)

    assert key(And(a, b)) == key(And(b, a))          # commutativity collides
    assert key(Or(a, b)) == key(Or(b, a))
    assert key(And(a, b)) != key(Or(a, b))           # structure distinguishes
    assert key(And(a, b)) != key(And(a, Not(b)))     # negation distinguishes
    assert key(a) != key(And(a, b))
    # double negation is semantic identity → canonical collision
    assert key(Not(Not(a))) == key(a)
    # a bare leaf and its legacy-field spelling collide (the shim contract)
    legacy = Request(1, q, PRED_CONTAIN, label_mask=np.asarray([8], np.uint32))
    assert request_key(legacy, **base) == key(Contain([3]))


@pytest.fixture(scope="module")
def auto_planner(world):
    from repro.core import fit_planner, generate_plan_training_data

    ds, engine, cfg, est = world
    wl = make_composite_workload(ds, batch=96, seed=11, structure="mixed",
                                 selectivities=(0.01, 0.1, 0.3))
    data = generate_plan_training_data(engine, ds, wl, cfg, probe_budget=48,
                                       chunk=48)
    return fit_planner(data, probe_budget=48, n_trees=60, depth=4)


def test_cache_plan_collision_matrix(world, auto_planner):
    """The plan ∈ key contract: plan enters the cache key exactly when it
    can change the answer. traverse == legacy key; scan/widen/auto are
    pairwise distinct; an auto completion is dual-put under the chosen
    forced key iff it executed the exact bitwise forced path (plan_pure)."""
    ds, engine, cfg, est = world
    base = dict(k=5, queue_size=64, alpha=1.5, probe_budget=48)
    probe = Request(0, np.ones(ds.dim, np.float32),
                    expr=And(Contain([3]), Range(0.25, 0.75)))
    keys = {p: request_key(probe, **base, plan=p)
            for p in ("traverse", "scan", "widen", "auto")}
    assert keys["traverse"] == request_key(probe, **base)  # legacy stable
    assert len(set(keys.values())) == 4                    # pairwise distinct

    # end-to-end: run an auto scheduler, then read the cache through every
    # forced-plan key — only the chosen plan's key may hit, and only when
    # the executed path was plan-pure
    scfg = ServeConfig(lane_width=8, buckets=(256, None), probe_budget=48,
                       plan="auto")
    sched = CostAwareScheduler(engine, est, cfg, scfg, planner=auto_planner)
    wl = make_composite_workload(ds, batch=8, seed=21, structure="mixed",
                                 selectivities=(0.01, 0.3))
    reqs = requests_from_workload(wl)
    for r in reqs:
        assert sched.submit(r, 0.0) == "queued"
    sched.run_until_idle(0.0)
    plans = {"scan", "traverse", "widen"}
    assert all(r.plan in plans for r in reqs)
    for r in reqs:
        hit = {p: sched.cache.get(sched._key_for(r, p)) is not None
               for p in plans | {"auto"}}
        assert hit["auto"]                       # always stored under auto
        assert hit[r.plan] == r.plan_pure        # dual-put iff bitwise-pure
        assert not any(hit[p] for p in plans - {r.plan})  # others never

    # a forced-plan scheduler sharing the cache hits exactly those entries
    pure = [r for r in reqs if r.plan_pure]
    assert pure                                  # routing produced pure lanes
    victim = pure[0]
    pos = reqs.index(victim)
    forced_same = CostAwareScheduler(
        engine, est, cfg, dataclasses.replace(scfg, plan=victim.plan),
        planner=auto_planner)
    forced_same.cache = sched.cache
    assert forced_same.submit(requests_from_workload(wl)[pos], 1.0) == "hit"
    other = next(p for p in plans if p != victim.plan)
    forced_other = CostAwareScheduler(
        engine, est, cfg, dataclasses.replace(scfg, plan=other),
        planner=auto_planner)
    forced_other.cache = sched.cache
    assert (forced_other.submit(requests_from_workload(wl)[pos], 1.0)
            == "queued")                         # forced-Y never sees X's entry

    # late-scan completions (probe counters leaked into NDC) must NOT be
    # dual-put: a forced-scan run never pays the probe
    late = Request(99, np.full(ds.dim, 0.5, np.float32),
                   expr=Contain([5]), arrival=2.0)
    late.plan, late.plan_pure = "scan", False
    sched._finish(late, np.full(cfg.k, -1, np.int32),
                  np.full(cfg.k, np.inf, np.float32), 17, 2.0)
    assert sched.cache.get(sched._key(late)) is not None
    assert sched.cache.get(sched._key_for(late, "scan")) is None


def test_uncompilable_filter_rejected_at_submit(world):
    """A filter the compiler rejects must raise at submit() with nothing
    queued — compiling after admission would poison the pump loop."""
    ds, engine, cfg, est = world
    sched = CostAwareScheduler(engine, est, cfg, ServeConfig(
        lane_width=4, buckets=(128, None), cache_capacity=0))
    pairs = [Or(Contain([2 * i]), Contain([2 * i + 1])) for i in range(6)]
    bomb = Request(0, np.zeros(ds.dim, np.float32), expr=And(*pairs))  # 2^6 DNF
    with pytest.raises(ValueError, match="clauses"):
        sched.submit(bomb, 0.0)
    assert sched.depth() == 0                        # nothing poisoned
    ok = requests_from_workload(make_label_workload(ds, batch=3, seed=1))
    for r in ok:
        assert sched.submit(r, 0.0) == "queued"
    sched.run_until_idle(0.0)                        # pump still healthy
    assert all(r.res_idx is not None for r in ok)


# ------------------------------------------------------------- admission ----
def test_admission_backpressure_and_deadlines():
    q = AdmissionQueue(capacity=2)
    a, b, c = (_req(i, arrival=float(i)) for i in range(3))
    assert q.offer(a, 0.0) and q.offer(b, 0.0)
    assert not q.offer(c, 0.0)                       # full → shed
    assert q.n_shed == 1
    d = _req(9, arrival=0.0)
    d.deadline = 1.0
    assert not q.offer(d, 2.0)                       # expired on arrival
    assert q.n_expired == 1
    assert len(q) == 2


def test_scheduler_reports_shed_and_metrics_json(world):
    ds, engine, cfg, est = world
    wl = make_label_workload(ds, batch=6, kind="contain", seed=9)
    sched = CostAwareScheduler(engine, est, cfg, ServeConfig(
        lane_width=4, buckets=(128, None), probe_budget=48, alpha=1.5,
        queue_capacity=4, cache_capacity=0))
    reqs = requests_from_workload(wl)
    outcomes = [sched.submit(r, 0.0) for r in reqs]
    assert outcomes.count("queued") == 4 and outcomes.count("shed") == 2
    sched.run_until_idle(0.0)
    s = sched.summary()
    assert s["n_completed"] == 4 and s["n_shed"] == 2
    assert s["latency"]["p50"] <= s["latency"]["p99"]
    json.dumps(s)  # BENCH artifact requirement: plain-JSON serializable


def test_profiler_bridged_tracing_changes_nothing(world, auto_planner,
                                                   tmp_path):
    """A `Tracer` bridged into a running JAX profiler trace leaves
    the auto plan's answers bit-identical (an int8 store, so finishing
    lanes go through the float32 rerank), spans the stage-0 bitmap, batch
    assembly and rerank, and closes every wait in a queue once, naming
    the batch that took the request."""
    import jax

    from repro.filters.compile import CLAUSE_FEATURE_SLOTS
    from repro.index.graph import GraphIndex
    from repro.obs import Tracer

    ds, engine, cfg, est = world
    graph = GraphIndex(neighbors=np.asarray(engine.neighbors),
                       entry_point=engine.entry_point, dim=ds.dim)
    eng8 = SearchEngine.build(ds, graph, precision="int8")
    scfg = ServeConfig(lane_width=8, buckets=(256, None), probe_budget=48,
                       plan="auto", cache_capacity=0)
    wl = make_composite_workload(ds, batch=12, seed=21, structure="mixed",
                                 selectivities=(0.01, 0.3))

    def serve(tracer):
        sched = CostAwareScheduler(eng8, est, cfg, scfg,
                                   planner=auto_planner, tracer=tracer)
        reqs = requests_from_workload(wl)
        for r in reqs:
            assert sched.submit(r, 0.0) == "queued"
        sched.run_until_idle(0.0)
        return reqs

    plain = serve(None)
    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        traced = serve(tr)
    for a, b in zip(plain, traced):
        assert np.array_equal(a.res_idx, b.res_idx)
        assert np.array_equal(a.res_dist, b.res_dist)
        assert (a.ndc, a.plan) == (b.ndc, b.plan)

    names = {s.name for s in tr.spans()}
    assert {"lanes", "plan-stage0", "filter-bitmap", "rerank",
            "queued"} <= names
    bitmap = tr.spans(name="filter-bitmap")[0].attrs
    assert bitmap["rows"] == len(ds.vectors)
    # 2500 rows are one step of the pass's in-program walk
    assert bitmap["chunks"] == 1
    # the bitmap stays on the device: only int32 counts [B] and clause
    # counts [B, CLAUSE_FEATURE_SLOTS] cross
    assert bitmap["bytes_to_host"] == (bitmap["lanes"] * 4
                                       * (1 + CLAUSE_FEATURE_SLOTS))
    waits = tr.spans(name="queued")
    ingress = [s for s in waits if s.attrs["queue"] == "ingress"]
    assert sorted(s.attrs["rid"] for s in ingress) == [r.rid for r in traced]
    assert (len(waits) - len(ingress)
            == sum(r.n_slices for r in traced))      # one per bucket slice
    assert all(s.duration >= 0 and s.attrs["batch"] for s in waits)
    assert all(r.queued_at is None for r in traced)
