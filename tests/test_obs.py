"""Observability subsystem: tracer ring/sink invariants, the
tracing-changes-nothing contract (bit-identical results, no extra device
dispatches), EXPLAIN termination semantics, calibration telemetry schema +
persistence, Prometheus exposition validity, ServeMetrics hardening, and
the scheduler's driver-observed launch accounting."""
import contextlib
import json
import types

import numpy as np
import pytest

from repro.core import (CostEstimator, SearchConfig, SearchEngine, e2e_search,
                        generate_training_data)
from repro.core.search import dispatch_counters
from repro.data import make_dataset, make_label_workload
from repro.filters.predicates import PRED_CONTAIN
from repro.index import build_graph_index
from repro.obs import (NO_TRACE, PLAN_NAMES, RECORD_FIELDS, SCHEMA_VERSION,
                       CalibrationMonitor, NullTracer, Tracer, as_tracer,
                       build_reports, feature_dict, prometheus_text,
                       termination_reasons, validate_prometheus)
from repro.obs.trace import _host_scalar
from repro.serve import (CostAwareScheduler, ServeConfig, ServeMetrics,
                         requests_from_workload)


# ------------------------------------------------------------- tracer ----
def test_tracer_ring_ids_and_filters():
    clock = iter(float(i) for i in range(10_000))
    tr = Tracer(capacity=4, clock=lambda: next(clock))
    assert tr.new_trace("q") == "q-000001"
    assert tr.new_trace("req") == "req-000002"      # one counter, replayable
    for i in range(6):
        tr.emit("launch", "q-000001", steps=i)
    assert tr.n_emitted == 6                        # lifetime count
    assert len(tr) == 4                             # ring evicted the oldest
    assert [s.attrs["steps"] for s in tr.spans()] == [2, 3, 4, 5]
    assert tr.spans(name="nope") == []
    assert len(tr.spans(trace_id="q-000001", name="launch")) == 4
    with tr.span("probe", "q-000001", budget=64) as sp:
        sp.set(steps=7)
    got = tr.spans(name="probe")[0]
    assert got.attrs == dict(budget=64, steps=7)
    assert got.t1 >= got.t0                         # monotonic interval
    tr.clear()
    assert len(tr) == 0 and tr.n_emitted == 7       # clear keeps lifetime


def test_tracer_sink_jsonl(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    tr = Tracer(capacity=2, sink=path)
    for i in range(5):
        tr.emit("launch", f"q-{i}", width=8)
    tr.close()
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 5                          # sink outlives the ring
    assert lines[3] == {**lines[3], "name": "launch", "trace": "q-3",
                        "width": 8}


def test_span_attrs_must_be_host_scalars():
    tr = Tracer()
    assert _host_scalar(np.int32(3)) == 3
    assert _host_scalar(np.float64(2.5)) == 2.5
    assert _host_scalar(np.bool_(True)) is True
    with pytest.raises(TypeError):
        tr.emit("bad", "t", arr=np.zeros(4))        # arrays must not leak
    with pytest.raises(TypeError):
        with tr.span("bad", "t") as sp:
            sp.set(arr=np.zeros(4))                 # ... via sp.set either


def test_only_a_real_tracer_annotates(monkeypatch):
    """NullTracer never calls jax.profiler; a Tracer enters one annotation
    for every span, named after it and carrying its trace id, and keeps
    instants and emitted intervals in the ring only."""
    import jax

    def boom(*a, **k):
        raise AssertionError("jax.profiler.TraceAnnotation called")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    with NO_TRACE.span("lanes", "q-1"):
        NO_TRACE.emit("admit", "q-1")
    seen = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name, **kw: seen.append((name, kw))
                        or contextlib.nullcontext())
    tr = Tracer()
    with tr.span("resume", "bucket-2"):
        with tr.span("launch", "bucket-2"):
            pass
    tr.emit("admit", "req-1")                           # ring only
    tr.emit("queued", "req-1", t0=0.0, t1=1.0)          # ring only
    assert seen == [("repro.resume", {"trace": "bucket-2"}),
                    ("repro.launch", {"trace": "bucket-2"})]
    assert [s.name for s in tr.spans()] == ["launch", "resume", "admit",
                                            "queued"]


def test_compile_counted_once_inside_innermost_span():
    """Each program lowered is one `compile` span and one count, however
    many jax.monitoring events its compilation fires, attributed to the
    innermost span open when it compiled."""
    import jax

    tr = Tracer()
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    f(np.arange(5, dtype=np.float32))                # outside any span
    with tr.span("resume", "bucket-1"):
        with tr.span("lanes", "bucket-1"):
            f(np.arange(5, dtype=np.float32))        # cached: no compile
            f(np.arange(7, dtype=np.float32))        # new shape: recompile
    assert tr.n_compiles == 2
    outside, inside = tr.spans(name="compile")
    assert outside.attrs["inside"] == "" and outside.trace_id == ""
    assert inside.attrs["inside"] == "lanes"
    assert inside.trace_id == "bucket-1"
    assert inside.attrs["seconds"] > 0 and inside.duration > 0
    assert isinstance(inside.attrs["cache_miss"], bool)


def test_null_tracer_is_inert():
    assert as_tracer(None) is NO_TRACE
    t = Tracer()
    assert as_tracer(t) is t
    assert isinstance(NO_TRACE, NullTracer)
    assert NO_TRACE.new_trace() == ""
    with NO_TRACE.span("x", "t", a=1) as sp:
        sp.set(b=2)                                 # writable, discarded
    NO_TRACE.emit("x", arr=np.zeros(3))             # no validation either
    assert len(NO_TRACE) == 0 and NO_TRACE.spans() == []


# ------------------------------------------------------------ explain ----
def _fake_state(cand_dist, cand_exp, res_worst, cnt):
    """Minimal duck-typed final carry for termination_reasons."""
    cand_dist = np.asarray(cand_dist, np.float32)
    k = 3
    res = np.full((cand_dist.shape[0], k), np.inf, np.float32)
    res[:, -1] = res_worst
    return types.SimpleNamespace(
        cand_dist=cand_dist,
        cand_idx=np.where(np.isfinite(cand_dist), 1, -1).astype(np.int32),
        cand_exp=np.asarray(cand_exp, bool),
        res_dist=res,
        cnt=np.asarray(cnt, np.int32),
        hops=np.zeros(cand_dist.shape[0], np.int32),
        res_idx=np.zeros((cand_dist.shape[0], k), np.int32),
    )


def test_termination_reason_priority():
    inf = np.inf
    st = _fake_state(
        # lane 0: every candidate expanded → queue-drained (beats budget:
        #         its cnt is also ≥ budget, drained wins the priority)
        # lane 1: unexpanded candidate + cnt ≥ budget → budget
        # lane 2: unexpanded cand worse than worst result → greedy
        # lane 3: none of the above → active
        cand_dist=[[1.0, 2.0], [1.0, inf], [9.0, inf], [1.0, inf]],
        cand_exp=[[True, True], [False, False], [False, False],
                  [False, False]],
        res_worst=[5.0, 5.0, 5.0, 5.0],
        cnt=[100, 100, 10, 10],
    )
    cfg = SearchConfig(k=3, greedy_stop=True)
    assert termination_reasons(cfg, st, 50) == [
        "queue-drained", "budget", "greedy", "active"]
    # greedy_stop off: the greedy condition must not fire
    cfg = SearchConfig(k=3, greedy_stop=False)
    assert termination_reasons(cfg, st, 50) == [
        "queue-drained", "budget", "active", "active"]
    # per-lane budgets broadcast
    assert termination_reasons(
        SearchConfig(k=3), st, [100, 101, 5, 100]) == [
        "queue-drained", "active", "budget", "active"]


def test_feature_dict_naming():
    from repro.core.features import FEATURE_NAMES
    n = len(FEATURE_NAMES)
    d = feature_dict(np.arange(2 * n + 1, dtype=np.float32))
    assert list(d)[:n] == list(FEATURE_NAMES)
    assert list(d)[n:2 * n] == [f"d_{f}" for f in FEATURE_NAMES]
    assert list(d)[-1] == f"f{2 * n}"               # overflow block
    assert d[FEATURE_NAMES[1]] == 1.0


def test_build_reports_roundtrip():
    st = _fake_state(cand_dist=[[1.0, np.inf]], cand_exp=[[False, False]],
                     res_worst=[5.0], cnt=[80])
    reports = build_reports(
        SearchConfig(k=3), st, 64, backend="dense", plans=["widen"],
        probe_ndc=[32], trace_ids=["t-1"],
        features=np.ones((1, 4), np.float32))
    r = reports[0]
    assert (r.plan, r.termination, r.predicted_budget, r.actual_ndc,
            r.probe_ndc) == ("widen", "budget", 64, 80, 32)
    d = json.loads(r.to_json())
    assert d["trace_id"] == "t-1" and d["backend"] == "dense"
    assert "plan=widen" in r.format() and "terminated=budget" in r.format()


# -------------------------------------------------------- calibration ----
def test_calibration_schema_is_frozen():
    """The recalibration PR trains from saved windows — names, dtypes and
    order are a contract. Changing them requires a SCHEMA_VERSION bump."""
    assert SCHEMA_VERSION == 1
    assert [(n, d) for n, d, _ in RECORD_FIELDS] == [
        ("rid", "int64"), ("plan", "int32"), ("predicted", "int64"),
        ("actual", "int64"), ("probe_ndc", "int64"), ("n_slices", "int32"),
        ("alpha", "float32"), ("recall", "float32")]
    assert PLAN_NAMES == ("traverse", "scan", "widen")


def test_calibration_report_math():
    mon = CalibrationMonitor()
    assert np.isfinite(list(mon.report()["predicted"].values())).all()
    # traverse: predicted 100 vs actual {50, 200} → one win, one loss
    mon.record(predicted=100, actual=50, plan="traverse", rid=0)
    mon.record(predicted=100, actual=200, plan="traverse", rid=1)
    mon.record(predicted=300, actual=100, plan="scan", rid=2, recall=0.9)
    rep = mon.report()
    assert rep["n_records"] == 3 and rep["n_recorded_total"] == 3
    assert rep["overprediction_rate"] == pytest.approx(2 / 3)
    assert rep["underprediction_rate"] == pytest.approx(1 / 3)
    assert rep["per_plan"]["traverse"]["win_rate"] == pytest.approx(0.5)
    assert rep["per_plan"]["scan"]["win_rate"] == 1.0
    assert rep["per_plan"]["scan"]["share"] == pytest.approx(1 / 3)
    assert rep["recall_mean"] == pytest.approx(0.9)
    assert rep["n_with_recall"] == 1
    expected = np.sqrt(np.mean(np.log([100 / 50, 100 / 200, 300 / 100]) ** 2))
    assert rep["log_rmse"] == pytest.approx(expected)
    mon.set_recall({0: 1.0})
    assert mon.report()["n_with_recall"] == 2


def test_calibration_save_load_roundtrip(tmp_path):
    mon = CalibrationMonitor()
    for i in range(7):
        mon.record(rid=i, predicted=64 + i, actual=60 + 2 * i,
                   plan=PLAN_NAMES[i % 3], probe_ndc=32, n_slices=1,
                   alpha=1.5, features=np.arange(6, dtype=np.float32) + i)
    path = mon.save(str(tmp_path), tag="win0")
    mon2, manifest = CalibrationMonitor.load(path)
    assert manifest["schema_version"] == SCHEMA_VERSION
    assert manifest["feature_width"] == 6
    a, b = mon.arrays(), mon2.arrays()
    for name, _, _ in RECORD_FIELDS:
        np.testing.assert_array_equal(a[name], b[name])
    np.testing.assert_array_equal(a["features"], b["features"])
    # integrity: a torn/tampered npz must not load silently
    import os
    data = os.path.join(path, "arrays.npz")
    with open(data, "ab") as f:
        f.write(b"x")
    with pytest.raises(IOError):
        CalibrationMonitor.load(path)
    CalibrationMonitor.load(path, validate=False)   # escape hatch


# --------------------------------------------------------- prometheus ----
def _tiny_summary():
    m = ServeMetrics()
    m.observe_batch("probe", size=8, fill=8, busy=0.1, steps=40, launches=5,
                    early_exit_frac=0.5)
    m.observe_batch("resume", size=4, fill=8, busy=0.2, steps=80, launches=10,
                    early_exit_frac=0.25)
    m.observe_depth(0.0, 3)
    req = types.SimpleNamespace(rid=0, completed=1.0, arrival=0.0,
                                probe_done=0.5, ndc=120, budget=128,
                                n_slices=1, cache_hit=False, deadline=None)
    m.complete(req)
    return m.summary()


def test_prometheus_text_is_valid_and_nan_free():
    mon = CalibrationMonitor()
    mon.record(predicted=100, actual=80, plan="scan")
    text = prometheus_text(_tiny_summary(), mon.report())
    names = validate_prometheus(text)               # raises on any violation
    for expect in ("repro_requests_completed_total", "repro_latency",
                   "repro_launches_total", "repro_early_exit_frac",
                   "repro_phase_batches_total", "repro_calibration_log_rmse",
                   "repro_plan_win_rate", "repro_plan_queries_total"):
        assert expect in names, (expect, sorted(names))
    assert "nan" not in text.lower()
    # a NaN smuggled into the summary renders as 0.0, not as "nan"
    s = _tiny_summary()
    s["latency"]["p99"] = float("nan")
    validate_prometheus(prometheus_text(s))
    # custom prefix propagates
    assert "acme_launches_total" in validate_prometheus(
        prometheus_text(_tiny_summary(), prefix="acme"))


def test_prometheus_validator_rejects_garbage():
    with pytest.raises(ValueError):
        validate_prometheus("")                     # empty scrape
    with pytest.raises(ValueError):
        validate_prometheus("this is not a metric line\n")
    with pytest.raises(ValueError):                 # sample before # TYPE
        validate_prometheus("repro_x 1.0\n")
    with pytest.raises(ValueError):                 # NaN sample
        validate_prometheus(
            "# HELP repro_x x\n# TYPE repro_x gauge\nrepro_x NaN\n")
    with pytest.raises(ValueError):                 # malformed labels
        validate_prometheus(
            "# HELP repro_x x\n# TYPE repro_x gauge\n"
            'repro_x{quantile=0.5} 1.0\n')


# ----------------------------------------------- metrics hardening (s1) ----
def test_metrics_summary_finite_on_empty_and_singleton():
    m = ServeMetrics()
    s = m.summary()
    flat = [s["latency"]["p50"], s["latency"]["p99"], s["latency_mean"],
            s["probe_latency"]["p95"], s["ndc"]["p50"], s["queue_depth_mean"],
            s["early_exit_frac"], s["deadline_miss_rate"]]
    assert np.isfinite(flat).all() and s["launches_total"] == 0
    req = types.SimpleNamespace(rid=0, completed=2.0, arrival=1.0,
                                probe_done=None, ndc=None, budget=None,
                                n_slices=0, cache_hit=False, deadline=None)
    m.complete(req)
    s = m.summary()                                 # singleton window
    assert s["latency"]["p50"] == s["latency"]["p99"] == 1.0
    assert s["ndc"]["p99"] == 0.0                   # ndc=None drops cleanly


def test_metrics_percentiles_drop_nonfinite():
    m = ServeMetrics()
    for lat in (1.0, float("nan"), 3.0, float("inf")):
        m.complete(types.SimpleNamespace(
            rid=0, completed=lat, arrival=0.0, probe_done=None, ndc=10,
            budget=None, n_slices=0, cache_hit=False, deadline=None))
    s = m.summary()
    assert s["latency"]["p50"] == pytest.approx(2.0)  # only {1, 3} survive
    assert np.isfinite(s["latency"]["p99"])


def test_metrics_early_exit_weighted_by_real_lanes():
    m = ServeMetrics()
    # a full 64-lane batch at 0.5 and a 1-lane tail at 1.0: an unweighted
    # mean says 0.75; the truth over the 65 real lanes is (32+1)/65
    m.observe_batch("resume", size=64, fill=64, busy=1.0, steps=10,
                    launches=2, early_exit_frac=0.5)
    m.observe_batch("resume", size=1, fill=8, busy=1.0, steps=10,
                    launches=1, early_exit_frac=1.0)
    s = m.summary()
    want = (0.5 * 64 + 1.0 * 1) / 65
    assert s["early_exit_frac"] == pytest.approx(want, abs=1e-4)
    assert s["batches_by_phase"]["resume"]["early_exit_frac"] == \
        pytest.approx(want, abs=1e-4)
    assert s["launches_total"] == 3 and s["steps_total"] == 20


# ------------------------------------------------ engine integration ----
@pytest.fixture(scope="module")
def world():
    ds = make_dataset(n=2000, dim=16, n_clusters=6, alphabet_size=32, seed=0)
    graph = build_graph_index(ds.vectors, degree=16, seed=0)
    cfg = SearchConfig(k=5, queue_size=64, pred_kind=PRED_CONTAIN)
    dense = SearchEngine.build(ds, graph, backend="dense")
    wl_tr = make_label_workload(ds, batch=96, kind="contain", seed=7)
    td = generate_training_data(dense, ds, wl_tr, cfg, probe_budget=48,
                                chunk=96)
    est = CostEstimator.fit(td.features, td.w_q, n_trees=40, depth=4)
    return ds, graph, cfg, dense, est


@pytest.mark.parametrize("backend", ["dense", "pallas_persistent"])
def test_e2e_tracing_changes_nothing_and_explains(world, backend):
    """The overhead contract: tracing+explain must return bit-identical
    results and (persistent) add zero device dispatches; the launch spans
    must account for every driver dispatch 1:1."""
    ds, graph, cfg, dense, est = world
    engine = (dense if backend == "dense"
              else SearchEngine.build(ds, graph, backend=backend))
    wl = make_label_workload(ds, batch=12, kind="contain", seed=3)

    d0 = dispatch_counters()
    plain = e2e_search(engine, est, cfg, wl.queries, wl.spec,
                       probe_budget=48, alpha=1.5)
    d1 = dispatch_counters()
    tr = Tracer()
    traced = e2e_search(engine, est, cfg, wl.queries, wl.spec,
                        probe_budget=48, alpha=1.5, tracer=tr, explain=True)
    d2 = dispatch_counters()

    np.testing.assert_array_equal(np.asarray(plain.state.res_idx),
                                  np.asarray(traced.state.res_idx))
    np.testing.assert_array_equal(np.asarray(plain.state.res_dist),
                                  np.asarray(traced.state.res_dist))
    np.testing.assert_array_equal(np.asarray(plain.state.cnt),
                                  np.asarray(traced.state.cnt))
    np.testing.assert_array_equal(np.asarray(plain.predicted_budget),
                                  np.asarray(traced.predicted_budget))

    if backend == "pallas_persistent":
        launches_plain = d1["launches"] - d0["launches"]
        launches_traced = d2["launches"] - d1["launches"]
        assert launches_traced == launches_plain     # zero added dispatches
        # every driver dispatch produced exactly one "launch" span
        assert len(tr.spans(name="launch")) == launches_traced
        for sp in tr.spans(name="launch"):
            assert sp.attrs["steps"] >= 1 and sp.attrs["width"] >= 1

    names = {s.name for s in tr.spans()}
    assert {"probe", "feature-extract", "estimate", "resume"} <= names
    assert len(tr.spans(name="probe")) == 2          # n_probes=2 snapshots

    reports = traced.reports
    assert plain.reports is None and len(reports) == wl.batch
    buds = np.asarray(traced.predicted_budget)
    cnts = np.asarray(traced.state.cnt)
    for i, r in enumerate(reports):
        assert r.backend == backend and r.plan == "traverse"
        assert r.termination in ("budget", "queue-drained", "greedy",
                                 "active")
        assert r.predicted_budget == int(buds[i])
        assert r.actual_ndc == int(cnts[i]) and r.probe_ndc > 0
        assert [s.name for s in r.stages] == ["probe", "estimate", "resume",
                                              "rerank"]
        probe_st, _, resume_st, _ = r.stages
        assert probe_st.ndc + resume_st.ndc == r.actual_ndc
        assert probe_st.launches >= 1 and r.features  # named feature dict
        assert "ndc=" in r.format(features=True)


def test_scheduler_launch_accounting_and_telemetry(world):
    """Satellite: Σ per-batch launches recorded by the scheduler must equal
    the driver-observed dispatch count on a persistent engine — the old
    ⌈steps/steps_per_launch⌉ estimate undercounted compaction relaunches
    and multi-snapshot probes. Also pins scheduled bit-identity under
    tracing and the calibration/Prometheus surfaces."""
    ds, graph, cfg, dense, est = world
    engine = SearchEngine.build(ds, graph, backend="pallas_persistent")
    wl = make_label_workload(ds, batch=24, kind="contain", seed=11)
    scfg = ServeConfig(lane_width=8, probe_budget=48)

    def run(tracer, calibration):
        sch = CostAwareScheduler(engine, est, cfg, scfg, tracer=tracer,
                                 calibration=calibration)
        reqs = requests_from_workload(wl, arrivals=np.zeros(wl.batch))
        d0 = dispatch_counters()["launches"]
        for r in reqs:
            sch.submit(r, now=0.0)
        sch.run_until_idle(now=0.0)
        return sch, reqs, dispatch_counters()["launches"] - d0

    tr = Tracer()
    s1, r1, delta = run(tr, True)
    s2, r2, delta2 = run(None, False)
    for a, b in zip(r1, r2):
        assert np.array_equal(a.res_idx, b.res_idx)
        assert np.array_equal(a.res_dist, b.res_dist)
        assert a.ndc == b.ndc
    assert delta == delta2                           # tracing adds nothing

    summ = s1.summary()
    assert summ["launches_total"] == delta           # 1:1 accounting
    assert summ["launches_total"] == sum(
        p["launches"] for p in summ["batches_by_phase"].values())

    n_miss = sum(1 for r in r1 if not r.cache_hit)
    rep = s1.calibration_report()
    assert rep["n_records"] == n_miss                # cache hits not recorded
    assert set(rep["per_plan"]) <= set(PLAN_NAMES)
    assert s2.calibration_report() is None           # opt-out honored

    names = validate_prometheus(s1.prometheus())
    assert "repro_calibration_records_total" in names
    assert all(r.trace_id.startswith("req-") for r in r1)
    assert len(tr.spans(name="admit")) == wl.batch
    assert len(tr.spans(name="complete")) == wl.batch
    done = tr.spans(name="probe-done")
    assert 0 < len(done) <= wl.batch                 # cache hits skip probe
    assert all("rid" in s.attrs and "budget" in s.attrs for s in done)
