"""Plan-parity + property test layer for the adaptive per-query planner.

Pins the three contracts the planning layer is built on:
  1. the pre-filter scan plan is bit-identical to the bruteforce oracle on
     float32 (dense + pallas dispatch, match-nothing / match-all included);
  2. a planner forced to one plan equals calling that plan directly —
     counters included — so "auto" can only ever *choose*, never perturb;
  3. routing never loses recall: planner recall ≥ best single plan (−2pp)
     and planned NDC ≤ standard traversal NDC on selective conjunctions.
"""
import dataclasses
import functools

import numpy as np
import pytest

from repro.core import (PLANS, SearchConfig, SearchEngine, extract_features,
                        fit_planner, generate_plan_training_data,
                        planned_search, run_plan, scan_search, scan_stats)
from repro.core.planner import static_features
from repro.core.plans import ScanStats
from repro.core.step import gather_frontier
from repro.data import make_composite_workload, make_dataset
from repro.filters import And, Contain, Range
from repro.index import build_graph_index
from repro.index.bruteforce import filtered_knn_exact, recall_at_k

from tests._hyp_compat import given, settings, st


# Cached module-level builders (not only fixtures): the hypothesis shim's
# @given wrapper takes no pytest fixtures, so the property test calls these
# directly.
@functools.lru_cache(maxsize=1)
def _world():
    ds = make_dataset(n=2500, dim=24, n_clusters=6, alphabet_size=32, seed=0)
    graph = build_graph_index(ds.vectors, degree=16, seed=0)
    engine = SearchEngine.build(ds, graph)
    cfg = SearchConfig(k=5, queue_size=64, degree=16)
    return ds, engine, cfg


@functools.lru_cache(maxsize=1)
def _planner():
    ds, engine, cfg = _world()
    wl = make_composite_workload(ds, batch=96, seed=11, structure="mixed",
                                 selectivities=(0.01, 0.1, 0.3))
    data = generate_plan_training_data(engine, ds, wl, cfg, probe_budget=48,
                                       chunk=48)
    return fit_planner(data, probe_budget=48, n_trees=60, depth=4)


@pytest.fixture(scope="module")
def world():
    return _world()


@pytest.fixture(scope="module")
def planner():
    return _planner()


def _oracle(ds, wl_or_filters, queries, k):
    filt = (wl_or_filters.filters
            if hasattr(wl_or_filters, "filters") else wl_or_filters)
    return filtered_knn_exact(queries, ds.vectors, filt, ds.labels_packed,
                              ds.value_matrix, k)


# ------------------------------------------------------------ scan plan ----
@pytest.mark.parametrize("structure", ["and", "mixed"])
def test_scan_bit_identity_vs_oracle(world, structure):
    """The scan plan IS the oracle: same distance source, same stable tie
    order — identical idx and bitwise-identical f32 distances."""
    ds, engine, cfg = world
    wl = make_composite_workload(ds, batch=24, seed=3, structure=structure,
                                 selectivities=(0.01, 0.1, 0.4))
    st_ = scan_search(engine, cfg, wl.queries, wl.filters)
    gi, gd = _oracle(ds, wl, wl.queries, cfg.k)
    assert np.array_equal(np.asarray(st_.res_idx), gi)
    assert np.array_equal(
        np.asarray(st_.res_dist).view(np.uint32), gd.view(np.uint32))
    # cost accounting is closed-form: cnt == σ·N exactly, 0 traversal hops
    stats = scan_stats(engine, engine.compile(wl.filters))
    assert np.array_equal(np.asarray(st_.cnt), stats.counts)
    assert not np.asarray(st_.hops).any()
    assert not np.asarray(st_.active).any()   # terminal — never resumed


def test_scan_match_nothing_and_match_all(world):
    ds, engine, cfg = world
    exprs = [Range(1e9, 1e9 + 1),          # matches nothing
             Range(-1e9, 1e9),             # matches everything
             And(Contain([1]), Range(1e9, 1e9 + 1))]  # conjunction → nothing
    q = np.asarray(ds.vectors[:3], np.float32)
    st_ = scan_search(engine, cfg, q, exprs)
    gi, gd = _oracle(ds, exprs, q, cfg.k)
    assert np.array_equal(np.asarray(st_.res_idx), gi)
    assert np.array_equal(
        np.asarray(st_.res_dist).view(np.uint32), gd.view(np.uint32))
    cnt = np.asarray(st_.cnt)
    assert cnt[0] == 0 and cnt[1] == ds.vectors.shape[0] and cnt[2] == 0
    # match-nothing rows pad with the oracle's sentinels
    assert (np.asarray(st_.res_idx)[0] == -1).all()
    assert np.isinf(np.asarray(st_.res_dist)[0]).all()


def test_scan_pallas_kernel_matches_host(world):
    """The TPU scan path (the traversal's masked-distance Pallas kernel)
    agrees with the per-lane host path on SCAN_ALIGN-shaped blocks."""
    from repro.kernels.distance import (SCAN_ALIGN, scan_sqdist_lanes,
                                        sqdist_masked)

    rng = np.random.default_rng(0)
    b, v, d = 6, 2 * SCAN_ALIGN, 24
    q = rng.standard_normal((b, d)).astype(np.float32)
    x = rng.standard_normal((b, v, d)).astype(np.float32)
    mask = rng.random((b, v)) < 0.7
    host = np.asarray(scan_sqdist_lanes(q, x, mask))
    kern = np.asarray(sqdist_masked(q, x, mask, interpret=True))
    assert np.isinf(host[~mask]).all() and np.isinf(kern[~mask]).all()
    np.testing.assert_allclose(kern[mask], host[mask], rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="SCAN_ALIGN"):
        scan_sqdist_lanes(q, x[:, : SCAN_ALIGN + 1], mask[:, : SCAN_ALIGN + 1])


def _bitmap_exprs(ds, b):
    """b filters cycling through every clause kind, negations included:
    a bare Not matches the zero label words and values of pad rows."""
    from repro.filters import Equal, In, Not, Or
    from repro.filters.expr import labels_from_mask

    own = labels_from_mask(np.asarray(ds.labels_packed)[7])
    pool = [Not(Range(0.2, 0.7, attr=1)), Equal(own), In([2, 5, 30]),
            And(In([3, 4]), Not(Contain([9]))), Contain([1]),
            Range(0.2, 0.7), Not(Equal(own)),
            Or(Range(-1e9, 0.1), And(Equal(own), Not(In([0])))),
            Not(In([0, 1, 2, 3])), Range(1e9, 1e9 + 1),
            Or(Contain([2, 7]), Not(Range(0.3, 0.6)), In([11])),
            And(Range(0.1, 0.9), Range(0.0, 0.5, attr=1), Not(Contain([4]))),
            Not(And(In([6]), Range(0.4, 1.0))), Contain([12]),
            Or(Not(Equal(own)), Range(0.0, 0.01)), In(list(range(32)))]
    return [pool[i % len(pool)] for i in range(b)]


@pytest.mark.parametrize("chunk", [384, 2048, None])
@pytest.mark.parametrize("b", [1, 4, 16])
def test_bitmap_pass_matches_host_oracle(world, b, chunk):
    """The one-pass bitmap equals the host oracle bit for bit, with N
    (2500) no multiple of an explicit chunk, and in the default one step:
    `valid` against bruteforce.valid_mask, counts its popcount, and
    clause_frac each canonical slot's literal counted over the store on
    the host."""
    from repro.filters.compile import CLAUSE_FEATURE_SLOTS, eval_program_matrix
    from repro.filters.expr import canonical_dnf, eval_leaf
    from repro.index.bruteforce import valid_mask

    ds, engine, _ = world
    exprs = _bitmap_exprs(ds, b)
    labels, values = np.asarray(ds.labels_packed), np.asarray(ds.value_matrix)
    n = labels.shape[0]
    assert chunk is None or n % chunk
    valid, counts, frac = eval_program_matrix(
        engine.compile(exprs), engine.label_attrs, engine.value_attrs,
        chunk=chunk)

    want = valid_mask(exprs, labels, values)
    assert np.array_equal(np.asarray(valid), want)
    assert counts.dtype == np.int64
    assert np.array_equal(counts, want.sum(axis=1))
    cc = np.zeros((b, CLAUSE_FEATURE_SLOTS), np.int64)
    for i, e in enumerate(exprs):
        lits = [lit for term in canonical_dnf(e) for lit in term]
        for j, (leaf, neg) in enumerate(lits[:CLAUSE_FEATURE_SLOTS]):
            cc[i, j] = (eval_leaf(leaf, labels, values) ^ neg).sum()
    ref = np.asarray(cc, np.float32) / float(n)
    assert np.array_equal(frac.view(np.uint32), ref.view(np.uint32))


@pytest.mark.parametrize("b,slots,words,n,want", [
    (16, 1, 1, 1 << 20, 1 << 18),     # the served cell: 4 steps
    (4, 1, 1, 1 << 20, 1 << 20),      # one step, capped at N
    (16, 1, 1, 2500, 2500),           # a small store: one step, no pad
    (16, 32, 4, 1 << 20, 2048),       # the widest program: smaller steps
    (256, 32, 4, 1 << 20, 1024),      # floor
])
def test_bitmap_chunk_bounds_the_step(b, slots, words, n, want):
    """The bitmap pass's rows per step follow the program's shape: a
    power of two keeping [B, S, rows, W] near BITMAP_STEP_ELEMENTS, at
    least 1024 rows and at most N."""
    from repro.filters import Equal
    from repro.filters.compile import (BITMAP_STEP_ELEMENTS, bitmap_chunk,
                                       compile_filters, pad_program)

    prog = pad_program(compile_filters([Equal([0])], words), n_slots=slots,
                       batch=b)
    assert prog.masks.shape == (b, slots, words)
    rows = bitmap_chunk(prog, n)
    assert rows == want
    assert b * slots * words * rows <= max(BITMAP_STEP_ELEMENTS,
                                           b * slots * words * 1024)


def test_scan_stats_leave_bitmap_on_device(world, monkeypatch):
    """Stage 0 copies only the counts to the host: the [B, N] bitmap is a
    device array, and the one explicit copy holds the counts [B] and the
    clause counts [B, CLAUSE_FEATURE_SLOTS]. The guard refuses implicit
    copies where the backend enforces it (accelerators; not the CPU)."""
    import jax

    from repro.filters import compile as fc

    fetched, real_get = [], jax.device_get

    def spy(x):
        fetched.extend(np.shape(a) for a in jax.tree.leaves(x))
        return real_get(x)

    monkeypatch.setattr(fc.jax, "device_get", spy)
    ds, engine, _ = world
    exprs = _bitmap_exprs(ds, 4)
    prog = engine.compile(exprs)
    with jax.transfer_guard_device_to_host("disallow"):
        stats = scan_stats(engine, prog)
    monkeypatch.undo()
    assert fetched == [(4,), (4, fc.CLAUSE_FEATURE_SLOTS)]
    assert isinstance(stats.valid, jax.Array)
    assert isinstance(stats.counts, np.ndarray)
    assert isinstance(stats.clause_frac, np.ndarray)
    sub = stats.rows(np.array([3, 0]))
    assert isinstance(sub.valid, jax.Array)
    want = np.asarray(stats.valid)[[3, 0]]
    assert np.array_equal(np.asarray(sub.valid), want)
    assert np.array_equal(sub.counts, want.sum(axis=1))


def test_scan_lane_and_width_invariance(world):
    """A lane's scan result is independent of batchmates and of the padded
    gather width — the property serving-time batch shapes rely on."""
    ds, engine, cfg = world
    wl = make_composite_workload(ds, batch=12, seed=5, structure="and",
                                 selectivities=(0.02, 0.3))
    full = scan_search(engine, cfg, wl.queries, wl.filters)
    sub_idx = [1, 4, 9]
    sub = scan_search(engine, cfg, wl.queries[sub_idx],
                      [wl.exprs[i] for i in sub_idx])
    for leaf_full, leaf_sub in zip(
            (full.res_idx, full.res_dist, full.cand_dist, full.cnt),
            (sub.res_idx, sub.res_dist, sub.cand_dist, sub.cnt)):
        assert np.array_equal(np.asarray(leaf_full)[sub_idx],
                              np.asarray(leaf_sub))


def test_quant_scan_pool_covers_exact(world):
    """Compressed-domain scan + exact rerank recovers the float32 oracle
    exactly whenever the candidate queue holds the whole valid set."""
    ds, _, _ = world
    graph = build_graph_index(ds.vectors, degree=16, seed=0)
    engine8 = SearchEngine.build(ds, graph, precision="int8")
    cfg8 = SearchConfig(k=5, queue_size=64, degree=16, precision="int8")
    wl = make_composite_workload(ds, batch=16, seed=7, structure="and",
                                 selectivities=(0.005, 0.01))
    stats = scan_stats(engine8, engine8.compile(wl.filters))
    assert (stats.counts <= cfg8.queue_size).all()   # pool ⊇ valid set
    st_ = scan_search(engine8, cfg8, wl.queries, wl.filters)
    assert (np.asarray(st_.q_err_sum)[stats.counts > 0] > 0).all()
    st_ = engine8.rerank(cfg8, wl.queries, st_)
    gi, _ = _oracle(ds, wl, wl.queries, cfg8.k)
    assert np.array_equal(np.asarray(st_.res_idx), gi)


# ----------------------------------------------------------- widen mode ----
def _widen_frontier_ref(neighbors, u, stride):
    """Independent host reference for the widened frontier: 1-hop ∪ strided
    2-hop, first occurrence kept, later duplicates blanked to -1."""
    nb = list(neighbors[u])
    out = list(nb)
    n2 = len(neighbors[0][::stride])
    for v in nb:
        out.extend(list(neighbors[v][::stride]) if v >= 0 else [-1] * n2)
    seen, res = set(), []
    for x in out:
        if x >= 0 and x in seen:
            res.append(-1)
        else:
            res.append(int(x))
            if x >= 0:
                seen.add(int(x))
    return res


def test_widen_frontier_matches_reference(world):
    import jax.numpy as jnp

    ds, engine, cfg = world
    cfgw = dataclasses.replace(cfg, mode="widen", two_hop_stride=4)
    nb = np.asarray(engine.neighbors)
    rng = np.random.default_rng(3)
    u = rng.integers(0, nb.shape[0], size=8).astype(np.int32)
    got = np.asarray(gather_frontier(cfgw, jnp.asarray(nb), jnp.asarray(u)))
    for i, ui in enumerate(u):
        assert got[i].tolist() == _widen_frontier_ref(nb, ui, 4)


def test_widen_post_accounting_and_backend_parity(world):
    """widen pays distance NDC for every new neighbor (post accounting,
    unlike pre), and dense/pallas agree bitwise."""
    ds, engine, cfg = world
    wl = make_composite_workload(ds, batch=12, seed=9, structure="and",
                                 selectivities=(0.01, 0.05))
    cfgw = dataclasses.replace(cfg, mode="widen")
    st_ = engine.search(cfgw, wl.queries, wl.filters, budgets=600)
    assert np.array_equal(np.asarray(st_.cnt), np.asarray(st_.n_inspected))
    stp = engine.search(dataclasses.replace(cfgw, backend="pallas"),
                        wl.queries, wl.filters, budgets=600)
    for a, b in zip(st_, stp):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------- plan parity ----
@pytest.mark.parametrize("plan", PLANS)
def test_forced_plan_equals_direct(world, planner, plan):
    """planned_search(force_plan=X) ≡ run_plan(X) bitwise, every state
    leaf — counters included. The router can choose, never perturb."""
    ds, engine, cfg = world
    wl = make_composite_workload(ds, batch=16, seed=13, structure="mixed",
                                 selectivities=(0.01, 0.2))
    forced = planned_search(engine, planner, cfg, wl.queries, wl.filters,
                            probe_budget=48, alpha=1.2, force_plan=plan)
    direct = run_plan(engine, planner, plan, cfg, wl.queries, wl.filters,
                      probe_budget=48, alpha=1.2)
    assert (forced.plan == PLANS.index(plan)).all()
    for name, a, b in zip(direct._fields, forced.state, direct):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_planner_degenerate_stats(world, planner):
    """Zero passing candidates / single-query batches route to scan at
    stage 0 (no probe) and every feature stays finite."""
    ds, engine, cfg = world
    exprs = [Range(1e9, 1e9 + 1)]            # matches nothing
    q = np.asarray(ds.vectors[:1], np.float32)
    res = planned_search(engine, planner, cfg, q, exprs, probe_budget=48)
    assert res.plan.tolist() == [0] and res.pre_probe.all()
    assert int(res.state.cnt[0]) == 0
    assert (np.asarray(res.state.res_idx)[0] == -1).all()
    assert np.isfinite(np.asarray(extract_features(res.state))).all()
    # static features are finite even at σ = 0
    stats = scan_stats(engine, engine.compile(exprs))
    sf = static_features(stats, engine.compile(exprs))
    assert np.isfinite(sf).all() and sf[0, 0] == 0.0


def test_scan_states_keep_features_finite(world):
    """extract_features on terminal scan states (the planner may hand them
    to downstream feature consumers) is NaN-free, including lanes whose
    queue is empty."""
    ds, engine, cfg = world
    exprs = [Range(1e9, 1e9 + 1), Range(-1e9, 1e9), Contain([1])]
    q = np.asarray(ds.vectors[:3], np.float32)
    st_ = scan_search(engine, cfg, q, exprs)
    assert np.isfinite(np.asarray(extract_features(st_))).all()


# ------------------------------------------------------- property tests ----
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_property_planner_dominates_single_plans(seed):
    """On selective conjunctions the planner's recall is at least the best
    single plan's (−2pp) and its NDC no worse than standard traversal —
    the two clauses of the routing guarantee, at matched α."""
    ds, engine, cfg = _world()
    planner = _planner()
    wl = make_composite_workload(ds, batch=12, seed=seed, structure="and",
                                 selectivities=(0.005, 0.01))
    gi, _ = _oracle(ds, wl, wl.queries, cfg.k)
    auto = planned_search(engine, planner, cfg, wl.queries, wl.filters,
                          probe_budget=48, alpha=1.2)
    singles = {p: run_plan(engine, planner, p, cfg, wl.queries, wl.filters,
                           probe_budget=48, alpha=1.2) for p in PLANS}
    rec_auto = recall_at_k(np.asarray(auto.state.res_idx), gi).mean()
    best_single = max(
        recall_at_k(np.asarray(s.res_idx), gi).mean()
        for s in singles.values())
    assert rec_auto >= best_single - 0.02
    ndc_auto = np.asarray(auto.state.cnt, np.int64).mean()
    ndc_trav = np.asarray(singles["traverse"].cnt, np.int64).mean()
    assert ndc_auto <= ndc_trav
