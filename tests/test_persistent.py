"""Persistent multi-step traversal: registry, bit-parity with the
single-step pallas backend across codecs and modes, launch-boundary
resume round-trips, steps_per_launch invariance, scheduler integration,
and interpret-mode parity of the VMEM-resident multi-step kernel."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (BIG_BUDGET, SearchConfig, SearchEngine,
                        available_backends, get_backend)
from repro.data import make_dataset, make_label_workload
from repro.index import build_graph_index

QCFG = dict(pq_subspaces=8, pq_centroids=32, pq_iters=8)


@pytest.fixture(scope="module")
def world():
    ds = make_dataset(n=2000, dim=24, n_clusters=6, alphabet_size=32, seed=0)
    graph = build_graph_index(ds.vectors, degree=16, seed=0)
    engines = {
        p: SearchEngine.build(ds, graph, precision=p, quant_cfg=QCFG)
        for p in ("float32", "int8", "pq")
    }
    return ds, graph, engines


def _workload(ds, batch=13, seed=3):
    # odd batch: the driver's power-of-two compaction ladder must pad
    wl = make_label_workload(ds, batch=batch, kind="contain", seed=seed)
    return wl, SearchConfig(k=5, queue_size=64)


def _assert_states_equal(a, b, quantized=False):
    """Exact equality on every field. For quantized codecs the two float
    distance fields are compared to the repo's standard kernel-vs-host
    tolerance instead: lane compaction changes the batch width per launch,
    and XLA:CPU contracts the int8-ADC/PQ-LUT reductions differently at
    different widths (the same ULP-level FMA effect test_quant pins for
    kernel vs dense). Ids, counters, visited bits stay exact."""
    float_fields = ("cand_dist", "res_dist", "q_err_sum", "d_start")
    for f in a._fields:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        if quantized and f in float_fields:
            np.testing.assert_array_equal(
                np.isfinite(x), np.isfinite(y),
                err_msg=f"SearchState field {f!r} finite pattern")
            fin = np.isfinite(x)
            np.testing.assert_allclose(x[fin], y[fin], rtol=1e-5, atol=1e-5,
                                       err_msg=f"SearchState field {f!r}")
        else:
            np.testing.assert_array_equal(
                x, y, err_msg=f"SearchState field {f!r} diverged")


# ------------------------------------------------------------- registry ----
def test_registry_has_persistent():
    assert "pallas_persistent" in available_backends()
    bk = get_backend("pallas_persistent")
    assert getattr(bk, "persistent", False) is True
    # single-step backends must not accidentally grow the flag
    assert not getattr(get_backend("pallas"), "persistent", False)
    assert not getattr(get_backend("dense"), "persistent", False)


# --------------------------------------------------------------- parity ----
@pytest.mark.parametrize("mode", ["post", "pre", "widen"])
@pytest.mark.parametrize("precision", ["float32", "int8", "pq"])
def test_persistent_matches_pallas_every_field(world, mode, precision):
    """The acceptance bar: every SearchState field identical to the
    single-step pallas backend (quant distance fields to the standard
    tolerance, see _assert_states_equal), for all three codecs × all three
    traversal modes, under heterogeneous per-lane budgets (so lanes
    terminate at different launches and the compaction ladder engages)."""
    ds, _, engines = world
    eng = engines[precision]
    wl, cfg = _workload(ds)
    cfg = dataclasses.replace(cfg, mode=mode)
    buds = np.random.default_rng(0).integers(40, 900, size=wl.batch)
    buds = buds.astype(np.int32)
    a = eng.search(dataclasses.replace(cfg, backend="pallas"),
                   wl.queries, wl.spec, buds)
    b = eng.search(dataclasses.replace(cfg, backend="pallas_persistent"),
                   wl.queries, wl.spec, buds)
    _assert_states_equal(a, b, quantized=precision != "float32")


@pytest.mark.parametrize("spl", [1, 3, 8, 64])
def test_steps_per_launch_invariance(world, spl):
    """The launch width is a dispatch knob, not a semantic one."""
    ds, _, engines = world
    eng = engines["float32"]
    wl, cfg = _workload(ds)
    ref = eng.search(dataclasses.replace(cfg, backend="pallas"),
                     wl.queries, wl.spec, 700)
    out = eng.search(
        dataclasses.replace(cfg, backend="pallas_persistent",
                            steps_per_launch=spl),
        wl.queries, wl.spec, 700)
    _assert_states_equal(ref, out)


def test_greedy_stop_parity(world):
    ds, _, engines = world
    eng = engines["float32"]
    wl, cfg = _workload(ds)
    cfg = dataclasses.replace(cfg, greedy_stop=True)
    a = eng.search(dataclasses.replace(cfg, backend="pallas"),
                   wl.queries, wl.spec, BIG_BUDGET)
    b = eng.search(dataclasses.replace(cfg, backend="pallas_persistent"),
                   wl.queries, wl.spec, BIG_BUDGET)
    _assert_states_equal(a, b)


def test_max_steps_cutoff_parity(world):
    """max_steps accounting across launches == the flat loop's cutoff,
    including cutoffs that land mid-launch."""
    ds, _, engines = world
    eng = engines["float32"]
    wl, cfg = _workload(ds)
    for max_steps in (1, 5, 17):
        c = dataclasses.replace(cfg, max_steps=max_steps)
        a = eng.search(dataclasses.replace(c, backend="pallas"),
                       wl.queries, wl.spec, BIG_BUDGET)
        b = eng.search(dataclasses.replace(c, backend="pallas_persistent"),
                       wl.queries, wl.spec, BIG_BUDGET)
        _assert_states_equal(a, b)


# ------------------------------------------------------ probe / resume ----
def test_probe_resume_roundtrip_at_launch_boundaries(world):
    """A probe stopped anywhere (budget boundaries ≠ launch boundaries)
    resumes bit-exactly — the launch grouping must exit with a full
    SearchState at whatever step the budget landed on."""
    ds, _, engines = world
    eng = engines["float32"]
    wl, cfg = _workload(ds)
    cfg = dataclasses.replace(cfg, backend="pallas_persistent",
                              steps_per_launch=8)
    one = eng.search(cfg, wl.queries, wl.spec, 700)
    st = eng.search(cfg, wl.queries, wl.spec, 120)  # mid-launch budgets
    st = eng.search(cfg, wl.queries, wl.spec, 700, state=st)
    _assert_states_equal(one, st)


@pytest.mark.parametrize("precision", ["int8", "pq"])
def test_cross_backend_resume(world, precision):
    """Persistent probe → single-step resume (and the reverse): the carry
    is one bit-compatible SearchState, so the serving layer may mix
    backends across slices."""
    ds, _, engines = world
    eng = engines[precision]
    wl, cfg = _workload(ds)
    cp = dataclasses.replace(cfg, backend="pallas_persistent")
    cs = dataclasses.replace(cfg, backend="pallas")
    one = eng.search(cs, wl.queries, wl.spec, 700)
    st = eng.search(cp, wl.queries, wl.spec, 120)
    st = eng.search(cs, wl.queries, wl.spec, 700, state=st)
    _assert_states_equal(one, st, quantized=True)
    st = eng.search(cs, wl.queries, wl.spec, 120)
    st = eng.search(cp, wl.queries, wl.spec, 700, state=st)
    _assert_states_equal(one, st, quantized=True)


def test_run_search_donation_does_not_copy_semantics(world):
    """Donated resume: the returned state is correct and the donated carry
    is consumed (reusing it raises on CPU) — callers pass fresh slices."""
    ds, _, engines = world
    eng = engines["float32"]
    wl, cfg = _workload(ds)
    cfg = dataclasses.replace(cfg, backend="pallas")
    one = eng.search(cfg, wl.queries, wl.spec, 700)
    st = eng.search(cfg, wl.queries, wl.spec, 120)
    keep = jax.tree.map(jnp.copy, st)
    out = eng.search(cfg, wl.queries, wl.spec, 700, state=st)
    _assert_states_equal(one, out)
    with pytest.raises(RuntimeError):
        np.asarray(st.cnt)  # donated buffer is gone
    out2 = eng.search(cfg, wl.queries, wl.spec, 700, state=keep)
    _assert_states_equal(one, out2)


# ------------------------------------------------------------ scheduler ----
def test_scheduled_equals_oneshot_persistent(world):
    """Scheduling on a persistent engine stays bit-invisible, and the
    metrics record launch amortization + early-exit lane fractions."""
    from repro.core import CostEstimator, e2e_search, generate_training_data
    from repro.serve import (CostAwareScheduler, ServeConfig,
                             requests_from_workload)

    ds, graph, engines = world
    engine = SearchEngine.build(ds, graph, backend="pallas_persistent")
    cfg = SearchConfig(k=5, queue_size=64)
    wl_tr = make_label_workload(ds, batch=96, kind="contain", seed=7)
    td = generate_training_data(engine, ds, wl_tr, cfg, probe_budget=48,
                                chunk=48)
    est = CostEstimator.fit(td.features, td.w_q, n_trees=40, depth=4)

    wl = make_label_workload(ds, batch=12, kind="contain", seed=42)
    one = e2e_search(engine, est, cfg, wl.queries, wl.spec, probe_budget=48,
                     alpha=1.5)
    scfg = ServeConfig(lane_width=8, buckets=(128, 512, None),
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
        np.asarray([r.ndc for r in reqs]), np.asarray(one.state.cnt))

    summ = sched.metrics.summary()
    probe = summ["batches_by_phase"]["probe"]
    # a persistent engine amortizes: strictly fewer launches than steps.
    # Launch counts are driver-observed dispatches (core.search dispatch
    # counters), never fewer than the ⌈steps/spl⌉ lower bound — a probe
    # dispatches once per snapshot and compaction relaunches add more.
    spl = max(1, cfg.steps_per_launch)
    probe_steps = [b["steps"] for b in sched.metrics.batches
                   if b["phase"] == "probe"]
    assert probe["launches"] >= sum(-(-s // spl) for s in probe_steps)
    assert 0 < probe["launches"] < sum(probe_steps)  # amortization is real
    assert 0.0 <= probe["early_exit_frac"] <= 1.0


# --------------------------------------------- interpret-mode kernel ----
@pytest.mark.parametrize("precision", ["float32", "int8", "pq"])
def test_persistent_kernel_interpret_parity(precision):
    """The VMEM-resident multi-step kernel vs U host single-steps, in
    Pallas interpret mode. float32 is fully bit-exact; compressed codecs
    pin ids/counters/visited exactly and distances to the repo's standard
    kernel-vs-host tolerance (XLA contracts FMAs differently between the
    two graphs). Micro sizes keep the unrolled bitonic networks (width 16)
    and the per-lane DMA unroll within XLA:CPU's compile budget."""
    from repro.core.state import init_state
    from repro.core.step import make_step
    from repro.filters import FilterSpec
    from repro.filters.compile import compile_spec
    from repro.filters.predicates import PRED_RANGE
    from repro.kernels.persistent_step import (build_persistent_operands,
                                               persistent_multi_step)
    from repro.quant.codecs import build_quant_index, prepare_query

    n, dim, r, b, m, k, u = 256, 8, 8, 8, 8, 4, 6
    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(n, dim)).astype(np.float32))
    nbrs = rng.integers(0, n, size=(n, r)).astype(np.int32)
    self_loop = nbrs == np.arange(n)[:, None]
    nbrs[self_loop] = (nbrs[self_loop] + 1) % n
    nbrs = jnp.asarray(nbrs)
    labels = jnp.asarray(rng.integers(0, 2 ** 16, size=(n, 1)).astype(np.uint32))
    values = jnp.asarray(rng.random((n, 1)).astype(np.float32))
    queries = jnp.asarray(rng.normal(size=(b, dim)).astype(np.float32))
    spec = FilterSpec(PRED_RANGE, None, np.full(b, 0.2, np.float32),
                      np.full(b, 0.9, np.float32))
    prog = jax.tree.map(jnp.asarray, compile_spec(spec, 1))
    budgets = jnp.asarray(rng.integers(20, 120, size=(b,)).astype(np.int32))
    gt = jnp.asarray(np.sort(rng.random((b, k)).astype(np.float32), axis=1))

    cfg = SearchConfig(k=k, queue_size=m, degree=r, mode="post",
                       precision=None if precision == "float32" else precision)
    quant = qprep = None
    if precision != "float32":
        quant = build_quant_index(precision, vecs, pq_subspaces=4,
                                  pq_centroids=16, pq_levels=1)
        qprep = prepare_query(precision, quant, queries)
    st0 = init_state(cfg, queries, prog, vecs, (labels, values), 0,
                     quant=quant, qprep=qprep)
    step = make_step(cfg, get_backend("pallas"), queries, prog, vecs,
                     (labels, values), nbrs, budgets, gt, quant=quant,
                     qprep=qprep)
    host = st0
    for _ in range(u):
        host = step(host)

    rows, aux, nbr_rows = build_persistent_operands(
        precision, vecs, labels, values, nbrs, quant)
    kern = persistent_multi_step(cfg, queries, prog, rows, aux, nbr_rows,
                                 budgets, st0, jnp.int32(10 ** 6), gt, qprep,
                                 steps=u, n_values=1, has_gt=True,
                                 interpret=True, block_b=4)
    for f in st0._fields:
        a, b_ = np.asarray(getattr(host, f)), np.asarray(getattr(kern, f))
        if precision != "float32" and f in ("cand_dist", "res_dist"):
            np.testing.assert_array_equal(np.isfinite(a), np.isfinite(b_),
                                          err_msg=f"{f} finite pattern")
            fin = np.isfinite(a)
            np.testing.assert_allclose(a[fin], b_[fin], rtol=1e-5,
                                       atol=1e-5, err_msg=f)
        else:
            np.testing.assert_array_equal(a, b_, err_msg=f)


@pytest.mark.parametrize("precision", ["float32", "int8"])
def test_persistent_operands_packed_once_per_engine(world, precision):
    """The kernel's lane-padded HBM operands are packed on first use and
    kept on the engine (probe and resume reuse them); replacing a source
    array repacks them."""
    _, _, engines = world
    eng = dataclasses.replace(engines[precision])  # a fresh operand cache
    first = eng.persistent_operands(precision)
    again = eng.persistent_operands(precision)
    assert all(a is b for a, b in zip(first, again))
    rows, aux, nbrs = first
    n, r = eng.neighbors.shape
    for a in first:
        assert a.shape[0] == n and a.shape[1] % 128 == 0
    np.testing.assert_array_equal(np.asarray(nbrs)[:, :r],
                                  np.asarray(eng.neighbors))
    assert not np.asarray(nbrs)[:, r:].any()
    eng.neighbors = eng.neighbors + 0
    repacked = eng.persistent_operands(precision)
    assert repacked[2] is not nbrs
    np.testing.assert_array_equal(np.asarray(repacked[2]), np.asarray(nbrs))
