"""The plain reference agrees with the program's exact oracle
(`index/bruteforce.filtered_knn_exact`) on every filter kind the traffic
makes, and its lower-precision controls are worse than it."""
import numpy as np
import pytest

from bench.lib import compare, data
from bench.lib.reference import Reference
from bench.lib.traffic import TrafficGen

K = 10
CFG = {"n": 4096, "dim": 32, "n_clusters": 8, "center_norm": 0.25,
       "spectrum_decay": 1.5,
       "labels": {"kind": "cluster_zipf", "alphabet_size": 40,
                  "max_labels": 3, "label_skew": 2.0},
       "values": {"kind": "linear_probe", "value_noise": 0.1}}
MIX = {"query_noise": 0.05, "filters": [
    {"kind": "contain", "share": 1, "hard_fraction": 0.5},
    {"kind": "range", "share": 1, "selectivities": [0.01, 0.2],
     "hard_fraction": 0.5},
    {"kind": "contain_and_range", "share": 1, "passing_rows": [20, 200],
     "hard_fraction": 0.5}]}


@pytest.fixture(scope="module")
def setup():
    dep = data.generate(CFG, 11)
    batch = TrafficGen(MIX, dep).batch(60, np.random.default_rng(4))
    return dep, batch, Reference(dep.vectors, dep.labels_packed, dep.values)


def test_matches_program_oracle(setup):
    from repro.index.bruteforce import filtered_knn_exact

    dep, batch, ref = setup
    ids, dist = ref.search(batch.queries, batch.filters, K, q_block=32,
                           block=1024)
    want_i, want_d = filtered_knn_exact(batch.queries, dep.vectors,
                                        batch.exprs, dep.labels_packed,
                                        dep.values, K)
    np.testing.assert_array_equal(ids, want_i)
    fin = np.isfinite(want_d)
    np.testing.assert_array_equal(np.isfinite(dist), fin)
    np.testing.assert_allclose(dist[fin], want_d[fin], rtol=1e-5, atol=1e-6)


def test_equality_filters():
    from repro.index.bruteforce import filtered_knn_exact

    cfg = dict(CFG, labels={"kind": "uniform_single", "alphabet_size": 12},
               values={"kind": "uniform"})
    dep = data.generate(cfg, 12)
    mix = {"query_noise": 0.05, "filters": [{"kind": "equal", "share": 1}]}
    batch = TrafficGen(mix, dep).batch(24, np.random.default_rng(5))
    ids, _ = Reference(dep.vectors, dep.labels_packed, dep.values).search(
        batch.queries, batch.filters, K, q_block=8, block=1024)
    want, _ = filtered_knn_exact(batch.queries, dep.vectors, batch.exprs,
                                 dep.labels_packed, dep.values, K)
    np.testing.assert_array_equal(ids, want)


def test_score_checks_filter_and_distance(setup):
    dep, batch, ref = setup
    ids, dist = ref.search(batch.queries, batch.filters, K, q_block=32,
                           block=1024)
    true_d, ok = ref.score(batch.queries, ids, batch.filters)
    assert ok[ids >= 0].all() and not ok[ids < 0].any()
    fin = ids >= 0
    np.testing.assert_allclose(true_d[fin], dist[fin], rtol=1e-5, atol=1e-6)
    nums, rec = compare.numbers(ids, dist, true_d, ok, ids, dist,
                                dep.n, 0)
    assert nums["bad_ids"] == 0 and nums["recall_loss"] == 0.0
    assert nums["dist_err_max"] < 1e-5


@pytest.mark.parametrize("mode", ["high", "int8"])
def test_controls_read_worse(setup, mode):
    dep, batch, ref = setup
    r_ids, r_d = ref.search(batch.queries, batch.filters, K, q_block=32,
                            block=1024)
    ids, dist = ref.search(batch.queries, batch.filters, K, mode=mode,
                           q_block=32, block=1024)
    true_d, ok = ref.score(batch.queries, ids, batch.filters)
    nums, _ = compare.numbers(ids, dist, true_d, ok, r_ids, r_d,
                              dep.n, 0)
    r_true, r_ok = ref.score(batch.queries, r_ids, batch.filters)
    base, _ = compare.numbers(r_ids, r_d, r_true, r_ok, r_ids, r_d,
                              dep.n, 0)
    assert nums["dist_err_max"] > 3 * base["dist_err_max"]
