"""Every seed gets the deployment's requests, in another order: the same
queries and filters within each block of the mix's `order_block`, so
the same recall set; a closed loop replays its pool in order."""
import numpy as np

from bench.lib import data, harness, manifest
from bench.lib.traffic import (TrafficGen, block_order, closed_pool,
                               recall_requests)

CELL = "sift1m-eq12-int8.eq.closed"


def test_block_order_permutes_within_blocks():
    idx = block_order(100, 32, np.random.default_rng(2**31 + 5))
    assert sorted(idx) == list(range(100))
    for start in range(0, 100, 32):
        assert sorted(idx[start:start + 32]) == list(
            range(start, min(start + 32, 100)))
    assert not np.array_equal(idx, np.arange(100))


def test_seeds_reorder_the_same_window_requests():
    cell = manifest.load_cell(CELL)
    cfg = harness.deep_merge(cell.config, {"data": {"n": 1024}})
    mix = harness.deep_merge(cell.traffic, {"outstanding": 8,
                                            "pool_qps": 20})
    dep = data.generate(cfg["data"], cfg["deployment_seed"])
    gen = TrafficGen(mix, dep)
    pool = cfg["deployment_seed"]
    a, _ = harness.window_requests(mix, gen, pool, 2**31 + 3, 2.0)
    b, _ = harness.window_requests(mix, gen, pool, 3_900_000_007, 2.0)
    assert len(a.exprs) == len(b.exprs) == 48
    assert not np.array_equal(a.queries, b.queries)
    block = mix["order_block"]
    for start in range(0, 48, block):
        sl = slice(start, start + block)
        ka = sorted(map(tuple, np.c_[a.queries[sl], a.filters.equal[sl]]))
        kb = sorted(map(tuple, np.c_[b.queries[sl], b.filters.equal[sl]]))
        assert ka == kb
    again, _ = harness.window_requests(mix, gen, pool, 2**31 + 3, 2.0)
    np.testing.assert_array_equal(again.queries, a.queries)


def _rows(batch, sl):
    return sorted(map(tuple, np.c_[batch.queries[sl], batch.filters.equal[sl]]))


class _Echo:
    """A scheduler that completes whatever was submitted at its next
    pump."""

    def __init__(self):
        self.q = []

    def submit(self, req, now):
        self.q.append(req)

    def has_work(self):
        return bool(self.q)

    def pump(self, now):
        done, self.q = self.q, []
        return done, now


def test_recall_set_is_fixed_and_replay_follows_the_pool():
    cell = manifest.load_cell(CELL)
    cfg = harness.deep_merge(cell.config, {"data": {"n": 1024}})
    mix, seconds = cell.traffic, 51.0
    dep = data.generate(cfg["data"], cfg["deployment_seed"])
    gen = TrafficGen(mix, dep)
    n_recall = recall_requests(mix, seconds)
    pool = closed_pool(mix, seconds)
    assert (n_recall, pool) == (480, 1148)
    a, _ = harness.window_requests(mix, gen, cfg["deployment_seed"],
                                   2**31 + 3, seconds)
    b, _ = harness.window_requests(mix, gen, cfg["deployment_seed"],
                                   3_900_000_007, seconds)
    first = slice(0, n_recall)
    assert not np.array_equal(a.queries[first], b.queries[first])
    assert _rows(a, first) == _rows(b, first)

    small = a.take(np.arange(40))
    subs, sent, done = harness.serve_closed(_Echo(), small, 8, 0.05)
    assert len(subs) > 2 * 40 and np.isfinite(done).all()
    assert [r.rid for r in subs] == list(range(len(subs)))
    for j, r in enumerate(subs):
        assert r.expr is small.exprs[j % 40]
        np.testing.assert_array_equal(r.query, small.queries[j % 40])
