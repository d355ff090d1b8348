"""Every seed gets the deployment's requests, in another order: the same
queries and filters within each block of the mix's `order_block`."""
import numpy as np

from bench.lib import data, harness, manifest
from bench.lib.traffic import TrafficGen, block_order

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
