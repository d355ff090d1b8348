"""One general generator for every traffic mix: a mix is a data file.

A mix file (`bench/traffic/<mix>.json`) holds:

  loop              "open" (arrivals by the clock) or "closed" (a fixed
                    number of requests outstanding)
  rate_qps          open loop: offered rate
  outstanding       closed loop: requests in flight
  pool_qps          closed loop: distinct requests drawn per window
                    second; the window's pool holds P = outstanding +
                    ceil(pool_qps x seconds) of them, and submission j
                    serves pool entry j mod P, so a loop that completes
                    more than P replays the pool instead of running dry
  recall_requests   closed loop: `recall_at_10` is the mean recall over
                    the first this many submissions (whole blocks of
                    `order_block`, at most P): the same requests for
                    every seed and at every rate. Submissions of this set
                    still due at the window's close go out during the
                    drain; they count toward recall and `correct`, never
                    toward `qps`
  warmup            [[depth, count], ...]: requests served closed-loop at
                    each depth before the window, to compile its shapes
  warmup_open_seconds  open loop: then this long at `rate_qps` (the lane
                    groups an open loop forms take more shapes)
  order_block       a run's seed shuffles the requests within consecutive
                    blocks of this many (the set of requests is the
                    deployment's, drawn from its fixed seed)
  query_noise       queries are held-in rows plus this much Gaussian noise
  filters           list of {kind, share, ...}:
      contain            labels of a row (easy: the query's own row; hard:
                         a row of another cluster), a random non-empty
                         subset, all required
      range              a window on the value CDF covering one of
                         `selectivities` of the rows, centred on the
                         query's own value (easy) or the opposite
                         quantile (hard)
      equal              the label set is exactly one label, uniform over
                         the alphabet
      contain_and_range  one label of a row (easy/hard as above) AND a
                         value window holding `passing_rows` [lo, hi] of
                         that label's rows

The requests are drawn from the deployment's fixed seed, as a public
benchmark's query file is fixed; a run's seed draws their order (within
blocks of `order_block`) and the order of the inter-arrival gaps. So
every seed gets the same queries, filters and gaps, in another order:
a seed changes the order of the work, not the work.

A replayed request is a new request (its own id) with the same query
and filter as its pool entry: the same work as a fresh one only while
nothing in the program is keyed on the query. So a closed mix may
replay only where no cache keyed on the query is on: the harness refuses
a closed loop unless the configuration's `serve.cache_capacity` is 0.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.lib.data import Deployment, n_words, unpack_bits


@dataclasses.dataclass
class Filters:
    """The reference's form of a batch of filters (conjunctions of the
    leaves above): contain masks, equality masks, one value window."""

    contain: np.ndarray   # [B, W] uint32, all bits required (0: none)
    equal: np.ndarray     # [B, W] uint32, the exact label set
    has_equal: np.ndarray  # [B] bool
    lo: np.ndarray        # [B] float32 (-inf: none)
    hi: np.ndarray        # [B] float32 (+inf: none)

    def take(self, idx) -> "Filters":
        return Filters(*(a[idx] for a in dataclasses.astuple(self)))


@dataclasses.dataclass
class Batch:
    queries: np.ndarray    # [B, d] float32
    exprs: list            # [B] program filter expressions
    filters: Filters       # the same filters, in the reference's form

    def take(self, idx) -> "Batch":
        return Batch(self.queries[idx], [self.exprs[i] for i in idx],
                     self.filters.take(idx))


def balanced(n: int, shares, rng) -> np.ndarray:
    """n draws over len(shares) categories in exact proportion (largest
    remainders), shuffled."""
    shares = np.asarray(shares, np.float64)
    raw = shares / shares.sum() * n
    cnt = np.floor(raw).astype(int)
    cnt[np.argsort(-(raw - cnt), kind="stable")[: n - cnt.sum()]] += 1
    out = np.repeat(np.arange(len(shares)), cnt)
    rng.shuffle(out)
    return out


def block_order(n: int, block: int, rng) -> np.ndarray:
    """0..n-1 shuffled within consecutive blocks of `block`: any prefix
    that ends on a block boundary holds the same requests in every
    order."""
    idx = np.arange(n)
    for start in range(0, n, block):
        rng.shuffle(idx[start:start + block])
    return idx


def gaps(n: int, rate: float, rng) -> np.ndarray:
    """Inter-arrival gaps of a Poisson process of `rate`: the n midpoint
    quantiles of the exponential distribution, in random order."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u) / rate
    rng.shuffle(g)
    return g


def closed_pool(mix: dict, seconds: float) -> int:
    """P: the distinct requests a closed loop's window draws."""
    return int(mix["outstanding"] + np.ceil(mix["pool_qps"] * seconds))


def recall_requests(mix: dict, seconds: float) -> int:
    """The closed loop's recall set size; refuses one that is not a
    positive whole number of `order_block` blocks within the pool."""
    r, block = mix["recall_requests"], int(mix["order_block"])
    pool = closed_pool(mix, seconds)
    if not isinstance(r, int) or r <= 0 or r % block or r > pool:
        raise ValueError(
            f"recall_requests {r!r} must be a positive multiple of "
            f"order_block {block} and at most the pool of {pool} requests "
            f"(outstanding + ceil(pool_qps x {seconds} s))")
    return r


class TrafficGen:
    """Requests of one mix over one deployment, drawn from a seed."""

    def __init__(self, mix: dict, dep: Deployment):
        self.mix = mix
        self.dep = dep
        self.n = dep.n
        self.w = n_words(dep.alphabet_size)
        self.order = np.argsort(dep.values, kind="stable")
        self.sorted_vals = dep.values[self.order]
        self.rank = np.empty(self.n, np.int64)
        self.rank[self.order] = np.arange(self.n)
        self._per_label = None

    # --------------------------------------------------------- pieces ----
    def _queries(self, src, rng):
        d = self.dep.vectors
        q = d[src] + self.mix["query_noise"] * rng.standard_normal(
            (len(src), d.shape[1])).astype(np.float32)
        q /= np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        return q.astype(np.float32)

    def _other_cluster_rows(self, src, rng):
        cid = self.dep.cluster_ids
        out = rng.integers(0, self.n, len(src))
        bad = cid[out] == cid[src]
        while bad.any():
            out[bad] = rng.integers(0, self.n, int(bad.sum()))
            bad = cid[out] == cid[src]
        return out

    def _label_rows(self, src, hard, rng):
        rows = src.copy()
        if hard.any():
            rows[hard] = self._other_cluster_rows(src[hard], rng)
        return unpack_bits(self.dep.labels_packed[rows],
                           self.dep.alphabet_size)

    def _label_subset(self, present, rng):
        """A random non-empty subset of each row's labels."""
        cnt = present.sum(axis=1)
        k = rng.integers(1, cnt + 1)
        pri = np.where(present, rng.random(present.shape), np.inf)
        rank = np.argsort(np.argsort(pri, axis=1), axis=1)
        return present & (rank < k[:, None])

    def _one_label(self, present, rng):
        pri = np.where(present, rng.random(present.shape), np.inf)
        return np.argmin(pri, axis=1)

    def _window(self, center_rank, width, sorted_vals):
        m = sorted_vals.shape[0]
        width = np.clip(width, 1, m)
        start = np.clip(center_rank - width // 2, 0, m - width)
        return (sorted_vals[start].astype(np.float32),
                sorted_vals[start + width - 1].astype(np.float32))

    def _pack(self, onehot):
        """[B, A] bool -> [B, W] uint32 multi-hot words."""
        bits = np.zeros((onehot.shape[0], 32 * self.w), np.uint64)
        bits[:, :onehot.shape[1]] = onehot
        words = bits.reshape(-1, self.w, 32) << np.arange(32, dtype=np.uint64)
        return words.sum(axis=-1).astype(np.uint32)

    def _label_values(self):
        """Per label: its rows' values, sorted."""
        if self._per_label is None:
            onehot = unpack_bits(self.dep.labels_packed,
                                 self.dep.alphabet_size)
            self._per_label = [np.sort(self.dep.values[onehot[:, a]])
                               for a in range(self.dep.alphabet_size)]
        return self._per_label

    # ---------------------------------------------------------- batch ----
    def batch(self, n: int, rng) -> Batch:
        from repro.filters.expr import And, Contain, Equal, Range

        specs = self.mix["filters"]
        kinds = balanced(n, [f["share"] for f in specs], rng)
        src = rng.integers(0, self.n, n)
        queries = self._queries(src, rng)
        contain = np.zeros((n, self.w), np.uint32)
        equal = np.zeros((n, self.w), np.uint32)
        has_equal = np.zeros(n, bool)
        lo = np.full(n, -np.inf, np.float32)
        hi = np.full(n, np.inf, np.float32)
        exprs = [None] * n
        for ki, spec in enumerate(specs):
            idx = np.flatnonzero(kinds == ki)
            if idx.size == 0:
                continue
            hard = balanced(idx.size, [1 - spec.get("hard_fraction", 0.0),
                                       spec.get("hard_fraction", 0.0)],
                            rng).astype(bool)
            kind = spec["kind"]
            if kind == "contain":
                sub = self._label_subset(self._label_rows(src[idx], hard,
                                                          rng), rng)
                contain[idx] = self._pack(sub)
                for j, row in zip(idx, sub):
                    exprs[j] = Contain(np.flatnonzero(row).tolist())
            elif kind == "range":
                sel = np.asarray(spec["selectivities"])[balanced(
                    idx.size, [1.0] * len(spec["selectivities"]), rng)]
                own = self.rank[src[idx]]
                center = np.where(hard, self.n - 1 - own, own)
                width = np.maximum(2, np.rint(sel * self.n)).astype(np.int64)
                lo[idx], hi[idx] = self._window(center, width,
                                                self.sorted_vals)
                for j in idx:
                    exprs[j] = Range(lo[j], hi[j])
            elif kind == "equal":
                lab = rng.integers(0, self.dep.alphabet_size, idx.size)
                onehot = np.zeros((idx.size, self.dep.alphabet_size), bool)
                onehot[np.arange(idx.size), lab] = True
                equal[idx] = self._pack(onehot)
                has_equal[idx] = True
                for j, a in zip(idx, lab):
                    exprs[j] = Equal([int(a)])
            elif kind == "contain_and_range":
                tag = self._one_label(self._label_rows(src[idx], hard, rng),
                                      rng)
                lo_p, hi_p = spec["passing_rows"]
                rows = np.rint(np.linspace(lo_p, hi_p, idx.size)).astype(
                    np.int64)
                rng.shuffle(rows)
                per = self._label_values()
                own_v = self.dep.values[src[idx]]
                onehot = np.zeros((idx.size, self.dep.alphabet_size), bool)
                onehot[np.arange(idx.size), tag] = True
                contain[idx] = self._pack(onehot)
                for t, (j, a) in enumerate(zip(idx, tag)):
                    sv = per[a]
                    own = int(np.searchsorted(sv, own_v[t]))
                    own = min(own, sv.shape[0] - 1)
                    c = sv.shape[0] - 1 - own if hard[t] else own
                    lo[j], hi[j] = self._window(np.int64(c),
                                                np.int64(rows[t]), sv)
                    exprs[j] = And(Contain([int(a)]), Range(lo[j], hi[j]))
            else:
                raise ValueError(f"unknown filter kind {kind!r}")
        return Batch(queries=queries, exprs=exprs,
                     filters=Filters(contain, equal, has_equal, lo, hi))
