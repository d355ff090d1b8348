"""Pre-filter scan plan: bitmap-compile the filter, scan only passing rows.

The planner's alternative to graph traversal for selective filters. Three
fixed-shape stages, all per-lane deterministic:

  bitmap    `filters.compile.eval_program_matrix` evaluates the compiled
            FilterProgram against the full attribute store — a [B, N] bool
            candidate bitmap plus exact per-query selectivity σ_q and
            per-clause global selectivities. Boolean work only: 0 NDC (the
            repo counts predicate evaluations in n_inspected, not cnt).
  gather    per lane, the σ_q·N passing row ids (stable ascending order),
            padded to a shared 64-aligned width V (kernels.distance
            .SCAN_ALIGN) so the distance block keeps a fixed shape and the
            padded width cannot change any value.
  distance  `kernels.ops.masked_scan_dist` — the traversal's masked-distance
            Pallas kernel on TPU, the per-lane-deterministic host path on
            CPU — then one stable top-M/top-k selection.

Cost is exactly σ_q·N distance computations per lane (`state.cnt`), the
closed-form quantity the planner compares against predicted traversal NDC.
On float32 engines the result is bit-identical to the bruteforce oracle
`index.bruteforce.filtered_knn_exact` (same distance source, same stable
tie order — tests/test_planner.py pins it). On quantized engines the scan
runs in the compressed domain (int8 ADC / PQ LUT over the gathered codes)
and fills the candidate queue with the top-M compressed candidates, so the
engine's terminal exact float32 rerank restores exact-domain results from
the same pool contract the traversal uses.

The returned SearchState is terminal: `active` is all-False and the queue
is fully expanded — scan states must not be resumed, only reranked/read.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import SearchEngine
from repro.core.search import SearchConfig, SearchState
from repro.core.state import INF
from repro.filters.compile import (CLAUSE_FEATURE_SLOTS, FilterProgram,
                                   bitmap_chunk, eval_program_matrix)
from repro.kernels import ops as kops
from repro.kernels.distance import SCAN_ALIGN


class ScanStats(NamedTuple):
    """Bitmap-stage output: the scan plan's input and the planner's exact
    pre-probe statistics (σ_q and global per-clause selectivities)."""

    valid: jax.Array         # [B, N] bool candidate bitmap, on the device
    counts: np.ndarray       # [B] i64 — σ_q·N, exact
    clause_frac: np.ndarray  # [B, CLAUSE_FEATURE_SLOTS] f32 global clause σ
    n: int                   # corpus size N

    @property
    def sigma(self) -> np.ndarray:
        return self.counts.astype(np.float64) / max(self.n, 1)

    def rows(self, idx) -> "ScanStats":
        """Lane subset (planner partition / serving batch slicing)."""
        idx = np.asarray(idx)
        return ScanStats(valid=jnp.take(self.valid, idx, axis=0),
                         counts=self.counts[idx],
                         clause_frac=self.clause_frac[idx], n=self.n)


def scan_stats(engine: SearchEngine, prog: FilterProgram,
               tracer=None, trace_id: str = "") -> ScanStats:
    """Compile the candidate bitmap + exact selectivity statistics.

    `tracer` spans the bitmap pass as `filter-bitmap`: the rows, the
    chunks the pass walks inside its program, the lanes, and the bytes
    copied device to host (each pass's counts and clause counts; the
    bitmap stays on the device)."""
    from repro.obs.trace import as_tracer

    with as_tracer(tracer).span("filter-bitmap", trace_id) as sp:
        if getattr(engine, "is_sharded", False):
            # index-axis-sharded engine: per-shard bitmap passes, one
            # global ScanStats (core.sharded) — keeps the planner
            # engine-agnostic
            stats = engine.scan_stats(prog)
            ns = engine.shard_size
            passes = engine.n_shards
            chunks = passes * -(-ns // bitmap_chunk(prog, ns))
        else:
            valid, counts, frac = eval_program_matrix(
                prog, engine.label_attrs, engine.value_attrs)
            stats = ScanStats(valid=valid, counts=counts, clause_frac=frac,
                              n=int(valid.shape[1]))
            passes, chunks = 1, -(-stats.n // bitmap_chunk(prog, stats.n))
        lanes = int(stats.valid.shape[0])
        # per pass: int32 counts [B] and clause counts [B, slots]
        sp.set(rows=stats.n, chunks=chunks, lanes=lanes,
               bytes_to_host=passes * lanes * 4 * (1 + CLAUSE_FEATURE_SLOTS))
    return stats


def _aligned_width(max_count: int, n: int) -> int:
    """Smallest power of two ≥ max(count, SCAN_ALIGN), capped at ⌈N⌉₆₄.

    Power-of-two rounding bounds the jit shape count across heterogeneous
    batches (the program compiler applies the same discipline to slot
    counts); every candidate width is a SCAN_ALIGN multiple, so which width
    a batch lands on cannot change any distance value.
    """
    v = max(SCAN_ALIGN, 1 << max(0, int(max_count - 1).bit_length()))
    cap = -(-n // SCAN_ALIGN) * SCAN_ALIGN
    return min(v, cap)


def scan_search(
    engine: SearchEngine,
    cfg: SearchConfig,
    queries: np.ndarray,
    filt,                                # FilterSpec | Expr(s) | FilterProgram
    stats: ScanStats | None = None,
    base_state: SearchState | None = None,
) -> SearchState:
    """Execute the pre-filter scan plan; returns a terminal SearchState.

    `stats` reuses a bitmap the planner already compiled for routing.
    `base_state` carries a probed lane's counters into the scan (the
    planner's post-probe fallback path): cnt/n_inspected/etc. accumulate on
    top of the probe's, and d_start is preserved so feature extraction on
    the merged batch stays finite and consistent. Result/queue buffers are
    *replaced* — the scan covers the full valid set, a superset of anything
    the probe saw.
    """
    if getattr(engine, "is_sharded", False):
        # sharded engines scan shard-by-shard and merge (core.sharded);
        # the returned ShardedSearchState is terminal like this one
        return engine.scan(cfg, queries, filt, stats=stats,
                           base_state=base_state)
    prog = engine.compile(filt)
    if stats is None:
        stats = scan_stats(engine, prog)
    q = jnp.asarray(queries, jnp.float32)
    b = q.shape[0]
    n = stats.n
    m, k = cfg.queue_size, cfg.k
    precision = engine.effective_precision(cfg)

    counts = jnp.asarray(stats.counts, jnp.int32)
    v = _aligned_width(int(stats.counts.max(initial=0)), n)
    take = min(v, n)
    validj = jnp.asarray(stats.valid)
    # stable argsort over ~valid puts passing rows first, in ascending id
    # order — deterministic per lane, which both the oracle tie order and
    # the serving bit-identity rely on
    order = jnp.argsort(~validj, axis=1, stable=True)[:, :take]
    idx = jnp.zeros((b, v), jnp.int32).at[:, :take].set(
        order.astype(jnp.int32))
    mask = jnp.arange(v)[None, :] < counts[:, None]

    if precision == "float32":
        if engine.base_vectors.shape[1] == 0:
            raise ValueError(
                "float32 scan on a host-tiered engine: the device holds "
                "only a vector placeholder — scan at the engine's "
                "compressed precision (the terminal rerank stays exact)")
        xg = engine.base_vectors[idx]
        dd = kops.masked_scan_dist(q, xg, mask)
        err_add = jnp.zeros((b,), jnp.float32)
    else:
        # compressed-domain ADC over the gathered codes — same dispatch the
        # traversal backends use, so the rerank pool lives in one metric
        from repro.quant.codecs import QuantGather, prepare_query, quant_dist

        quant = engine.quant
        prep = prepare_query(precision, quant, q)
        codes_g = quant.codes[idx]
        if codes_g.dtype == jnp.uint8:
            codes_g = codes_g.astype(jnp.int32)
        dd = quant_dist(precision,
                        QuantGather(prep=prep, codes=codes_g,
                                    norms=quant.norms[idx]))
        dd = jnp.where(mask, dd, INF)
        err_add = jnp.where(mask, quant.err[idx], 0.0).sum(axis=1)

    # one stable ascending selection serves both buffers: results are the
    # first k columns of the top-M candidate pool
    p = min(v, m)
    sel = jnp.argsort(dd, axis=1, stable=True)[:, :p]
    top_d = jnp.take_along_axis(dd, sel, axis=1)
    top_i = jnp.where(jnp.isfinite(top_d),
                      jnp.take_along_axis(idx, sel, axis=1), -1)
    pad = m - p
    cand_dist = jnp.pad(top_d, ((0, 0), (0, pad)), constant_values=INF)
    cand_idx = jnp.pad(top_i, ((0, 0), (0, pad)), constant_values=-1)
    in_pool = cand_idx >= 0
    res_dist, res_idx = cand_dist[:, :k], cand_idx[:, :k]

    cnt_add = counts
    if base_state is None:
        carry = SearchState(
            cand_dist=cand_dist, cand_idx=cand_idx, cand_exp=in_pool,
            cand_valid=in_pool, res_dist=res_dist, res_idx=res_idx,
            visited=jnp.zeros((b, (n + 31) // 32), jnp.uint32),
            cnt=jnp.zeros((b,), jnp.int32),
            n_inspected=jnp.zeros((b,), jnp.int32),
            n_valid_visited=jnp.zeros((b,), jnp.int32),
            n_clause_valid=jnp.zeros((b, CLAUSE_FEATURE_SLOTS), jnp.int32),
            n_pop_valid=jnp.zeros((b,), jnp.int32),
            q_err_sum=jnp.zeros((b,), jnp.float32),
            hops=jnp.zeros((b,), jnp.int32),
            active=jnp.zeros((b,), bool),
            d_start=jnp.zeros((b,), jnp.float32),
            conv_cnt=jnp.full((b,), -1, jnp.int32),
            res_full_cnt=jnp.full((b,), -1, jnp.int32),
        )
    else:
        carry = base_state._replace(
            cand_dist=cand_dist, cand_idx=cand_idx, cand_exp=in_pool,
            cand_valid=in_pool, res_dist=res_dist, res_idx=res_idx,
            active=jnp.zeros((b,), bool))
    clause_add = jnp.asarray(
        np.rint(stats.clause_frac * n).astype(np.int32))
    return carry._replace(
        cnt=carry.cnt + cnt_add,
        n_inspected=carry.n_inspected + jnp.full((b,), n, jnp.int32),
        n_valid_visited=carry.n_valid_visited + counts,
        n_clause_valid=carry.n_clause_valid + clause_add,
        q_err_sum=carry.q_err_sum + err_add,
        res_full_cnt=jnp.where(jnp.isfinite(res_dist[:, -1]),
                               carry.cnt + cnt_add, carry.res_full_cnt),
    )
