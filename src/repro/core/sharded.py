"""Index-axis sharding: per-shard traversal + log-depth global top-k merge.

`SearchEngine` (core.engine) scales over the *batch* axis — every device
holds the whole index. This module scales over the *index* axis: the corpus
is cut into S contiguous equal slices, each with its own independent graph
(shard-local node ids, own entry point), quant codes and attribute bundle.
A query traverses every shard with budget ⌈W/S⌉ and per-shard state
(candidate queue, result set, visited bitset over the shard's N/S nodes —
which is what keeps the PR-6 bitset bound of N ≤ 4M *per shard*), and the S
sorted pools are combined by the log-depth cross-shard merge
(distributed.merge) into the global result set.

Two execution paths:

  loop   (mesh=None, the default) — a host loop over shards, each through
         the plain per-shard `SearchEngine.search` (persistent driver,
         compaction and tracing included), then `merge_shard_states` on
         the stacked states.
  mesh   a 2-D ("data" × "index") `shard_map`: each device runs its local
         shards' traversals, merges them locally, and joins the XOR
         butterfly (`distributed.merge.butterfly_merge`) over the index
         axis — ⌈log2 S⌉ pairwise merge rounds instead of gathering S
         pools anywhere.

Bit-parity argument: per-shard traversals are the same traced computation
in both paths; pool entries carry unique (dist, pos) keys (pos = global
shard · width + slot), a total order under which top-m is associative and
commutative — so the host merge tree and the device butterfly produce THE
unique sorted top-m of the pool union. Counters are merged outside the
mesh in both paths, by the same jitted reduction over the same stacked
values.

The loop path is bit-identical to the single-device engine at every
precision. The mesh path runs as one jitted program (`_mesh_pools`); run
eagerly, `shard_map` executes its body op by op, unfused, which both
recompiled every op on every call and moved distances by a few ulp. Jitted,
it is bit-identical to the loop path on XLA:CPU at float32, int8 and pq.
The quantized mesh-path contract stays "within 1 ulp" (ids and counters
exact): a compiler may fuse the ADC float tail (qn + xn − 2·s·dot)
differently under SPMD partitioning than under plain `jit`, contracting the
mul/subtract into an FMA in one context but not the other.

Accounting contract (what keeps the estimator, planner, probe→resume and
EXPLAIN working unchanged):

  exact      cnt (NDC), n_inspected, n_valid_visited, n_clause_valid,
             n_pop_valid, hops — integer sums over shards; q_err_sum —
             float sum in a fixed shard order (same order both paths).
  semantics  active = any(shard active); d_start = min over shards (the
             best entry distance a query saw); visited = concatenation of
             the word-padded per-shard bitsets [B, S·ceil(Ns/32)].
  approx     conv_cnt / res_full_cnt: summed when every shard reached the
             milestone, else -1 ("not yet"). A single shard usually cannot
             reach global full-recall on its own, so these fire later than
             on an unsharded engine — the feature extractor already treats
             -1 as "not converged" and substitutes its sentinel, so
             features stay well-defined (they are *trained* per deployment
             anyway; an estimator is fitted on the engine shape it serves).

Memory tiering composes here exactly as on the plain engine: compressed
engines keep per-shard [Ns, 0] float32 placeholders and route the exact
rerank through one global `quant.tiering` store (device- or host-resident)
gathering only the ≤ (M+K) merged-pool rows per query.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import ClassVar, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.search import SearchConfig, SearchState, run_search_impl
from repro.core.state import pad_lanes, stack_shards, take_shard
from repro.data.synthetic import AttributedDataset
from repro.distributed.merge import butterfly_merge, merge_plan, merge_stacked
from repro.obs.trace import as_tracer
from repro.distributed.sharding import INDEX_AXIS, search_mesh_2d
from repro.filters.compile import FilterProgram, as_program
from repro.index.graph import ShardedGraphIndex
from repro.kernels.topk import pack_payload, unpack_payload

BATCH_AXIS = "data"


class ShardedSearchState(NamedTuple):
    """Full state of a sharded search: per-shard carries + the merged view.

    `shard` is a SearchState whose leaves carry the shard axis SECOND
    ([B, S, ...]), so the serving layer's lane surgery (take/put/concat/pad
    on axis 0) keeps working on sharded states untouched. `merged` is a
    plain [B, ...] SearchState — the global view every consumer (features,
    planner, EXPLAIN, rerank, serving) reads; all 17 SearchState field
    names delegate to it, so a ShardedSearchState quacks like the state
    those consumers were written against. Resume reads `shard` (per-shard
    queues and bitsets are the resumable truth); results read `merged`.
    """

    shard: SearchState    # [B, S, ...] leaves
    merged: SearchState   # [B, ...] leaves — global pools + summed counters

    # -- delegation: every SearchState field name reads the merged view ----
    @property
    def cand_dist(self): return self.merged.cand_dist

    @property
    def cand_idx(self): return self.merged.cand_idx

    @property
    def cand_exp(self): return self.merged.cand_exp

    @property
    def cand_valid(self): return self.merged.cand_valid

    @property
    def res_dist(self): return self.merged.res_dist

    @property
    def res_idx(self): return self.merged.res_idx

    @property
    def visited(self): return self.merged.visited

    @property
    def cnt(self): return self.merged.cnt

    @property
    def n_inspected(self): return self.merged.n_inspected

    @property
    def n_valid_visited(self): return self.merged.n_valid_visited

    @property
    def n_clause_valid(self): return self.merged.n_clause_valid

    @property
    def n_pop_valid(self): return self.merged.n_pop_valid

    @property
    def q_err_sum(self): return self.merged.q_err_sum

    @property
    def hops(self): return self.merged.hops

    @property
    def active(self): return self.merged.active

    @property
    def d_start(self): return self.merged.d_start

    @property
    def conv_cnt(self): return self.merged.conv_cnt

    @property
    def res_full_cnt(self): return self.merged.res_full_cnt


def _merged_from(stacked: SearchState, rd, rp, cd, cp) -> SearchState:
    """Assemble the merged view from stacked states + already-merged pools."""
    b = stacked.res_dist.shape[0]
    ci, ce, cv = unpack_payload(cp)
    isum = lambda x: jnp.sum(x, axis=1)                          # noqa: E731
    # "reached on every shard" counters: sum when all shards report ≥ 0,
    # else the -1 "not yet" sentinel the feature extractor substitutes for
    opt = lambda x: jnp.where(jnp.all(x >= 0, axis=1),           # noqa: E731
                              jnp.sum(x, axis=1), -1).astype(jnp.int32)
    return SearchState(
        cand_dist=cd, cand_idx=ci, cand_exp=ce, cand_valid=cv,
        res_dist=rd, res_idx=rp,
        visited=stacked.visited.reshape(b, -1),
        cnt=isum(stacked.cnt),
        n_inspected=isum(stacked.n_inspected),
        n_valid_visited=isum(stacked.n_valid_visited),
        n_clause_valid=isum(stacked.n_clause_valid),
        n_pop_valid=isum(stacked.n_pop_valid),
        q_err_sum=isum(stacked.q_err_sum),
        hops=isum(stacked.hops),
        active=jnp.any(stacked.active, axis=1),
        d_start=jnp.min(stacked.d_start, axis=1),
        conv_cnt=opt(stacked.conv_cnt),
        res_full_cnt=opt(stacked.res_full_cnt),
    )


def _merge_pools(stacked: SearchState, offsets):
    """Host merge tree over the stacked per-shard pools → global pools.

    Result pools merge on bare global ids; candidate pools pack
    (global id, expanded, valid) into one int32 payload (kernels.topk)
    so the queue flags ride the merge with their entry.
    """
    k = stacked.res_dist.shape[2]
    m = stacked.cand_dist.shape[2]
    off = jnp.asarray(offsets, jnp.int32)[None, :, None]
    res_g = jnp.where(stacked.res_idx >= 0, stacked.res_idx + off, -1)
    rd, rp, _ = merge_stacked(stacked.res_dist, res_g, k)
    cand_g = jnp.where(stacked.cand_idx >= 0, stacked.cand_idx + off, -1)
    cpay = pack_payload(cand_g, stacked.cand_exp, stacked.cand_valid)
    cd, cp, _ = merge_stacked(stacked.cand_dist, cpay, m)
    return rd, rp, cd, cp


@jax.jit
def merge_shard_states(stacked: SearchState, offsets) -> SearchState:
    """Merged global view of stacked per-shard states ([B, S, ...] leaves).

    `offsets` [S] — each shard's first global row (shard-local id s,i ↦
    global id offsets[s] + i). The loop execution path's merge; the mesh
    path substitutes its butterfly-merged pools via `merge_with_pools` and
    shares everything else.
    """
    rd, rp, cd, cp = _merge_pools(stacked, offsets)
    return _merged_from(stacked, rd, rp, cd, cp)


@jax.jit
def merge_with_pools(stacked: SearchState, rd, rp, cd, cp) -> SearchState:
    """`merge_shard_states` with externally merged (butterfly) pools."""
    return _merged_from(stacked, rd, rp, cd, cp)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def _mesh_pools(cfg, mesh, q, prog, sbud, stx, state, gt):
    """The mesh path's traversals and butterfly merge as one program.

    Under jit, not eagerly: an eager `shard_map` runs its body op by op
    and compiles every op again on every call. Returns the stacked
    per-shard states and the merged pools (rd, rp, cd, cp)."""
    dindex = int(mesh.shape[INDEX_AXIS])
    nloc = stx["entries"].shape[0] // dindex   # shards per index device
    k, m = cfg.k, cfg.queue_size
    bspec = P(BATCH_AXIS)
    ispec = P(INDEX_AXIS)
    bsspec = P(BATCH_AXIS, INDEX_AXIS)
    has_state, has_gt = state is not None, gt is not None
    has_quant = cfg.precision != "float32"

    args = [q, prog, sbud, stx["base"], stx["labels"], stx["values"],
            stx["neighbors"], stx["entries"], stx["offsets"]]
    specs = [bspec, bspec, bspec, ispec, ispec, ispec, ispec, ispec, ispec]
    if has_state:
        args.append(state)
        specs.append(bsspec)
    if has_gt:
        args.append(gt)
        specs.append(bspec)
    if has_quant:
        args.append(stx["quant"])
        specs.append(ispec)

    def fn(qq, qa, bud, base, labels, values, nb, entries, offs, *rest):
        j = 0
        st = rest[j] if has_state else None
        j += has_state
        g = rest[j] if has_gt else None
        j += has_gt
        qt = rest[j] if has_quant else None
        outs = []
        for jj in range(nloc):                # static unroll: local shards
            stj = (None if st is None
                   else jax.tree.map(lambda a: a[:, jj], st))
            qtj = (None if qt is None
                   else jax.tree.map(lambda a: a[jj], qt))
            outs.append(run_search_impl(
                cfg, qq, qa, base[jj], (labels[jj], values[jj]), nb[jj],
                bud, entries[jj], state=stj, gt_dist=g, quant=qtj))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=1), *outs)
        # local merge tree on the global position space (shard0 keys this
        # device's pools into the virtual concatenation of all S)
        shard0 = jax.lax.axis_index(INDEX_AXIS) * nloc
        off = offs[None, :, None]
        res_g = jnp.where(stacked.res_idx >= 0, stacked.res_idx + off, -1)
        rd, rp, ro = merge_stacked(stacked.res_dist, res_g, k, shard0=shard0)
        cpay = pack_payload(
            jnp.where(stacked.cand_idx >= 0, stacked.cand_idx + off, -1),
            stacked.cand_exp, stacked.cand_valid)
        cd, cp, co = merge_stacked(stacked.cand_dist, cpay, m, shard0=shard0)
        # cross-device butterfly: after log2(dindex) rounds every index
        # device holds the identical global pools
        rd, rp, ro = butterfly_merge(rd, rp, ro, k, INDEX_AXIS, dindex)
        cd, cp, co = butterfly_merge(cd, cp, co, m, INDEX_AXIS, dindex)
        return stacked, rd, rp, cd, cp

    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(specs),
        out_specs=(bsspec, bspec, bspec, bspec, bspec), check_vma=False,
    )(*args)


@dataclasses.dataclass
class ShardedSearchEngine:
    """S per-shard `SearchEngine`s + the cross-shard merge, one facade.

    Duck-type compatible with `SearchEngine` everywhere the stack consumes
    an engine (`search`/`rerank`/`compile`/`codec_key`/`n_words`/...), and
    its states are `ShardedSearchState` — consumers reading state fields
    get the merged global view transparently.
    """

    shards: list                       # [S] SearchEngine (mesh=None each)
    offsets: np.ndarray                # [S] first global row per shard
    entry_points: np.ndarray           # [S] shard-local entry node ids
    backend: str | None = None
    mesh: Mesh | None = None           # 2-D ("data", "index") | None → loop
    precision: str = "float32"
    vector_store: object | None = None  # global rerank tier (compressed mode)
    tier: str = "device"
    _stacked: dict | None = dataclasses.field(
        default=None, repr=False, compare=False)

    #: duck-typing marker — plans/planner route on this, never on isinstance
    is_sharded: ClassVar[bool] = True

    # ------------------------------------------------------------ build ----
    @classmethod
    def build(cls, ds: AttributedDataset, graph: ShardedGraphIndex | int,
              backend: str | None = None, mesh: Mesh | str | None = "auto",
              precision: str = "float32", quant_cfg: dict | None = None,
              tier: str = "device") -> "ShardedSearchEngine":
        """Construct an index-axis-sharded engine over `ds`.

        graph   a ShardedGraphIndex (index.build_sharded_graph_index), or an
                int shard count to build one here with default knobs.
        mesh    "auto" → 2-D (data × index) mesh when >1 device is visible
                (distributed.search_mesh_2d); an explicit Mesh must carry a
                "data" axis and an "index" axis whose size divides S; None
                forces the single-device shard loop.
        tier    "device" | "host" — where the float32 rerank tier lives in
                compressed mode (quant.tiering). Compressed shard engines
                always hold [Ns, 0] vector placeholders: exactly one global
                float32 copy exists, in the chosen tier.

        Quantized builds train every shard's codec on the SAME global
        sample (ds.sample_vectors), so codec parameters — and therefore the
        compressed metric and the per-query ADC prep — are identical across
        shards: per-shard distances are mutually comparable and the merged
        pool lives in one metric.
        """
        if isinstance(graph, (int, np.integer)):
            from repro.index.builder import build_sharded_graph_index

            graph = build_sharded_graph_index(np.asarray(ds.vectors),
                                              int(graph))
        graph.validate()
        n, s = graph.n, graph.n_shards
        if len(ds.vectors) != n:
            raise ValueError(
                f"dataset has {len(ds.vectors)} rows but the sharded graph "
                f"covers {n}")
        if tier != "device" and precision == "float32":
            raise ValueError(
                "tier='host' requires a compressed traversal precision "
                "('int8' or 'pq') — a float32 traversal reads the full "
                "vector store every step, which defeats the tier")
        ns = graph.shard_size
        offsets = np.asarray(graph.offsets)

        quants = [None] * s
        store = None
        if precision != "float32":
            from repro.quant import build_quant_index
            from repro.quant.tiering import as_vector_store

            qcfg = dict(quant_cfg or {})
            sample_n = qcfg.pop("train_sample_size", 16384)
            sample = ds.sample_vectors(sample_n, seed=qcfg.get("seed", 0))
            quants = [
                build_quant_index(precision, ds.vectors[offsets[i]:
                                                        offsets[i] + ns],
                                  train_sample=sample, **qcfg)
                for i in range(s)
            ]
            store = as_vector_store(ds.vectors, tier)

        from repro.core.engine import SearchEngine

        vals = np.asarray(ds.value_matrix)
        shards = []
        for i in range(s):
            lo, hi = int(offsets[i]), int(offsets[i]) + ns
            if precision != "float32":
                vec = jnp.zeros((ns, 0), jnp.float32)  # placeholder: only
                # the row count is read in compressed mode
            else:
                vec = jnp.asarray(ds.vectors[lo:hi], jnp.float32)
            shards.append(SearchEngine(
                base_vectors=vec,
                label_attrs=jnp.asarray(ds.labels_packed[lo:hi]),
                value_attrs=jnp.asarray(vals[lo:hi]),
                neighbors=jnp.asarray(graph.shards[i].neighbors),
                entry_point=int(graph.shards[i].entry_point),
                backend=backend,
                mesh=None,              # batch sharding happens above, once
                precision=precision,
                quant=quants[i],
            ))
        if mesh == "auto":
            mesh = search_mesh_2d(s)
        if mesh is not None:
            if BATCH_AXIS not in mesh.shape or INDEX_AXIS not in mesh.shape:
                raise ValueError(
                    f"sharded engine mesh needs axes ({BATCH_AXIS!r}, "
                    f"{INDEX_AXIS!r}); got {mesh.axis_names}")
            if s % mesh.shape[INDEX_AXIS]:
                raise ValueError(
                    f"index axis of size {mesh.shape[INDEX_AXIS]} does not "
                    f"divide {s} shards")
        return cls(shards=shards, offsets=offsets,
                   entry_points=np.asarray(graph.entry_points),
                   backend=backend, mesh=mesh, precision=precision,
                   vector_store=store, tier=tier)

    # ------------------------------------------------------- properties ----
    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def shard_size(self) -> int:
        return int(self.shards[0].neighbors.shape[0])

    @property
    def n(self) -> int:
        return self.n_shards * self.shard_size

    @property
    def n_words(self) -> int:
        return self.shards[0].n_words

    @property
    def n_values(self) -> int:
        return self.shards[0].n_values

    @property
    def quant(self):
        """Shard 0's quant index — codec parameters are shared by training
        contract, so this is *the* codec for identity purposes."""
        return self.shards[0].quant

    @property
    def quant_concat(self):
        """Global-view quant index: per-shard codes/norms/err concatenated
        in shard order (= global row order), codec parameters from shard 0
        (identical across shards by the shared-sample training contract).
        This is what corpus-wide consumers (compressed ground truth in
        core.training / core.planner) read; it is NOT cached — they call
        it once per training run and the concat would double code memory.
        """
        q0 = self.shards[0].quant
        if q0 is None:
            return None
        from repro.quant.codecs import Int8Index, PQIndex

        def cat(name):
            return jnp.concatenate(
                [getattr(e.quant, name) for e in self.shards], axis=0)

        if isinstance(q0, Int8Index):
            return Int8Index(codes=cat("codes"), scale=q0.scale,
                             zero=q0.zero, norms=cat("norms"),
                             err=cat("err"))
        if isinstance(q0, PQIndex):
            return PQIndex(codes=cat("codes"), codebooks=q0.codebooks,
                           norms=cat("norms"), err=cat("err"))
        raise TypeError(f"unknown quant index {type(q0).__name__}")

    @property
    def label_attrs(self):
        """Concatenated [N, W] label words (global row order) — for host
        consumers like the bruteforce validity oracle; traversals read the
        per-shard bundles, never this."""
        return jnp.concatenate([e.label_attrs for e in self.shards], axis=0)

    @property
    def value_attrs(self):
        return jnp.concatenate([e._attrs()[1] for e in self.shards], axis=0)

    def compile(self, filt) -> FilterProgram:
        prog = as_program(filt, self.n_words, self.n_values)
        return FilterProgram(*(jnp.asarray(a) for a in prog))

    def effective_precision(self, cfg: SearchConfig) -> str:
        return cfg.precision or self.precision

    def codec_key(self, cfg: SearchConfig | None = None) -> str:
        return self.shards[0].codec_key(cfg)

    # ----------------------------------------------------------- search ----
    def _resolve(self, cfg: SearchConfig) -> SearchConfig:
        cfg = dataclasses.replace(
            cfg, degree=int(self.shards[0].neighbors.shape[1]))
        if cfg.backend is None:
            cfg = dataclasses.replace(cfg, backend=self.backend or "dense")
        cfg = dataclasses.replace(cfg,
                                  precision=self.effective_precision(cfg))
        if cfg.precision != "float32" and self.quant is None:
            raise ValueError(
                f"SearchConfig(precision={cfg.precision!r}) on a sharded "
                "engine without a quant index — build with precision=...")
        if (cfg.precision == "float32"
                and self.shards[0].base_vectors.shape[1] == 0):
            raise ValueError(
                "float32 traversal on a compressed sharded engine: shards "
                "hold only vector placeholders (the float32 copy lives in "
                "the rerank tier) — search at the engine's compressed "
                "precision, the terminal rerank stays exact")
        return cfg

    def search(self, cfg: SearchConfig, queries, filt, budgets,
               state: ShardedSearchState | None = None,
               gt_dist=None, tracer=None, trace_id: str = "",
               ) -> ShardedSearchState:
        """Sharded search/probe/resume. Same contract as SearchEngine.search
        except states are ShardedSearchState and `budgets` is the *global*
        NDC budget: each shard runs under ⌈W/S⌉, and the merged `cnt` is
        the exact total the query actually spent (Σ per-shard NDC), which
        is what the estimator's features and EXPLAIN read."""
        cfg = self._resolve(cfg)
        prog = self.compile(filt)
        q = jnp.asarray(queries, jnp.float32)
        b = q.shape[0]
        s = self.n_shards
        budgets = jnp.broadcast_to(jnp.asarray(budgets, jnp.int32), (b,))
        # per-shard slice of the global budget; ⌈W/S⌉ so S·shard ≥ W and a
        # budget-terminated query is still visible as cnt ≥ W to EXPLAIN
        sbud = (budgets + jnp.int32(s - 1)) // jnp.int32(s)
        gt = None if gt_dist is None else jnp.asarray(gt_dist, jnp.float32)
        tr = as_tracer(tracer)
        if self.mesh is None:
            # spans wrap host dispatches that exist regardless of tracing
            # (per-shard engine.search calls, the one merge jit call) with
            # static int attrs — no device reads, so the PR-7 zero-added-
            # dispatch / bit-identity contract holds on sharded engines too
            outs = []
            for i, eng in enumerate(self.shards):
                st = None if state is None else take_shard(state.shard, i)
                with tr.span("shard-search", trace_id, shard=i, n_shards=s):
                    outs.append(eng.search(
                        cfg, q, prog, sbud, state=st, gt_dist=gt,
                        tracer=tracer,
                        trace_id=f"{trace_id}/s{i}" if trace_id else ""))
            pairwise, depth = merge_plan(s)
            with tr.span("shard-merge", trace_id, n_shards=s,
                         pairwise=pairwise, depth=depth, path="loop"):
                stacked = stack_shards(outs)
                merged = merge_shard_states(stacked, self.offsets)
            return ShardedSearchState(shard=stacked, merged=merged)
        pairwise, depth = merge_plan(s)
        with tr.span("shard-search", trace_id, shard=-1, n_shards=s,
                     pairwise=pairwise, depth=depth, path="mesh"):
            return self._search_mesh(cfg, q, prog, sbud, state, gt)

    # ------------------------------------------------------ mesh path ------
    def _stacked_arrays(self) -> dict:
        """Index-side arrays stacked [S, ...] and placed P(index) once."""
        if self._stacked is None:
            stx = {
                "neighbors": jnp.stack([e.neighbors for e in self.shards]),
                "labels": jnp.stack([e.label_attrs for e in self.shards]),
                "values": jnp.stack([e._attrs()[1] for e in self.shards]),
                "base": jnp.stack([e.base_vectors for e in self.shards]),
                "entries": jnp.asarray(self.entry_points, jnp.int32),
                "offsets": jnp.asarray(self.offsets, jnp.int32),
            }
            if self.quant is not None:
                stx["quant"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *[e.quant for e in self.shards])
            if self.mesh is not None:
                sh = NamedSharding(self.mesh, P(INDEX_AXIS))
                stx = {k: jax.device_put(v, sh) for k, v in stx.items()}
            self._stacked = stx
        return self._stacked

    def _search_mesh(self, cfg, q, prog, sbud, state, gt):
        b = q.shape[0]
        pad = (-b) % int(self.mesh.shape[BATCH_AXIS])
        q = pad_lanes(q, pad)
        prog = pad_lanes(prog, pad)
        sbud = pad_lanes(sbud, pad)           # 0-budget pad lanes are inert
        st_in = None if state is None else pad_lanes(state.shard, pad)
        gt = None if gt is None else pad_lanes(gt, pad)
        stacked, rd, rp, cd, cp = _mesh_pools(
            cfg, self.mesh, q, prog, sbud, self._stacked_arrays(), st_in, gt)
        out = ShardedSearchState(
            shard=stacked, merged=merge_with_pools(stacked, rd, rp, cd, cp))
        if pad:
            out = jax.tree.map(lambda a: a[:b], out)
        return out

    # ------------------------------------------------------------- scan ----
    def scan_stats(self, prog: FilterProgram):
        """Global ScanStats assembled from per-shard bitmap passes.

        counts is exactly the sum of per-shard counts (each the popcount of
        its bitmap slice); clause_frac is the Ns-weighted mean of per-shard
        fractions, i.e. the global fraction.
        """
        from repro.core.plans import ScanStats, scan_stats

        per = [scan_stats(e, prog) for e in self.shards]
        frac = np.sum([p.clause_frac * p.n for p in per], axis=0)
        frac = (frac / max(self.n, 1)).astype(np.float32)
        return ScanStats(valid=jnp.concatenate([p.valid for p in per], axis=1),
                         counts=np.sum([p.counts for p in per], axis=0),
                         clause_frac=frac, n=self.n)

    def scan(self, cfg: SearchConfig, queries, filt, stats=None,
             base_state: ShardedSearchState | None = None,
             ) -> ShardedSearchState:
        """Pre-filter scan plan on a sharded engine: per-shard scans over
        the bitmap slices, merged like a traversal. Exactness carries over:
        merged cnt adds exactly σ_q·N (Σ of per-shard popcounts) and the
        result pool equals the unsharded scan's (same distances, same
        global-id tie order). Per-shard clause_add rounds rint(frac·Ns), so
        the merged n_clause_valid may differ from the unsharded engine's
        rint(frac·N) by ±S/2 — a feature input, not an accounting value.
        """
        from repro.core.plans import ScanStats, scan_search

        prog = self.compile(filt)
        if stats is None:
            stats = self.scan_stats(prog)
        ns = self.shard_size
        outs = []
        for i, eng in enumerate(self.shards):
            lo = int(self.offsets[i])
            sl = stats.valid[:, lo:lo + ns]
            sstats = ScanStats(valid=sl,
                               counts=np.asarray(sl.sum(axis=1), np.int64),
                               clause_frac=stats.clause_frac, n=ns)
            bs = (None if base_state is None
                  else take_shard(base_state.shard, i))
            outs.append(scan_search(eng, cfg, queries, prog, stats=sstats,
                                    base_state=bs))
        stacked = stack_shards(outs)
        merged = merge_shard_states(stacked, self.offsets)
        return ShardedSearchState(shard=stacked, merged=merged)

    # ----------------------------------------------------------- rerank ----
    def rerank_arrays(self, queries, state):
        """Exact float32 re-scoring of the merged candidate pool via the
        global vector store — ≤ (M+K) streamed row gathers per query
        regardless of tier."""
        from repro.quant import exact_rerank_store

        st = state.merged if isinstance(state, ShardedSearchState) else state
        if self.vector_store is None:
            raise ValueError("rerank on a float32 sharded engine is a no-op "
                             "(results are already exact)")
        return exact_rerank_store(
            jnp.asarray(queries, jnp.float32), self.vector_store,
            st.cand_idx, st.cand_valid, st.res_idx,
            int(st.res_idx.shape[1]))

    def rerank(self, cfg: SearchConfig, queries,
               state: ShardedSearchState) -> ShardedSearchState:
        """Terminal exact rerank of the merged view (no-op at float32).
        Only `merged` is rewritten — per-shard carries keep compressed
        pools, and like the plain engine a reranked state must not be
        resumed."""
        if self.effective_precision(cfg) == "float32":
            return state
        rd, ri = self.rerank_arrays(queries, state)
        return ShardedSearchState(
            shard=state.shard,
            merged=state.merged._replace(res_dist=rd, res_idx=ri))
