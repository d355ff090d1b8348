"""SearchEngine — shard-aware device-resident index + traversal facade.

Bundles the arrays every search needs (vectors, packed attributes, graph,
entry point), selects a traversal backend by name, and places everything on
a 1-D device mesh when more than one accelerator is visible:

  index data (base_vectors / neighbors / attrs)  replicated over the mesh
  per-query arrays (queries, compiled filter programs, budgets,
                    every SearchState buffer)     sharded over the batch axis

Filters are accepted in any of three forms — a legacy `FilterSpec` batch, a
sequence of filter-algebra expressions (`repro.filters.expr`), or an
already-compiled `FilterProgram` — and are lowered here to one compiled
program per batch, so the traversal layers below never branch on a
predicate kind. The engine keeps *one* attribute bundle (label words +
numeric channels) and always passes both: which attributes a clause reads
is part of the program, not of the engine call.

The lockstep while_loop contains no cross-lane collectives, so `shard_map`
over the batch axis runs one independent traversal per device — each shard
even gets its own trip count (lanes on a finished shard stop paying for
stragglers elsewhere). Partition specs reuse `distributed.sharding`
(`batch_spec`), keeping the logical-axis rules in one place.

Probe/resume/search entry points are unchanged from the pre-shard engine:
the E2E pipeline, baselines, benchmarks and serving only change at the
constructor (`SearchEngine.build(ds, graph, backend="pallas")`).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.search import (SearchConfig, SearchState, get_backend,
                               run_search, run_search_persistent)
from repro.core.state import init_state, pad_lanes  # noqa: F401  (re-export)
from repro.data.synthetic import AttributedDataset
from repro.distributed.sharding import batch_spec
from repro.filters.compile import FilterProgram, as_program
from repro.index.graph import GraphIndex

BIG_BUDGET = 1 << 30

BATCH_AXIS = "data"


def make_search_mesh(devices=None) -> Mesh | None:
    """1-D batch mesh over the visible devices; None on a single device."""
    devices = list(jax.devices()) if devices is None else list(devices)
    if len(devices) <= 1:
        return None
    return Mesh(np.asarray(devices), (BATCH_AXIS,))


# Shard padding shares the serving layer's lane-surgery helper: padded lanes
# self-deactivate on their 0 NDC budget, so the values never influence real
# lanes.
_pad_batch = pad_lanes


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "bspec"))
def _batch_mesh_search(cfg, mesh, bspec, q, prog, base, attrs, nb, budgets,
                       entry, state, gt, quant):
    """One traversal per device over the batch axis, as one program (an
    eager `shard_map` would trace and dispatch its body anew each call)."""
    rep = P()
    args = [q, prog, base, attrs, nb, budgets, entry]
    specs = [bspec, bspec, rep, rep, rep, bspec, rep]
    has_state, has_gt = state is not None, gt is not None
    has_quant = quant is not None
    if has_state:
        args.append(state)
        specs.append(bspec)
    if has_gt:
        args.append(gt)
        specs.append(bspec)
    if has_quant:
        args.append(quant)          # index data: replicated like the vectors
        specs.append(rep)

    def fn(*a):
        qq, qa, base, at, nb, bud, ep = a[:7]
        st = a[7] if has_state else None
        g = a[7 + has_state] if has_gt else None
        qt = a[7 + has_state + has_gt] if has_quant else None
        return run_search(cfg, qq, qa, base, at, nb, bud, ep,
                          state=st, gt_dist=g, quant=qt)

    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(specs),
                         out_specs=bspec, check_vma=False)(*args)


@dataclasses.dataclass
class SearchEngine:
    base_vectors: jnp.ndarray   # [N, d]
    label_attrs: jnp.ndarray    # [N, W] uint32
    value_attrs: jnp.ndarray    # [N, V] f32 (a bare [N] is accepted and
                                # treated as one channel)
    neighbors: jnp.ndarray      # [N, R]
    entry_point: int
    backend: str | None = None  # None → whatever SearchConfig carries
    mesh: Mesh | None = None    # None → single-device execution
    precision: str = "float32"  # deployment default ("float32"|"int8"|"pq");
                                # a per-call SearchConfig(precision=...) wins
    quant: object | None = None  # Int8Index | PQIndex (repro.quant) — the
                                # compressed vector store the traversal
                                # gathers from when precision != float32
    vector_store: object | None = None  # quant.tiering store for the exact
                                # rerank; when set (host tier), base_vectors
                                # is a [N, 0] placeholder — only its row
                                # count is read in compressed mode
    # precision -> (source arrays, (rows, aux, nbrs)): the persistent
    # kernel's lane-padded HBM operands, see `persistent_operands`
    _persistent_ops: dict = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)

    @classmethod
    def build(cls, ds: AttributedDataset, graph: GraphIndex,
              backend: str | None = None, mesh: Mesh | str | None = "auto",
              precision: str = "float32", quant_cfg: dict | None = None,
              tier: str = "device",
              ) -> "SearchEngine":
        """Construct a device-resident engine.

        backend    registered TraversalBackend name ("dense" | "pallas"),
                   used whenever the per-call SearchConfig doesn't set one;
                   an explicit SearchConfig(backend=...) always wins.
        mesh       "auto" builds a 1-D batch mesh when >1 device is visible;
                   pass an explicit Mesh (first axis = batch) or None to
                   force single-device placement.
        precision  "float32" (default, bit-identical to the pre-quant
                   engine), or "int8" / "pq" — trains the codec on a sample
                   of the dataset, encodes the full store, and evaluates
                   traversal distances in the compressed domain (exact
                   float32 rerank available via `rerank`).
        quant_cfg  codec knobs forwarded to quant.build_quant_index
                   (pq_subspaces, pq_centroids, pq_iters, pq_levels, seed)
                   plus "train_sample_size" for the codec-fitting sample.
        tier       "device" keeps float32 vectors device-resident;
                   "host" (requires a non-float32 precision) moves them to
                   a host-memory rerank tier (quant.tiering
                   .HostVectorStore) and leaves only a [N, 0] placeholder
                   on device — the compressed codes bound device memory,
                   not the float32 store.
        """
        graph.validate()
        if mesh == "auto":
            mesh = make_search_mesh()
        store = None
        vectors = jnp.asarray(ds.vectors)
        if tier != "device":
            from repro.quant.tiering import as_vector_store

            if precision == "float32":
                raise ValueError(
                    "tier='host' requires a compressed traversal precision "
                    "('int8' or 'pq') — a float32 traversal reads the full "
                    "vector store every step, which defeats the tier")
            store = as_vector_store(ds.vectors, tier)
            vectors = jnp.zeros((vectors.shape[0], 0), jnp.float32)
        eng = cls(
            base_vectors=vectors,
            label_attrs=jnp.asarray(ds.labels_packed),
            value_attrs=jnp.asarray(ds.value_matrix),
            neighbors=jnp.asarray(graph.neighbors),
            entry_point=graph.entry_point,
            backend=backend,
            mesh=mesh,
            precision=precision,
            vector_store=store,
        )
        if precision != "float32":
            from repro.quant import build_quant_index

            qcfg = dict(quant_cfg or {})
            sample_n = qcfg.pop("train_sample_size", 16384)
            sample = ds.sample_vectors(sample_n, seed=qcfg.get("seed", 0))
            eng.quant = build_quant_index(precision, ds.vectors,
                                          train_sample=sample, **qcfg)
        if mesh is not None:
            rep = NamedSharding(mesh, P())
            eng.base_vectors = jax.device_put(eng.base_vectors, rep)
            eng.label_attrs = jax.device_put(eng.label_attrs, rep)
            eng.value_attrs = jax.device_put(eng.value_attrs, rep)
            eng.neighbors = jax.device_put(eng.neighbors, rep)
            if eng.quant is not None:
                eng.quant = jax.device_put(eng.quant, rep)
        return eng

    @property
    def n_words(self) -> int:
        return int(self.label_attrs.shape[1])

    @property
    def n_values(self) -> int:
        return 1 if self.value_attrs.ndim == 1 else int(self.value_attrs.shape[1])

    def _attrs(self):
        """The uniform (labels, values[N, V]) bundle every search receives."""
        vals = self.value_attrs
        if vals.ndim == 1:  # hand-built engines may carry a single channel
            vals = vals[:, None]
        return self.label_attrs, vals

    def persistent_operands(self, precision: str):
        """(rows, aux, nbrs) for the persistent multi-step kernel at
        `precision`: per-node stores widened to 128-lane rows
        (kernels/persistent_step.py). Packed on the first kernel search
        and kept for the engine's lifetime, so probe and resume do not
        re-pack them; repacked only if a source array was replaced."""
        from repro.kernels.persistent_step import build_persistent_operands

        src = (self.base_vectors, self.label_attrs, self.value_attrs,
               self.neighbors, self.quant)
        hit = self._persistent_ops.get(precision)
        if hit is None or any(a is not b for a, b in zip(hit[0], src)):
            labels, values = self._attrs()
            hit = (src, build_persistent_operands(
                precision, self.base_vectors, labels, values, self.neighbors,
                self.quant))
            self._persistent_ops[precision] = hit
        return hit[1]

    def compile(self, filt) -> FilterProgram:
        """Lower FilterSpec | Expr | sequence[Expr] to a device program."""
        prog = as_program(filt, self.n_words, self.n_values)
        return FilterProgram(*(jnp.asarray(a) for a in prog))

    # ------------------------------------------------------------- quant ----
    def effective_precision(self, cfg: SearchConfig) -> str:
        """The precision a call with `cfg` runs at (per-call override wins)."""
        return cfg.precision or self.precision

    def codec_key(self, cfg: SearchConfig | None = None) -> str:
        """Codec identity for result caching ("float32" | "int8:…" | "pq:…").

        Precision changes answers (compressed-domain traversal order), so
        the serving cache folds this into every request key. Pass the
        call's `cfg` so a per-call precision override (e.g. a quantized
        engine served at float32) keys under the precision the searches
        actually run at, not the engine default.
        """
        from repro.quant import codec_key

        prec = self.precision if cfg is None else self.effective_precision(cfg)
        return codec_key(prec, self.quant)

    def rerank_arrays(self, queries, state: SearchState):
        """Exact float32 re-scoring of a finished traversal's candidate pool.

        Returns (res_dist [B, K], res_idx [B, K]) — the compressed-domain
        pool (result set ∪ valid candidate queue) re-ranked against the
        retained full-precision vectors. Constant ≤ (M+K) float32 distance
        computations per query, not counted into `state.cnt`.
        """
        from repro.quant import exact_rerank, exact_rerank_store

        if self.vector_store is not None:
            return exact_rerank_store(jnp.asarray(queries, jnp.float32),
                                      self.vector_store, state.cand_idx,
                                      state.cand_valid, state.res_idx,
                                      int(state.res_idx.shape[1]))
        return exact_rerank(jnp.asarray(queries, jnp.float32),
                            self.base_vectors, state.cand_idx,
                            state.cand_valid, state.res_idx,
                            int(state.res_idx.shape[1]))

    def rerank(self, cfg: SearchConfig, queries, state: SearchState,
               ) -> SearchState:
        """Terminal exact-rerank: replace the result buffers with float32
        re-scored top-k. No-op at float32 precision. The returned state
        must not be resumed (results are exact, queue stays compressed)."""
        if self.effective_precision(cfg) == "float32":
            return state
        rd, ri = self.rerank_arrays(queries, state)
        return state._replace(res_dist=rd, res_idx=ri)

    def search(
        self,
        cfg: SearchConfig,
        queries: np.ndarray,
        filt,                         # FilterSpec | Expr(s) | FilterProgram
        budgets,                      # scalar or [B]
        state: SearchState | None = None,
        gt_dist: np.ndarray | None = None,
        tracer=None,                  # obs.Tracer | None — persistent driver
        trace_id: str = "",           # spans only; never enters traced code
    ) -> SearchState:
        cfg = dataclasses.replace(cfg, degree=int(self.neighbors.shape[1]))
        if cfg.backend is None:
            # engine default applies only when the call doesn't pick one:
            # an explicit SearchConfig(backend=...) always wins.
            cfg = dataclasses.replace(cfg, backend=self.backend or "dense")
        # same inheritance rule for precision: per-call override wins,
        # None inherits the engine's deployment default
        cfg = dataclasses.replace(cfg, precision=self.effective_precision(cfg))
        if cfg.precision != "float32" and self.quant is None:
            raise ValueError(
                f"SearchConfig(precision={cfg.precision!r}) on an engine "
                "without a quant index — build with precision=...")
        if cfg.precision == "float32" and self.base_vectors.shape[1] == 0:
            raise ValueError(
                "float32 traversal on a host-tiered engine: the device "
                "holds only a vector placeholder — search at the engine's "
                "compressed precision (rerank stays exact via the host "
                "tier) or rebuild with tier='device'")
        quant = self.quant if cfg.precision != "float32" else None
        prog = self.compile(filt)
        attrs = self._attrs()
        q = jnp.asarray(queries, jnp.float32)
        b = q.shape[0]
        budgets = jnp.broadcast_to(jnp.asarray(budgets, jnp.int32), (b,))
        gt = None if gt_dist is None else jnp.asarray(gt_dist, jnp.float32)
        if self.mesh is None:
            # Persistent backends go through the eager launch-loop driver:
            # same bit-exact results, but finished lanes are compacted away
            # between multi-step launches instead of riding as no-ops (and
            # on TPU each launch is the VMEM-resident multi-step kernel).
            # Under a mesh the traced run_search handles persistence via its
            # launch-grouped loop — host compaction can't cross shard_map.
            if getattr(get_backend(cfg.backend), "persistent", False):
                return run_search_persistent(
                    cfg, q, prog, self.base_vectors, attrs, self.neighbors,
                    budgets, self.entry_point, state=state, gt_dist=gt,
                    quant=quant, tracer=tracer, trace_id=trace_id,
                    kernel_operands=self.persistent_operands,
                )
            return run_search(
                cfg, q, prog, self.base_vectors, attrs, self.neighbors,
                budgets, self.entry_point, state=state, gt_dist=gt,
                quant=quant,
            )
        return self._search_sharded(cfg, q, prog, attrs, budgets, state, gt,
                                    quant)

    # ---------------------------------------------------------- sharded ----
    def _search_sharded(self, cfg, q, prog, attrs, budgets, state, gt,
                        quant=None):
        mesh = self.mesh
        ndev = int(np.prod(list(mesh.shape.values())))
        b = q.shape[0]
        pad = (-b) % ndev
        bspec = batch_spec(mesh, b + pad)
        if bspec == P(None):
            # explicit mesh whose axis names the sharding rule table doesn't
            # know — shard over the first axis rather than silently
            # replicating the whole batch on every device. (b + pad is a
            # multiple of ndev, hence of the first-axis size.)
            bspec = P(mesh.axis_names[0])

        q = _pad_batch(q, pad)
        # program rows pad with all-zero (match-nothing) clauses — inert
        # under the 0 NDC budget the pad lanes carry
        prog = _pad_batch(prog, pad)
        budgets = _pad_batch(budgets, pad)  # 0-budget lanes stop immediately
        state = None if state is None else _pad_batch(state, pad)
        gt = None if gt is None else _pad_batch(gt, pad)
        out = _batch_mesh_search(cfg, mesh, bspec, q, prog, self.base_vectors,
                                 attrs, self.neighbors, budgets,
                                 jnp.int32(self.entry_point), state, gt, quant)
        if pad:
            out = jax.tree.map(lambda a: a[:b], out)
        return out
