"""Vector-quantization codecs: int8 scalar quantization + product quantization.

The paper's adaptive termination reduces the *number* of distance
computations (NDC); this layer reduces the *cost and bandwidth of each one*.
Both codecs replace the float32 vector store in the traversal hot loop with
a compressed code store and an asymmetric distance (ADC: the query stays
full precision on the host side, the database side is compressed):

  int8  per-dimension affine quantization. A vector decodes as
        x̂ = zero + scale ⊙ c with c ∈ [-127, 127]^d (int8). The distance
        ‖q − x̂‖² = ‖q − zero‖² + ‖scale ⊙ c‖² − 2·(q − zero)⊙scale · c
        needs one integer dot per candidate: the query factor
        qs = (q − zero) ⊙ scale is itself quantized once per query to
        int8 (one per-query scale sq), so the per-candidate work is an
        int8×int8 → int32 dot — the MXU-native low-precision path — plus
        two precomputed scalars (‖q − zero‖² per query, ‖scale ⊙ c‖² per
        node). ~4× less index bandwidth per NDC.

  pq    multi-level product quantization (residual / additive PQ). d splits
        into S subspaces; level 0 k-means-quantizes each subspace
        (Kc ≤ 256 centroids), and each further level quantizes the
        *residual* left by the previous ones, so a vector is S·L bytes and
        reconstructs as the sum of L centroids per subspace. Reconstruction
        error falls geometrically in L (≈ Kc^(2/dsub) per level), which is
        what keeps compressed-domain *routing* faithful enough for
        matched-budget recall. Distances use the inner-product ADC form —
        d̂ = ‖q‖² + ‖x̂‖² − 2·Σ_sl lut[sl, code_sl] with
        lut[sl, c] = q_s · centroid — which stays a plain per-code table
        lookup for any L (the cross-level terms live in the stored ‖x̂‖²,
        one f32 per node). L=1 is classical PQ.

Both codecs also store a per-node reconstruction error ‖x − x̂‖² (the
compressed-distance bias scale). The traversal accumulates it over
inspected nodes, and the feature extractor turns it into the
`quant_err_*` probe features — how noisy the compressed distances a lane
has seen are, relative to the distances that matter — which keeps the GBDT
cost model calibrated under quantization.

Parity contract: `quant_dist` is the single source of the compressed
distance expression. The dense backend and the fused kernel's host path
both call it, so dense/pallas top-k and NDC agree exactly on CPU (the
int8 dot is integer arithmetic — exact — and the float tail is the same
traced expression). The TPU kernel body re-states the same arithmetic and
is validated against it in interpret mode (tests/test_quant.py).
"""
from __future__ import annotations

import functools
import hashlib
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-12


# ---------------------------------------------------------------- indices ----
class Int8Index(NamedTuple):
    """Device-resident int8 scalar-quantized vector store."""

    codes: jax.Array   # [N, d] int8
    scale: jax.Array   # [d] f32 — dequant step per dimension
    zero: jax.Array    # [d] f32 — per-dimension zero point
    norms: jax.Array   # [N] f32 — ‖scale ⊙ codes‖² (the ADC xn term)
    err: jax.Array     # [N] f32 — ‖x − x̂‖² reconstruction error


class PQIndex(NamedTuple):
    """Device-resident (multi-level) product-quantized vector store.

    The L levels are flattened level-major into one slot axis of size S·L
    (slot l·S + s holds level l of subspace s), so the per-step gather and
    the ADC lookup sum are shape-identical to classical PQ.
    """

    codes: jax.Array      # [N, S·L] uint8 — per-slot centroid ids
                          # (slot l·S + s = level l of subspace s)
    codebooks: jax.Array  # [L, S, Kc, dsub] f32
    norms: jax.Array      # [N] f32 — ‖x̂‖² (the ADC xn term)
    err: jax.Array        # [N] f32 — ‖x − x̂‖² reconstruction error


class Int8Prep(NamedTuple):
    """Per-query ADC state for the int8 codec (built once per search)."""

    qq: jax.Array  # [B, d] int8 — quantized (q − zero) ⊙ scale
    sq: jax.Array  # [B] f32 — per-query dequant step for qq
    qn: jax.Array  # [B] f32 — ‖q − zero‖²


class PQPrep(NamedTuple):
    """Per-query ADC state for the PQ codec: inner-product lookup table."""

    lut: jax.Array  # [B, S·L, Kc] f32 — q_s · centroid (slot l·S + s)
    qn: jax.Array   # [B] f32 — ‖q‖²


class QuantGather(NamedTuple):
    """One traversal step's gathered compressed data, handed to the backend.

    `codes` is [B, R, d] int8 (int8 codec) or [B, R, S·L] int32 (pq —
    widened after the gather; the resident store stays uint8). `norms` is
    [B, R] f32: ‖scale⊙c‖² for int8, ‖x̂‖² for pq.
    """

    prep: Any              # Int8Prep | PQPrep
    codes: jax.Array
    norms: jax.Array


# --------------------------------------------------------------- int8 SQ ----
def train_int8(vectors) -> tuple[jax.Array, jax.Array]:
    """Per-dimension affine parameters (scale, zero) from a training sample."""
    v = jnp.asarray(vectors, jnp.float32)
    lo = v.min(axis=0)
    hi = v.max(axis=0)
    scale = jnp.maximum((hi - lo) / 254.0, _EPS)
    zero = (hi + lo) / 2.0
    return scale, zero


@jax.jit
def encode_int8(scale, zero, vectors):
    """vectors [N, d] → (codes int8 [N, d], norms [N], err [N]).

    jitted: encoding is ~6 elementwise ops over [N, d]; eager per-op
    dispatch (~0.7 ms/op on this CPU) would dominate index build for the
    many small encodes in tests and serving bring-up.
    """
    v = jnp.asarray(vectors, jnp.float32)
    c = jnp.clip(jnp.round((v - zero) / scale), -127, 127)
    dec = c * scale                       # x̂ − zero
    norms = jnp.sum(dec * dec, axis=1)
    resid = (v - zero) - dec
    err = jnp.sum(resid * resid, axis=1)
    return c.astype(jnp.int8), norms, err


@jax.jit
def prep_int8(index: Int8Index, queries) -> Int8Prep:
    """Quantize the per-query ADC factor qs = (q − zero) ⊙ scale to int8."""
    q = jnp.asarray(queries, jnp.float32)
    qz = q - index.zero[None, :]
    qs = qz * index.scale[None, :]
    sq = jnp.maximum(jnp.max(jnp.abs(qs), axis=1) / 127.0, _EPS)
    qq = jnp.clip(jnp.round(qs / sq[:, None]), -127, 127).astype(jnp.int8)
    qn = jnp.sum(qz * qz, axis=1)
    return Int8Prep(qq=qq, sq=sq, qn=qn)


def _int8_assemble(prep: Int8Prep, norms, dot):
    """The int8 ADC float tail: qn + xn − 2·sq·dot, clamped ≥ 0.

    Single source of the rescale/clamp for every int8 distance layout —
    the per-step gathered form (`adc_int8`) and the corpus-blocked
    brute-force form share it, so the two can never drift apart.
    """
    d = prep.qn[:, None] + norms - 2.0 * prep.sq[:, None] * dot.astype(jnp.float32)
    return jnp.maximum(d, 0.0)


def adc_int8(prep: Int8Prep, codes_g, norms_g):
    """Compressed squared L2: prep + gathered codes [B,R,d] / norms [B,R].

    The dot is int8×int8 → int32 (exact integer arithmetic, MXU-native on
    TPU); only the final rescale is float.
    """
    dot = jax.lax.dot_general(
        prep.qq[:, None, :], codes_g,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )[:, 0, :]
    return _int8_assemble(prep, norms_g, dot)


def decode_int8(index: Int8Index, codes=None):
    """codes int8 [..., d] → float32 reconstruction x̂."""
    c = index.codes if codes is None else codes
    return index.zero + c.astype(jnp.float32) * index.scale


# -------------------------------------------------------------------- PQ ----
def _kmeans(x, cent0, iters: int):
    """Lloyd iterations on one subspace: x [n, dsub], cent0 [Kc, dsub]."""

    def step(_, cent):
        d = (jnp.sum(x * x, axis=1)[:, None]
             + jnp.sum(cent * cent, axis=1)[None, :]
             - 2.0 * x @ cent.T)
        assign = jnp.argmin(d, axis=1)
        onehot = jax.nn.one_hot(assign, cent.shape[0], dtype=jnp.float32)
        counts = onehot.sum(axis=0)
        sums = onehot.T @ x
        return jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None],
                         cent)

    return jax.lax.fori_loop(0, iters, step, cent0)


_kmeans_jit = jax.jit(_kmeans, static_argnames=("iters",))


def train_pq(vectors, n_subspaces: int, n_centroids: int = 256,
             iters: int = 20, seed: int = 0, n_levels: int = 1) -> jax.Array:
    """Residual k-means codebooks [L, S, Kc, dsub] from a training sample.

    Level 0 quantizes the subspace vectors; level l > 0 quantizes the
    residual left by levels < l (additive quantization).
    """
    v = np.asarray(vectors, np.float32)
    n, d = v.shape
    if d % n_subspaces:
        raise ValueError(f"dim {d} not divisible by {n_subspaces} subspaces")
    if not 2 <= n_centroids <= 256:
        raise ValueError(f"n_centroids must be in [2, 256] (uint8 codes), "
                         f"got {n_centroids}")
    if n_levels < 1:
        raise ValueError(f"n_levels must be >= 1, got {n_levels}")
    dsub = d // n_subspaces
    rng = np.random.default_rng(seed)
    xs = v.reshape(n, n_subspaces, dsub).copy()
    books = []
    for _ in range(n_levels):
        level = []
        for s in range(n_subspaces):
            init = xs[rng.choice(n, size=n_centroids,
                                 replace=n < n_centroids), s]
            cent = np.asarray(_kmeans_jit(jnp.asarray(xs[:, s]),
                                          jnp.asarray(init), iters=iters))
            level.append(cent)
            dd = ((xs[:, s][:, None, :] - cent[None]) ** 2).sum(-1)
            xs[:, s] -= cent[dd.argmin(axis=1)]
        books.append(np.stack(level))
    return jnp.asarray(np.stack(books))


@jax.jit
def _encode_pq_chunk(codebooks, v):
    levels, s, kc, dsub = codebooks.shape
    n = v.shape[0]
    xs = v.reshape(n, s, dsub)
    codes = []
    for lvl in range(levels):
        bl = codebooks[lvl]                                    # [S, Kc, dsub]
        dd = (jnp.sum(xs * xs, axis=2)[:, :, None]
              + jnp.sum(bl * bl, axis=2)[None, :, :]
              - 2.0 * jnp.einsum("nsd,scd->nsc", xs, bl))
        c = jnp.argmin(dd, axis=2)                             # [n, S]
        codes.append(c)
        picked = jnp.take_along_axis(bl[None], c[:, :, None, None],
                                     axis=2)[:, :, 0, :]       # [n, S, dsub]
        xs = xs - picked
    codes = jnp.concatenate(codes, axis=1)                     # [n, S·L]
    err = jnp.sum(xs * xs, axis=(1, 2))
    dec = v.reshape(n, s, dsub) - xs                           # x̂ per subspace
    norms = jnp.sum(dec * dec, axis=(1, 2))
    return codes.astype(jnp.uint8), norms, err


def encode_pq(codebooks, vectors, chunk: int = 4096):
    """vectors [N, d] → (codes uint8 [N, S·L], norms ‖x̂‖² [N], err [N]);
    chunked over N to bound the [chunk, S, Kc] assignment intermediate."""
    v = jnp.asarray(vectors, jnp.float32)
    parts = [_encode_pq_chunk(codebooks, v[i:i + chunk])
             for i in range(0, v.shape[0], chunk)]
    return tuple(jnp.concatenate([p[i] for p in parts]) for i in range(3))


@jax.jit
def build_pq_lut(codebooks, queries):
    """Per-query inner-product ADC table [B, S·L, Kc] (slot l·S + s holds
    q_s · centroid_{l,s,c}).

    jitted: rebuilt for every probe/resume call in the serving hot path —
    un-jitted it is ~8 eager dispatches per search, which previously bit
    this suite on other many-tiny-op helpers.
    """
    levels, s, kc, dsub = codebooks.shape
    q = jnp.asarray(queries, jnp.float32)
    qs = q.reshape(q.shape[0], s, dsub)
    lut = jnp.einsum("bsd,lscd->blsc", qs, codebooks,
                     precision=jax.lax.Precision.HIGHEST)
    return lut.reshape(q.shape[0], levels * s, kc)


def _pq_assemble(prep: PQPrep, norms, ip):
    """The PQ ADC float tail: qn + xn − 2·Σ lookups, clamped ≥ 0 — shared
    by the gathered and corpus-blocked layouts (see `_int8_assemble`)."""
    return jnp.maximum(prep.qn[:, None] + norms - 2.0 * ip, 0.0)


def adc_pq(prep: PQPrep, codes_g, norms_g):
    """Compressed squared L2 via the inner-product lookup sum.

    codes_g [B, R, S·L] int, norms_g [B, R] = gathered ‖x̂‖²:
    d̂ = ‖q‖² + ‖x̂‖² − 2·Σ_sl lut[sl, code_sl].
    """
    idx = codes_g.astype(jnp.int32).transpose(0, 2, 1)        # [B, S·L, R]
    ip = jnp.take_along_axis(prep.lut, idx, axis=2).sum(axis=1)
    return _pq_assemble(prep, norms_g, ip)


def decode_pq(index: PQIndex, codes=None):
    """codes [..., S·L] → float32 reconstruction x̂ (sum of the L level
    centroids per subspace)."""
    c = (index.codes if codes is None else codes).astype(jnp.int32)
    levels, s, kc, dsub = index.codebooks.shape
    n = c.shape[0]
    flat = index.codebooks.reshape(levels * s, kc, dsub)
    gathered = jnp.take_along_axis(
        flat[None], c[:, :, None, None], axis=2
    )[:, :, 0, :]                                              # [N, S·L, dsub]
    return gathered.reshape(n, levels, s, dsub).sum(axis=1).reshape(n, s * dsub)


def pad_rows_for_dma(arr, multiple: int = 128):
    """Zero-pad the trailing axis of a per-node row store to a lane multiple.

    The persistent traversal kernel (kernels/persistent_step.py) gathers
    node rows — float vectors, int8 codes, widened PQ codes, packed
    attribute words — straight from HBM with one async copy per row;
    padding every row to a 128-lane multiple keeps each copy a clean,
    tileable VMEM landing. Zero fill is semantics-free for every consumer:
    dot-product contractions against zero-padded queries, sliced-off PQ
    slots, and ignored attribute columns.
    """
    a = jnp.asarray(arr)
    pad = (-a.shape[-1]) % multiple
    if pad == 0:
        return a
    widths = ((0, 0),) * (a.ndim - 1) + ((0, pad),)
    return jnp.pad(a, widths, constant_values=0)


# ------------------------------------------------------------- dispatch ----
def prepare_query(precision: str, index, queries):
    """Per-search query preparation (the satellite-jitted helpers above)."""
    if precision == "int8":
        return prep_int8(index, queries)
    if precision == "pq":
        q = jnp.asarray(queries, jnp.float32)
        return PQPrep(lut=build_pq_lut(index.codebooks, q),
                      qn=jnp.sum(q * q, axis=1))
    raise ValueError(f"unknown precision {precision!r}")


def quant_dist(precision: str, qg: QuantGather):
    """[B, R] compressed squared L2 from one step's gathered codes.

    The single source of the ADC expression: the dense backend and the
    fused kernel's host path both call this, which is what makes
    dense/pallas compressed-domain parity exact by construction.
    """
    if precision == "int8":
        return adc_int8(qg.prep, qg.codes, qg.norms)
    if precision == "pq":
        return adc_pq(qg.prep, qg.codes, qg.norms)
    raise ValueError(f"unknown precision {precision!r}")


def build_quant_index(precision: str, vectors, train_sample=None, *,
                      pq_subspaces: int | None = None, pq_centroids: int = 256,
                      pq_iters: int = 20, pq_levels: int | None = None,
                      seed: int = 0):
    """Train a codec and encode the full vector store.

    train_sample: optional [n, d] subset for codec fitting (k-means /
    min-max); defaults to the full set. Encoding always covers `vectors`.
    """
    v = jnp.asarray(vectors, jnp.float32)
    t = v if train_sample is None else jnp.asarray(train_sample, jnp.float32)
    if precision == "int8":
        scale, zero = train_int8(t)
        codes, norms, err = encode_int8(scale, zero, v)
        return Int8Index(codes=codes, scale=scale, zero=zero, norms=norms,
                         err=err)
    if precision == "pq":
        d = int(v.shape[1])
        if pq_subspaces is None:
            # 4-dim subspaces by default (S·L stays well under d)
            pq_subspaces = next(s for s in (d // 4, 8, 4, 2, 1)
                                if s >= 1 and d % s == 0)
        if pq_levels is None:
            # Three residual levels: reconstruction error falls ~Kc^(2/dsub)
            # per level, and err ≈ 1e-3·‖x‖² is what keeps compressed
            # *routing* (not just the reranked pool) faithful enough for
            # matched-budget recall. S·L + 8 bytes/vec stays ≥4x under 4d.
            pq_levels = 3
        books = train_pq(t, pq_subspaces, pq_centroids, pq_iters, seed,
                         n_levels=pq_levels)
        codes, norms, err = encode_pq(books, v)
        return PQIndex(codes=codes, codebooks=books, norms=norms, err=err)
    raise ValueError(f"unknown precision {precision!r} "
                     "(expected 'int8' or 'pq')")


def codec_key(precision: str, index) -> str:
    """Stable identity string for a codec: precision tag + parameter digest.

    Hashes only the small codec parameters (scale/zero or codebooks), not
    the [N, ...] code arrays — two engines over the same corpus with the
    same trained codec collide on purpose (same answers), while a retrained
    codebook or different precision changes every cache key.
    """
    if index is None or precision == "float32":
        return "float32"
    h = hashlib.sha1()
    if isinstance(index, Int8Index):
        h.update(np.asarray(index.scale).tobytes())
        h.update(np.asarray(index.zero).tobytes())
    elif isinstance(index, PQIndex):
        h.update(np.asarray(index.codebooks).tobytes())
    else:
        raise TypeError(f"unknown quant index {type(index).__name__}")
    return f"{precision}:{h.hexdigest()[:12]}"


def index_nbytes(index) -> int:
    """Traversal-resident bytes of a quant index (codes + per-node stats +
    codec parameters) — the quantity the ≥4× memory claim is about."""
    return sum(np.asarray(leaf).nbytes for leaf in jax.tree.leaves(index))


def store_ratio(index, base_vectors) -> float:
    """How many × smaller the quant store is than the float32 vector store
    (total bytes incl. codec parameters). One definition shared by every
    surface that prints the claim (quickstart, serving launcher, bench)."""
    return np.asarray(base_vectors).nbytes / index_nbytes(index)


@jax.jit
def _compressed_dist_int8(prep, codes, norms):
    dot = jax.lax.dot_general(
        prep.qq, codes,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return _int8_assemble(prep, norms[None, :], dot)


@jax.jit
def _compressed_dist_pq(prep, codes, norms):
    idx = codes.astype(jnp.int32)                              # [Nb, S·L]
    ip = jnp.take_along_axis(
        prep.lut[:, None, :, :],                               # [B,1,SL,Kc]
        idx[None, :, :, None], axis=3)[..., 0].sum(axis=2)     # [B,Nb]
    return _pq_assemble(prep, norms[None, :], ip)


@functools.partial(jax.jit, static_argnames=("k",))
def _masked_topk(dd, valid, k):
    dd = jnp.where(valid, dd, jnp.inf)
    neg, ti = jax.lax.top_k(-dd, k)
    return -neg, jnp.where(jnp.isfinite(-neg), ti, -1)


def compressed_filtered_topk(precision: str, index, queries, valid_mask, k: int,
                             chunk: int = 128, n_block: int = 1024):
    """Brute-force compressed-domain filtered top-k (dist [B,k], idx [B,k]).

    The compressed-domain analogue of `index.bruteforce.filtered_knn_exact`:
    the best any traversal can do *before* the exact rerank. Training uses
    its distances as the convergence target on quantized engines — against
    exact float32 ground truth a compressed traversal would (rightly) never
    converge, and every W_q label would degenerate to the exhaustion cost.

    Blocked over queries (`chunk`) *and* corpus (`n_block`): the PQ lookup
    materializes a [chunk, n_block, S·L] intermediate, which unblocked
    would scale host memory with N — the same [B, N, ·] blowup the chunked
    filter-selectivity oracle exists to avoid.
    """
    q = jnp.asarray(queries, jnp.float32)
    dist_fn = (_compressed_dist_int8 if precision == "int8"
               else _compressed_dist_pq)
    n = index.codes.shape[0]
    outs_d, outs_i = [], []
    for s in range(0, q.shape[0], chunk):
        prep = prepare_query(precision, index, q[s:s + chunk])
        dd = jnp.concatenate(
            [dist_fn(prep, index.codes[b:b + n_block],
                     index.norms[b:b + n_block])
             for b in range(0, n, n_block)], axis=1)           # [B, N]
        d, i = _masked_topk(dd, jnp.asarray(valid_mask[s:s + chunk]), k)
        outs_d.append(d)
        outs_i.append(i)
    return (np.asarray(jnp.concatenate(outs_d)),
            np.asarray(jnp.concatenate(outs_i)))
