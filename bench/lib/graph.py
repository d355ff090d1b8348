"""The deployment's proximity graph, built on the device from the seed.

The rules of the program's host builder (`index/builder.py`, steps 3-5)
over candidate lists made by matmul:

  1. candidates   the 2R nearest rows of every row: bfloat16 matmul
                  distances over the whole corpus, `approx_min_k` (recall
                  target 0.95) to pick 2R + 1, then exact float32 distances
                  at HIGHEST precision, sorted ascending, self removed
     plus `random` rows drawn from the seed, as Vamana's random initial
     graph gives each row: far candidates that the prune keeps where no
     near one dominates them, so clusters stay linked
  2. alpha-prune  Vamana's robust prune at alpha = 1.2 down to R
  3. symmetrise   spare slots filled with reverse edges, nearest first;
                  each row keeps at most R neighbours
  4. entry point  the medoid (row nearest the mean)

The result is this deployment's offline index: fixed by the deployment's
seed, the same for every run of the cell. The program's host NN-descent builder
cannot build a million rows inside a run (PERF.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INF = jnp.float32(jnp.inf)


def _sqdist_rows(x, y):
    """x [B, d], y [B, C, d] -> [B, C] float32 squared L2 at HIGHEST."""
    xn = jnp.sum(x * x, axis=-1)[:, None]
    yn = jnp.sum(y * y, axis=-1)
    xy = jnp.einsum("bd,bcd->bc", x, y, precision=HIGHEST)
    return jnp.maximum(xn + yn - 2.0 * xy, 0.0)


@functools.partial(jax.jit, static_argnames=("c", "block"))
def candidates(vectors, c: int, block: int):
    """[N, c] nearest other rows of every row, ascending by exact distance
    (ids, squared distances)."""
    n, d = vectors.shape
    vb = vectors.astype(jnp.bfloat16)
    norms = jnp.sum(vectors * vectors, axis=1)

    def one(i):
        rows = jax.lax.dynamic_slice_in_dim(vectors, i * block, block)
        ids = i * block + jnp.arange(block)
        dots = jnp.dot(rows.astype(jnp.bfloat16), vb.T,
                       preferred_element_type=jnp.float32)
        dist = norms[ids][:, None] + norms[None, :] - 2.0 * dots
        dist = dist.at[jnp.arange(block), ids].set(INF)
        _, idx = jax.lax.approx_min_k(dist, c + 1, recall_target=0.95)
        idx = idx.astype(jnp.int32)
        exact = _sqdist_rows(rows, vectors[idx])
        exact = jnp.where(idx == ids[:, None], INF, exact)
        order = jnp.argsort(exact, axis=1, stable=True)[:, :c]
        return (jnp.take_along_axis(idx, order, axis=1),
                jnp.take_along_axis(exact, order, axis=1))

    cand, dist = jax.lax.map(one, jnp.arange(n // block))
    return cand.reshape(n, c), dist.reshape(n, c)


@functools.partial(jax.jit, static_argnames=("extra", "block"))
def add_random(cand, cand_dist, vectors, key, extra: int, block: int):
    """[N, C + extra] candidates: the given ones and `extra` random rows
    each, ascending by exact distance (self and repeats at +inf)."""
    n, c = cand.shape
    rnd = jax.random.randint(key, (n, extra), 0, n, jnp.int32)

    def one(i):
        rows = jax.lax.dynamic_slice_in_dim(vectors, i * block, block)
        ids = i * block + jnp.arange(block, dtype=jnp.int32)
        ci = jax.lax.dynamic_slice_in_dim(cand, i * block, block)
        cd = jax.lax.dynamic_slice_in_dim(cand_dist, i * block, block)
        ri = jax.lax.dynamic_slice_in_dim(rnd, i * block, block)
        rd = _sqdist_rows(rows, vectors[ri])
        dup = (ri == ids[:, None]) | (ri[:, :, None] == ci[:, None, :]).any(2)
        rd = jnp.where(dup, INF, rd)
        both_i = jnp.concatenate([ci, ri], axis=1)
        both_d = jnp.concatenate([cd, rd], axis=1)
        order = jnp.argsort(both_d, axis=1, stable=True)
        return (jnp.take_along_axis(both_i, order, axis=1),
                jnp.take_along_axis(both_d, order, axis=1))

    out_i, out_d = jax.lax.map(one, jnp.arange(n // block))
    return out_i.reshape(n, c + extra), out_d.reshape(n, c + extra)


def alpha_prune(cand, cand_dist, vectors, r: int, alpha: float):
    """Vamana robust prune of one block of rows, the rule of
    `index/builder.py::_alpha_prune_block`: walking the candidates in
    ascending order, keep one unless a kept one dominates it
    (alpha^2 * d(u, j) <= d(p, j) in squared distances), up to r kept.
    cand [b, C] ascending by cand_dist; returns [b, r], kept first."""
    b, c = cand.shape
    cv = vectors[jnp.maximum(cand, 0)]
    nrm = jnp.sum(cv * cv, axis=-1)
    cc = jnp.maximum(nrm[:, :, None] + nrm[:, None, :] - 2.0 * jnp.einsum(
        "bcd,bed->bce", cv, cv, precision=HIGHEST), 0.0)
    a2 = jnp.float32(alpha * alpha)
    cols = jnp.arange(c)

    def body(j, carry):
        keep, pruned, kept = carry
        sel = ~pruned[:, j] & (kept < r)
        keep = keep.at[:, j].set(sel)
        kept = kept + sel.astype(jnp.int32)
        dom = (a2 * cc[:, j, :] <= cand_dist) & (cols[None, :] > j)
        return keep, pruned | (dom & sel[:, None]), kept

    pruned0 = ~jnp.isfinite(cand_dist) | (cand < 0)
    keep, _, _ = jax.lax.fori_loop(
        0, c, body, (jnp.zeros((b, c), bool), pruned0,
                     jnp.zeros((b,), jnp.int32)))
    out = jnp.where(keep, cand, -1)
    order = jnp.argsort(~keep, axis=1, stable=True)
    return jnp.take_along_axis(out, order, axis=1)[:, :r]


@functools.partial(jax.jit, static_argnames=("r", "alpha", "block"))
def prune_all(cand, cand_dist, vectors, r: int, alpha: float, block: int):
    n, c = cand.shape
    out = jax.lax.map(
        lambda a: alpha_prune(a[0], a[1], vectors, r, alpha),
        (cand.reshape(n // block, block, c),
         cand_dist.reshape(n // block, block, c)))
    return out.reshape(n, r)


@functools.partial(jax.jit, static_argnames=("cap",))
def reverse_edges(neighbors, cap: int):
    """[N, cap] sources of each row's in-edges, ascending by source id
    (the first `cap` of them), -1 padded."""
    n, r = neighbors.shape
    src = jnp.repeat(jnp.arange(n, dtype=jnp.int32), r)
    dst = neighbors.reshape(-1)
    dst = jnp.where(dst >= 0, dst, n)
    dst_s, src_s = jax.lax.sort((dst, src), num_keys=2)
    pos = jnp.arange(dst_s.shape[0], dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), dst_s[1:] != dst_s[:-1]])
    rank = pos - jax.lax.cummax(jnp.where(first, pos, 0))
    rank = jnp.where(rank < cap, rank, cap)        # out of range: dropped
    rev = jnp.full((n + 1, cap), -1, jnp.int32)
    return rev.at[dst_s, rank].set(src_s, mode="drop")[:n]


@functools.partial(jax.jit, static_argnames=("block",))
def fill_reverse(pruned, rev, vectors, block: int):
    """Spare slots of each pruned row topped up with its reverse edges,
    nearest first, skipping ids already present (builder step 4)."""
    n, r = pruned.shape

    def one(i):
        blk = jax.lax.dynamic_slice_in_dim(pruned, i * block, block)
        cb = jax.lax.dynamic_slice_in_dim(rev, i * block, block)
        rows = jax.lax.dynamic_slice_in_dim(vectors, i * block, block)
        ids = i * block + jnp.arange(block, dtype=jnp.int32)
        db = _sqdist_rows(rows, vectors[jnp.maximum(cb, 0)])
        dup = (cb[:, :, None] == blk[:, None, :]).any(axis=2)
        bad = (cb < 0) | dup | (cb == ids[:, None])
        db = jnp.where(bad, INF, db)
        order = jnp.argsort(db, axis=1, stable=True)
        fills = jnp.where(jnp.isfinite(jnp.take_along_axis(db, order, 1)),
                          jnp.take_along_axis(cb, order, 1), -1)
        both = jnp.concatenate([blk, fills], axis=1)
        first = jnp.argsort(both < 0, axis=1, stable=True)[:, :r]
        return jnp.take_along_axis(both, first, axis=1)

    return jax.lax.map(one, jnp.arange(n // block)).reshape(n, r)


@jax.jit
def medoid(vectors):
    mean = vectors.mean(axis=0)
    return jnp.argmin(jnp.sum((vectors - mean) ** 2, axis=1))


def _block(n: int, want: int) -> int:
    b = min(want, n)
    while n % b:
        b //= 2
    return b


def build(vectors: np.ndarray, degree: int, alpha: float = 1.2,
          random: int = 0, key=None, cand_block: int = 256,
          prune_block: int = 4096):
    """(neighbors [N, R] int32 numpy, entry point) of the deployment;
    `random` candidates a row are drawn with `key`."""
    v = jnp.asarray(vectors, jnp.float32)
    n = v.shape[0]
    cand, dist = candidates(v, 2 * degree, _block(n, cand_block))
    if random:
        cand, dist = add_random(cand, dist, v, key, random,
                                _block(n, prune_block))
    pruned = prune_all(cand, dist, v, degree, float(alpha),
                       _block(n, prune_block))
    del cand, dist
    rev = reverse_edges(pruned, 2 * degree)
    nbrs = fill_reverse(pruned, rev, v, _block(n, prune_block))
    entry = int(medoid(v))
    return np.asarray(nbrs, np.int32), entry
