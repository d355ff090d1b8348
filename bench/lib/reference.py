"""The plain reference: blocked brute-force filtered k-NN in `jax.numpy`.

Filters are evaluated from the raw label words and values (contain:
every required bit set; equal: the words match exactly; one closed value
window), and distances are float32 squared L2 at Precision.HIGHEST. It
shares no code with the program (`index/bruteforce.py`,
`filters/compile.py`). Queries go in blocks and the corpus in row blocks
with a running top-k, so it fits beside nothing else on the chip.

`mode` selects the precision the distances are computed in: "highest"
is the reference; "high" (three bfloat16 passes, the lo*lo term dropped)
and "int8" (rows quantised to 8-bit codes per dimension, the distances to
the codes returned with no float32 rerank) are the lower-precision
controls that must come out as not correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INF = jnp.float32(jnp.inf)
MODES = ("highest", "high", "int8")


def _bf16(x):
    """x rounded to bfloat16's 8 mantissa bits, kept in float32 (a
    rounding XLA may not fold away, unlike a convert pair)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _dot_high(q, x):
    """q [B, d] . x [R, d]^T in three bfloat16 passes, as
    Precision.HIGH computes it on a TPU: each operand split into a high
    and a low bfloat16 part, the low x low product dropped. The same
    numbers on every backend (products of bfloat16 values are exact in
    float32)."""
    qh, xh = _bf16(q), _bf16(x)
    ql, xl = _bf16(q - qh), _bf16(x - xh)

    def dot(a, b):
        return jnp.dot(a, b.T, precision=HIGHEST)

    return dot(qh, xh) + dot(qh, xl) + dot(ql, xh)


@jax.jit
def int8_rows(vectors):
    """Rows quantised to 8-bit codes, affine per dimension (256 levels
    between the column's min and max), returned dequantised: the int8
    control's store, its distances taken with no float32 rerank."""
    lo = jnp.min(vectors, axis=0)
    scale = jnp.maximum(jnp.max(vectors, axis=0) - lo, 1e-12) / 255.0
    return lo + jnp.clip(jnp.round((vectors - lo) / scale), 0, 255) * scale


def passes(labels, values, contain, equal, has_equal, lo, hi):
    """[B, R] bool: rows (labels [R, W], values [R]) against B filters."""
    lab = labels[None, :, :]
    ok = jnp.all((lab & contain[:, None, :]) == contain[:, None, :], axis=-1)
    eq = jnp.all(lab == equal[:, None, :], axis=-1)
    ok &= ~has_equal[:, None] | eq
    return ok & (values[None, :] >= lo[:, None]) & (
        values[None, :] <= hi[:, None])


@functools.partial(jax.jit, static_argnames=("k", "block", "mode"))
def _search(q, filt, vectors, labels, values, k: int, block: int, mode: str):
    n, d = vectors.shape
    b = q.shape[0]
    qn = jnp.sum(q * q, axis=1)

    def step(carry, j):
        best_d, best_i = carry
        x = jax.lax.dynamic_slice_in_dim(vectors, j * block, block)
        lab = jax.lax.dynamic_slice_in_dim(labels, j * block, block)
        val = jax.lax.dynamic_slice_in_dim(values, j * block, block)
        if mode == "high":
            qx = _dot_high(q, x)
        else:
            qx = jnp.dot(q, x.T, precision=HIGHEST)
        dist = jnp.maximum(qn[:, None] + jnp.sum(x * x, axis=1)[None, :]
                           - 2.0 * qx, 0.0)
        dist = jnp.where(passes(lab, val, *filt), dist, INF)
        ids = j * block + jnp.arange(block, dtype=jnp.int32)
        cat_d = jnp.concatenate([best_d, dist], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, (b, block))],
                                axis=1)
        neg, pos = jax.lax.top_k(-cat_d, k)
        return (-neg, jnp.take_along_axis(cat_i, pos, axis=1)), None

    init = (jnp.full((b, k), INF), jnp.full((b, k), -1, jnp.int32))
    (best_d, best_i), _ = jax.lax.scan(step, init, jnp.arange(n // block))
    return best_d, jnp.where(jnp.isfinite(best_d), best_i, -1)


@jax.jit
def _score(q, ids, filt, vectors, labels, values):
    """For returned ids [B, k]: their exact distances (direct float32 sum
    of squared differences) and whether each passes its filter."""
    safe = jnp.maximum(ids, 0)
    diff = vectors[safe] - q[:, None, :]
    dist = jnp.sum(diff * diff, axis=-1)
    contain, equal, has_equal, lo, hi = filt
    lab = labels[safe]
    val = values[safe]
    ok = jnp.all((lab & contain[:, None, :]) == contain[:, None, :], -1)
    ok &= ~has_equal[:, None] | jnp.all(lab == equal[:, None, :], -1)
    ok &= (val >= lo[:, None]) & (val <= hi[:, None])
    return dist, ok & (ids >= 0)


class Reference:
    """The corpus on the device, for the reference and its controls."""

    def __init__(self, vectors, labels_packed, values):
        self.vectors = jnp.asarray(vectors, jnp.float32)
        self.labels = jnp.asarray(labels_packed, jnp.uint32)
        self.values = jnp.asarray(values, jnp.float32)
        self.n = self.vectors.shape[0]
        self._int8 = None

    @staticmethod
    def _filt(f):
        return tuple(jnp.asarray(a) for a in (f.contain, f.equal,
                                              f.has_equal, f.lo, f.hi))

    def _block(self, want: int) -> int:
        b = min(want, self.n)
        while self.n % b:
            b //= 2
        return b

    def search(self, queries, filters, k: int, mode: str = "highest",
               q_block: int = 256, block: int = 32768):
        """Exact filtered top-k (ids [B, k] -1 padded, distances [B, k]
        +inf padded, ascending) at the precision `mode` names."""
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}")
        vec = self.vectors
        if mode == "int8":
            if self._int8 is None:
                self._int8 = int8_rows(self.vectors)
            vec = self._int8
        out_d, out_i = [], []
        for s in range(0, queries.shape[0], q_block):
            sl = slice(s, s + q_block)
            q = jnp.asarray(queries[sl], jnp.float32)
            pad = q_block - q.shape[0]
            f = self._filt(filters.take(np.arange(s, s + q.shape[0])))
            if pad:
                q = jnp.pad(q, ((0, pad), (0, 0)))
                f = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                          for a in f)
            d, i = _search(q, f, vec, self.labels, self.values, k,
                           self._block(block), mode)
            out_d.append(np.asarray(d)[: q_block - pad])
            out_i.append(np.asarray(i)[: q_block - pad])
        return np.concatenate(out_i), np.concatenate(out_d)

    def score(self, queries, ids, filters, block: int = 1024):
        """(exact distances, passes-filter) of returned ids [B, k]."""
        dist, ok = [], []
        for s in range(0, queries.shape[0], block):
            sl = slice(s, s + block)
            d, o = _score(jnp.asarray(queries[sl], jnp.float32),
                          jnp.asarray(ids[sl], jnp.int32),
                          self._filt(filters.take(np.arange(
                              s, min(s + block, queries.shape[0])))),
                          self.vectors, self.labels, self.values)
            dist.append(np.asarray(d))
            ok.append(np.asarray(o))
        return np.concatenate(dist), np.concatenate(ok)
