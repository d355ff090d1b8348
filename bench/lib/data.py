"""Vectorised generator of the benchmark's deployments, on the device.

The labels and the value attribute have the semantics of the program's
`make_dataset` (per-cluster Zipf label weights over a random permutation
of the alphabet; 1..max_labels distinct labels a row, drawn without
replacement from its cluster's weights; a numeric attribute that is a
noisy linear probe of the vector), made in one jitted call from the
seed, with no per-row Python loop: labels are drawn by Gumbel top-k,
which samples without replacement in the same way as successive
weighted draws.

The vectors are not `make_dataset`'s. Its rows, unit(center + 0.35 *
N(0, I_d)), carry isotropic noise of norm about 4 against a unit
center: nearly uniform on the sphere, with an intrinsic dimension near
d, which no embedding or descriptor set has. Here each cluster spans its
own rotated copy of one decaying spectrum, so the local intrinsic
dimension is low (tens, as published estimates for SIFT and learned
embeddings are), and the clusters are weak enough that a proximity graph
stays navigable between them:

  unit(center_norm * center[c] + (z * s) @ rot[c]),  z ~ N(0, I_d),
  s_j proportional to (j + 1)^(-spectrum_decay / 2), sum s_j^2 = 1,
  center[c] unit, rot[c] a random orthogonal matrix per cluster (made on
  the host, the rest in one jitted call).

Configuration (`data` block of a config file):

  n, dim, n_clusters             sizes
  center_norm, spectrum_decay    vectors, as above
  labels.kind = "cluster_zipf"   alphabet_size, max_labels, label_skew
  labels.kind = "uniform_single" one label a row, uniform over the alphabet,
                                 independent of the vectors
  values.kind = "linear_probe"   value_noise: (x.w + noise) scaled to [0, 1]
  values.kind = "uniform"        uniform on [0, 1), independent of x
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def key_from_seed(seed: int, stream: int = 0) -> jax.Array:
    """A threefry key from any non-negative seed (larger than 32 bits too)
    and a stream number, so each part of a run draws independently."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def n_words(alphabet_size: int) -> int:
    return -(-alphabet_size // 32)


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def pack_bits(onehot):
    """[N, A] bool -> [N, ceil(A/32)] uint32 multi-hot words."""
    n, a = onehot.shape
    w = n_words(a)
    bits = jnp.pad(onehot, ((0, 0), (0, 32 * w - a))).reshape(n, w, 32)
    shifts = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(jnp.where(bits, shifts, jnp.uint32(0)), axis=-1,
                   dtype=jnp.uint32)


def zipf_label_probs(key, n_clusters: int, alphabet_size: int,
                     label_skew: float):
    """[C, A] per-cluster label weights: 1/rank^skew over a random
    permutation of the alphabet per cluster, normalised."""
    base = 1.0 / jnp.arange(1, alphabet_size + 1, dtype=jnp.float32) ** (
        label_skew)
    perm = jnp.argsort(jax.random.uniform(key, (n_clusters, alphabet_size)),
                       axis=1)
    rank_of = jnp.argsort(perm, axis=1)          # label -> its rank
    probs = base[rank_of]
    return probs / probs.sum(axis=1, keepdims=True)


def sample_label_sets(key, probs, cluster_ids, max_labels: int):
    """[N, A] bool: 1..max_labels distinct labels a row, drawn without
    replacement from the row's cluster weights (Gumbel top-k)."""
    k_g, k_n = jax.random.split(key)
    n = cluster_ids.shape[0]
    a = probs.shape[1]
    logits = jnp.log(probs)[cluster_ids]
    g = logits + jax.random.gumbel(k_g, (n, a), jnp.float32)
    _, top = jax.lax.top_k(g, max_labels)                 # [N, L]
    count = jax.random.randint(k_n, (n,), 1, max_labels + 1)
    take = jnp.arange(max_labels)[None, :] < count[:, None]
    onehot = jax.nn.one_hot(top, a, dtype=jnp.bool_) & take[:, :, None]
    return onehot.any(axis=1)


def rotations(seed: int, n_clusters: int, dim: int) -> np.ndarray:
    """[C, d, d] random orthogonal matrices (Haar), one per cluster, from
    the seed: Gram-Schmidt, twice over, of Gaussian columns, in float64
    on the host (XLA's batched QR takes seconds on a CPU, and a threaded
    LAPACK as long on a busy host)."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
    a = rng.standard_normal((n_clusters, dim, dim))
    q = np.zeros_like(a)
    for j in range(dim):
        v = a[:, :, j]
        for _ in range(2):
            v = v - np.einsum("bdk,bk->bd", q[:, :, :j], np.einsum(
                "bdk,bd->bk", q[:, :, :j], v))
        q[:, :, j] = v / np.linalg.norm(v, axis=1, keepdims=True)
    return q.astype(np.float32)


def cluster_rows(key, rots, cluster_ids, center_norm: float,
                 spectrum_decay: float):
    """[N, d] unit rows: cluster c a copy of one decaying spectrum turned
    by rots[c], around a center of norm `center_norm`."""
    n_clusters, dim, _ = rots.shape
    k_c, k_z = jax.random.split(key)
    centers = _unit(jax.random.normal(k_c, (n_clusters, dim), jnp.float32))
    s = (jnp.arange(dim, dtype=jnp.float32) + 1.0) ** (-spectrum_decay / 2)
    s = s / jnp.linalg.norm(s)
    z = jax.random.normal(k_z, (cluster_ids.shape[0], dim), jnp.float32) * s

    def one(c, acc):
        y = jnp.dot(z, rots[c], precision=HIGHEST)
        return jnp.where((cluster_ids == c)[:, None], y, acc)

    local = jax.lax.fori_loop(0, n_clusters, one, jnp.zeros_like(z))
    return _unit(center_norm * centers[cluster_ids] + local)


@functools.partial(jax.jit, static_argnames=("shape",))
def _generate(key, rots, shape):
    (n, dim, n_clusters, center_norm, spectrum_decay, label_kind,
     alphabet_size, max_labels, label_skew, value_kind, value_noise) = shape
    k_v, k_id, k_p, k_l, k_w, k_e = jax.random.split(key, 6)
    cluster_ids = jax.random.randint(k_id, (n,), 0, n_clusters, jnp.int32)
    vecs = cluster_rows(k_v, rots, cluster_ids, center_norm, spectrum_decay)
    if label_kind == "cluster_zipf":
        probs = zipf_label_probs(k_p, n_clusters, alphabet_size, label_skew)
        onehot = sample_label_sets(k_l, probs, cluster_ids, max_labels)
    elif label_kind == "uniform_single":
        lab = jax.random.randint(k_l, (n,), 0, alphabet_size)
        onehot = jax.nn.one_hot(lab, alphabet_size, dtype=jnp.bool_)
    else:
        raise ValueError(f"unknown label kind {label_kind!r}")
    if value_kind == "linear_probe":
        w = jax.random.normal(k_w, (dim,), jnp.float32)
        raw = (jnp.dot(vecs, w, precision=HIGHEST)
               + value_noise * jax.random.normal(k_e, (n,), jnp.float32))
        values = (raw - raw.min()) / jnp.maximum(raw.max() - raw.min(), 1e-9)
    elif value_kind == "uniform":
        values = jax.random.uniform(k_w, (n,), jnp.float32)
    else:
        raise ValueError(f"unknown value kind {value_kind!r}")
    return vecs, pack_bits(onehot), values.astype(jnp.float32), cluster_ids


def _shape(cfg: dict) -> tuple:
    lab, val = cfg["labels"], cfg["values"]
    return (int(cfg["n"]), int(cfg["dim"]), int(cfg["n_clusters"]),
            float(cfg["center_norm"]), float(cfg["spectrum_decay"]),
            lab["kind"], int(lab["alphabet_size"]),
            int(lab.get("max_labels", 1)), float(lab.get("label_skew", 0.0)),
            val["kind"], float(val.get("value_noise", 0.0)))


@dataclasses.dataclass
class Deployment:
    """The generated rows, on the host (numpy)."""

    vectors: np.ndarray        # [N, d] float32, unit norm
    labels_packed: np.ndarray  # [N, W] uint32 multi-hot
    values: np.ndarray         # [N] float32 numeric attribute
    cluster_ids: np.ndarray    # [N] int32
    alphabet_size: int

    @property
    def n(self) -> int:
        return self.vectors.shape[0]


def generate(cfg: dict, seed: int) -> Deployment:
    """Rows of the deployment a config's `data` block describes."""
    shape = _shape(cfg)
    rots = rotations(seed, shape[2], shape[1])
    out = jax.device_get(_generate(key_from_seed(seed, 0), rots, shape))
    vecs, labels, values, cids = (np.asarray(a) for a in out)
    return Deployment(vectors=vecs, labels_packed=labels, values=values,
                      cluster_ids=cids,
                      alphabet_size=int(cfg["labels"]["alphabet_size"]))


class LabelSets:
    """Per-row label tuples, unpacked from the multi-hot words on demand
    (the program's workload helpers index single rows)."""

    def __init__(self, labels_packed: np.ndarray):
        self._packed = labels_packed

    def __len__(self) -> int:
        return self._packed.shape[0]

    def __getitem__(self, i: int) -> tuple:
        row = self._packed[int(i)]
        return tuple(32 * w + b for w in range(row.shape[0])
                     for b in range(32) if (int(row[w]) >> b) & 1)


def unpack_bits(words: np.ndarray, alphabet_size: int) -> np.ndarray:
    """[.., W] uint32 -> [.., A] bool."""
    bits = (words[..., :, None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(*words.shape[:-1], -1)[..., :alphabet_size] > 0
