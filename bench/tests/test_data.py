"""The vectorised generator reproduces the program's `make_dataset`
distributions: label-set sizes, each cluster's label rank-frequency
curve (which sets tag selectivities), and the value attribute. Its rows,
unlike `make_dataset`'s, have a low local intrinsic dimension."""
import numpy as np
import pytest

from bench.lib import data

N, DIM, C, A, L = 20000, 64, 40, 64, 4


@pytest.fixture(scope="module")
def both():
    from repro.data import make_dataset

    prog = make_dataset(n=N, dim=DIM, n_clusters=C, alphabet_size=A,
                        max_labels=L, label_skew=4.0, seed=3,
                        n_value_attrs=1)
    cfg = {"n": N, "dim": DIM, "n_clusters": C, "center_norm": 0.25,
           "spectrum_decay": 1.5,
           "labels": {"kind": "cluster_zipf", "alphabet_size": A,
                      "max_labels": L, "label_skew": 4.0},
           "values": {"kind": "linear_probe", "value_noise": 0.1}}
    mine = data.generate(cfg, seed=2**31 + 7)
    return prog, mine


def _onehot(labels_packed):
    return data.unpack_bits(np.asarray(labels_packed), A)


def _rank_frequency(onehot, cids):
    """Mean over clusters of the share of rows holding the cluster's
    r-th most common label, r = 0..A-1."""
    curves = []
    for c in range(C):
        rows = onehot[cids == c]
        if len(rows):
            curves.append(np.sort(rows.mean(axis=0))[::-1])
    return np.mean(curves, axis=0)


def test_shapes_and_units(both):
    _, mine = both
    assert mine.vectors.shape == (N, DIM)
    assert mine.labels_packed.shape == (N, 2)
    np.testing.assert_allclose(np.linalg.norm(mine.vectors, axis=1), 1.0,
                               atol=1e-5)
    assert mine.values.min() == 0.0 and mine.values.max() == 1.0


def test_label_set_sizes(both):
    prog, mine = both
    for ds in (prog, mine):
        sizes = _onehot(ds.labels_packed).sum(axis=1)
        frac = np.bincount(sizes, minlength=L + 1)[1:] / N
        np.testing.assert_allclose(frac, 1.0 / L, atol=0.02)


def test_label_rank_frequency(both):
    prog, mine = both
    a = _rank_frequency(_onehot(prog.labels_packed), prog.cluster_ids)
    b = _rank_frequency(_onehot(mine.labels_packed), mine.cluster_ids)
    np.testing.assert_allclose(a[:8], b[:8], atol=0.03)


def test_tag_selectivities(both):
    """Single-tag Contain selectivities over the alphabet, sorted."""
    prog, mine = both
    a = np.sort(_onehot(prog.labels_packed).mean(axis=0))[::-1]
    b = np.sort(_onehot(mine.labels_packed).mean(axis=0))[::-1]
    np.testing.assert_allclose(a.sum(), b.sum(), rtol=0.02)
    np.testing.assert_allclose(np.quantile(a, [0.5, 0.9]),
                               np.quantile(b, [0.5, 0.9]), atol=0.03)


def test_values_distribution(both):
    prog, mine = both
    q = [0.1, 0.25, 0.5, 0.75, 0.9]
    np.testing.assert_allclose(np.quantile(prog.values, q),
                               np.quantile(mine.values, q), atol=0.08)


def test_same_seed_same_rows():
    cfg = {"n": 512, "dim": 16, "n_clusters": 4, "center_norm": 0.25,
           "spectrum_decay": 1.5,
           "labels": {"kind": "uniform_single", "alphabet_size": 12},
           "values": {"kind": "uniform"}}
    a, b = data.generate(cfg, 5), data.generate(cfg, 5)
    c = data.generate(cfg, 6)
    np.testing.assert_array_equal(a.vectors, b.vectors)
    np.testing.assert_array_equal(a.labels_packed, b.labels_packed)
    assert not np.array_equal(a.vectors, c.vectors)
    assert (data.unpack_bits(a.labels_packed, 12).sum(axis=1) == 1).all()


def _lid(x, q, k=20):
    """Median maximum-likelihood local intrinsic dimension of x around
    the queries q, from their k nearest rows."""
    d2 = ((q * q).sum(1)[:, None] + (x * x).sum(1)[None]
          - 2.0 * q.astype(np.float64) @ x.T)
    d = np.sqrt(np.maximum(np.sort(d2, axis=1)[:, 1:k + 1], 1e-12))
    return float(np.median(-1.0 / np.mean(np.log(d / d[:, -1:]), axis=1)))


def test_rows_have_low_intrinsic_dimension(both):
    """make_dataset's rows are near uniform on the sphere; these reach
    the low local intrinsic dimension of real descriptor and embedding
    sets (and stay unit norm, in the same clusters)."""
    prog, mine = both
    x_p, x_m = np.asarray(prog.vectors, np.float64), mine.vectors
    lid_p = _lid(x_p, x_p[:200])
    lid_m = _lid(x_m.astype(np.float64), x_m[:200].astype(np.float64))
    assert lid_m < 0.5 * lid_p
    assert lid_m < 0.4 * DIM
