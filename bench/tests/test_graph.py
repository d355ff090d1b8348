"""The device graph builder keeps the host builder's rules
(`index/builder.py`): the same alpha-prune on the same candidates, the
same reverse-edge lists, and a valid graph end to end."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import graph


def _data(n=600, d=16, seed=0):
    rng = np.random.default_rng(seed)
    v = rng.standard_normal((n, d)).astype(np.float32)
    return v / np.linalg.norm(v, axis=1, keepdims=True)


def _exact_candidates(v, c):
    d2 = ((v[:, None, :] - v[None, :, :]) ** 2).sum(-1)
    np.fill_diagonal(d2, np.inf)
    cand = np.argsort(d2, axis=1, kind="stable")[:, :c].astype(np.int32)
    return cand, np.take_along_axis(d2, cand, axis=1).astype(np.float32)


@pytest.mark.parametrize("alpha,r", [(1.2, 8), (1.2, 16), (1.0, 8)])
def test_alpha_prune_matches_host_rule(alpha, r):
    from repro.index.builder import _alpha_prune_block

    v = _data()
    cand, dist = _exact_candidates(v, 2 * r)
    host = _alpha_prune_block(np.arange(len(v)), cand, dist, v, r, alpha)
    dev = np.asarray(graph.alpha_prune(jnp.asarray(cand), jnp.asarray(dist),
                                       jnp.asarray(v), r, alpha))
    np.testing.assert_array_equal(dev, host)


def test_reverse_edges_match_host():
    from repro.index.builder import _symmetrize

    rng = np.random.default_rng(1)
    n, r = 500, 6
    nb = rng.integers(0, n, (n, r)).astype(np.int32)
    nb[rng.random((n, r)) < 0.2] = -1
    host, _ = _symmetrize(nb, r_cap=r)
    dev = np.asarray(graph.reverse_edges(jnp.asarray(nb), 2 * r))
    np.testing.assert_array_equal(dev, host)


def test_build_gives_valid_navigable_graph():
    from repro.index.graph import GraphIndex

    v = _data(n=1024, d=16, seed=2)
    nbrs, entry = graph.build(v, degree=8, cand_block=256, prune_block=256)
    g = GraphIndex(neighbors=nbrs, entry_point=entry, dim=16)
    g.validate()
    assert (g.out_degrees() >= 4).mean() > 0.99
    cand, _ = _exact_candidates(v, 1)
    # every row's nearest neighbour is kept (the prune never drops it)
    assert (nbrs == cand[:, :1]).any(axis=1).mean() > 0.95


def _reached(nbrs, entry):
    seen = np.zeros(len(nbrs), bool)
    seen[entry] = True
    front = [entry]
    while front:
        nxt = nbrs[front].ravel()
        nxt = np.unique(nxt[nxt >= 0])
        nxt = nxt[~seen[nxt]]
        seen[nxt] = True
        front = list(nxt)
    return seen.mean()


def test_random_candidates_link_separate_clusters():
    """Clusters of low intrinsic dimension far apart: the exact-candidate
    graph leaves most rows unreachable from the entry point; Vamana's
    random candidates fill the slots the prune leaves and link them, and
    the prune still keeps each row's nearest neighbour."""
    import jax

    rng = np.random.default_rng(3)
    centers = rng.standard_normal((4, 16)) * 10.0
    plane = rng.standard_normal((2, 16))
    v = (centers[rng.integers(0, 4, 1024)]
         + rng.standard_normal((1024, 2)) @ plane).astype(np.float32)
    plain, e0 = graph.build(v, degree=8, cand_block=256, prune_block=256)
    linked, e1 = graph.build(v, degree=8, random=8,
                             key=jax.random.PRNGKey(0), cand_block=256,
                             prune_block=256)
    assert _reached(plain, e0) < 0.5
    assert _reached(linked, e1) == 1.0
    cand, _ = _exact_candidates(v, 1)
    assert (linked == cand[:, :1]).any(axis=1).mean() > 0.95
