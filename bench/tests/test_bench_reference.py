import numpy as np
import pytest

from bench.reference import scan as ref


def _hist(rng, n, d, alpha):
    x = rng.dirichlet(np.full(d, alpha), n)
    x = np.maximum(x, 1e-6)
    return (x / x.sum(axis=1, keepdims=True)).astype(np.float32)


def _direct(name, u, v):
    """d(u, v) in float64, straight from the definition."""
    u = np.maximum(u.astype(np.float64), 1e-6)
    v = np.maximum(v.astype(np.float64), 1e-6)
    if name == "kl":
        return np.sum(u * np.log(u / v), axis=-1)
    a = float(name.split("_")[1])
    return np.log(np.sum(u**a * v ** (1 - a), axis=-1)) / (a - 1)


@pytest.mark.parametrize("name,alpha", [("kl", 0.08), ("renyi_2", 1.0)])
def test_scan_is_exact_in_the_left_order(name, alpha):
    rng = np.random.default_rng(0)
    X, Q = _hist(rng, 3000, 32, alpha), _hist(rng, 40, 32, alpha)
    left = _direct(name, X[None, :, :], Q[:, None, :])  # d(x, q)
    right = _direct(name, Q[:, None, :], X[None, :, :])  # d(q, x)
    d, ids = ref.scan(ref.distance(name), X, Q, 10, block_rows=1024,
                      block_queries=16)
    want = np.argsort(left, axis=1, kind="stable")[:, :10]
    # the same top-10 sets as the left order, distances to float32 rounding
    assert all(set(a) == set(b) for a, b in zip(ids.tolist(), want.tolist()))
    np.testing.assert_allclose(d, np.take_along_axis(left, ids, 1),
                               rtol=2e-5, atol=2e-6)
    # and not the right order: these distances are not symmetric
    wrong = np.argsort(right, axis=1, kind="stable")[:, :10]
    assert np.mean([len(set(a) & set(b)) for a, b in
                    zip(ids.tolist(), wrong.tolist())]) < 8


@pytest.mark.parametrize("name,alpha", [("kl", 0.08), ("renyi_2", 1.0)])
def test_pair_matches_the_definition_in_both_orders(name, alpha):
    rng = np.random.default_rng(1)
    U, V = _hist(rng, 200, 128, alpha), _hist(rng, 200, 128, alpha)
    dist = ref.distance(name)
    ids = np.arange(200)[:, None]
    for a, b in ((U, V), (V, U)):
        # d(a[i], b[i]) through the id lookup the check uses
        d32, d64 = ref.distances_of(dist, a, b, ids)
        np.testing.assert_allclose(d64[:, 0], _direct(name, a, b),
                                   rtol=1e-12)
        np.testing.assert_allclose(d32[:, 0], _direct(name, a, b),
                                   rtol=1e-5, atol=1e-6)
    assert np.max(np.abs(_direct(name, U, V) - _direct(name, V, U))) > 1e-2


def test_bf16x3_is_coarser_than_highest():
    rng = np.random.default_rng(2)
    X, Q = _hist(rng, 2000, 128, 0.08), _hist(rng, 16, 128, 0.08)
    exact = _direct("kl", X[None, :, :], Q[:, None, :])
    kl = ref.distance("kl")
    hi, hi_ids = ref.scan(kl, X, Q, 10)
    lo, lo_ids = ref.scan(kl, X, Q, 10, precision="bf16x3")
    err_hi = np.max(np.abs(hi - np.take_along_axis(exact, hi_ids, 1)))
    err_lo = np.max(np.abs(lo - np.take_along_axis(exact, lo_ids, 1)))
    assert err_lo > 5 * err_hi


def test_distances_of_marks_bad_ids():
    rng = np.random.default_rng(3)
    X, Q = _hist(rng, 50, 8, 1.0), _hist(rng, 2, 8, 1.0)
    for d in ref.distances_of(ref.distance("kl"), X, Q,
                              np.array([[0, -1], [49, 50]])):
        assert np.isfinite(d[0, 0]) and np.isfinite(d[1, 0])
        assert np.isnan(d[0, 1]) and np.isnan(d[1, 1])


def test_unknown_distance_names_the_known_ones():
    with pytest.raises(KeyError, match="kl"):
        ref.distance("bm25")
    with pytest.raises(ValueError):
        ref.distance("../scan")
