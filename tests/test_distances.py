"""Distance zoo: matmul-form decomposition must match the pointwise oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import distances as D
from repro.core.symmetrize import symmetrized
from repro.data.synthetic import random_histograms, text_collection

ALL_HIST_DISTS = ["kl", "itakura_saito", "renyi_0.25", "renyi_0.75", "renyi_2", "l2"]


def _hists(seed, n, d):
    return random_histograms(jax.random.PRNGKey(seed), n, d)


@pytest.mark.parametrize("name", ALL_HIST_DISTS)
def test_matrix_matches_pairwise(name):
    dist = D.get_distance(name)
    U = _hists(0, 7, 16)
    V = _hists(1, 5, 16)
    M = dist.matrix(U, V)
    for i in range(7):
        for j in range(5):
            np.testing.assert_allclose(
                M[i, j], dist.pairwise(U[i], V[j]), rtol=2e-4, atol=2e-5
            )


@pytest.mark.parametrize("name", ALL_HIST_DISTS)
def test_query_matrix_left_convention(name):
    """Left queries: D[b, i] = d(X[i], Q[b]) - data point is the left arg."""
    dist = D.get_distance(name)
    Q = _hists(2, 4, 8)
    X = _hists(3, 6, 8)
    got = dist.query_matrix(Q, X, mode="left")
    want = dist.matrix(X, Q).T
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    got_r = dist.query_matrix(Q, X, mode="right")
    want_r = dist.matrix(Q, X)
    np.testing.assert_allclose(got_r, want_r, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name", ["kl", "itakura_saito", "renyi_0.25", "renyi_2"])
def test_nonsymmetry_is_substantial(name):
    """These are the paper's 'substantially non-symmetric' distances."""
    dist = D.get_distance(name)
    U = _hists(4, 64, 32)
    V = _hists(5, 64, 32)
    fwd = dist.pairwise_batch(U, V)
    rev = dist.pairwise_batch(V, U)
    assert float(jnp.max(jnp.abs(fwd - rev))) > 1e-3


def test_kl_properties():
    dist = D.get_distance("kl")
    U = _hists(6, 16, 24)
    self_d = dist.pairwise_batch(U, U)
    np.testing.assert_allclose(self_d, 0.0, atol=1e-5)
    V = _hists(7, 16, 24)
    assert float(jnp.min(dist.pairwise_batch(U, V))) > 0.0  # Gibbs inequality


def test_itakura_saito_nonnegative_zero_self():
    dist = D.get_distance("itakura_saito")
    U = _hists(8, 16, 24)
    np.testing.assert_allclose(dist.pairwise_batch(U, U), 0.0, atol=1e-4)
    V = _hists(9, 16, 24)
    assert float(jnp.min(dist.pairwise_batch(U, V))) > 0.0


@pytest.mark.parametrize("mode", ["avg", "min", "reverse"])
@pytest.mark.parametrize("name", ["kl", "itakura_saito", "renyi_2"])
def test_symmetrizations(name, mode):
    base = D.get_distance(name)
    sym = symmetrized(base, mode)
    U = _hists(10, 5, 12)
    V = _hists(11, 4, 12)
    M = sym.matrix(U, V)
    for i in range(5):
        for j in range(4):
            if mode == "avg":
                want = (base.pairwise(U[i], V[j]) + base.pairwise(V[j], U[i])) / 2
            elif mode == "min":
                want = jnp.minimum(base.pairwise(U[i], V[j]), base.pairwise(V[j], U[i]))
            else:
                want = base.pairwise(V[j], U[i])
            np.testing.assert_allclose(M[i, j], want, rtol=2e-4, atol=2e-5)
    if mode in ("avg", "min"):
        # symmetric by construction
        np.testing.assert_allclose(M, sym.matrix(V, U).T, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode", ["avg", "min", "reverse"])
def test_score_contract_matches_query_matrix(mode):
    """prep_scan/prep_query/score must agree with query_matrix(mode='left')."""
    base = D.get_distance("kl")
    dist = symmetrized(base, mode)
    Q = _hists(12, 3, 10)
    X = _hists(13, 9, 10)
    consts = dist.prep_scan(X)
    want = dist.query_matrix(Q, X, mode="left")
    for b in range(3):
        qc = dist.prep_query(Q[b])
        got = dist.score(consts, qc)
        np.testing.assert_allclose(got, want[b], rtol=1e-5, atol=1e-6)


def test_bm25_views_nonsymmetric_and_natural_symmetric():
    tc = text_collection(jax.random.PRNGKey(0), n=64, vocab=256, mean_len=30)
    bm25 = tc.bm25()
    nat = tc.natural()
    C = tc.counts
    M = bm25.matrix(C[:8], C[8:16])
    Mt = bm25.matrix(C[8:16], C[:8]).T
    assert float(jnp.max(jnp.abs(M - Mt))) > 1e-3  # asymmetric vectorization
    N = nat.matrix(C[:8], C[8:16])
    Nt = nat.matrix(C[8:16], C[:8]).T
    np.testing.assert_allclose(N, Nt, rtol=1e-5, atol=1e-6)  # Eq. 4 symmetric
    assert float(jnp.max(N)) <= 0.0 + 1e-6  # negated similarity


@settings(max_examples=25, deadline=None)
@given(
    d=st.integers(min_value=2, max_value=48),
    seed=st.integers(min_value=0, max_value=2**30),
    name=st.sampled_from(ALL_HIST_DISTS),
)
def test_property_decomposition_random_shapes(d, seed, name):
    """Property: matmul form == oracle for any simplex data/shape/distance."""
    dist = D.get_distance(name)
    U = random_histograms(jax.random.PRNGKey(seed), 3, d)
    V = random_histograms(jax.random.PRNGKey(seed + 1), 4, d)
    M = dist.matrix(U, V)
    want = jax.vmap(lambda u: jax.vmap(lambda v: dist.pairwise(u, v))(V))(U)
    np.testing.assert_allclose(M, want, rtol=5e-4, atol=5e-5)


def _dot_precisions(lowered):
    import re

    lines = [ln for ln in lowered.as_text().splitlines() if "dot_general" in ln]
    assert lines, "no matmul in the lowered program"
    return [re.search(r"precision = \[(\w+), (\w+)\]", ln).groups() for ln in lines]


def _rows_and_query(dist, n=12, m=16):
    X = _hists(2, n, m)
    consts = dist.prep_scan(X)
    return consts, dist.prep_query(_hists(3, 1, m)[0])


@pytest.mark.parametrize("name", ["kl", "itakura_saito", "renyi_2", "l2", "negdot"])
def test_ranking_matmuls_pin_highest_precision(name):
    """The exact scan, the beam's score and the kernel oracles run their
    matmuls at HIGHEST: XLA's default on a TPU passes f32 through bf16, and
    the reference every recall divides by would rank wrongly there."""
    from repro.core.brute_force import knn_scan
    from repro.kernels import ref

    dist = D.get_distance(name)
    Q, X = _hists(4, 3, 16), _hists(5, 40, 16)
    lowered = [
        knn_scan.lower(dist, Q, X, 5, chunk=16),
        jax.jit(dist.score).lower(*_rows_and_query(dist)),
        jax.jit(lambda q, x: dist.query_matrix(q, x, mode="right")).lower(Q, X),
        jax.jit(ref.distance_matrix_ref, static_argnums=(4, 5)).lower(
            Q, X, Q[:, 0], X[:, 0], dist.post_id, dist.c0),
        jax.jit(ref.gather_scores_ref, static_argnums=(5, 6)).lower(
            jnp.zeros((3, 4), jnp.int32), Q, X, Q[:, 0], X[:, 0], dist.post_id, dist.c0),
    ]
    for lo in lowered:
        assert set(_dot_precisions(lo)) == {("HIGHEST", "HIGHEST")}


@pytest.mark.parametrize("m", [16, 128])
@pytest.mark.parametrize("alpha", [0.0, 0.25, 0.5, 0.75])
@pytest.mark.parametrize("name", ["kl", "itakura_saito"])
def test_linear_blend_matches_definition(name, alpha, m):
    """alpha*d(u,v) + (1-alpha)*d(v,u) lowered to one matmul-form Distance
    (m' = 2m, m' = m for the reversal) against the definition, through every
    entry point.  rtol 1e-5: one float32 matmul over <= 256 terms against
    two pointwise sums of m terms."""
    from repro.core.spec import Blend

    base = D.get_distance(name)
    dist = Blend(alpha).bind(base)
    assert type(dist) is D.Distance and dist.post_id == D.POST_LINEAR
    U = _hists(30 + m, 6, m)
    V = _hists(31 + m, 5, m)
    assert dist.prep_left(U).shape[1] == (m if alpha == 0.0 else 2 * m)

    def definition(u, v):
        return alpha * base.pairwise(u, v) + (1.0 - alpha) * base.pairwise(v, u)

    want = np.array([[definition(u, v) for v in V] for u in U])
    np.testing.assert_allclose(dist.matrix(U, V), want, rtol=1e-5)
    np.testing.assert_allclose(dist.query_matrix(V, U, mode="left"), want.T, rtol=1e-5)
    np.testing.assert_allclose(dist.query_matrix(U, V, mode="right"), want, rtol=1e-5)
    consts = dist.prep_scan(U)
    for j in range(V.shape[0]):
        np.testing.assert_allclose(dist.score(consts, dist.prep_query(V[j])),
                                   want[:, j], rtol=1e-5)
    np.testing.assert_allclose(dist.pairwise_batch(U[:5], V), np.diagonal(want),
                               rtol=1e-5)
