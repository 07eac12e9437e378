"""Pallas kernel validation: interpret-mode kernels vs pure-jnp oracles.

Per the deliverable spec: sweep shapes/dtypes per kernel and
assert_allclose against ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.distances import get_distance
from repro.data.synthetic import random_histograms
from repro.kernels import ref as kref
from repro.kernels.distance_matrix import distance_matrix
from repro.kernels.frontier_gather import frontier_scores, row_view
from repro.kernels.ops import beam_gather_scores, query_distance_matrix

DISTS = ["kl", "itakura_saito", "renyi_0.25", "renyi_2", "l2", "negdot"]


def _reps(dist, B, N, m, seed=0, dtype=jnp.float32):
    Q = random_histograms(jax.random.PRNGKey(seed), B, m).astype(dtype)
    X = random_histograms(jax.random.PRNGKey(seed + 1), N, m).astype(dtype)
    return (
        dist.prep_right(Q), dist.prep_left(X),
        dist.bias_right(Q), dist.bias_left(X),
        Q, X,
    )


@pytest.mark.parametrize("name", DISTS)
@pytest.mark.parametrize("shape", [(4, 16, 8), (33, 300, 64), (128, 512, 128)])
def test_distance_matrix_kernel_vs_ref(name, shape):
    B, N, m = shape
    dist = get_distance(name)
    q_rep, x_rep, q_bias, x_bias, _, _ = _reps(dist, B, N, m)
    got = distance_matrix(q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0,
                          block_q=32, block_x=128, interpret=True)
    want = kref.distance_matrix_ref(q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", ["kl", "l2"])
def test_distance_matrix_kernel_tiled_k(name):
    """Reduction-tiled variant (m > block_k) must accumulate correctly."""
    B, N, m = 16, 96, 512
    dist = get_distance(name)
    q_rep, x_rep, q_bias, x_bias, _, _ = _reps(dist, B, N, m)
    got = distance_matrix(q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0,
                          block_q=8, block_x=32, block_k=128, interpret=True)
    want = kref.distance_matrix_ref(q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_distance_matrix_dtypes(dtype):
    dist = get_distance("kl")
    q_rep, x_rep, q_bias, x_bias, _, _ = _reps(dist, 16, 64, 32, dtype=dtype)
    got = distance_matrix(q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0,
                          block_q=8, block_x=32, interpret=True)
    want = kref.distance_matrix_ref(q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert got.dtype == jnp.float32  # f32 accumulation regardless of input


@pytest.mark.parametrize("name", DISTS)
def test_gather_scores_kernel_vs_ref(name):
    dist = get_distance(name)
    B, M, n, m = 6, 10, 40, 16
    q_rep, x_rep, q_bias, x_bias, _, _ = _reps(dist, B, n, m, seed=3)
    ids = jax.random.randint(jax.random.PRNGKey(9), (B, M), -1, n)
    got = frontier_scores(ids, q_rep, q_bias, row_view(x_rep), x_bias, dist.post_id,
                          dist.c0, interpret=True)
    want = kref.gather_scores_ref(ids, q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert bool(jnp.all(jnp.isinf(got[ids < 0])))


@pytest.mark.parametrize("name", ["kl", "renyi_2", "l2"])
@pytest.mark.parametrize("B,R,m", [(9, 30, 8), (3, 7, 128), (17, 12, 200), (2, 5, 256)])
def test_frontier_kernel_row_views(name, B, R, m):
    """Every corpus row view the kernel DMAs from: lane-padded (m' < 128),
    as-is (m' = 128) and split into K rows (m' > 128, ragged or not), with
    B padded up to whole 8-query grid steps."""
    dist = get_distance(name)
    q_rep, x_rep, q_bias, x_bias, _, _ = _reps(dist, B, 23, m, seed=m)
    ids = jax.random.randint(jax.random.PRNGKey(B), (B, R), -1, 23)
    got = frontier_scores(ids, q_rep, q_bias, row_view(x_rep), x_bias, dist.post_id,
                          dist.c0, interpret=True)
    want = kref.gather_scores_ref(ids, q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0)
    assert got.shape == (B, R)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert bool(jnp.all(jnp.isinf(got[ids < 0])))


def test_ops_wrappers_match_distance_object():
    """ops.query_distance_matrix == Distance.query_matrix (the library path)."""
    dist = get_distance("itakura_saito")
    Q = random_histograms(jax.random.PRNGKey(5), 9, 24)
    X = random_histograms(jax.random.PRNGKey(6), 31, 24)
    want = dist.query_matrix(Q, X, mode="left")
    got_k = query_distance_matrix(dist, Q, X, block_q=8, block_x=16)
    got_r = query_distance_matrix(dist, Q, X, use_pallas=False)
    np.testing.assert_allclose(got_k, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got_r, want, rtol=1e-4, atol=1e-5)

    ids = jnp.array([[0, 3, 30, -1], [5, 5, 1, 2]], jnp.int32)
    got_g = beam_gather_scores(dist, ids, Q[:2], X)
    ref_g = beam_gather_scores(dist, ids, Q[:2], X, use_pallas=False)
    np.testing.assert_allclose(got_g, ref_g, rtol=1e-4, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(
    B=st.integers(1, 40),
    N=st.integers(1, 200),
    m=st.integers(2, 80),
    name=st.sampled_from(DISTS),
    seed=st.integers(0, 1000),
)
def test_property_kernel_any_shape(B, N, m, name, seed):
    """Property: kernel == oracle for arbitrary (B, N, m) incl. ragged pads."""
    dist = get_distance(name)
    q_rep, x_rep, q_bias, x_bias, _, _ = _reps(dist, B, N, m, seed=seed)
    got = distance_matrix(q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0,
                          block_q=16, block_x=64, interpret=True)
    want = kref.distance_matrix_ref(q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
