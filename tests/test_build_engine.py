"""Wave-parallel construction engine: parity, invariants, routing.

Parity contract: at wave=1 the wave builder inserts one point per wave
through the batched beam engine with frontier=1 — bit-identical adjacency
to the sequential ``build_swgraph`` across non-symmetric distances and
symmetrization regimes.  At wave>1 the NMSLIB-style relaxed ordering may
change WHICH edges exist, but never the structural invariants: no duplicate
ids per row, no self loops, degrees capped at M_max, all ids in range.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    ANNIndex,
    build_sharded,
    build_swgraph,
    build_swgraph_wave,
    get_distance,
    knn_scan,
    recall_at_k,
    reverse_edge_merge,
    symmetrized,
)
from repro.core.nndescent import _sampled_reverse
from repro.data.synthetic import lda_like_histograms, split_queries

from graph_invariants import (
    check_adjacency_invariants,
    check_merge_only_added_submitted_edges,
)

N_DB, N_Q, DIM, K = 420, 16, 16, 10


@pytest.fixture(scope="module")
def data():
    X = lda_like_histograms(jax.random.PRNGKey(0), N_DB + N_Q, DIM)
    Q, db = split_queries(X, N_Q, jax.random.PRNGKey(1))
    return Q, db


@pytest.mark.parametrize("index_sym", ["none", "min"])
@pytest.mark.parametrize("name", ["kl", "itakura_saito"])
def test_wave1_bit_identical_to_sequential(name, index_sym, data):
    """wave=1 => the exact sequential insertion order, edge for edge."""
    _, db = data
    db = db[:240]
    dist = symmetrized(get_distance(name), index_sym)
    adj_s, deg_s = build_swgraph(dist, db, NN=8, ef_construction=40)
    adj_w, deg_w = build_swgraph_wave(dist, db, NN=8, ef_construction=40, wave=1)
    np.testing.assert_array_equal(np.asarray(adj_s), np.asarray(adj_w))
    np.testing.assert_array_equal(np.asarray(deg_s), np.asarray(deg_w))


@settings(max_examples=6, deadline=None)
@given(
    wave=st.integers(min_value=2, max_value=48),
    name=st.sampled_from(["kl", "itakura_saito", "renyi_0.25"]),
)
def test_wave_build_invariants_hold(wave, name, data):
    """W>1 relaxed ordering never violates the degree-cap/dedup invariants,
    including under strongly non-symmetric build distances."""
    _, db = data
    db = db[:180]
    dist = get_distance(name)
    adj, deg = build_swgraph_wave(dist, db, NN=6, ef_construction=24, wave=wave)
    check_adjacency_invariants(adj, db.shape[0], 12)
    assert int(jnp.max(deg)) <= 12
    # every non-seed point got forward edges (the graph stays navigable)
    assert int(jnp.min(deg[1:])) >= 1


def test_wave_graph_reaches_sequential_quality(data):
    Q, db = data
    dist = get_distance("kl")
    _, true_ids = knn_scan(dist, Q, db, K)
    recalls = {}
    for engine, wave in [("sequential", 1), ("wave", 32)]:
        idx = ANNIndex.build(db, dist, builder="swgraph", build_engine=engine,
                             wave=wave, NN=10, ef_construction=60)
        _, ids, _, _ = idx.search(Q, k=K, ef_search=80)
        recalls[engine] = recall_at_k(np.asarray(ids), np.asarray(true_ids))
    assert recalls["wave"] >= 0.9
    assert recalls["wave"] >= recalls["sequential"] - 0.05, recalls


def test_index_build_engine_routing(data):
    _, db = data
    db = db[:160]
    dist = get_distance("kl")
    idx = ANNIndex.build(db, dist, builder="swgraph", build_engine="wave", wave=16,
                         NN=6, ef_construction=24)
    assert idx.build_info["build_engine"] == "wave"
    assert idx.build_info["wave"] == 16
    idx = ANNIndex.build(db, dist, builder="swgraph", build_engine="sequential",
                         NN=6, ef_construction=24)
    assert idx.build_info["build_engine"] == "sequential"
    assert idx.build_info["wave"] is None
    idx = ANNIndex.build(db, dist, builder="nndescent", NN=6, nnd_iters=4)
    assert idx.build_info["build_engine"] == "nndescent"
    with pytest.raises(ValueError):
        ANNIndex.build(db, dist, builder="swgraph", build_engine="nope")


def test_sampled_reverse_single_scatter_edges_are_real():
    """Every reverse entry (j, i) corresponds to a forward edge i -> j."""
    adj = jnp.asarray(
        np.random.RandomState(0).randint(-1, 40, size=(40, 6)), jnp.int32
    )
    rev = np.asarray(_sampled_reverse(adj, 8, jax.random.PRNGKey(3)))
    fwd = np.asarray(adj)
    assert rev.shape == (40, 8)
    for j in range(40):
        for i in rev[j]:
            if i >= 0:
                assert j in fwd[i], (j, i)


# ---------------------------------------------------------------------------
# reverse-edge eviction merge invariants (shared by build AND online insert)
# ---------------------------------------------------------------------------


def _random_merge_state(seed, n, M_max, U):
    """Random partial adjacency (no dups/self-loops) + a random update batch."""
    rng = np.random.RandomState(seed)
    adj = np.full((n, M_max), -1, np.int32)
    adj_d = np.full((n, M_max), np.inf, np.float32)
    for j in range(n):
        deg = rng.randint(0, M_max + 1)
        others = np.setdiff1d(np.arange(n), [j])
        picks = rng.choice(others, size=min(deg, len(others)), replace=False)
        adj[j, : len(picks)] = picks
        adj_d[j, : len(picks)] = rng.rand(len(picks)).astype(np.float32) * 10
    owners = rng.randint(0, n, U).astype(np.int32)
    cands = rng.randint(0, n, U).astype(np.int32)  # may collide with owners
    d_rev = (rng.rand(U) * 10).astype(np.float32)
    ok = rng.rand(U) < 0.8
    return adj, adj_d, owners, cands, d_rev, ok


@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**30),
    n=st.integers(min_value=4, max_value=48),
    M_max=st.integers(min_value=2, max_value=8),
    rounds=st.integers(min_value=1, max_value=6),
)
def test_reverse_edge_merge_invariants(seed, n, M_max, rounds):
    """Degree cap never exceeded, no self-loops, no duplicate neighbors —
    even under adversarial updates (self-candidates, duplicate (owner, cand)
    pairs, masked slots).  The same checkers guard the online insert path
    (tests/test_online_index.py)."""
    U = 3 * n
    adj, adj_d, owners, cands, d_rev, ok = _random_merge_state(seed, n, M_max, U)
    out_adj, out_d = reverse_edge_merge(
        jnp.asarray(adj), jnp.asarray(adj_d), jnp.asarray(owners),
        jnp.asarray(cands), jnp.asarray(d_rev), jnp.asarray(ok), rounds
    )
    check_adjacency_invariants(out_adj, n, M_max, adj_d=out_d)
    check_merge_only_added_submitted_edges(adj, out_adj, owners, cands, ok)


def test_reverse_edge_merge_keeps_closest_and_respects_rounds():
    """A full row keeps the M_max closest of {existing} u {applied updates};
    an owner receiving more than ``rounds`` candidates keeps the closest
    ``rounds`` of them (the documented NMSLIB-style relaxation)."""
    M_max = 3
    adj = jnp.asarray([[1, 2, 3], [-1, -1, -1], [-1, -1, -1], [-1, -1, -1]], jnp.int32)
    adj_d = jnp.asarray(
        [[1.0, 5.0, 9.0], [np.inf] * 3, [np.inf] * 3, [np.inf] * 3], jnp.float32
    )
    owners = jnp.asarray([0, 0, 1, 1, 1, 1], jnp.int32)
    cands = jnp.asarray([2, 3, 0, 2, 3, 1], jnp.int32)  # 2/3 dup targets; 1 self
    d_rev = jnp.asarray([0.5, 2.0, 4.0, 1.0, 3.0, 0.1], jnp.float32)
    ok = jnp.ones((6,), bool)
    out_adj, out_d = reverse_edge_merge(adj, adj_d, owners, cands, d_rev, ok, 2)
    a = np.asarray(out_adj)
    # owner 0: candidates 2 and 3 are already present -> skipped; unchanged
    assert set(a[0].tolist()) == {1, 2, 3}
    # owner 1, rounds=2: the self-candidate (d=.1) is rank 0 and is guarded
    # out (its round is still consumed); rank 1 applies the closest real
    # candidate 2 (d=1); candidates 3 and 0 exceed the round budget
    assert set(x for x in a[1].tolist() if x >= 0) == {2}
    out_adj3, out_d3 = reverse_edge_merge(adj, adj_d, owners, cands, d_rev, ok, 3)
    # one more round admits candidate 3 (d=3) as well
    assert set(x for x in np.asarray(out_adj3)[1].tolist() if x >= 0) == {2, 3}
    check_adjacency_invariants(out_adj, 4, M_max, adj_d=out_d)
    check_adjacency_invariants(out_adj3, 4, M_max, adj_d=out_d3)


def test_build_sharded_single_shard_smoke(data):
    """1-shard mesh: stitched graph == local graph in global ids, searchable."""
    Q, db = data
    db = db[:256]
    dist = get_distance("kl")
    mesh = jax.make_mesh((1,), ("data",))
    nbrs = build_sharded(mesh, dist, db, NN=8, builder="wave", wave=16,
                         cross_links=3, key=jax.random.PRNGKey(5))
    assert nbrs.shape == (256, 2 * 8 + 3)
    # single shard -> every cross-link candidate is own-shard, hence masked
    assert int(jnp.max(nbrs[:, -3:])) == -1
    check_adjacency_invariants(nbrs[:, :-3], 256, 16)
    _, true_ids = knn_scan(dist, Q, db, K)
    idx_like = ANNIndex(X=db, neighbors=nbrs, dist=dist, search_dist=dist,
                        query_sym="none")
    _, ids, _, _ = idx_like.search(Q, k=K, ef_search=80)
    r = recall_at_k(np.asarray(ids), np.asarray(true_ids))
    assert r >= 0.85, r


def test_lowered_avg_build_matches_composite():
    """KL-avg lowered to one Distance builds the graph the composite
    ``SymmetrizedDistance`` builds: same key, n = 2,000, m = 16.  Neighbour
    lists agree, except where the two orders of one list hold ids at equal
    distance (ties within 1e-6 relative), and KL recall@10 over both graphs
    agrees within 0.01."""
    from repro.core.batched_beam import make_step_searcher
    from repro.core.distances import Distance
    from repro.core.symmetrize import SymmetrizedDistance

    X = lda_like_histograms(jax.random.PRNGKey(5), 2000 + 64, 16)
    Q, db = split_queries(X, 64, jax.random.PRNGKey(6))
    kl = get_distance("kl")
    lowered, composite = symmetrized(kl, "avg"), SymmetrizedDistance(kl, "avg")
    assert type(lowered) is Distance
    graphs = [np.asarray(build_swgraph_wave(d, db, NN=10, ef_construction=48, wave=32)[0])
              for d in (lowered, composite)]
    differ = np.flatnonzero(np.any(graphs[0] != graphs[1], axis=1))
    for i in differ:
        ds = []
        for g in graphs:
            nb = g[i][g[i] >= 0]
            ds.append(np.sort(np.asarray(composite.query_matrix(db[i][None], db[nb]))[0]))
        assert len(ds[0]) == len(ds[1]), i
        np.testing.assert_allclose(ds[0], ds[1], rtol=1e-6)

    _, true_ids = knn_scan(kl, Q, db, K)
    recalls = []
    for g in graphs:
        search = make_step_searcher(kl, jnp.asarray(g), db, 64, K,
                                    entries=jnp.zeros((1,), jnp.int32), frontier=2)
        _, ids, _, _ = search(Q)
        recalls.append(recall_at_k(np.asarray(ids), np.asarray(true_ids)))
    assert abs(recalls[0] - recalls[1]) <= 0.01, recalls
