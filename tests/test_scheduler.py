"""Continuous-batching slot scheduler: parity, recycling, adaptive frontier.

Acceptance contract (ISSUE 4): with every query submitted up front and
enough slots that none is ever refilled, the slot engine is EXACTLY
``batched_beam_search`` — same beams, same distances, same eval and hop
counts.  Slot recycling (more queries than slots) must not change any
query's result, only its admission time.  The adaptive frontier must cut
distance evaluations at equal recall.  On a mutable index, mutations
interleave with in-flight queries without surfacing tombstoned points.
A tick reads the slot state once (the step's retire record) and dispatches
at most an admit and a step; masked serving adds one read of the whole
beam on ticks that retire.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ANNIndex,
    build_swgraph_wave,
    get_distance,
    knn_scan,
    make_step_searcher,
    recall_at_k,
    select_entries,
)
from repro.core.batched_beam import batched_beam_search
from repro.core.scheduler import GraphView, SlotScheduler
from repro.data.synthetic import lda_like_histograms, split_queries

N_DB, N_Q, DIM, K, EF = 420, 24, 16, 10, 48


@pytest.fixture(scope="module")
def setup():
    dist = get_distance("kl")
    X = lda_like_histograms(jax.random.PRNGKey(0), N_DB + N_Q, DIM)
    Q, db = split_queries(X, N_Q, jax.random.PRNGKey(1))
    adj, _ = build_swgraph_wave(dist, db, NN=10, ef_construction=48, wave=16)
    entries = select_entries(dist, db, 4, jax.random.PRNGKey(3))
    consts = dist.prep_scan(db)
    view = GraphView(adj, consts, None, entries)
    return dist, Q, db, view


def _reference_state(dist, Q, view, ef, frontier):
    """batched_beam_search with the scheduler's generic scoring closure."""
    qc = jax.vmap(dist.prep_query)(Q)

    def score_rows(ids):
        rows = jax.tree.map(lambda a: a[ids], view.consts)
        return jax.vmap(dist.score)(rows, qc)

    return batched_beam_search(view.neighbors, score_rows, view.entries,
                               Q.shape[0], ef, frontier=frontier, compact=32)


@pytest.mark.parametrize("frontier", [1, 4])
def test_exact_parity_all_at_once_no_refill(setup, frontier):
    """S >= B, all queries at t=0: bit-identical to batched_beam_search."""
    dist, Q, db, view = setup
    st = _reference_state(dist, Q, view, EF, frontier)
    sched = SlotScheduler(dist, lambda: view, dim=DIM, slots=N_Q, ef=EF, k=K,
                          frontier=frontier, use_pallas=False)
    res = sched.run_stream(np.asarray(Q))
    assert [r.rid for r in res] == list(range(N_Q))
    for j, r in enumerate(res):
        np.testing.assert_array_equal(r.dists, np.asarray(st.beam_d[j, :K]))
        np.testing.assert_array_equal(r.ids, np.asarray(st.beam_i[j, :K]))
        assert r.n_evals == int(st.n_evals[j])
        assert r.hops == int(st.hops[j])


@pytest.mark.parametrize("steps_per_sync", [1, 3])
def test_slot_recycling_preserves_results(setup, steps_per_sync):
    """6 slots, 24 queries: refilled slots produce the same per-query
    results as the all-at-once batch, regardless of sync granularity."""
    dist, Q, db, view = setup
    st = _reference_state(dist, Q, view, EF, 4)
    sched = SlotScheduler(dist, lambda: view, dim=DIM, slots=6, ef=EF, k=K,
                          frontier=4, steps_per_sync=steps_per_sync,
                          use_pallas=False)
    res = sched.run_stream(np.asarray(Q))
    for j, r in enumerate(res):
        np.testing.assert_array_equal(r.ids, np.asarray(st.beam_i[j, :K]))
        np.testing.assert_array_equal(r.dists, np.asarray(st.beam_d[j, :K]))
        assert r.n_evals == int(st.n_evals[j])


def test_kernel_path_matches_step_searcher(setup):
    """The scheduler's default (kernel) scoring agrees with the jitted
    batched searcher the index serves with."""
    dist, Q, db, view = setup
    eng = make_step_searcher(dist, view.neighbors, db, EF, K,
                             entries=view.entries, frontier=4)
    d_ref, i_ref, _, _ = eng(Q)
    sched = SlotScheduler(dist, lambda: view, dim=DIM, slots=8, ef=EF, k=K,
                          frontier=4)
    res = sched.run_stream(np.asarray(Q))
    for j, r in enumerate(res):
        np.testing.assert_array_equal(r.ids, np.asarray(i_ref[j]))
        np.testing.assert_allclose(r.dists, np.asarray(d_ref[j]),
                                   rtol=1e-5, atol=1e-6)


def test_poisson_arrivals_preserve_request_response_mapping(setup):
    """request -> queue -> slot -> response: staggered arrivals and
    out-of-order retirement never cross-wire responses.  Each request
    queries a database point, so its own id must come back first."""
    dist, Q, db, view = setup
    probes = np.asarray(db[37:37 + 16])
    arrivals = np.linspace(0.0, 0.05, 16)[np.random.RandomState(5).permutation(16)]
    sched = SlotScheduler(dist, lambda: view, dim=DIM, slots=4, ef=EF, k=1,
                          frontier=2)
    res = sched.run_stream(probes, arrivals)
    assert [r.rid for r in res] == list(range(16))
    got = np.asarray([r.ids[0] for r in res])
    np.testing.assert_array_equal(got, np.arange(37, 37 + 16))
    for r in res:
        assert r.t_done >= r.t_admit >= r.t_arrival >= 0.0


def test_adaptive_frontier_cuts_evals_at_equal_recall(setup):
    dist, Q, db, view = setup
    _, true_ids = knn_scan(dist, Q, db, K)
    fixed = SlotScheduler(dist, lambda: view, dim=DIM, slots=8, ef=EF, k=K,
                          frontier=4)
    adapt = SlotScheduler(dist, lambda: view, dim=DIM, slots=8, ef=EF, k=K,
                          frontier=4, adaptive=True)
    r_f = fixed.run_stream(np.asarray(Q))
    r_a = adapt.run_stream(np.asarray(Q))
    e_f = np.mean([r.n_evals for r in r_f])
    e_a = np.mean([r.n_evals for r in r_a])
    assert e_a < 0.95 * e_f, (e_a, e_f)
    rec_f = recall_at_k(np.stack([r.ids for r in r_f]), np.asarray(true_ids))
    rec_a = recall_at_k(np.stack([r.ids for r in r_a]), np.asarray(true_ids))
    assert rec_a >= rec_f - 0.02, (rec_a, rec_f)


def test_online_mutations_interleave_with_inflight_queries(setup):
    """Deletes mid-flight never surface in later responses; inserts become
    searchable for queries admitted after them — while earlier requests
    are still occupying slots."""
    dist, Q, db, _ = setup
    X_new = lda_like_histograms(jax.random.PRNGKey(7), 8, DIM)
    idx = ANNIndex.build(db[:300], dist, builder="swgraph", build_engine="wave",
                         wave=32, NN=10, ef_construction=48, capacity=400,
                         key=jax.random.PRNGKey(2))
    sched = idx.scheduler(K, EF, slots=4, frontier=2)
    sched.warmup(np.asarray(Q[0]))

    # occupy all slots + queue extras, then mutate while they're in flight
    for j in range(12):
        sched.submit(np.asarray(Q[j]), rid=j)
    first = sched.tick()
    baseline = idx.search(Q[:12], k=K, ef_search=EF)
    victims = np.unique(np.asarray(baseline[1])[:, 0])[:5]  # popular answers
    idx.delete(victims)
    new_ids = idx.insert(X_new)
    results = {r.rid: r for r in first}
    while len(results) < 12:
        for r in sched.tick():
            results[r.rid] = r
    late = [results[j] for j in range(12) if j not in {r.rid for r in first}]
    assert late, "mutations should have landed while queries were in flight"
    alive_now = np.asarray(idx.online.alive)
    recycled = victims[alive_now[victims]]  # victim slots reused by the insert
    for r in late:
        valid = r.ids[r.ids >= 0].astype(int)
        # never surface a tombstone, whatever the admission time
        assert alive_now[valid].all(), (r.rid, r.ids)
        # in-flight when the delete landed (admitted before it): the
        # killed-epoch guard must void every victim — including recycled
        # slots, whose id now names a DIFFERENT point than the one scored
        if r.rid < 4:
            assert not np.isin(valid, victims).any(), (r.rid, r.ids, victims)
            # voided entries backfill from the ef-wide beam: still k results
            assert len(valid) == K, (r.rid, r.ids)
        # admitted after the mutations: a victim id may appear only if its
        # slot was recycled into a live new point
        assert not np.isin(valid, np.setdiff1d(victims, recycled)).any()
    # a query for an inserted vector, admitted after the insert, finds it
    probe = sched.run_stream(np.asarray(idx.online.X[jnp.asarray(new_ids[:4])]))
    np.testing.assert_array_equal(np.asarray([r.ids[0] for r in probe]),
                                  new_ids[:4])


def test_static_scheduler_fails_loud_after_online_conversion(setup):
    """A scheduler snapshotting a frozen index must not silently serve the
    stale graph once the index becomes mutable (deleted points would keep
    surfacing): the next tick raises instead."""
    dist, Q, db, _ = setup
    idx = ANNIndex.build(db[:150], dist, builder="nndescent", NN=8,
                         nnd_iters=4, key=jax.random.PRNGKey(11))
    sched = idx.scheduler(K, EF, slots=4)
    assert sched.run_stream(np.asarray(Q[:2]))  # frozen serving works
    idx.delete([5])  # lazy online conversion
    sched.submit(np.asarray(Q[0]))
    with pytest.raises(RuntimeError, match="mutable"):
        sched.tick()
    # a scheduler created AFTER the conversion serves the live graph
    fresh = idx.scheduler(K, EF, slots=4)
    res = fresh.run_stream(np.asarray(db[5:6]))
    assert 5 not in set(res[0].ids.tolist())


def test_scheduler_serves_rerank_spec_identical_to_batch_searcher(setup):
    """ISSUE-5 acceptance: a rerank spec (search_policy != none) is served
    by the scheduler with results identical to the batch searcher + rerank
    path — the beams are bit-identical (same slot state machine) and each
    retired request's k_c candidates re-rank under the original distance
    (ids exactly equal; distances to float precision, since the batch path
    reranks all B rows in one vmapped call and the scheduler reranks one
    fixed-shape row per retire)."""
    from repro.core import RetrievalSpec

    dist, Q, db, _ = setup
    spec = RetrievalSpec(distance="kl", build_policy="min", search_policy="min",
                         k_c=40, builder="nndescent", NN=8, nnd_iters=4,
                         ef_search=EF, k=K)
    idx = ANNIndex.build(db[:300], spec=spec, key=jax.random.PRNGKey(4))
    bd, bi, bev, _ = idx.searcher(spec=spec)(Q)
    # slot recycling in play: fewer slots than queries.  The scheduler's
    # frontier is pinned to the batch searcher's (its spec default is the
    # fatter sched_frontier) so the beam state machines match step for step
    # and even the eval counts agree exactly.
    sched = idx.scheduler(spec=spec, slots=6, frontier=spec.frontier)
    res = sched.run_stream(np.asarray(Q))
    assert [r.rid for r in res] == list(range(N_Q))
    for j, r in enumerate(res):
        np.testing.assert_array_equal(r.ids, np.asarray(bi[j]))
        np.testing.assert_allclose(r.dists, np.asarray(bd[j]),
                                   rtol=1e-6, atol=1e-7)
        # rerank evals are accounted exactly like the batch path
        assert r.n_evals == int(bev[j])
    # reported distances are the ORIGINAL distance of the returned ids
    want = np.asarray(dist.query_matrix(Q, db[:300], mode="left"))
    for j, r in enumerate(res):
        valid = r.ids >= 0
        np.testing.assert_allclose(r.dists[valid],
                                   want[j][r.ids[valid].astype(int)],
                                   rtol=1e-4, atol=1e-5)


def test_scheduler_rerank_spec_on_mutable_index(setup):
    """The rerank scenario composes with the online index: deleted points
    never surface after the retire-time rerank either."""
    from repro.core import RetrievalSpec

    dist, Q, db, _ = setup
    spec = RetrievalSpec(distance="kl", build_policy="min", search_policy="min",
                         k_c=30, builder="nndescent", NN=8, nnd_iters=4,
                         ef_search=EF, k=K, capacity=360)
    idx = ANNIndex.build(db[:300], spec=spec, key=jax.random.PRNGKey(4))
    sched = idx.scheduler(spec=spec, slots=4)
    sched.warmup(np.asarray(Q[0]))
    base = idx.search(Q[:8], k=K, ef_search=EF)
    victims = np.unique(np.asarray(base[1])[:, 0])[:4]
    idx.delete(victims)
    res = sched.run_stream(np.asarray(Q[:8]))
    alive_now = np.asarray(idx.online.alive)
    for r in res:
        valid = r.ids[r.ids >= 0].astype(int)
        assert alive_now[valid].all(), (r.rid, r.ids)
        assert not np.isin(valid, victims).any()


def _count_tick_calls(sched, monkeypatch):
    """Wrap the scheduler's jitted programs and its host read of the slot
    state, on the instance: per tick (its index in the tick log), the
    programs dispatched by name and the shapes of each read."""
    calls = collections.defaultdict(lambda: {"programs": [], "reads": []})

    def program(name, fn):
        def counted(*args):
            calls[sched.log.n]["programs"].append(name)
            return fn(*args)
        return counted

    for name in ("_admit", "_step"):
        monkeypatch.setattr(sched, name, program(name, getattr(sched, name)))
    read = sched._read

    def counted_read(*arrays):
        calls[sched.log.n]["reads"].append(tuple(a.shape for a in arrays))
        return read(*arrays)

    monkeypatch.setattr(sched, "_read", counted_read)
    return calls


@pytest.mark.parametrize("index", ["static", "masked"])
def test_tick_reads_one_record_and_dispatches_no_release(setup, monkeypatch,
                                                          index):
    """More queries than slots, so slots are retired and refilled: every
    tick that steps makes exactly one read (the (S, 2k + 3) retire record)
    and dispatches at most admit and step.  The masked index reads its
    ef-wide beam besides, on the ticks that retire and on no others."""
    dist, Q, db, view = setup
    S = 4
    if index == "static":
        sched = SlotScheduler(dist, lambda: view, dim=DIM, slots=S, ef=EF,
                              k=K, frontier=4, use_pallas=False)
    else:
        idx = ANNIndex.build(db[:300], dist, builder="swgraph",
                             build_engine="wave", wave=32, NN=10,
                             ef_construction=48, capacity=400,
                             key=jax.random.PRNGKey(2))
        sched = idx.scheduler(K, EF, slots=S, frontier=4)
    assert sched._masked == (index == "masked")
    assert not hasattr(sched, "_release")
    sched.warmup(np.asarray(Q[0]))
    calls = _count_tick_calls(sched, monkeypatch)
    for j in range(12):
        sched.submit(np.asarray(Q[j]), rid=j)
    got = []
    while sched.n_pending or sched.n_inflight:
        got += sched.tick()
    assert sorted(r.rid for r in got) == list(range(12))
    rows = sched.log.rows()
    assert rows["admitted"][1:].sum() > 0  # slots were refilled
    wide = ((S, EF), (S, EF))
    for i in range(sched.log.n):
        programs, reads = calls[i]["programs"], calls[i]["reads"]
        assert programs.count("_step") == 1 and len(programs) <= 2, programs
        assert set(programs) <= {"_admit", "_step"}
        assert reads[0] == ((S, 2 * K + 3),)
        assert reads[1:] == ([wide] if sched._masked and rows["retired"][i]
                             else []), (i, reads)
    assert set(calls) == set(range(sched.log.n))
