"""Wave-parallel index construction engine (NMSLIB-style relaxed ordering).

Sequential SW-graph insertion (``build_swgraph``) is a serial chain of n
beam searches — one ``fori_loop`` step per point — which makes index builds
the wall-clock bottleneck of the experiment loop.  NMSLIB parallelizes
insertion across threads with only soft ordering guarantees (Naidan &
Boytsov, 1508.05470); this module maps that relaxation onto the lock-step
batched beam engine:

  * points are inserted in waves of W.  Each wave runs its W construction
    beam searches through ``batched_beam_search`` against the FROZEN prefix
    graph (``n_active`` masking): intra-wave points do not see each other,
    exactly the relaxed ordering NMSLIB accepts across insert threads.
  * forward edges land as one masked scatter; reverse edges are applied by a
    vectorized scatter-with-eviction merge — updates are sorted by
    (owner, distance), ranked within each owner segment, and each rank round
    scatters its (conflict-free, because owners are distinct within a rank)
    updates into the farthest-edge slot of the owner rows.  Ascending-order
    insert-with-evict is a streaming top-M, so per owner the merge keeps the
    M_max closest of {existing edges} u {wave candidates}.
  * at W=1 every wave has a single point, every owner has a single
    candidate, and ``batched_beam_search`` with frontier=1 is step-for-step
    identical to ``beam_search_impl`` — the wave builder is parity-tested
    bit-identical to ``build_swgraph`` (tests/test_build_engine.py).

``build_sharded`` is the multi-device composition: per-shard subgraphs are
built under ``jax.shard_map`` (wave engine or NN-descent) and stitched into
one global-id graph by a cross-shard neighbor exchange — every shard
broadcasts a sample of its rows, scores its local points against the union
in matmul form, and keeps the best ``cross_links`` remote edges per point.
This is the precursor to serving ``distributed.sharded_graph_search``
directly from engine-built shards.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .batched_beam import batched_beam_search
from .distances import Distance

INF = jnp.inf


def reverse_edge_merge(adj, adj_d, owners, cands, d_rev, ok, rounds: int):
    """Degree-capped reverse-edge scatter-with-eviction merge.

    Applies up to U candidate edges ``owners[u] -> cands[u]`` (slot distance
    ``d_rev[u] = d_build(x_cand, x_owner)``, the left-query distance of the
    candidate towards the owner) into the fixed-degree rows of
    ``adj``/``adj_d``, evicting each owner's farthest edge when the row is
    full.  Updates are sorted by (owner, distance) and ranked within each
    owner segment; rank round r scatters its (conflict-free, because owners
    are distinct within a rank) updates into the farthest-edge slot of the
    owner rows.  Ascending-order insert-with-evict is a streaming top-M, so
    per owner the merge keeps the M_max closest of
    {existing edges} u {candidates}.

    An owner receiving more than ``rounds`` candidates keeps only the
    closest ``rounds`` of them (the rest are the farthest candidates of the
    batch — the documented NMSLIB-style relaxation).  Self-loops and
    already-present neighbors are never written.

    Shared by the wave construction engine and the online mutable index
    (inserts and compaction repairs).  ``ok`` masks padded update slots.
    """
    n = adj.shape[0]
    U = owners.shape[0]
    d_rev = jnp.where(ok, d_rev, INF)
    owner_key = jnp.where(ok, owners, jnp.int32(n))
    order = jnp.lexsort((d_rev, owner_key))
    o_j, o_i, o_d, o_ok = (a[order] for a in (owner_key, cands, d_rev, ok))
    prev = jnp.concatenate([jnp.full((1,), -1, o_j.dtype), o_j[:-1]])
    idxs = jnp.arange(U, dtype=jnp.int32)
    rank = idxs - jax.lax.cummax(jnp.where(o_j == prev, 0, idxs))

    def rev_round(r, carry):
        adj, adj_d = carry
        m = o_ok & (rank == r)
        oj = jnp.where(m, o_j, 0)
        rows_d = adj_d[oj]  # (U, M_max)
        slot = jnp.argmax(rows_d, axis=1)  # free slots are +inf -> first
        cur = jnp.take_along_axis(rows_d, slot[:, None], axis=1)[:, 0]
        # the owner may already hold this candidate as one of ITS forward
        # edges (mutual intra-wave links; impossible for wave=1, where
        # owners predate the candidate) — never duplicate it, and never
        # write a self-loop
        already = jnp.any(adj[oj] == o_i[:, None], axis=1)
        do = m & (o_d < cur) & ~already & (o_i != oj)
        oj_w = jnp.where(do, o_j, n)  # losers scatter out of bounds
        adj = adj.at[oj_w, slot].set(o_i, mode="drop")
        adj_d = adj_d.at[oj_w, slot].set(o_d, mode="drop")
        return adj, adj_d

    return jax.lax.fori_loop(0, rounds, rev_round, (adj, adj_d))


def reverse_edge_scores(dist, consts, qc_all, flat_i, safe_j):
    """Slot distances for reverse candidates: d_build(x_i, x_j) with i the
    candidate (left) and j the owner (query side, gathered from the
    once-prepped ``qc_all``) — the composition every wave writer shares."""

    def rev_score(i, j):
        rows_i = jax.tree.map(lambda a: a[i[None]], consts)
        qc_j = jax.tree.map(lambda a: a[j], qc_all)
        return dist.score(rows_i, qc_j)[0].astype(jnp.float32)

    return jax.vmap(rev_score)(flat_i, safe_j)


def wave_connect(dist, consts, qc_all, adj, adj_d, pids, ok_pt, beam_i, beam_d,
                 *, NN, L, R):
    """Connect one wave of points into the graph from their beam results.

    The shared wave body of ``build_swgraph_wave`` and the online index's
    ``_insert_wave`` (only their beam-search masking differs: frozen-prefix
    ``n_active`` at build time, ``alive`` tombstone mask online):

      1. intra-wave links — the beam's masking hides wave-mates from each
         other, so score the wave against itself (one exact (W, W) block)
         and let each point's closest L wave-mates compete with its beam
         candidates for the NN forward slots;
      2. forward edges — one dropped-padding scatter of the wave's rows;
      3. reverse edges — the degree-capped ``reverse_edge_merge``.

    ``beam_i``/``beam_d`` are the wave's (W, ef) beam results; rows with
    ``ok_pt[w] == False`` are padding and write nothing.  Returns the
    updated ``(adj, adj_d)``.
    """
    cap, M_max = adj.shape
    W = pids.shape[0]
    safe_p = jnp.where(ok_pt, pids, 0)
    ids = beam_i[:, :NN]  # (W, NN)
    ds = beam_d[:, :NN]

    if L > 0:
        qc = jax.tree.map(lambda a: a[safe_p], qc_all)
        rows_w = jax.tree.map(lambda a: a[safe_p], consts)
        D_intra = jax.vmap(lambda q: dist.score(rows_w, q))(qc).astype(jnp.float32)
        iw = jnp.arange(W)
        bad = (iw[None, :] == iw[:, None]) | ~ok_pt[None, :] | ~ok_pt[:, None]
        D_intra = jnp.where(bad, INF, D_intra)
        negi, posi = jax.lax.top_k(-D_intra, L)
        intra_i = jnp.where(jnp.isfinite(negi), safe_p[posi], -1)
        cand_i = jnp.concatenate([ids, intra_i], axis=1)
        cand_d = jnp.concatenate([jnp.where(ids >= 0, ds, INF), -negi], axis=1)
        negf, sel = jax.lax.top_k(-cand_d, NN)  # beam ids and wave-mates
        ds = -negf  # ids are disjoint (settled graph vs wave), no dedup here
        ids = jnp.take_along_axis(cand_i, sel, axis=1)
    valid = (ids >= 0) & jnp.isfinite(ds) & ok_pt[:, None]

    # -- forward edges: one dropped-padding scatter for the whole wave
    row_i = jnp.full((W, M_max), -1, jnp.int32).at[:, :NN].set(jnp.where(valid, ids, -1))
    row_d = jnp.full((W, M_max), INF, jnp.float32).at[:, :NN].set(
        jnp.where(valid, ds, INF)
    )
    dst = jnp.where(ok_pt, pids, cap)  # out-of-bounds rows are dropped
    adj = adj.at[dst].set(row_i, mode="drop")
    adj_d = adj_d.at[dst].set(row_d, mode="drop")

    # -- reverse edges: flatten the wave's (owner j, candidate i,
    # d_build(x_i, x_j)) updates through the shared eviction merge
    U = W * NN
    flat_j = ids.reshape(U)
    flat_ok = valid.reshape(U)
    flat_i = jnp.repeat(safe_p, NN)
    safe_j = jnp.where(flat_ok, flat_j, 0)
    d_rev = jnp.where(flat_ok, reverse_edge_scores(dist, consts, qc_all, flat_i, safe_j), INF)
    return reverse_edge_merge(adj, adj_d, flat_j, flat_i, d_rev, flat_ok, R)


def wave_knobs(n: int, NN: int = 15, ef_construction: int = 100,
               M_max: int | None = None, wave: int = 32,
               rev_rounds: int | None = None, frontier: int | None = None,
               intra_links: int | None = None) -> dict:
    """The static shape of a wave build over ``n`` rows (see
    ``build_swgraph_wave`` for the knobs): degree cap ``M_max``, beam width
    ``ef``, wave size ``W``, reverse-merge rounds ``R``, frontier ``T``,
    intra-wave links ``L``."""
    if M_max is None:
        M_max = 2 * NN
    if M_max < NN:
        raise ValueError(f"M_max {M_max} < NN {NN}")
    W = int(max(1, min(wave, n - 1)))
    return dict(
        NN=NN, M_max=M_max, ef=max(ef_construction, NN), W=W,
        R=int(min(W, 8 if rev_rounds is None else rev_rounds)),
        T=int(frontier) if frontier is not None else (1 if W == 1 else 4),
        L=int(min(NN if intra_links is None else intra_links, W - 1)),
    )


def wave_count(n: int, W: int) -> int:
    """Waves of a build: point 0 is the seed, waves cover rows 1..n-1."""
    return -(-(n - 1) // W)


def wave_evals(steps: int, n: int, *, NN, M_max, ef, W, T, L, **_) -> int:
    """Distances a wave build over ``n`` rows computed, given ``steps``, the
    lock-steps its beams ran summed over the waves (``wave_insert``): per
    wave, the seed entry and then a (W, T * M_max) candidate block per
    lock-step for the beams, the (W, W) intra-wave block, and W * NN
    reverse-edge scores.  Padding rows of the last wave count: they are
    scored like the rest."""
    per_wave = W * (1 + NN + (W if L > 0 else 0))
    return int(steps) * W * min(T, ef) * M_max + wave_count(n, W) * per_wave


@functools.partial(jax.jit, static_argnames=("dist",))
def wave_prep(dist, X):
    """The per-row constants a wave build scores with, prepped once: the
    corpus reps (``prep_scan``) and every row's query constants
    (``prep_query``), each row acting as its own query."""
    return dist.prep_scan(X), jax.vmap(dist.prep_query)(X)


@functools.partial(
    jax.jit,
    static_argnames=("dist", "NN", "M_max", "ef", "W", "R", "T", "L", "use_pallas"),
)
def wave_insert(dist, consts, qc_all, *, NN, M_max, ef, W, R, T, L, use_pallas=None):
    """Insert rows 1..n-1 wave by wave (one ``lax.scan``) from the prepped
    constants of ``wave_prep``; knobs from ``wave_knobs``.  Returns the
    ``(n, M_max)`` adjacency and ``steps``, the lock-steps the waves' beams
    ran in all (each wave's longest beam, plus the step that finds every
    beam done), the count ``wave_evals`` turns into distances."""
    n = jax.tree.leaves(qc_all)[0].shape[0]
    n_waves = wave_count(n, W)
    # point 0 is the seed node (no insertion); waves cover 1..n-1, padded
    pids_all = 1 + jnp.arange(n_waves * W, dtype=jnp.int32).reshape(n_waves, W)

    adj = jnp.full((n, M_max), -1, jnp.int32)
    adj_d = jnp.full((n, M_max), INF, jnp.float32)
    entries = jnp.zeros((1,), jnp.int32)

    from repro.kernels import ops

    # the kernel's row view of the corpus, laid out once per build
    x_rows = ops.kernel_rows(dist, consts, use_pallas)

    def wave_step(carry, pids):
        adj, adj_d, steps = carry
        base = pids[0]  # every point in the wave sees exactly the prefix
        ok_pt = pids < n
        safe_p = jnp.where(ok_pt, pids, 0)
        qc = jax.tree.map(lambda a: a[safe_p], qc_all)

        if x_rows is not None:

            def score_rows(ids):
                return ops.frontier_gather_scores(
                    dist, ids, qc["rep"], qc["bias"], consts["rep"], consts["bias"],
                    x_rows=x_rows,
                )
        else:

            def score_rows(ids):
                rows = jax.tree.map(lambda a: a[ids], consts)
                return jax.vmap(dist.score)(rows, qc)

        st = batched_beam_search(adj, score_rows, entries, W, ef, n_active=base, frontier=T)
        adj, adj_d = wave_connect(
            dist, consts, qc_all, adj, adj_d, pids, ok_pt, st.beam_i, st.beam_d,
            NN=NN, L=L, R=R,
        )
        return (adj, adj_d, steps + jnp.max(st.hops) + 1), None

    (adj, _, steps), _ = jax.lax.scan(wave_step, (adj, adj_d, jnp.int32(0)), pids_all)
    return adj, steps


@functools.partial(
    jax.jit,
    static_argnames=(
        "dist", "NN", "ef_construction", "M_max", "wave", "rev_rounds", "frontier",
        "intra_links", "use_pallas",
    ),
)
def build_swgraph_wave(
    dist,
    X,
    NN: int = 15,
    ef_construction: int = 100,
    M_max: int | None = None,
    wave: int = 32,
    rev_rounds: int | None = None,
    frontier: int | None = None,
    intra_links: int | None = None,
    use_pallas=None,
):
    """Wave-parallel SW-graph build over X under ``dist`` (any PairDistance).

    Same contract as ``build_swgraph``: returns
    ``(neighbors (n, M_max) int32, degrees (n,) int32)``.

    ``wave``: points inserted per wave (W=1 reproduces the sequential builder
    bit-for-bit).  ``frontier``: beam candidates expanded per lock-step of
    the construction searches (defaults to 1 at W=1 for exact parity, 4
    otherwise — same knob as the serving engine).  ``intra_links``: each wave
    point also considers its closest wave-mates (exact (W, W) block) as edge
    candidates, recovering the links NMSLIB's threads would have seen in
    points inserted concurrently; defaults to min(NN, W-1), empty at W=1.
    ``rev_rounds``: reverse-edge merge rounds per wave; an owner row
    receiving more than ``rev_rounds`` reverse candidates in one wave keeps
    only the closest ``rev_rounds`` of them (the rest are the farthest
    candidates of that wave — a documented NMSLIB-style relaxation).

    ``use_pallas``: None (default) scores construction frontiers through the
    fused Pallas gather+distance kernel ON TPU ONLY — off-TPU the generic
    jnp path runs, which is also what guarantees W=1 bit-parity with the
    sequential builder; True forces the kernel (interpret mode off-TPU),
    False forces jnp.  Composite distances always take the generic path.

    One program: the composition of ``wave_prep`` and ``wave_insert``,
    which ``ANNIndex.build`` runs as two, timing each.
    """
    knobs = wave_knobs(X.shape[0], NN, ef_construction, M_max, wave,
                       rev_rounds, frontier, intra_links)
    consts, qc_all = wave_prep(dist, X)
    adj, _ = wave_insert(dist, consts, qc_all, use_pallas=use_pallas, **knobs)
    return adj, graph_degrees(adj)


def graph_degrees(adj):
    """Out-degree of each row of an adjacency (-1 padding)."""
    return jnp.sum(adj >= 0, axis=1, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# shard-and-merge builds
# ---------------------------------------------------------------------------


def build_sharded(
    mesh,
    dist,
    X_sharded,
    *,
    NN: int = 15,
    db_axes=("data",),
    builder: str = "wave",
    wave: int = 32,
    ef_construction: int = 100,
    M_max: int | None = None,
    nnd_iters: int = 8,
    cross_links: int = 4,
    sample_per_shard: int = 64,
    key=None,
    use_pallas=False,
):
    """Build per-shard subgraphs under shard_map, stitch with a cross-shard
    neighbor exchange.

    ``X_sharded``: (n, m) with rows sharded over ``db_axes``.  Each shard
    builds a local subgraph over its own rows (``builder`` in
    {"wave", "nndescent"}), then broadcasts ``sample_per_shard`` sampled rows
    (one ``all_gather``); every local point scores the gathered union in one
    matmul-form block and keeps its best ``cross_links`` REMOTE edges.

    Returns a (n, M_local + cross_links) int32 adjacency in GLOBAL row ids,
    replicated over ``mesh`` — so the single-device engines gather from it
    directly, whatever the mesh's axis types.
    """
    from .nndescent import build_nndescent

    if builder not in ("wave", "nndescent"):
        raise ValueError(f"unknown sharded builder {builder!r}; known: wave, nndescent")
    n_shards = 1
    for a in db_axes:
        n_shards *= int(mesh.shape[a])
    n = X_sharded.shape[0]
    if n % n_shards:
        # build_sharded emits a GLOBAL-id stitched graph for replicated
        # search, so wrap-around padding (which would mint duplicate global
        # ids) does not apply — unlike distributed.build_local_subgraphs,
        # which pads.  Refuse loudly instead of silently dropping rows.
        raise ValueError(
            f"build_sharded needs n ({n}) divisible by the shard count "
            f"({n_shards}); pad the corpus or use "
            f"distributed.build_local_subgraphs for scatter-gather serving")
    n_local = n // n_shards
    key = key if key is not None else jax.random.PRNGKey(0)

    def local(X_local, key):
        shard = jax.lax.axis_index(db_axes)
        k_shard = jax.random.fold_in(key, shard)
        if builder == "wave":
            nbrs, _ = build_swgraph_wave(
                dist, X_local, NN=NN, ef_construction=ef_construction, M_max=M_max,
                wave=wave, use_pallas=use_pallas,
            )
        else:
            nbrs, _ = build_nndescent(dist, X_local, k_shard, K=NN, iters=nnd_iters, M_out=M_max)

        # cross-shard neighbor exchange: sample rows, broadcast, score, link
        S = min(sample_per_shard, n_local)
        sample_idx = jax.random.choice(
            jax.random.fold_in(k_shard, 1), n_local, (S,), replace=False
        ).astype(jnp.int32)
        gids = sample_idx + shard * n_local
        all_Xs = jax.lax.all_gather(X_local[sample_idx], db_axes, axis=0, tiled=True)
        all_gids = jax.lax.all_gather(gids, db_axes, axis=0, tiled=True)
        # D[b, t] = d_build(sample_t, x_b): the owner-row slot convention
        if isinstance(dist, Distance):
            from repro.kernels.ops import query_distance_matrix

            D = query_distance_matrix(dist, X_local, all_Xs, use_pallas=use_pallas)
        else:
            D = dist.query_matrix(X_local, all_Xs, mode="left")
        own = (all_gids // n_local) == shard
        D = jnp.where(own[None, :], INF, D)
        neg, pos = jax.lax.top_k(-D, min(cross_links, all_gids.shape[0]))
        cross = jnp.where(jnp.isfinite(neg), all_gids[pos], -1)
        local_global = jnp.where(nbrs >= 0, nbrs + shard * n_local, -1)
        stitched = jnp.concatenate([local_global, cross], axis=1)
        return jax.lax.all_gather(stitched, db_axes, axis=0, tiled=True)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(db_axes, None), P()),
        out_specs=P(),
        check_vma=False,
    )(X_sharded, key)
