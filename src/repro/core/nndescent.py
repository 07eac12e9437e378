"""Parallel NN-descent graph construction (Dong et al. 2011).

The TPU-native alternative to sequential SW-graph insertion (DESIGN.md
SS2.3): every refinement round is a fully batched neighbor-of-neighbor join -

    candidates(i) = adj[adj[i]]  u  sampled-reverse(i)  u  random(i)
    adj(i) <- top-K by d_build(x_c, x_i) after id-dedup

All rounds are dense gathers + matmul-form distance blocks + top-K merges, so
construction itself runs at MXU throughput: candidate scoring goes through
the fused gather+score kernel (``repro.kernels.frontier_gather``) for plain
matmul-form Distances.  Like SW-graph construction, the build distance is
the INDEX-time distance (symmetrization knob applies).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .distances import Distance

INF = jnp.inf


def _score_rows(dist, consts, x_rows, qc_all, ids):
    """d_build(X[ids[i, c]], X[i]) for every node i, candidate c. (n, C).

    Plain matmul-form Distances route through the fused gather+score kernel
    (``repro.kernels.frontier_gather``, given its row view ``x_rows`` of the
    corpus: MXU contraction per node on TPU) or one fused einsum elsewhere;
    composite/symmetrized distances take the generic pytree path.
    ``qc_all`` is the whole database prepped as queries ONCE per build
    (``jax.vmap(dist.prep_query)(X)``).
    """
    safe = jnp.where(ids >= 0, ids, 0)
    if isinstance(dist, Distance):
        from repro.kernels.ops import frontier_gather_scores

        return frontier_gather_scores(
            dist, safe, qc_all["rep"], qc_all["bias"], consts["rep"],
            consts["bias"], x_rows=x_rows,
        ).astype(jnp.float32)
    rows = jax.tree.map(lambda a: a[safe], consts)
    return jax.vmap(dist.score)(rows, qc_all).astype(jnp.float32)


def _dedup_topk(d, ids, K: int):
    """Per-row: drop duplicate ids (keep best), return K smallest by d."""
    # sort by id; mark repeats as +inf; then sort by distance.  Both are
    # stable key-value sorts: an argsort + gather formulation of the same
    # thing takes minutes for the chip's compiler at some row counts
    ids_s, d_s = jax.lax.sort((ids, d), dimension=1, num_keys=1)
    dup = jnp.concatenate(
        [jnp.zeros((ids.shape[0], 1), bool), ids_s[:, 1:] == ids_s[:, :-1]], axis=1
    )
    d_s = jnp.where(dup | (ids_s < 0), INF, d_s)
    d_o, i_o = jax.lax.sort((d_s, ids_s), dimension=1, num_keys=1)
    return d_o[:, :K], i_o[:, :K]


def _take(a, rows):
    # in-bounds row gather; the default (fill) mode of ``a[rows]`` costs the
    # chip's compiler close to a minute on some shapes
    return jnp.take(a, rows, axis=0, mode="clip")


def _join_rows(dist, consts, x_rows, qc_all, adj_d, adj, rev, rnd, rows,
               K: int):
    """One refinement of the node block ``rows`` against the previous
    round's graph: (len(rows), K) best (dists, ids) over the old lists and
    the block's neighbor-of-neighbor / reverse / random candidates."""
    safe = jnp.where(adj >= 0, adj, 0)
    # adj[adj[rows]] as ONE scalar gather from the flat list: the row-gather
    # form takes the chip's compiler minutes at K = 30
    hop = (_take(safe, rows)[:, :, None] * K + jnp.arange(K, dtype=jnp.int32)).reshape(-1)
    two_hop = _take(safe.reshape(-1), hop).reshape(rows.shape[0], K * K)
    cand = jnp.concatenate([two_hop, _take(rev, rows), _take(rnd, rows)], axis=1)
    cand = jnp.where(cand == rows[:, None], -1, cand)  # no self loops
    qc = jax.tree.map(lambda a: _take(a, rows), qc_all)
    cand_d = _score_rows(dist, consts, x_rows, qc, cand)
    cand_d = jnp.where(cand >= 0, cand_d, INF)
    all_d = jnp.concatenate([_take(adj_d, rows), cand_d], axis=1)
    all_i = jnp.concatenate([_take(adj, rows), cand], axis=1)
    return _dedup_topk(all_d, all_i, K)


def _sampled_reverse(adj, K_rev: int, key):
    """A sampled fixed-width reverse-neighbor list via ONE colliding scatter.

    Every edge (src, dst) bids for a randomized slot of ``rev[dst]``; slot
    collisions are resolved by scatter-max over the source id — a single
    segment-style scatter whose trace and HLO are independent of K (the old
    per-column Python loop unrolled into K sequential scatters).
    """
    n, K = adj.shape
    # randomize slot assignment so collisions evict uniformly across rounds
    slots = jnp.broadcast_to(jax.random.randint(key, (K,), 0, K_rev), (n, K))
    src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, K))
    dst = jnp.where(adj >= 0, adj, n)  # invalid edges scatter out of bounds
    rev = jnp.full((n, K_rev), -1, jnp.int32)
    return rev.at[dst.reshape(-1), slots.reshape(-1)].max(src.reshape(-1), mode="drop")


# nodes joined per block within a NN-descent round: bounds the join's
# (block, K*K + K + n_random) transients, which at n = 10^6 would otherwise
# take most of a 16 GB chip.  Every block reads the previous round's graph,
# so the result does not depend on it.
_JOIN_ROWS = 131072


@functools.partial(
    jax.jit,
    static_argnames=("dist", "K", "iters", "n_random", "M_out", "add_reverse"),
)
def build_nndescent(
    dist,
    X,
    key,
    K: int = 16,
    iters: int = 8,
    n_random: int = 8,
    M_out: int | None = None,
    add_reverse: bool = True,
):
    """Returns ``(neighbors (n, M_out) int32, degrees (n,))``.

    ``M_out`` defaults to 2K when ``add_reverse`` (forward + sampled reverse
    edges - undirected graphs searched better in the paper's refs [20]).
    Each round joins the nodes in blocks of ``_JOIN_ROWS``.
    """
    n = X.shape[0]
    K = min(K, n - 1)
    consts = dist.prep_scan(X)
    qc_all = jax.vmap(dist.prep_query)(X)  # whole DB prepped as queries once
    from repro.kernels.ops import kernel_rows

    x_rows = kernel_rows(dist, consts)  # the kernel's corpus view, once
    iota = jnp.arange(n, dtype=jnp.int32)

    # --- init: random neighbors (exclude self by +1 shift mod n) ---
    key, k0 = jax.random.split(key)
    init_ids = (iota[:, None] + 1 + jax.random.randint(k0, (n, K), 0, n - 1)) % n
    init_d = _score_rows(dist, consts, x_rows, qc_all, init_ids)
    adj_d, adj = _dedup_topk(init_d, init_ids, K)

    n_blocks = -(-n // _JOIN_ROWS)
    # tail block rows repeat node n-1; their duplicate results are sliced off
    blocks = jnp.minimum(
        jnp.arange(n_blocks * min(_JOIN_ROWS, n), dtype=jnp.int32), n - 1
    ).reshape(n_blocks, -1)

    def round_(carry, key_r):
        adj_d, adj = carry
        k1, k2 = jax.random.split(key_r)
        rev = _sampled_reverse(adj, K, k1)
        rnd = jax.random.randint(k2, (n, n_random), 0, n)

        def join(rows):
            return _join_rows(dist, consts, x_rows, qc_all, adj_d, adj, rev,
                              rnd, rows, K)

        if n_blocks == 1:
            new_d, new_i = join(iota)
        else:
            new_d, new_i = jax.lax.map(join, blocks)
            new_d, new_i = new_d.reshape(-1, K)[:n], new_i.reshape(-1, K)[:n]
        n_changed = jnp.sum(new_i != adj)
        return (new_d, new_i), n_changed

    keys = jax.random.split(key, iters)
    (adj_d, adj), changes = jax.lax.scan(round_, (adj_d, adj), keys)

    if add_reverse:
        M_out = M_out or 2 * K
        rev = _sampled_reverse(adj, M_out - K, jax.random.fold_in(key, 7))
        # drop reverse edges that duplicate forward ones
        dup = (rev[:, :, None] == adj[:, None, :]).any(axis=2)
        rev = jnp.where(dup, -1, rev)
        neighbors = jnp.concatenate([adj, rev], axis=1)
    else:
        M_out = M_out or K
        neighbors = adj[:, :M_out]

    degrees = jnp.sum(neighbors >= 0, axis=1, dtype=jnp.int32)
    return neighbors, degrees
