"""Step-synchronized batched beam-search engine.

All B queries advance in lock-step through ONE ``while_loop``.  Each step:

  1. every active query pops its ``frontier`` best unexpanded beam entries,
  2. their neighbor rows are gathered as one (B, frontier*M) id block,
  3. the block is scored in one fused batched call (jnp einsum path or the
     Pallas gather+distance kernel, see ``repro.kernels.frontier_gather``),
  4. a batched (B, ef + frontier*M) merge-sort refreshes every beam,
  5. per-query convergence masking freezes finished queries (their beam,
     visited set, eval counter and hop counter stop changing) so they stop
     paying for stragglers.

Versus the reference ``beam_search_impl`` under ``jax.vmap`` this removes the
per-query while_loop (one fused loop for the whole batch), expands several
frontier candidates per step (``frontier`` knob: fewer, MXU-fatter steps for
the same efSearch semantics) and seeds from multiple entry points (medoid +
random, replacing the hardcoded node 0).

With ``frontier=1`` and a single entry the engine is step-for-step identical
to ``beam_search_impl`` (the parity tests in tests/test_batched_engine.py
assert exact equality of beams, eval counts and hop counts).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .distances import Distance

INF = jnp.inf


class BatchBeamState(NamedTuple):
    beam_d: jax.Array  # (B, ef) f32, ascending, inf-padded
    beam_i: jax.Array  # (B, ef) i32, -1-padded
    expanded: jax.Array  # (B, ef) bool (padding = True)
    visited: jax.Array  # (B, ceil(n/32)) uint32 bit-packed visited set
    n_evals: jax.Array  # (B,) i32 distance evaluations (the paper's cost unit)
    hops: jax.Array  # (B,) i32 graph hops taken by each query
    done: jax.Array  # (B,) bool frozen queries


# ---------------------------------------------------------------------------
# entry-point selection
# ---------------------------------------------------------------------------


def select_entries(dist, X, n_entries: int = 4, key=None, sample: int = 256):
    """Entry points for the beam: left-medoid + random spread.

    The medoid minimises the mean left-query distance d(x_i, .) towards a
    random sample of the database (one matmul-form block), replacing the
    arbitrary hardcoded entry node 0.  The remaining entries are drawn
    uniformly so multi-entry seeding covers disconnected or polarised
    regions of a graph built under a non-symmetric distance.
    """
    n = X.shape[0]
    n_entries = max(1, min(n_entries, n))
    if key is None:
        key = jax.random.PRNGKey(0)
    k_sample, k_rand = jax.random.split(key)
    s = min(sample, n)
    probe = jax.random.choice(k_sample, n, (s,), replace=False)
    # D[b, i] = d(X[i], X[probe[b]]) — column means rank centrality of i.
    D = dist.query_matrix(X[probe], X, mode="left")
    medoid = jnp.argmin(jnp.mean(D, axis=0)).astype(jnp.int32)
    if n_entries == 1:
        return medoid[None]
    rand = jax.random.choice(k_rand, n, (min(4 * n_entries, n),), replace=False)
    # fixed-shape medoid exclusion: a stable argsort keys the (at most one)
    # medoid hit to the tail, so the head slice is the same elements in the
    # same order as the old boolean mask — without the data-dependent shape
    rand = rand[jnp.argsort(rand == medoid)][: n_entries - 1].astype(jnp.int32)
    return jnp.concatenate([medoid[None], rand])


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def seed_beams(
    score_rows,  # (B, R) int32 ids -> (B, R) f32 left-query distances
    entries,  # (E,) i32 shared entry nodes
    B: int,
    ef: int,
    n: int,
    n_active=None,  # optional () i32: only nodes < n_active are searchable
    alive=None,  # optional (n,) bool: tombstoned nodes are never scored
) -> BatchBeamState:
    """Score the shared entry nodes for B queries and seed their beams.

    The returned state is exactly the pre-loop state of
    ``batched_beam_search``; the slot scheduler reuses it to (re)seed
    individual slots as requests are admitted, so an admitted query starts
    from the same floats as a batch-at-once query.
    """
    E = entries.shape[0]
    masked = n_active is not None or alive is not None

    # ---- seed: score every entry for every query, keep the best ef
    d0 = score_rows(jnp.broadcast_to(entries[None, :], (B, E))).astype(jnp.float32)
    if masked:
        entry_ok = jnp.ones((E,), bool)
        if n_active is not None:
            entry_ok &= entries < n_active
        if alive is not None:
            entry_ok &= alive[entries]
        d0 = jnp.where(entry_ok[None, :], d0, INF)
    order0 = jnp.argsort(d0, axis=1)
    take = min(E, ef)
    d0_sorted = jnp.take_along_axis(d0, order0, axis=1)[:, :take]
    i0_sorted = entries[order0][:, :take].astype(jnp.int32)
    if masked:
        # blocked entries seed as (inf, -1) padding and are never expanded
        i0_sorted = jnp.where(jnp.isfinite(d0_sorted), i0_sorted, -1)
    beam_d = jnp.full((B, ef), INF, jnp.float32).at[:, :take].set(d0_sorted)
    beam_i = jnp.full((B, ef), -1, jnp.int32).at[:, :take].set(i0_sorted)
    expanded = jnp.ones((B, ef), bool)
    if masked:
        expanded = expanded.at[:, :take].set(~jnp.isfinite(d0_sorted))
    else:
        expanded = expanded.at[:, :take].set(False)
    # visited is a bit-packed (B, ceil(n/32)) uint32 set: 32x less state to
    # carry through the loop than a bool mask, and updates become a handful
    # of word-sized ops instead of an O(B*n) scatter.  Seed bits are OR-ed
    # one entry at a time (E is small and static) so duplicate entry ids
    # cannot carry into neighboring bits.
    nw = -(-n // 32)
    if not masked:
        seed = jnp.zeros((nw,), jnp.uint32)
    else:
        # block the suffix and the tombstones: bit v set iff v is not
        # searchable (bits are distinct, so a plain sum over the word
        # assembles the OR of the 32 lanes)
        bit_ids = jnp.arange(nw * 32, dtype=jnp.int32)
        blocked = jnp.zeros((nw * 32,), bool)
        if n_active is not None:
            blocked |= bit_ids >= n_active
        if alive is not None:
            alive_pad = jnp.pad(alive, (0, nw * 32 - n), constant_values=False)
            blocked |= ~alive_pad
        lane = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
        seed = jnp.sum(
            jnp.where(blocked.reshape(nw, 32), lane[None, :], jnp.uint32(0)),
            axis=1,
            dtype=jnp.uint32,
        )
    for j in range(E):
        w = entries[j] // 32
        seed = seed.at[w].set(seed[w] | (jnp.uint32(1) << (entries[j] % 32).astype(jnp.uint32)))
    visited = jnp.broadcast_to(seed, (B, nw))
    if masked:
        n_evals0 = jnp.broadcast_to(jnp.sum(entry_ok, dtype=jnp.int32), (B,))
    else:
        n_evals0 = jnp.full((B,), E, jnp.int32)
    return BatchBeamState(
        beam_d,
        beam_i,
        expanded,
        visited,
        n_evals0,
        jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), bool),
    )


def beam_step(
    st: BatchBeamState,
    neighbors,  # (n, M) int32 adjacency, -1 padding
    score_rows,  # (B, R) int32 ids -> (B, R) f32 left-query distances
    ef: int,
    T: int,
    C: int,
    max_steps: int,
    t_active=None,  # optional (B,) i32: per-query frontier width this step
    ef_active=None,  # optional (B,) i32: per-query effective beam width
) -> BatchBeamState:
    """One lock-step of the batched beam engine (the while_loop body).

    Exposed so the slot scheduler can drive the identical step from a
    host-side loop (retiring and refilling slots between steps).  With
    ``t_active=None`` this is byte-for-byte the engine's loop body; a
    per-query ``t_active`` additionally caps how many of the top-T popped
    candidates each query may expand this step (clamped to [the candidates
    that exist], used by the adaptive-frontier policy).  Queries with
    ``done=True`` are frozen: their beam, visited set and counters pass
    through unchanged.

    ``ef_active`` (per-query, <= ef) runs a query at a NARROWER efSearch
    inside the fixed (B, ef) arrays: the termination/pruning radius is read
    at position ``ef_active - 1`` and beam entries at positions
    >= ``ef_active`` are voided after the merge, which makes the state
    machine entry-for-entry identical to an engine compiled at
    ``ef = ef_active`` (the scheduler's QoS demotion ladder relies on this
    parity; see tests/test_admission.py).
    """
    B = st.beam_d.shape[0]
    rows_b = jnp.arange(B)[:, None]
    M = neighbors.shape[1]

    # named scopes label the step's device ops by part (op metadata only)
    with jax.named_scope("expand"):
        # -- per-query convergence masking (NMSLIB efSearch semantics)
        cand = jnp.where(st.expanded, INF, st.beam_d)  # (B, ef)
        best = jnp.min(cand, axis=1)
        if ef_active is None:
            worst = st.beam_d[:, -1]
        else:
            wi = jnp.clip(ef_active - 1, 0, ef - 1)[:, None]
            worst = jnp.take_along_axis(st.beam_d, wi, axis=1)[:, 0]
        done = st.done | ~((best <= worst) & jnp.isfinite(best)) | (st.hops >= max_steps)
        active = ~done

        # -- pop the top-T unexpanded candidates of each active query,
        # gated to the termination radius (a candidate farther than the
        # current worst beam member would never be expanded sequentially)
        neg_d, slots = jax.lax.top_k(-cand, T)  # (B, T), best-first
        ok = jnp.isfinite(neg_d) & (-neg_d <= worst[:, None]) & active[:, None]  # (B, T)
        if t_active is not None:
            ok &= jnp.arange(T)[None, :] < jnp.minimum(t_active, T)[:, None]
        nodes = jnp.take_along_axis(st.beam_i, slots, axis=1)
        expanded = st.expanded.at[rows_b, slots].max(ok)

    with jax.named_scope("score"):
        # -- gather + score the (B, T*M) neighbor frontier in one fused call
        safe_nodes = jnp.where(ok, nodes, 0)
        nbrs = neighbors[safe_nodes].reshape(B, T * M)
        ok_r = jnp.repeat(ok, M, axis=1)  # (B, T*M), block-aligned
        safe = jnp.where(nbrs >= 0, nbrs, 0)
        words = jnp.take_along_axis(st.visited, safe // 32, axis=1)
        unvisited = ((words >> (safe % 32).astype(jnp.uint32)) & 1) == 0
        valid = (nbrs >= 0) & unvisited & ok_r
        d = jnp.where(valid, score_rows(safe).astype(jnp.float32), INF)

    with jax.named_scope("merge"):
        # -- compact to the C best candidates (top_k breaks distance ties by
        # position, i.e. exactly like a stable sort of the frontier)
        neg_kept, kidx = jax.lax.top_k(-d, C)
        kept_d = -neg_kept
        kept_i = jnp.take_along_axis(nbrs, kidx, axis=1)
        kept_ok = jnp.take_along_axis(valid, kidx, axis=1)
        # two expanded nodes may share a neighbor (and adjacency rows may
        # repeat ids): find later duplicates on the compacted block (O(C^2))
        later = jnp.arange(C)[:, None] > jnp.arange(C)[None, :]  # [j, s]
        dup = jnp.any(
            (kept_i[:, :, None] == kept_i[:, None, :]) & later[None] & kept_ok[:, None, :],
            axis=2,
        )
        if T > 1:
            # keep the first (best) occurrence in the beam, void the rest,
            # then restore sortedness (top_k ties-by-index keeps the order
            # of the surviving entries) — the merge needs an ascending block
            kept_d = jnp.where(dup, INF, kept_d)
            kept_ok = kept_ok & ~dup
            neg_srt, ridx = jax.lax.top_k(-kept_d, C)
            kept_d = -neg_srt
            kept_i = jnp.take_along_axis(kept_i, ridx, axis=1)
            kept_ok = jnp.take_along_axis(kept_ok, ridx, axis=1)
            mark = kept_ok
        else:
            mark = kept_ok & ~dup

    with jax.named_scope("visited"):
        # mark kept candidates visited: per-row-unique (word, bit) updates,
        # so a scatter-add of fresh bits then a word-wise OR is exact
        safe_kept = jnp.where(mark, kept_i, 0)
        bits = jnp.where(mark, jnp.uint32(1) << (safe_kept % 32).astype(jnp.uint32), 0)
        step_mask = jnp.zeros_like(st.visited).at[rows_b, safe_kept // 32].add(bits)
        visited = st.visited | step_mask

    with jax.named_scope("merge"):
        # -- bitonic merge of the sorted beam with the sorted candidates:
        # lexicographic (distance, position) keys reproduce the stable
        # argsort of [beam | candidates] that the reference engine computes.
        beam_d, beam_i, beam_e = _bitonic_merge(
            (st.beam_d, st.beam_i, expanded), (kept_d, kept_i, ~kept_ok), ef
        )
        if ef_active is not None:
            # void the beam tail beyond each query's effective width: the
            # first ef_active entries of the stable merge are exactly what a
            # merge into an ef_active-wide beam would keep, so voiding the
            # rest keeps the narrow-engine equivalence exact
            off = jnp.arange(ef, dtype=jnp.int32)[None, :] >= ef_active[:, None]
            beam_d = jnp.where(off, INF, beam_d)
            beam_i = jnp.where(off, -1, beam_i)
            beam_e = beam_e | off
    return BatchBeamState(
        beam_d,
        beam_i,
        beam_e,
        visited,
        st.n_evals + jnp.sum(valid, axis=1, dtype=jnp.int32),
        st.hops + active.astype(jnp.int32),
        done,
    )


def frontier_compact_width(T: int, M: int, compact: int) -> int:
    """Per-step merge width: only the C best-scoring candidates can enter
    the beam.  C >= M makes frontier=1 EXACT (a single expansion yields at
    most M candidates); for frontier > 1 it bounds the merge width, and
    dropped candidates stay unvisited so other paths can still reach them."""
    return min(T * M, max(M, compact))


def adaptive_width_update(core: BatchBeamState, t_cur, stall, worst, T: int,
                          patience: int, radius=None):
    """One step of the per-query adaptive-frontier policy (PR 4).

    The beam radius (worst member) is the pruning threshold: while it is
    still shrinking — or the beam has not even filled (greedy-descent
    phase, radius +inf) — expansion ORDER matters and top-T overspends
    evaluations, so the query expands sequentially (width 1); once it
    stalls for ``patience`` steps the evaluation set is fixed and the
    width doubles per step back up to ``T`` to drain the beam in fat
    steps.  Shared verbatim by the slot scheduler's host tick loop and
    the offline ``batched_beam_search`` while_loop, so a closed-batch
    adaptive run is bit-identical to the all-at-once scheduler run.

    ``radius`` overrides the watermark source for callers whose effective
    beam width is narrower than the array width (the scheduler's per-slot
    ``ef_active`` demotion path reads the radius at ``ef_active - 1``).
    """
    if radius is None:
        radius = core.beam_d[:, -1]
    improved = (radius < worst) | ~jnp.isfinite(radius)
    stall = jnp.where(improved, 0, stall + 1)
    t_cur = jnp.where(
        improved,
        1,
        jnp.where(stall >= patience, jnp.minimum(t_cur * 2, T), t_cur),
    )
    return t_cur, stall, radius


def batched_beam_search(
    neighbors,  # (n, M) int32 adjacency, -1 padding
    score_rows,  # (B, R) int32 ids -> (B, R) f32 left-query distances
    entries,  # (E,) i32 shared entry nodes
    B: int,
    ef: int,
    max_steps: int | None = None,
    frontier: int = 1,
    compact: int = 32,
    n_active=None,  # optional () i32: only nodes < n_active are searchable
    alive=None,  # optional (n,) bool: tombstoned nodes are never scored
    adaptive: bool = False,  # per-query adaptive frontier width (PR 4 policy)
    patience: int = 1,  # stalled steps before the adaptive width regrows
):
    """Run B queries to convergence in lock-step.  Returns BatchBeamState.

    ``score_rows`` closes over the query batch and the database constants
    (jnp einsum or the fused Pallas kernel); invalid slots in its output are
    masked here, so it may score placeholder id 0 freely.

    ``n_active`` (may be traced) pre-marks every node >= n_active as visited,
    mirroring ``beam_search_impl``'s construction-time prefix masking: the
    wave build engine searches the frozen prefix graph of already-inserted
    points without ever scoring the not-yet-inserted suffix.

    ``alive`` (may be traced) pre-marks every node with ``alive[v] == False``
    as visited — the online mutable index's tombstone mask.  Dead nodes are
    never scored, never enter any beam, and never appear in results; entry
    nodes failing either mask are seeded at +inf with id -1, so a fully
    tombstoned (or ``n_active=0``) database yields empty (-1 / inf) beams
    rather than out-of-bounds gathers.

    Seed and step are exposed separately (``seed_beams`` / ``beam_step``)
    so ``repro.core.scheduler`` can run the identical state machine with
    slot retire/refill between steps.

    ``adaptive=True`` carries the PR-4 per-query frontier width ``t_cur``
    (plus its stall counter and radius watermark) in the while_loop state:
    closed-batch runs get the same sequential-while-improving /
    fat-drain-once-stalled evaluation policy the slot scheduler applies
    per slot, with ``adaptive=False`` leaving the loop state — and hence
    the existing parity suites — untouched.
    """
    n, M = neighbors.shape
    if frontier < 1:
        raise ValueError(f"frontier must be >= 1, got {frontier}")
    T = min(frontier, ef)
    if max_steps is None:
        max_steps = n
    state = seed_beams(score_rows, entries, B, ef, n, n_active=n_active, alive=alive)
    C = frontier_compact_width(T, M, compact)

    if not adaptive:

        def cond(st: BatchBeamState):
            return jnp.any(~st.done)

        def body(st: BatchBeamState):
            return beam_step(st, neighbors, score_rows, ef, T, C, max_steps)

        return jax.lax.while_loop(cond, body, state)

    # adaptive: every query starts in the width-1 fill/descent phase, exactly
    # like a freshly admitted scheduler slot
    ext0 = (
        state,
        jnp.ones((B,), jnp.int32),  # t_cur
        jnp.zeros((B,), jnp.int32),  # stall
        jnp.full((B,), INF, jnp.float32),  # worst (radius watermark)
    )

    def cond_a(carry):
        return jnp.any(~carry[0].done)

    def body_a(carry):
        st, t_cur, stall, worst = carry
        st = beam_step(st, neighbors, score_rows, ef, T, C, max_steps,
                       t_active=t_cur)
        t_cur, stall, worst = adaptive_width_update(st, t_cur, stall, worst, T,
                                                    patience)
        return st, t_cur, stall, worst

    return jax.lax.while_loop(cond_a, body_a, ext0)[0]


def _bitonic_merge(beam, kept, ef: int):
    """Merge a sorted (B, ef) beam with sorted (B, C) candidates, keep ef.

    Both inputs are ascending by (distance, position); the output is the
    first ef entries of their stable merge (ties resolved beam-first, then
    candidate order) — identical to the reference engine's stable argsort of
    the concatenated arrays.  Runs as a log2(W)-stage compare-exchange
    network of vectorized min/max ops: no scatter, no per-row sort, MXU/VPU
    friendly on TPU and orders of magnitude faster than jnp.argsort rows on
    CPU.
    """
    beam_d, beam_i, beam_e = beam
    kept_d, kept_i, kept_e = kept
    B, C = kept_d.shape
    W = 1 << (ef + C - 1).bit_length()
    pad = W - ef - C

    # positions double as stable tie-breakers: beam 0..ef-1, candidates
    # ef..ef+C-1, padding last
    pos_b = jnp.broadcast_to(jnp.arange(ef, dtype=jnp.int32), (B, ef))
    pos_k = jnp.broadcast_to(jnp.arange(ef, ef + C, dtype=jnp.int32), (B, C))

    def cat(b, k, fill):
        p = jnp.full((B, pad), fill, k.dtype)
        # ascending beam | descending (padded) candidates = bitonic sequence
        return jnp.concatenate([b, jnp.flip(jnp.concatenate([k, p], axis=1), axis=1)], axis=1)

    d = cat(beam_d, kept_d, INF)
    i = cat(beam_i, kept_i, -1)
    e = cat(beam_e, kept_e, True)
    p = cat(pos_b, pos_k, jnp.int32(W))

    s = W // 2
    while s >= 1:
        shape = (B, W // (2 * s), 2, s)
        dr, ir, er, pr = (a.reshape(shape) for a in (d, i, e, p))
        a_d, b_d = dr[:, :, 0], dr[:, :, 1]
        a_p, b_p = pr[:, :, 0], pr[:, :, 1]
        swap = (a_d > b_d) | ((a_d == b_d) & (a_p > b_p))

        def cx(ar, sw=swap):
            lo = jnp.where(sw, ar[:, :, 1], ar[:, :, 0])
            hi = jnp.where(sw, ar[:, :, 0], ar[:, :, 1])
            return jnp.stack([lo, hi], axis=2)

        d, i, e, p = (cx(a).reshape(B, W) for a in (dr, ir, er, pr))
        s //= 2

    return d[:, :ef], i[:, :ef], e[:, :ef]


# ---------------------------------------------------------------------------
# searcher factory (the batched drop-in for make_batched_searcher)
# ---------------------------------------------------------------------------


def make_step_searcher(
    dist,
    neighbors,
    X,
    ef: int,
    k: int,
    entries=None,
    frontier: int = 4,
    compact: int = 32,
    max_steps: int | None = None,
    use_pallas=None,
    adaptive: bool = False,
    patience: int = 1,
):
    """Jitted batched searcher over the step-synchronized engine.

    Returns ``search(Q) -> (dists (B,k), ids (B,k), n_evals (B,), hops (B,))``
    — the same contract as ``make_batched_searcher``.  ``adaptive=True``
    runs the per-query adaptive frontier policy inside the while_loop
    (``frontier`` becomes the maximum width).

    ``search`` is ``functools.partial(jitted, consts, rows, neighbors,
    entries)``: the corpus, its kernel row view (``kernels.ops.kernel_rows``,
    laid out here once; None on the jnp path) and the graph reach the
    executable as ARGUMENTS — closed over, jit would bake them into the
    program as constants (gigabytes at a deployment's corpus size).
    ``search.func.lower(*search.args, Q)`` lowers the step.

    ``use_pallas``: None routes scoring through the fused Pallas
    gather+distance kernel on TPU and the jnp einsum path elsewhere; True
    forces the kernel (interpret mode off-TPU); False forces jnp.  The kernel
    path requires a plain single-matmul ``Distance``; composite distances
    (avg/min symmetrizations) always use the generic pytree path.
    """
    consts = dist.prep_scan(X)
    if entries is None:
        entries = jnp.zeros((1,), jnp.int32)
    # order-preserving dedup: the bit-packed visited seeding requires each
    # entry to contribute its bit exactly once
    e = np.asarray(entries)
    _, first = np.unique(e, return_index=True)
    entries = jnp.asarray(e[np.sort(first)], jnp.int32)

    # use_pallas=False deliberately takes the generic vmap(dist.score) path
    # (not ops' einsum oracle): it is the parity reference — the same floats
    # in the same reduction order as beam_search_impl.
    kernel_ok = isinstance(dist, Distance) and use_pallas is not False
    rows = None
    if kernel_ok:
        from repro.kernels.ops import frontier_gather_scores, kernel_rows

        rows = kernel_rows(dist, consts, use_pallas)

    @jax.jit
    def search(consts, rows, neighbors, entries, Q):
        B = Q.shape[0]
        qc = jax.vmap(dist.prep_query)(Q)

        if kernel_ok:
            def score_rows(ids):
                return frontier_gather_scores(
                    dist, ids, qc["rep"], qc["bias"], consts["rep"], consts["bias"],
                    x_rows=rows,
                )
        else:
            def score_rows(ids):
                rows = jax.tree.map(lambda a: a[ids], consts)
                return jax.vmap(dist.score)(rows, qc)

        st = batched_beam_search(
            neighbors, score_rows, entries, B, ef,
            max_steps=max_steps, frontier=frontier, compact=compact,
            adaptive=adaptive, patience=patience,
        )
        return st.beam_d[:, :k], st.beam_i[:, :k], st.n_evals, st.hops

    return functools.partial(search, consts, rows, neighbors, entries)
