"""Distributed (sharded) retrieval: scatter-gather over DB shards.

The 1000-node serving architecture (DESIGN.md SS2.4): database rows are
sharded over the ("pod", "data") mesh axes; every shard owns a LOCAL
subgraph built over its rows; a query batch is broadcast, each shard runs a
local beam search (or brute-force scan), and the per-shard top-k are merged
with one all_gather + re-sort.  Exactness of the merge: global top-k is a
subset of the union of per-shard top-k, so the merge loses nothing.

Non-divisible corpora: every sharded entry point pads the row count up to a
multiple of the shard count with WRAP-AROUND duplicates (``pad_to_shards``);
a padded row is a copy of a real row, so it is a harmless Steiner node for
graph construction and traversal, and its global id (>= the real row count)
is voided to (inf, -1) before any merge so it can never surface in results.
Every real row lives on exactly one shard, so exactness is preserved.

Straggler mitigation (design for real clusters): the merge is
order-insensitive, so a serving frontend can accept the first s-of-S shard
responses - bounded-staleness top-k; recall impact is benchmarked in
benchmarks/fig12_swgraph.py via shard-dropout simulation here.  Dropped
shards contribute nothing: distances void to inf, ids void to -1, and their
evaluation counters are zeroed out of the psum.

``ShardedSlotScheduler`` is the serving layer over the same primitives: the
continuous-batching slot engine (``repro.core.scheduler``) run per shard
under one ``shard_map``, with a cross-shard candidate exchange (all_gather +
``_merge``) at every ``steps_per_sync`` sync point — the one-shot
``sharded_graph_search`` merge generalized to per-sync.  A slot retires when
EVERY surviving shard's beam converged, and the retire-time merge of the
per-shard beams is exact over the union corpus (same argument as above), so
retired results match searching the union with the replicated scheduler.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .batched_beam import (
    BatchBeamState,
    batched_beam_search,
    beam_step,
    frontier_compact_width,
    seed_beams,
)
from .beam_search import beam_search_impl
from .scheduler import Rung, SchedulerHost, SlotResult

INF = jnp.inf


def _merge(all_d, all_i, k):
    neg, pos = jax.lax.top_k(-all_d, k)
    return -neg, jnp.take_along_axis(all_i, pos, axis=-1)


def _n_shards(mesh, db_axes) -> int:
    n_shards = 1
    for a in db_axes:
        n_shards *= int(mesh.shape[a])
    return n_shards


def pad_to_shards(X, n_shards: int):
    """Pad rows up to a multiple of ``n_shards`` with wrap-around duplicates.

    Returns ``(X_pad, n_real, n_local)``.  Padded rows are copies of the
    FIRST rows (``X[j % n_real]``), so they are valid vectors under every
    registry distance — graph builders may traverse them freely — and their
    global ids (>= ``n_real``) are voided out of every merge.  A no-op
    (same array back) when the row count already divides.
    """
    n = X.shape[0]
    n_local = -(-n // n_shards)
    n_pad = n_local * n_shards
    if n_pad == n:
        return X, n, n_local
    idx = jnp.arange(n_pad, dtype=jnp.int32) % n
    return jnp.asarray(X)[idx], n, n_local


def _globalize_void_topk(dloc, iloc, shard, n_local, n_real, k, dead=None):
    """Local ids -> global ids, void pads/dead shards, re-top-k to width k.

    ``iloc`` holds LOCAL row ids (-1 padding); padded duplicate rows map to
    global ids >= ``n_real`` and are voided to (inf, -1) along with a dead
    shard's whole contribution, then a local top-k sinks the voided entries
    so the cross-shard merge stays exact.  On an ascending beam with nothing
    voided this is exactly the first-k slice (``top_k`` breaks ties by
    position), so the divisible no-drop path is bit-identical to the
    pre-padding behavior.
    """
    gid = jnp.where(iloc >= 0, iloc + shard * n_local, -1)
    void = (gid < 0) | (gid >= n_real)
    if dead is not None:
        void = void | dead
    d = jnp.where(void, INF, dloc)
    gid = jnp.where(void, -1, gid)
    return _merge(d, gid, k)


def sharded_knn_scan(mesh, dist, Q, X_sharded, k: int, db_axes=("data",)):
    """Exact distributed brute-force k-NN.

    X_sharded: (n, m) rows to shard over ``db_axes`` (any n — non-divisible
    row counts are padded internally); Q replicated.  Returns
    (dists (B, k), ids (B, k)) replicated, ids GLOBAL row indices < n.
    """
    n_shards = _n_shards(mesh, db_axes)
    X_pad, n_real, n_local = pad_to_shards(X_sharded, n_shards)

    def local(Q, X_local):
        shard = jax.lax.axis_index(db_axes)
        d = dist.query_matrix(Q, X_local, mode="left")  # (B, n_local)
        # padded duplicate rows are masked BEFORE the local top-k, so they
        # can never displace a real candidate
        gid = shard * n_local + jnp.arange(n_local, dtype=jnp.int32)
        d = jnp.where(gid[None, :] >= n_real, INF, d)
        kk = min(k, n_local)
        neg, pos = jax.lax.top_k(-d, kk)
        dloc = -neg
        iloc = jnp.where(jnp.isfinite(dloc), pos + shard * n_local, -1)
        # gather all shards' candidates and merge (replicated result)
        all_d = jax.lax.all_gather(dloc, db_axes, axis=1, tiled=True)
        all_i = jax.lax.all_gather(iloc, db_axes, axis=1, tiled=True)
        return _merge(all_d, all_i, k)

    db_spec = P(db_axes, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None), db_spec),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )(Q, X_pad)


def sharded_graph_search(mesh, dist, Q, X_sharded, neighbors_sharded, k: int,
                         ef: int, db_axes=("data",), drop_shards: int = 0,
                         engine: str = "batched", frontier: int = 1):
    """Distributed graph search: local beam per shard + global merge.

    ``neighbors_sharded``: (n_pad, M) int32 with LOCAL row ids per shard
    (each shard's subgraph indexes its own rows 0..n_local-1), built over
    the PADDED row layout — pass ``build_local_subgraphs`` output.
    ``drop_shards``: simulate straggler-dropped shards (first s responses);
    a dropped shard's candidates void to (inf, -1) and its distance
    evaluations do not count.

    ``engine="batched"`` (default) runs each shard's query batch through the
    step-synchronized lock-step engine (one while_loop per shard instead of
    a vmapped per-query loop); at ``frontier=1`` it is step-for-step
    identical to the ``engine="reference"`` vmapped ``beam_search_impl``
    path, and ``frontier>1`` trades extra distance evaluations for fewer,
    MXU-fatter lock-steps exactly like single-host serving.
    """
    if engine not in ("batched", "reference"):
        raise ValueError(f"unknown engine {engine!r}; known: batched, reference")
    n_shards = _n_shards(mesh, db_axes)
    X_pad, n_real, n_local = pad_to_shards(X_sharded, n_shards)
    if neighbors_sharded.shape[0] != X_pad.shape[0]:
        raise ValueError(
            f"neighbors rows {neighbors_sharded.shape[0]} != padded corpus "
            f"rows {X_pad.shape[0]}; build them with build_local_subgraphs "
            f"over the same mesh/db_axes")

    def local(Q, X_local, nbrs_local):
        shard = jax.lax.axis_index(db_axes)
        consts = dist.prep_scan(X_local)

        if engine == "batched":
            qc = jax.vmap(dist.prep_query)(Q)

            def score_rows(ids):
                rows = jax.tree.map(lambda a: a[ids], consts)
                return jax.vmap(dist.score)(rows, qc)

            st = batched_beam_search(
                nbrs_local, score_rows, jnp.zeros((1,), jnp.int32),
                Q.shape[0], ef, frontier=frontier,
            )
            dloc, iloc, evals = st.beam_d, st.beam_i, st.n_evals
        else:

            def single(q):
                qc = dist.prep_query(q)
                st = beam_search_impl(nbrs_local, consts, qc, dist.score,
                                      jnp.int32(0), ef)
                return st.beam_d, st.beam_i, st.n_evals

            dloc, iloc, evals = jax.vmap(single)(Q)
        dead = None
        if drop_shards:
            dead = shard >= (n_shards - drop_shards)
            evals = jnp.where(dead, 0, evals)
        # full ef-wide beams go through the void + re-top-k, so a voided
        # (padded / dead) candidate backfills from positions k..ef
        dloc, iloc = _globalize_void_topk(dloc, iloc, shard, n_local, n_real,
                                          min(k, ef), dead=dead)
        all_d = jax.lax.all_gather(dloc, db_axes, axis=1, tiled=True)
        all_i = jax.lax.all_gather(iloc, db_axes, axis=1, tiled=True)
        d, i = _merge(all_d, all_i, k)
        return d, i, jax.lax.psum(evals, db_axes)

    db_spec = P(db_axes, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None), db_spec, db_spec),
        out_specs=(P(None, None), P(None, None), P(None)),
        check_vma=False,
    )(Q, X_pad, neighbors_sharded)


def build_local_subgraphs(mesh, dist, X_sharded, db_axes=("data",), NN: int = 15,
                          nnd_iters: int = 8, key=None, builder: str = "nndescent",
                          wave: int = 32):
    """Build per-shard subgraphs (local row ids) under shard_map.

    Returns (n_pad, M) adjacency over the PADDED row layout (see
    ``pad_to_shards``) — pass it straight to ``sharded_graph_search`` /
    ``ShardedSlotScheduler``.  Each shard folds its ``axis_index`` into the
    PRNG key, so stochastic builders (NN-descent) are decorrelated across
    shards instead of replaying one shard's random choices everywhere.

    ``builder="wave"`` routes through the wave-parallel insertion engine
    (``repro.core.build_engine``); ``build_sharded`` there additionally
    stitches the shards into one global-id graph via cross-shard neighbor
    exchange.
    """
    from .build_engine import build_swgraph_wave
    from .nndescent import build_nndescent

    key = key if key is not None else jax.random.PRNGKey(0)

    if builder not in ("wave", "nndescent"):
        raise ValueError(f"unknown builder {builder!r}; known: wave, nndescent")

    n_shards = _n_shards(mesh, db_axes)
    X_pad, _, _ = pad_to_shards(X_sharded, n_shards)

    def local(X_local, key):
        key = jax.random.fold_in(key, jax.lax.axis_index(db_axes))
        if builder == "wave":
            nbrs, _ = build_swgraph_wave(dist, X_local, NN=NN, wave=wave)
        else:
            nbrs, _ = build_nndescent(dist, X_local, key, K=NN, iters=nnd_iters)
        return nbrs

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(db_axes, None), P(None)),
        out_specs=P(db_axes, None),
        check_vma=False,
    )(X_pad, key)


# ---------------------------------------------------------------------------
# sharded serving: the slot scheduler under shard_map
# ---------------------------------------------------------------------------


class ShardSlotState(NamedTuple):
    """Device state of the sharded scheduler (all arrays fixed-shape).

    ``core`` leaves carry a leading shard axis of size D (the shard count),
    partitioned over ``db_axes`` so each shard owns its own slice of every
    slot's beam/visited state; the remaining leaves are replicated.
    """

    core: BatchBeamState  # per-shard per-slot beam state, leading axes (D, S)
    qc: Any  # per-slot prepped query constants, leading axis S (replicated)
    glob_d: jax.Array  # (S, k) f32 merged global top-k distances (replicated)
    glob_i: jax.Array  # (S, k) i32 merged global top-k ids (replicated)


class ShardedSlotScheduler(SchedulerHost):
    """Slot-recycling continuous batching over a SHARDED corpus.

    The single-device ``SlotScheduler``'s serving model — S fixed slots,
    admit from a DRR queue, ``steps_per_sync`` lock-steps per tick, retire
    on convergence — run scatter-gather: every shard advances its OWN beam
    for each slot over its local subgraph, and each tick ends in a sync
    point that all_gathers the shards' voided top-k candidates and merges
    them into the slot's replicated global top-k (the one-shot
    ``sharded_graph_search`` merge, per sync).  A slot retires when every
    surviving shard's beam converged; because each shard's final beam holds
    its best-ef candidates and the merge keeps the global best-k of their
    union, the retired id set equals a one-shot scatter-gather search of
    the union corpus — and matches the replicated scheduler up to graph
    approximation (each shard searches its LOCAL subgraph).

    All device state is fixed-shape in (D, S, ef, capacity): steady-state
    serving never recompiles, no matter how requests arrive.  Tenant DRR
    fairness and the stream drivers come from ``SchedulerHost``; the QoS
    demotion ladder is not wired up here (single full-fidelity rung).

    ``drop_shards`` freezes the LAST s shards at admission (their slots
    are born done, contribute no candidates and no evaluations) — the
    bounded-staleness straggler model of ``sharded_graph_search``, applied
    to serving.
    """

    def __init__(self, mesh, dist, X, *, neighbors=None, slots: int = 32,
                 ef: int = 96, k: int = 10, frontier: int = 1,
                 compact: int = 32, steps_per_sync: int = 1,
                 max_steps: Optional[int] = None, db_axes=("data",),
                 drop_shards: int = 0, NN: int = 15, nnd_iters: int = 8,
                 key=None, builder: str = "nndescent",
                 slo_ms: Optional[float] = None,
                 tenant_weights: Optional[dict] = None,
                 background_fn=None):
        if ef < k:
            raise ValueError(f"ef {ef} < k {k}")
        if frontier < 1:
            raise ValueError(f"frontier must be >= 1, got {frontier}")
        self.mesh = mesh
        self.db_axes = tuple(db_axes)
        self.n_shards = _n_shards(mesh, self.db_axes)
        if not 0 <= drop_shards < self.n_shards:
            raise ValueError(
                f"drop_shards {drop_shards} outside [0, {self.n_shards})")
        self.drop_shards = int(drop_shards)
        X = jnp.asarray(X)
        X_pad, self.n_real, self.n_local = pad_to_shards(X, self.n_shards)
        if neighbors is None:
            neighbors = build_local_subgraphs(
                mesh, dist, X, db_axes=self.db_axes, NN=NN,
                nnd_iters=nnd_iters, key=key, builder=builder)
        if neighbors.shape[0] != X_pad.shape[0]:
            raise ValueError(
                f"neighbors rows {neighbors.shape[0]} != padded corpus rows "
                f"{X_pad.shape[0]}; build them with build_local_subgraphs "
                f"over the same mesh/db_axes")
        self.dist = dist
        self.dim = int(X.shape[1])
        self.S = int(slots)
        self.ef = int(ef)
        self.k = int(k)
        M = int(neighbors.shape[1])
        self.T = int(min(frontier, ef))
        self.C = frontier_compact_width(self.T, M, compact)
        self.max_steps = int(self.n_local if max_steps is None else max_steps)
        self.steps_per_sync = int(max(1, steps_per_sync))
        # one-time constant placement: every jitted call sees the SAME array
        # object, so this cannot split the dispatch cache (cf. init(), where
        # per-call host-built state did exactly that)
        nbrs_dev = jax.device_put(  # jaxlint: disable=JL001 (placed once)
            jnp.asarray(neighbors, jnp.int32),
            NamedSharding(mesh, P(self.db_axes, None)))
        self._neighbors = nbrs_dev
        # per-shard scan constants, computed ONCE (leading row axis sharded)
        consts_shape = jax.eval_shape(
            dist.prep_scan,
            jax.ShapeDtypeStruct((self.n_local, self.dim), X_pad.dtype))
        self._consts = jax.shard_map(
            dist.prep_scan, mesh=mesh,
            in_specs=(P(self.db_axes, None),),
            out_specs=jax.tree.map(
                lambda s: P(self.db_axes, *([None] * (len(s.shape) - 1))),
                consts_shape),
            check_vma=False,
        )(X_pad)
        self._dtype = jax.tree.leaves(self._consts)[0].dtype
        # SchedulerHost contract: single full-fidelity rung, no QoS ladder
        self.rungs = [Rung(ef=self.ef, name="full")]
        self.slo_s = None if slo_ms is None else float(slo_ms) / 1e3
        self._background = background_fn
        self._init_host(tenant_weights)
        self.reset()  # host-built template state for _build_jits' spec trees
        self._build_jits()
        self.reset()  # re-commit through _init: canonical jit-output shardings

    # ------------------------------------------------------------- jit setup

    def _score_fn(self, consts, qc):
        dist = self.dist

        def score_rows(ids):
            rows = jax.tree.map(lambda a: a[ids], consts)
            return jax.vmap(dist.score)(rows, qc)

        return score_rows

    def _specs(self, template, sharded: bool):
        ax = self.db_axes

        def leaf(a):
            nones = [None] * (a.ndim - 1)
            return P(ax, *nones) if sharded else P(None, *nones)

        return jax.tree.map(leaf, template)

    def _build_jits(self):
        S, ef, k = self.S, self.ef, self.k
        T, C, max_steps = self.T, self.C, self.max_steps
        dist, n_local, n_real = self.dist, self.n_local, self.n_real
        D, drop, db_axes = self.n_shards, self.drop_shards, self.db_axes
        entries = jnp.zeros((1,), jnp.int32)
        mesh = self.mesh

        core_spec = self._specs(self.state.core, sharded=True)
        qc_spec = self._specs(self.state.qc, sharded=False)
        repl2 = P(None, None)
        repl1 = P(None)
        consts_spec = self._specs(self._consts, sharded=True)
        nbrs_spec = P(db_axes, None)

        @jax.named_scope("admit")
        def admit(core_g, qc, glob_d, glob_i, Q_new, write, consts):
            # core leaves arrive as (1, S, ...): each shard's slice of the
            # leading shard axis — squeeze for the slot-level state machine
            core = jax.tree.map(lambda a: a[0], core_g)
            shard = jax.lax.axis_index(db_axes)
            qc_new = jax.vmap(dist.prep_query)(Q_new)
            score_rows = self._score_fn(consts, qc_new)
            fresh = seed_beams(score_rows, entries, S, ef, n_local)
            if drop:
                # dead shards' slots are born done: beam_step freezes them,
                # so a dropped shard does no work and contributes nothing
                dead = shard >= (D - drop)
                fresh = fresh._replace(done=fresh.done | dead)

            def sel(a, b):
                w = write.reshape((S,) + (1,) * (a.ndim - 1))
                return jnp.where(w, a, b)

            core = jax.tree.map(sel, fresh, core)
            qc = jax.tree.map(sel, qc_new, qc)
            glob_d = jnp.where(write[:, None], INF, glob_d)
            glob_i = jnp.where(write[:, None], -1, glob_i)
            return (jax.tree.map(lambda a: a[None], core), qc, glob_d, glob_i)

        @jax.named_scope("step")
        def step(core_g, qc, consts, neighbors):
            core = jax.tree.map(lambda a: a[0], core_g)
            shard = jax.lax.axis_index(db_axes)
            score_rows = self._score_fn(consts, qc)
            for _ in range(self.steps_per_sync):
                core = beam_step(core, neighbors, score_rows, ef, T, C,
                                 max_steps)
            # sync point: cross-shard candidate exchange.  Each shard voids
            # its padded/dead candidates out of the full ef-wide beam,
            # re-top-ks locally, and the all_gather + merge rebuilds every
            # slot's replicated global top-k from the current beams — the
            # one-shot sharded_graph_search merge, run per sync.
            dead = None
            evals = core.n_evals
            if drop:
                dead = shard >= (D - drop)
                evals = jnp.where(dead, 0, evals)
            dloc, iloc = _globalize_void_topk(
                core.beam_d, core.beam_i, shard, n_local, n_real,
                min(k, ef), dead=dead)
            all_d = jax.lax.all_gather(dloc, db_axes, axis=1, tiled=True)
            all_i = jax.lax.all_gather(iloc, db_axes, axis=1, tiled=True)
            glob_d, glob_i = _merge(all_d, all_i, k)
            # a slot is globally done when every surviving shard's beam
            # converged (dead shards were born done)
            live = jnp.logical_not(core.done).astype(jnp.int32)
            done_g = jax.lax.psum(live, db_axes) == 0
            evals_g = jax.lax.psum(evals, db_axes)
            hops_g = jax.lax.pmax(core.hops, db_axes)
            return (jax.tree.map(lambda a: a[None], core), glob_d, glob_i,
                    done_g, evals_g, hops_g)

        nw = -(-n_local // 32)

        def init(q0):
            # fresh idle state, built ON device through the same
            # out_specs as admit/step: every steady-state input is then a
            # jit output with identical sharding normalization, so each
            # jitted path keeps exactly ONE executable (a host-built
            # reset state hashes differently at the dispatch cache even
            # when its placement is the same)
            core = BatchBeamState(
                beam_d=jnp.full((1, S, ef), INF, jnp.float32),
                beam_i=jnp.full((1, S, ef), -1, jnp.int32),
                expanded=jnp.ones((1, S, ef), bool),
                visited=jnp.zeros((1, S, nw), jnp.uint32),
                n_evals=jnp.zeros((1, S), jnp.int32),
                hops=jnp.zeros((1, S), jnp.int32),
                done=jnp.ones((1, S), bool),
            )
            qc = jax.vmap(dist.prep_query)(q0)
            glob_d = jnp.full((S, k), INF, jnp.float32)
            glob_i = jnp.full((S, k), -1, jnp.int32)
            return core, qc, glob_d, glob_i

        self._init = jax.jit(jax.shard_map(
            init, mesh=mesh,
            in_specs=(repl2,),
            out_specs=(core_spec, qc_spec, repl2, repl2),
            check_vma=False,
        ))
        self._admit = jax.jit(jax.shard_map(
            admit, mesh=mesh,
            in_specs=(core_spec, qc_spec, repl2, repl2, repl2, repl1,
                      consts_spec),
            out_specs=(core_spec, qc_spec, repl2, repl2),
            check_vma=False,
        ))
        self._step = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(core_spec, qc_spec, consts_spec, nbrs_spec),
            out_specs=(core_spec, repl2, repl2, repl1, repl1, repl1),
            check_vma=False,
        ))

    # ----------------------------------------------------------- state mgmt

    def reset(self):
        """Clear all slots, the pending queue, and per-request bookkeeping."""
        D, S, ef, k = self.n_shards, self.S, self.ef, self.k
        # uniform histogram placeholder: valid under every registry distance,
        # so idle slots never score NaNs (their rows are masked anyway)
        q0 = jnp.full((S, self.dim), 1.0 / self.dim, self._dtype)
        if hasattr(self, "_init"):
            self.state = ShardSlotState(*self._init(q0))
        else:
            # pre-jit path (first reset during __init__): a plain host-built
            # state, used only as the pytree/shape template for _build_jits.
            # __init__ resets again afterwards so serving always starts from
            # _init's canonically sharded output.
            nw = -(-self.n_local // 32)
            core = BatchBeamState(
                beam_d=jnp.full((D, S, ef), INF, jnp.float32),
                beam_i=jnp.full((D, S, ef), -1, jnp.int32),
                expanded=jnp.ones((D, S, ef), bool),
                visited=jnp.zeros((D, S, nw), jnp.uint32),
                n_evals=jnp.zeros((D, S), jnp.int32),
                hops=jnp.zeros((D, S), jnp.int32),
                done=jnp.ones((D, S), bool),
            )
            self.state = ShardSlotState(
                core=core,
                qc=jax.vmap(self.dist.prep_query)(q0),
                glob_d=jnp.full((S, k), INF, jnp.float32),
                glob_i=jnp.full((S, k), -1, jnp.int32),
            )
        self._clear_host()
        self._slot_rid = np.full((S,), -1, np.int64)
        # rid -> (arrival, admit time, tenant, priority, admit tick)
        self._meta: dict[int, tuple] = {}

    # -------------------------------------------------------------- serving

    def _tick(self, now: float, t) -> list[SlotResult]:
        """Admit pending requests into free slots (DRR across tenants), run
        ``steps_per_sync`` lock-steps on every shard, exchange + merge at
        the sync point, retire every globally converged slot.  ``t`` is the
        tick's open log row (the same spans as ``SlotScheduler``'s tick;
        admission writes over a retired slot, so there is no release)."""
        st = self.state
        counters = self.log.counters
        free = np.flatnonzero(self._slot_rid < 0)
        if len(free) and self._n_pending:
            with t.span("select"):
                Q_new = np.full((self.S, self.dim), 1.0 / self.dim,
                                np.float32)
                write = np.zeros((self.S,), bool)
                reqs = self._drr_select(len(free))
                for s, req in zip(free, reqs):
                    Q_new[s] = req.q
                    write[s] = True
                    self._slot_rid[s] = req.rid
                    self._meta[req.rid] = (req.t_arrival, now, req.tenant,
                                           req.priority, t.index)
                counters["admitted"] += len(reqs)
            if reqs:
                with t.span("put"):
                    Q_dev = jnp.asarray(Q_new, self._dtype)
                    write_dev = jnp.asarray(write)
                with t.span("admit"):
                    core, qc, glob_d, glob_i = self._admit(
                        st.core, st.qc, st.glob_d, st.glob_i, Q_dev,
                        write_dev, self._consts,
                    )
                st = ShardSlotState(core, qc, glob_d, glob_i)
        if (self._background is not None and not self._n_pending
                and (self._slot_rid < 0).any()):
            with t.span("background"):
                self._background()
        occupied = int((self._slot_rid >= 0).sum())
        t.set("occupied", occupied)
        if not occupied:
            self.state = st
            return []

        with t.span("step"):
            core, glob_d, glob_i, done_g, evals_g, hops_g = self._step(
                st.core, st.qc, self._consts, self._neighbors)
        self.state = ShardSlotState(core, st.qc, glob_d, glob_i)

        with t.span("sync"):
            done = np.asarray(done_g)  # syncs the step
        finished = done & (self._slot_rid >= 0)
        if not finished.any():
            return []
        # fixed-shape device reads (full S rows, host-side row select), so
        # retiring any number of slots reuses the same executables
        with t.span("retire_read"):
            idx = np.flatnonzero(finished)
            d = np.asarray(glob_d)[idx]
            ids = np.asarray(glob_i).astype(np.int64)[idx]
            evals = np.asarray(evals_g)[idx]
            hops = np.asarray(hops_g)[idx]
        out = []
        with t.span("retire"):
            for j, s in enumerate(idx):
                rid = int(self._slot_rid[s])
                t_arr, t_adm, tenant, priority, t_idx = self._meta.pop(
                    rid, (0.0, 0.0, 0, 0, t.index))
                counters["held_ticks"] += t.index - t_idx + 1
                out.append(SlotResult(rid=rid, dists=d[j], ids=ids[j],
                                      n_evals=int(evals[j]),
                                      hops=int(hops[j]), t_arrival=t_arr,
                                      t_admit=t_adm, tenant=tenant,
                                      priority=priority))
                self._slot_rid[s] = -1
            counters["retired"] += len(idx)
        return out
