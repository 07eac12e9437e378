"""High-level ANN index API: the paper's SW-graph scenarios as one object.

Since ISSUE 5 the scenario currency is a ``RetrievalSpec``
(``repro.core.spec``): one frozen, JSON-round-trippable object carrying the
base distance (registry name), the graph-construction distance policy, the
search-guidance policy + rerank ``k_c``, the builder/engine knobs and the
scheduler knobs.  ``build``/``searcher``/``scheduler`` all consume specs:

    spec = RetrievalSpec(distance="kl", build_policy=Blend(0.25),
                         builder="swgraph", ef_search=96)
    idx = ANNIndex.build(X, spec=spec)
    search = idx.searcher(spec=spec)       # or idx.searcher() — the index
    sched = idx.scheduler(spec=spec)       # remembers its spec

The historical kwargs (``index_sym``/``query_sym`` strings + loose knobs)
still work through a thin shim that constructs the equivalent spec —
bit-identical results, with a ``DeprecationWarning`` on the two string
knobs.  ``search_policy != none`` is the paper's full-symmetrization
scenario: the beam runs under the bound search policy and ``k_c``
candidates are re-ranked under the original distance — by the batch
searcher AND by the slot scheduler at retire time.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry
from .batched_beam import make_step_searcher, select_entries
from .beam_search import make_batched_searcher
from .build_engine import (graph_degrees, wave_count, wave_evals, wave_insert, wave_knobs,
                           wave_prep)
from .filter_refine import rerank
from .nndescent import build_nndescent
from .online import OnlineIndex
from .spec import RetrievalSpec
from .swgraph import build_swgraph


def _legacy_spec(index_sym, query_sym, builder, build_engine, wave,
                 build_frontier, NN, ef_construction, M_max, nnd_iters,
                 n_entries, capacity) -> RetrievalSpec:
    """Deprecation shim: the old loose kwargs, folded into one spec.  Only
    explicitly-passed kwargs are forwarded, so the spec's own field defaults
    apply exactly once (no duplicated default table to drift)."""
    if index_sym is not None or query_sym is not None:
        warnings.warn(
            "index_sym/query_sym string kwargs are deprecated; pass a "
            "RetrievalSpec (spec=...) with build_policy/search_policy instead",
            DeprecationWarning,
            stacklevel=3,
        )
    passed = {
        "build_policy": index_sym,
        "search_policy": query_sym,
        "builder": builder,
        "build_engine": build_engine,
        "wave": wave,
        "build_frontier": build_frontier,
        "NN": NN,
        "ef_construction": ef_construction,
        "M_max": M_max,
        "nnd_iters": nnd_iters,
        "n_entries": n_entries,
        "capacity": capacity,
    }
    return RetrievalSpec(**{k: v for k, v in passed.items() if v is not None})


@dataclasses.dataclass
class ANNIndex:
    """A built neighborhood-graph index over a database X.

    With a ``capacity`` (set at build time or on the first mutation) the
    index becomes MUTABLE: ``insert``/``delete``/``compact`` route through
    ``repro.core.online.OnlineIndex`` and the default batched searcher
    serves the live (tombstone-masked) graph.
    """

    X: jax.Array
    neighbors: jax.Array  # (n, M) int32
    dist: object  # original distance (PairDistance)
    search_dist: object  # distance guiding the beam (may equal dist)
    query_sym: str
    entries: Optional[jax.Array] = None  # (E,) i32 beam entry points
    build_info: dict = dataclasses.field(default_factory=dict)
    build_dist: object = None  # index-time distance (defaults to dist)
    capacity: Optional[int] = None  # mutable-index slot budget
    online: Optional[OnlineIndex] = None  # created lazily on first mutation
    spec: RetrievalSpec = dataclasses.field(default_factory=RetrievalSpec)

    @property
    def entry(self) -> int:
        """Primary entry node (the medoid when entries were selected)."""
        return 0 if self.entries is None else int(self.entries[0])

    # ------------------------------------------------------------------ build

    @classmethod
    def build(
        cls,
        X,
        dist=None,
        *,
        spec: Optional[RetrievalSpec] = None,
        index_sym: Optional[str] = None,
        query_sym: Optional[str] = None,
        builder: Optional[str] = None,
        build_engine: Optional[str] = None,
        wave: Optional[int] = None,
        build_frontier: Optional[int] = None,
        NN: Optional[int] = None,
        ef_construction: Optional[int] = None,
        M_max: Optional[int] = None,
        nnd_iters: Optional[int] = None,
        n_entries: Optional[int] = None,
        capacity: Optional[int] = None,
        key=None,
        natural: Optional[Callable] = None,
    ) -> "ANNIndex":
        """Build an index from a ``RetrievalSpec`` (the preferred path) or
        from the legacy kwargs (folded into an equivalent spec by the
        deprecation shim — bit-identical results).

        Args:
            X: (n, m) database array (rows are points in the base
                distance's native representation, e.g. histograms on the
                simplex for KL).
            dist: optional explicit base distance (e.g. a
                ``ViewedDistance`` whose role-dependent views the registry
                cannot name); otherwise resolved from ``spec.distance``.
            spec: the ``RetrievalSpec`` scenario to build.  Data-calibrated
                policies (``RankBlend`` with ``tau=None``) are resolved
                against ``X`` here; the concrete parameters land in
                ``build_info["index_sym_resolved"]`` /
                ``["query_sym_resolved"]``.
            key: PRNG key for the nndescent builder / entry sampling.
            natural: optional callable returning the distance-specific
                natural symmetrization (Eq. 4 of the paper).

        Returns:
            A built ``ANNIndex`` whose ``neighbors`` is the (n, M_max)
            int32 adjacency and whose ``build_info`` records the resolved
            scenario (spec dict + fingerprint, mean degree, engine).

        ``spec.capacity``: total slot budget for online mutation (inserted
        points consume slots).  Setting it makes the index mutable
        immediately; otherwise the first ``insert``/``delete`` call
        converts it lazily with a default budget of ``2 * n``.
        """
        if spec is None:
            spec = _legacy_spec(index_sym, query_sym, builder, build_engine,
                                wave, build_frontier, NN, ef_construction,
                                M_max, nnd_iters, n_entries, capacity)
            if dist is not None and getattr(dist, "name", None):
                # record the REAL distance so build_info / bench artifacts /
                # fingerprints self-describe the scenario actually run (for
                # registry distances the name round-trips through
                # get_distance; view-wrapped ones record their true name)
                spec = spec.replace(distance=dist.name)
        elif any(v is not None for v in (index_sym, query_sym, builder,
                                         build_engine, wave, build_frontier,
                                         NN, ef_construction, M_max, nnd_iters,
                                         n_entries, capacity)):
            raise ValueError(
                "pass EITHER spec=... or the legacy kwargs, not both "
                "(use spec.replace(...) to tweak a spec)"
            )
        if dist is None:
            dist = spec.base_distance()
        wave_engine = (spec.builder, spec.build_engine) == ("swgraph", "wave")
        record = telemetry.BuildRecord()
        with record:
            with record.part("prep"):
                # resolve data-calibrated policy parameters (RankBlend
                # tau=None) against the database ONCE; the spec itself stays
                # as written so later spec-equality checks
                # (searcher/scheduler) keep working
                build_policy = spec.build_policy.resolve(dist, X)
                search_policy = spec.search_policy.resolve(dist, X)
                build_dist = build_policy.bind(dist, natural=natural)
                search_dist = (search_policy.bind(dist, natural=natural)
                               if spec.needs_rerank else dist)
                if wave_engine:
                    knobs = wave_knobs(
                        X.shape[0], spec.NN, spec.ef_construction, spec.M_max,
                        spec.wave, frontier=spec.build_frontier)
                    consts, qc_all = jax.block_until_ready(wave_prep(build_dist, X))

            with record.part("waves"):
                if wave_engine:
                    from repro.kernels.ops import kernel_scores

                    neighbors, steps = wave_insert(build_dist, consts, qc_all, **knobs)
                    # the rep width each scored candidate brings (the sum of
                    # a composite's branches)
                    record.m_prime = sum(a.shape[1] for a in jax.tree.leaves(consts)
                                         if a.ndim == 2)
                    del consts, qc_all
                    record.waves = wave_count(X.shape[0], knobs["W"])
                    record.kernel = kernel_scores(build_dist, record.m_prime)
                    record.build_evals = wave_evals(int(steps), X.shape[0], **knobs)
                elif spec.builder == "swgraph":
                    neighbors, degrees = build_swgraph(
                        build_dist, X, NN=spec.NN,
                        ef_construction=spec.ef_construction, M_max=spec.M_max,
                    )
                else:
                    key = key if key is not None else jax.random.PRNGKey(0)
                    neighbors, degrees = build_nndescent(
                        build_dist, X, key, K=spec.NN, iters=spec.nnd_iters,
                        M_out=spec.M_max,
                    )
                jax.block_until_ready(neighbors)

            with record.part("finish"):
                if wave_engine:
                    degrees = graph_degrees(neighbors)
                entries = select_entries(
                    search_dist, X, n_entries=spec.n_entries,
                    key=jax.random.fold_in(key, 0xE) if key is not None else None,
                )

                info = dict(
                    builder=spec.builder,
                    build_engine=spec.build_engine if spec.builder == "swgraph" else "nndescent",
                    wave=spec.wave if wave_engine else None,
                    index_sym=str(spec.build_policy),
                    query_sym=str(spec.search_policy),
                    # concrete policies actually bound (differ from the
                    # spec's only when a data-calibrated parameter was
                    # resolved at build time)
                    index_sym_resolved=str(build_policy),
                    query_sym_resolved=str(search_policy),
                    NN=spec.NN,
                    ef_construction=spec.ef_construction,
                    mean_degree=float(jnp.mean(degrees.astype(jnp.float32))),
                    spec=spec.to_dict(),
                    spec_fingerprint=spec.fingerprint(),
                )
                idx = cls(
                    X=X,
                    neighbors=neighbors,
                    dist=dist,
                    search_dist=search_dist,
                    query_sym=str(spec.search_policy),
                    entries=entries,
                    build_info=info,
                    build_dist=build_dist,
                    capacity=spec.capacity,
                    spec=spec,
                )
                if spec.capacity is not None:
                    idx.ensure_online()
                jax.block_until_ready(idx.entries)
        return idx

    # ----------------------------------------------------------------- online

    def ensure_online(self, capacity: Optional[int] = None) -> OnlineIndex:
        """Convert to a mutable index (idempotent).  See ``OnlineIndex``."""
        if self.online is None:
            cap = capacity or self.capacity or 2 * int(self.X.shape[0])
            self.online = OnlineIndex.from_graph(
                self.X, self.neighbors, self.build_dist or self.dist,
                self.search_dist, capacity=cap, entries=self.entries,
                NN=self.build_info.get("NN") or self.neighbors.shape[1] // 2,
                ef_construction=self.build_info.get("ef_construction") or 100,
                wave=self.build_info.get("wave") or 32,
                spec=self.spec,
            )
            self.capacity = self.online.capacity
        return self.online

    def insert(self, X_new):
        """Insert points into the live graph; returns their slot ids
        (arena semantics: a deleted id's slot may be recycled — see
        ``OnlineIndex.insert``)."""
        ids = self.ensure_online().insert(X_new)
        self._sync_from_online()
        return ids

    def delete(self, ids) -> int:
        """Tombstone points by id; returns how many were newly deleted."""
        n = self.ensure_online().delete(ids)
        # tombstoning touches only the alive mask — no row data changed, so
        # skip the O(n) X/neighbors mirroring and resync just the entries
        self.entries = self.online.entries
        return n

    def compact(self) -> dict:
        """Re-link the graph around tombstones (no full rebuild)."""
        stats = self.ensure_online().compact()
        self._sync_from_online()
        return stats

    def _sync_from_online(self):
        """Mirror the mutable state so X/neighbors stay inspectable (NOTE:
        the mirrored arrays include tombstoned rows — serving always goes
        through the alive-masked online searcher)."""
        o = self.online
        self.X = o.X[: o.n_total]
        self.neighbors = o.adj[: o.n_total]
        self.entries = o.entries

    # ----------------------------------------------------------------- search

    def _make_searcher(self, dist, ef: int, k: int, engine: str, frontier: int,
                       adaptive: bool = False, patience: int = 1):
        if self.online is not None:
            if engine != "batched":
                raise ValueError(
                    f"engine {engine!r} does not support the online mutable "
                    f"index; use engine='batched'"
                )
            return self.online.searcher(k, ef, frontier=frontier,
                                        adaptive=adaptive, patience=patience)
        if engine == "batched":
            return make_step_searcher(dist, self.neighbors, self.X, ef, k,
                                      entries=self.entries, frontier=frontier,
                                      adaptive=adaptive, patience=patience)
        if engine == "reference":
            if adaptive:
                raise ValueError("adaptive frontier requires engine='batched'")
            return make_batched_searcher(dist, self.neighbors, self.X, ef, k,
                                         entry=self.entry)
        raise ValueError(f"unknown engine {engine!r}; known: batched, reference")

    def _check_search_policy(self, spec: Optional[RetrievalSpec]):
        """The search distance is BOUND at build time; a spec passed later
        can tune knobs but cannot silently switch the scenario — a
        mismatched search_policy would serve the wrong distance without
        any error, so fail loud and point at a rebuild instead."""
        if spec is not None and str(spec.search_policy) != self.query_sym:
            raise ValueError(
                f"spec.search_policy {str(spec.search_policy)!r} does not "
                f"match this index's bound search policy {self.query_sym!r}; "
                f"rebuild with ANNIndex.build(X, spec=spec) to change the "
                f"search scenario"
            )

    def searcher(self, k: Optional[int] = None, ef_search: Optional[int] = None,
                 k_c: Optional[int] = None, engine: Optional[str] = None,
                 frontier: Optional[int] = None, *,
                 adaptive: Optional[bool] = None,
                 patience: Optional[int] = None,
                 spec: Optional[RetrievalSpec] = None):
        """Return a jitted ``search(Q) -> (dists, ids, n_evals, hops)``.

        Knobs resolve spec-first: explicit arguments override ``spec``
        (default: the spec the index was built with).  ``engine="batched"``
        runs the step-synchronized batched beam engine with multi-entry
        seeding and ``frontier`` candidates expanded per lock-step;
        ``adaptive=True`` gives every query the per-query adaptive frontier
        width inside the while_loop (the PR-4 policy, offline);
        ``engine="reference"`` keeps the vmapped per-query while_loop that
        parity tests compare against.

        Full-symmetrization scenario (``search_policy != none``): the beam
        runs under the bound search policy with ef >= k_c, producing k_c
        candidates re-ranked under the original distance (counted into
        n_evals).
        """
        self._check_search_policy(spec)
        spec = spec if spec is not None else self.spec
        k = spec.k if k is None else k
        ef_search = spec.ef_search if ef_search is None else ef_search
        k_c = spec.k_c if k_c is None else k_c
        engine = spec.engine if engine is None else engine
        frontier = spec.frontier if frontier is None else frontier
        adaptive = spec.adaptive if adaptive is None else adaptive
        patience = spec.patience if patience is None else patience

        if self.query_sym == "none":
            ef = max(ef_search, k)
            return self._make_searcher(self.dist, ef, k, engine, frontier,
                                       adaptive, patience)

        k_c = k_c or max(ef_search, k)
        ef = max(ef_search, k_c)
        inner = self._make_searcher(self.search_dist, ef, k_c, engine, frontier,
                                    adaptive, patience)

        if self.online is not None:
            # not jitted as a whole: the inner searcher must re-read the
            # live graph state on every call (rerank is jitted separately)
            online = self.online

            def search(Q):
                _, cand, n_evals, hops = inner(Q)
                d, ids = rerank(self.dist, Q, online.X, cand, k)
                return d, ids, n_evals + jnp.int32(k_c), hops

            return search

        @jax.jit
        def search(Q):
            _, cand, n_evals, hops = inner(Q)
            d, ids = rerank(self.dist, Q, self.X, cand, k)
            return d, ids, n_evals + jnp.int32(k_c), hops

        return search

    def search(self, Q, k: Optional[int] = None, ef_search: Optional[int] = None,
               k_c: Optional[int] = None, engine: Optional[str] = None,
               frontier: Optional[int] = None):
        """One-shot ``searcher(...)(Q)`` — identical knob resolution (explicit
        args override the index's spec), so the two entry points can never
        silently serve different scenarios."""
        return self.searcher(k, ef_search, k_c, engine=engine, frontier=frontier)(Q)

    # -------------------------------------------------------------- serving

    def scheduler(self, k: Optional[int] = None, ef_search: Optional[int] = None,
                  *, slots: Optional[int] = None, frontier: Optional[int] = None,
                  adaptive: Optional[bool] = None, patience: Optional[int] = None,
                  steps_per_sync: Optional[int] = None,
                  compact: Optional[int] = None, k_c: Optional[int] = None,
                  use_pallas=None, spec: Optional[RetrievalSpec] = None,
                  ladder: Optional[list] = None, slo_ms: Optional[float] = None,
                  shed: bool = True, tenant_weights: Optional[dict] = None,
                  background=False, service_prior: Optional[float] = None,
                  admission_margin: float = 1.0):
        """Continuous-batching slot scheduler over this index.

        Returns a ``repro.core.scheduler.SlotScheduler``: ``slots``
        concurrent queries advance in lock-step, each retiring the moment
        it converges and handing its slot to the next pending request —
        the serving-side answer to straggler queries that the all-at-once
        ``searcher`` batch must wait for.  Knobs resolve spec-first
        (``frontier`` defaults to ``spec.sched_frontier`` — the slot
        engine prefers a fatter frontier than the dispatch-batched
        engine).  ``adaptive=True`` additionally gives every slot its own
        frontier width, recovering the paper's distance-evaluation counts
        at batched throughput.

        On a mutable index the scheduler reads the live graph every tick:
        inserts/deletes/compaction interleave with in-flight queries, and
        results are re-masked against the current ``alive`` set at retire
        time.  A rerank spec (``search_policy != none``) is served too:
        the beams run under the bound search policy and each retired
        request's ``k_c`` candidates are re-ranked under the original
        distance — results identical to ``searcher()`` on the same spec.

        QoS serving: ``ladder`` (a ``spec.demotion_ladder`` list — rung 0
        must be the serving operating point) maps each ladder spec onto a
        scheduler ``Rung`` so SLO admission control (``slo_ms``, ``shed``)
        can demote requests to cheaper effective-ef points; rung cost
        scales default to the ef ratio and ``admission_margin`` adds
        planning slack over the learned mean service times.
        ``tenant_weights`` configures DRR
        fairness; ``background=True`` hangs one
        ``OnlineIndex.compact_slice`` per idle tick (mutable index only; a
        callable is used as the hook verbatim).
        """
        from .scheduler import GraphView, Rung, SlotScheduler

        self._check_search_policy(spec)
        spec = spec if spec is not None else self.spec
        k = spec.k if k is None else k
        ef_search = spec.ef_search if ef_search is None else ef_search
        slots = spec.slots if slots is None else slots
        frontier = spec.sched_frontier if frontier is None else frontier
        adaptive = spec.adaptive if adaptive is None else adaptive
        patience = spec.patience if patience is None else patience
        steps_per_sync = spec.steps_per_sync if steps_per_sync is None else steps_per_sync
        compact = spec.compact if compact is None else compact

        rerank_fn = None
        if self.query_sym != "none":
            k_c = k_c or spec.k_c or max(ef_search, k)
            ef = max(ef_search, k_c)
            beam_dist = self.search_dist
            orig, k_final = self.dist, k
            online = self.online

            def rerank_fn(q, cand):
                X_now = online.X if online is not None else self.X
                d, ids = rerank(orig, jnp.asarray(q)[None],
                                X_now, jnp.asarray(cand, jnp.int32)[None],
                                k_final)
                return np.asarray(d[0]), np.asarray(ids[0], np.int64)
        else:
            k_c = None
            ef = max(ef_search, k)
            beam_dist = self.dist

        dim = int(self.X.shape[1])
        if self.online is not None:
            online = self.online

            def graph_fn():
                return GraphView(online.adj, online._search_consts(),
                                 online.alive, online.entries,
                                 epoch=online.mutation_epoch,
                                 killed_epoch=online.killed_epoch)
        else:
            consts = (self.search_dist if self.query_sym != "none"
                      else self.dist).prep_scan(self.X)
            entries = (self.entries if self.entries is not None
                       else jnp.zeros((1,), jnp.int32))
            view = GraphView(self.neighbors, consts, None, entries)

            def graph_fn():
                if self.online is not None:
                    # the slot state is fixed-shape in the FROZEN graph
                    # (visited width, masking) — it cannot adopt the
                    # capacity-padded mutable arrays mid-life, and silently
                    # serving the stale snapshot would surface deleted
                    # points.  Recreate the scheduler after ensure_online().
                    raise RuntimeError(
                        "index became mutable after this scheduler was "
                        "created; create a new scheduler (it will read the "
                        "live graph)"
                    )
                return view

        rungs = None
        if ladder is not None:
            rungs = []
            for s in ladder:
                self._check_search_policy(s)
                if s.k != k:
                    raise ValueError(
                        f"ladder spec k {s.k} != serving k {k}; every rung "
                        f"must honor the same result contract")
                if s.k_c != spec.k_c:
                    raise ValueError(
                        f"ladder spec k_c {s.k_c} != serving k_c "
                        f"{spec.k_c}; rerank width cannot vary per rung")
                r_ef = min(max(s.ef_search, k_c or k), ef)
                name = f"ef{s.ef_search}" + ("+adaptive" if s.adaptive else "")
                rungs.append(Rung(ef=r_ef, adaptive=bool(s.adaptive),
                                  name=name, scale=r_ef / ef))

        background_fn = None
        if callable(background):
            background_fn = background
        elif background:
            if self.online is None:
                raise ValueError(
                    "background=True hangs OnlineIndex.compact_slice on "
                    "idle ticks and requires a mutable index — call "
                    "ensure_online() first (or pass a callable hook)")
            online_bg = self.online

            def background_fn():
                return online_bg.compact_slice()

        return SlotScheduler(
            beam_dist, graph_fn, dim=dim, slots=slots, ef=ef, k=k,
            frontier=frontier, adaptive=adaptive, patience=patience,
            steps_per_sync=steps_per_sync, compact=compact,
            use_pallas=use_pallas, k_c=k_c, rerank_fn=rerank_fn,
            ladder=rungs, slo_ms=slo_ms, shed=shed,
            tenant_weights=tenant_weights, background_fn=background_fn,
            service_prior=service_prior, admission_margin=admission_margin,
        )
