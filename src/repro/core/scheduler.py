"""Continuous-batching query scheduler: the slot-recycling beam engine.

The lock-step engine (``batched_beam_search``) retires a whole batch only
when its SLOWEST query converges — under strongly non-symmetric distances
(KL, Itakura-Saito) per-query search difficulty varies sharply, so one
straggler holds hostage every co-batched easy query, and a new batch cannot
start until the old one drains.  This module serves queries the way an LLM
inference server does continuous batching:

  * the engine state is S fixed SLOTS, each carrying an independent query
    with its own beam, visited set, and convergence flag;
  * every host-side tick runs ``steps_per_sync`` lock-steps of the SAME
    ``beam_step`` the batched engine uses (bit-identical state machine),
    then retires every slot whose query converged — freeing the slot
    IMMEDIATELY instead of at batch end;
  * freed slots are refilled from a pending-request queue inside the step
    loop.  Admission reuses ``seed_beams``, so an admitted query starts
    from exactly the floats a batch-at-once query would start from;
  * all device state is fixed-shape in (S, ef, capacity): steady-state
    serving never recompiles, no matter how requests arrive.

Per-query ADAPTIVE FRONTIER (``adaptive=True``): each slot carries its own
frontier width ``t_cur`` ∈ [1, frontier].  The paper's cost unit is
distance evaluations, and ``frontier > 1`` overspends them exactly while
the beam radius is SHRINKING (the top-T candidates are expanded together,
but expanding the best first would have pruned the rest).  The policy
therefore tracks the beam radius per slot: while the radius is improving
the slot expands 1 candidate per step (sequential-order evaluations); once
it stalls for ``patience`` steps — the drain phase, where expansion order
no longer changes the evaluation set — the width doubles per step back up
to ``frontier`` to finish in few fat steps.  This recovers the paper's
eval-reduction metric at batched-throughput wall-clock (see
``benchmarks/bench_serve.py``).

Mutability: the scheduler reads the graph through a ``graph_fn`` snapshot
every tick, so an ``OnlineIndex`` can insert/delete/compact between ticks
while queries are in flight.  Newly admitted queries see the current
``alive`` mask; in-flight beams keep their admission-time view, and retire
results are re-masked against the CURRENT ``alive`` so a point deleted
mid-flight never reaches a response.

Rerank scenarios (since the ``RetrievalSpec`` API): a spec with
``search_policy != none`` is served end-to-end — the slots' beams run
under the BOUND search policy (``dist`` here is already the bound
distance) and each retired request's best ``k_c`` candidates are
re-ranked under the original distance via ``rerank_fn`` before the
``SlotResult`` is emitted, with the ``k_c`` extra evaluations counted
into ``n_evals``.  Results match ``ANNIndex.searcher()`` on the same
spec; ``ANNIndex.scheduler(spec=...)`` wires all of this up.

SLO-aware admission & multi-tenant QoS: the pending queue is a set of
per-tenant weighted queues drained by deficit round-robin (one hot tenant
cannot starve the rest), and an ``AdmissionController`` tracks the
scheduler's service rate (retires/sec per occupied slot, an EWMA over
retired requests).  When a request's predicted completion no longer fits
its SLO budget, admission DEMOTES it down a ladder of cheaper operating
points (``Rung``: lower effective ef and/or the adaptive frontier —
typically drawn from the tuned-spec artifact's Pareto frontier via
``repro.core.spec.demotion_ladder``) before resorting to load-shedding;
a request is shed only when even the cheapest rung is predicted to finish
past budget.  Demotion runs inside the fixed (S, ef) arrays through
``beam_step``'s per-query ``ef_active``, so a demoted request's results
are bit-identical to submitting it to a scheduler built at the rung's ef.
``background_fn`` hangs incremental maintenance (one
``OnlineIndex.compact_slice`` per call) on idle ticks.
"""

from __future__ import annotations

import collections
import itertools
import time
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .batched_beam import (
    BatchBeamState,
    adaptive_width_update,
    beam_step,
    frontier_compact_width,
    seed_beams,
)
from .distances import Distance
from .telemetry import TickLog

INF = jnp.inf


class GraphView(NamedTuple):
    """One tick's snapshot of the (possibly mutable) index state."""

    neighbors: jax.Array  # (n, M) int32 adjacency, -1 padding
    consts: Any  # dist.prep_scan pytree, leading axis n
    alive: Optional[jax.Array]  # (n,) bool tombstone mask, or None (static)
    entries: jax.Array  # (E,) i32 unique beam entry nodes
    epoch: int = 0  # mutation epoch at snapshot time
    killed_epoch: Optional[np.ndarray] = None  # (n,) host i64: epoch each
    # slot was last tombstoned — guards retire results against slots that
    # died (and were possibly reused for a NEW point) mid-flight


class SlotState(NamedTuple):
    """Device state of the S slots (all arrays fixed-shape)."""

    core: BatchBeamState  # per-slot beam state, leading axis S
    retire: jax.Array  # (S, 2·width + 3) i32 ``retire_record(core, width)``
    qc: Any  # per-slot prepped query constants, leading axis S
    t_cur: jax.Array  # (S,) i32 adaptive frontier width (== T when fixed)
    stall: jax.Array  # (S,) i32 steps since the slot's beam radius improved
    worst: jax.Array  # (S,) f32 beam radius watermark for the policy
    ef_act: jax.Array  # (S,) i32 effective beam width (== ef when undemoted)
    adapt: jax.Array  # (S,) bool — slot runs the adaptive frontier policy


def retire_record(core: BatchBeamState, width: int) -> jax.Array:
    """What the host needs to retire any slot, packed into one int32 array
    so a tick reads it in one transfer: per slot ``done``, ``n_evals``,
    ``hops``, then the first ``width`` beam distances (float32 bits,
    exact) and ids.  Fixed-shape over all S slots: the host picks the
    retired rows, so no per-retired-count program is ever compiled."""
    return jnp.concatenate([
        core.done.astype(jnp.int32)[:, None],
        core.n_evals[:, None],
        core.hops[:, None],
        jax.lax.bitcast_convert_type(core.beam_d[:, :width], jnp.int32),
        core.beam_i[:, :width],
    ], axis=1)


def unpack_retire(rec: np.ndarray, width: int):
    """Host side of ``retire_record`` (whose column 0 is ``done``):
    ``n_evals``, ``hops``, beam distances and ids (int64) of the rows of
    ``rec``."""
    d = np.ascontiguousarray(rec[:, 3:3 + width]).view(np.float32)
    return rec[:, 1], rec[:, 2], d, rec[:, 3 + width:].astype(np.int64)


@dataclass
class SlotResult:
    """One retired request (distances ascending, -1/inf padded)."""

    rid: int
    dists: np.ndarray  # (k,) f32
    ids: np.ndarray  # (k,) i64 stable slot/database ids
    n_evals: int
    hops: int
    t_arrival: float = 0.0
    t_admit: float = 0.0
    t_done: float = 0.0
    tenant: int = 0
    priority: int = 0
    level: int = 0  # demotion-ladder rung served at (-1 for shed requests)
    shed: bool = False  # load-shed: no search ran, ids/dists are -1/inf

    @property
    def latency(self) -> float:
        return self.t_done - self.t_arrival


@dataclass(frozen=True)
class Rung:
    """One operating point on the QoS demotion ladder (cheapest last).

    ``scale`` is the rung's expected service cost relative to rung 0 (the
    full-fidelity point) — used by the admission controller to predict a
    demoted request's service time; defaults to the ef ratio when built by
    ``ANNIndex.scheduler``.
    """

    ef: int
    adaptive: bool = False
    name: str = ""
    scale: float = 1.0


@dataclass
class _Request:
    """A pending queue entry (host-side only)."""

    rid: int
    q: np.ndarray
    t_arrival: float
    tenant: int
    priority: int
    slo_s: Optional[float]
    level: Optional[int]  # pinned operating point (bypasses admission)


class ServiceRateEstimator:
    """EWMA estimates of per-request service time, overall and per rung.

    The admission controller's model of the scheduler: each occupied slot
    retires ``rate_per_slot = 1 / mean`` requests per second, so with every
    slot busy the queue drains at ``slots / mean`` req/s (``mean`` is the
    all-rung mix actually being served — the right drain rate for queue-wait
    prediction).  Each rung additionally keeps its OWN observed mean: a
    demoted beam converges in fewer steps than the ef ratio suggests but not
    proportionally fewer, so a static scale mis-prices demotion — the
    per-rung estimate learns the true cost from the first few retires at
    that rung, falling back to ``rung-0 mean x scale`` until then.  Until
    the first observation every prediction is 0 — the controller admits
    optimistically while cold.
    """

    def __init__(self, slots: int, alpha: float = 0.25,
                 prior: Optional[float] = None, n_rungs: int = 1):
        self.slots = int(slots)
        self.alpha = float(alpha)
        self.mean: Optional[float] = None if prior is None else float(prior)
        self._rung: list[Optional[float]] = [None] * max(1, int(n_rungs))
        if prior is not None:
            self._rung[0] = float(prior)

    def observe(self, service_s: float, level: int = 0) -> None:
        if not service_s > 0.0:
            return
        a = self.alpha
        self.mean = (service_s if self.mean is None
                     else (1.0 - a) * self.mean + a * service_s)
        lvl = min(max(int(level), 0), len(self._rung) - 1)
        m = self._rung[lvl]
        self._rung[lvl] = (service_s if m is None
                           else (1.0 - a) * m + a * service_s)

    @property
    def rate_per_slot(self) -> Optional[float]:
        """Retires/sec per occupied slot (None until the first observation)."""
        return None if self.mean is None else 1.0 / max(self.mean, 1e-12)

    def service_s(self, level: int = 0, scale: float = 1.0) -> float:
        """Predicted service seconds at a rung (0 while fully cold).

        Prefers the rung's own observed mean; before the rung's first
        retire, extrapolates rung 0 (or the overall mean) by the rung's
        static cost ``scale``.
        """
        lvl = min(max(int(level), 0), len(self._rung) - 1)
        if self._rung[lvl] is not None:
            return self._rung[lvl]
        base = self._rung[0] if self._rung[0] is not None else self.mean
        return 0.0 if base is None else base * scale

    def predicted_wait(self, position: int, free_slots: int) -> float:
        """Predicted queue wait for the request at 0-indexed queue
        ``position`` given ``free_slots`` currently idle slots.

        The first ``free_slots`` queued requests admit immediately; each
        deeper position must wait for one more retire, and a fully occupied
        scheduler retires ``slots / mean`` requests per second — so
        position ``p`` waits ``(p - free + 1) * mean / slots`` seconds.
        """
        if self.mean is None or position < free_slots:
            return 0.0
        return (position - free_slots + 1) * self.mean / max(self.slots, 1)


class AdmissionController:
    """SLO admission policy: demote to a cheaper rung before shedding.

    ``decide`` picks the operating point for one request: starting from its
    class's base rung, walk DOWN the ladder until the predicted completion
    (elapsed wait + predicted residual queue wait + predicted service at
    that rung) fits the remaining SLO budget.  A request is shed only when
    even the CHEAPEST rung's predicted completion is past budget — demotion
    strictly precedes load-shedding; with ``shed=False`` hopeless requests
    run best-effort at the cheapest rung instead of being dropped.

    ``margin`` is a planning slack factor on the predicted service time:
    the estimator tracks EWMA *means*, but per-request service disperses
    around them (beam convergence varies by query), so a request admitted
    with exactly mean-sized budget left misses its SLO about half the
    time — slot time a shed would have saved.  Planning with
    ``mean * margin`` converts those admitted-but-doomed requests into
    earlier demotions/sheds, which is what keeps goodput near peak under
    deep overload.

    Each demotion and shed is counted in ``counters`` (``"demoted"``,
    ``"shed"``), a ``collections.Counter`` of the controller's own until a
    scheduler hands it its tick log's counters.
    """

    def __init__(self, rungs: list[Rung], slots: int, *, shed: bool = True,
                 alpha: float = 0.25, prior: Optional[float] = None,
                 margin: float = 1.0):
        self.rungs = list(rungs)
        self.shed = bool(shed)
        if not margin > 0:
            raise ValueError(f"admission margin must be > 0, got {margin}")
        self.margin = float(margin)
        self.estimator = ServiceRateEstimator(slots, alpha=alpha, prior=prior,
                                              n_rungs=len(self.rungs))
        self.counters = collections.Counter()

    @property
    def n_demoted(self) -> int:
        return self.counters["demoted"]

    @property
    def n_shed(self) -> int:
        return self.counters["shed"]

    def decide(self, *, elapsed: float, slo_s: Optional[float],
               base_level: int = 0, queue_wait: float = 0.0) -> Optional[int]:
        """Rung index to serve the request at, or None to shed it."""
        last = len(self.rungs) - 1
        base = min(max(int(base_level), 0), last)
        if slo_s is None:
            return base
        remaining = slo_s - elapsed - queue_wait
        for lvl in range(base, last + 1):
            planned = self.estimator.service_s(lvl, self.rungs[lvl].scale)
            if planned * self.margin <= remaining:
                if lvl > base:
                    self.counters["demoted"] += 1
                return lvl
        if self.shed:
            self.counters["shed"] += 1
            return None
        if last > base:
            self.counters["demoted"] += 1
        return last


class SchedulerHost:
    """Host-side serving machinery shared by every slot scheduler.

    Owns the pending-request queues (per-tenant DRR with strict priority
    within a tenant), request submission, the tick log
    (``repro.core.telemetry``: ``tick`` runs the subclass's ``_tick``
    inside one logged row) and the drain / warmup / ``run_stream``
    drivers.  Subclasses — the single-device ``SlotScheduler`` and the
    scatter-gather ``repro.core.distributed.ShardedSlotScheduler`` —
    provide the device state plus ``_tick(now, t)`` / ``reset()``, the
    ``dim`` / ``rungs`` / ``slo_s`` attributes, the host-side ``_slot_rid``
    occupancy array and an optional ``_background`` idle hook; everything
    here is device-layout agnostic.
    """

    def _init_host(self, tenant_weights=None):
        """Validate tenant weights; create the (empty) queue state and the
        tick log."""
        self.log = TickLog()
        self._rid_gen = itertools.count()
        self._weights = {int(t): float(w)
                         for t, w in (tenant_weights or {}).items()}
        for t, w in self._weights.items():
            if not w > 0:
                raise ValueError(f"tenant {t} weight must be > 0, got {w}")
        self._queues: dict[int, dict[int, collections.deque]] = {}
        self._tenant_order: list[int] = []
        self._deficit: dict[int, float] = {}
        self._n_pending = 0

    def _clear_host(self):
        self._queues.clear()
        self._tenant_order.clear()
        self._deficit.clear()
        self._n_pending = 0
        self.log.reset()

    def tick(self, now: float = 0.0) -> list[SlotResult]:
        """Serve one tick (the subclass's ``_tick``) as one row of the tick
        log, inside the ``repro.tick`` span; ``now`` is the caller's clock,
        echoed into the results' ``t_admit``."""
        with self.log.tick(now) as t:
            return self._tick(now, t)

    @property
    def n_inflight(self) -> int:
        return int((self._slot_rid >= 0).sum())

    @property
    def n_pending(self) -> int:
        return self._n_pending

    def submit(self, q, rid: Optional[int] = None, t_arrival: float = 0.0, *,
               tenant: int = 0, priority: int = 0,
               slo_ms: Optional[float] = None,
               level: Optional[int] = None) -> int:
        """Enqueue one query row ``q`` of shape (dim,).

        ``rid`` (optional) names the request; auto-assigned from a counter
        otherwise.  ``t_arrival`` is echoed into the eventual
        ``SlotResult`` for latency accounting.  ``tenant`` selects the DRR
        fairness queue; ``priority`` is the QoS class (0 = highest; class p
        starts at demotion-ladder rung min(p, len(ladder)-1) and within a
        tenant strictly precedes higher-numbered classes).  ``slo_ms``
        overrides the scheduler's default SLO budget for this request;
        ``level`` pins an explicit operating point, bypassing admission
        control.  Returns the request id.
        """
        if rid is None:
            rid = next(self._rid_gen)
        tenant, priority = int(tenant), max(0, int(priority))
        slo_s = self.slo_s if slo_ms is None else float(slo_ms) / 1e3
        if level is not None:
            level = min(max(int(level), 0), len(self.rungs) - 1)
        tq = self._queues.get(tenant)
        if tq is None:
            tq = self._queues[tenant] = {}
            self._tenant_order.append(tenant)
            self._deficit[tenant] = 0.0
        dq = tq.get(priority)
        if dq is None:
            dq = tq[priority] = collections.deque()
        dq.append(_Request(int(rid), np.asarray(q), float(t_arrival), tenant,
                           priority, slo_s, level))
        self._n_pending += 1
        return int(rid)

    def _tenant_pending(self, tenant: int) -> bool:
        return any(self._queues[tenant][p] for p in self._queues[tenant])

    def _pop_tenant(self, tenant: int) -> _Request:
        tq = self._queues[tenant]
        for prio in sorted(tq):
            if tq[prio]:
                self._n_pending -= 1
                return tq[prio].popleft()
        raise LookupError(f"tenant {tenant} has no pending requests")

    def _drr_select(self, n: int) -> list[_Request]:
        """Pop up to ``n`` requests across the tenant queues.

        Deficit round-robin with per-tenant weights (quantum = weight, cost
        1 per request) over tenants in first-seen order; strict priority
        order within a tenant.  A tenant's deficit resets when its queue
        drains, so burst credit cannot be banked — the classic DRR
        starvation bound (at most one quantum of lag per competitor over
        any window) holds no matter how hot one tenant runs.
        """
        out: list[_Request] = []
        while len(out) < n and self._n_pending:
            active = [t for t in self._tenant_order if self._tenant_pending(t)]
            for t in active:
                self._deficit[t] += self._weights.get(t, 1.0)
            for t in active:
                while (len(out) < n and self._deficit[t] >= 1.0
                       and self._tenant_pending(t)):
                    out.append(self._pop_tenant(t))
                    self._deficit[t] -= 1.0
                if not self._tenant_pending(t):
                    self._deficit[t] = 0.0
        return out

    def drain(self, now: float = 0.0) -> list[SlotResult]:
        """Run ticks until the queue and every slot are empty."""
        out = []
        while self._n_pending or (self._slot_rid >= 0).any():
            out.extend(self.tick(now))
        return out

    def warmup(self, q=None):
        """Compile the admit/step/retire paths outside any timed region."""
        if q is None:
            q = np.full((self.dim,), 1.0 / self.dim, np.float32)
        self.submit(np.asarray(q))
        self.drain()
        self.reset()

    def run_stream(self, Q, arrivals=None, realtime: bool = False,
                   warm: bool = True, tenants=None, priorities=None,
                   slo_ms: Optional[float] = None,
                   tick_cost: Optional[float] = None) -> list[SlotResult]:
        """Serve a request stream with per-request arrival times.

        ``arrivals=None`` submits everything at t=0 (a closed batch).  By
        default the clock is VIRTUAL: it advances only by the measured
        compute time of each tick, so latency percentiles reflect scheduler
        behavior rather than host sleep jitter; ``realtime=True`` uses the
        wall clock and sleeps through idle gaps instead (the serving
        driver's mode).  ``tick_cost`` (exclusive with ``realtime``)
        advances the virtual clock by a FIXED cost per tick instead of the
        measured one — the lock-step tick runs full-batch compute
        regardless of slot occupancy, so a constant cost is faithful, and
        arrivals/SLOs expressed in the same unit make queueing behavior
        deterministic and machine-independent (the overload bench's mode).
        ``tenants``/``priorities`` (optional per-request arrays) and
        ``slo_ms`` (stream-wide SLO override) forward to ``submit``.
        Returns results ordered by request index, with
        ``t_arrival``/``t_admit``/``t_done`` filled in on the chosen clock;
        load-shed requests come back with ``shed=True``.
        """
        if realtime and tick_cost is not None:
            raise ValueError("tick_cost is a virtual-clock mode; "
                             "incompatible with realtime=True")
        Q = np.asarray(Q)
        n_req = Q.shape[0]
        if arrivals is None:
            arrivals = np.zeros((n_req,), float)
        arrivals = np.asarray(arrivals, float)
        order = np.argsort(arrivals, kind="stable")
        if warm:
            self.warmup(Q[0])
        else:
            self.reset()
        results: dict[int, SlotResult] = {}
        t0 = time.perf_counter()
        clock = 0.0
        i = 0
        while len(results) < n_req:
            if realtime:
                clock = time.perf_counter() - t0
            while i < n_req and arrivals[order[i]] <= clock:
                rid = int(order[i])
                self.submit(
                    Q[rid], rid=rid, t_arrival=float(arrivals[rid]),
                    tenant=0 if tenants is None else int(tenants[rid]),
                    priority=0 if priorities is None else int(priorities[rid]),
                    slo_ms=slo_ms,
                )
                i += 1
            if not self._n_pending and not (self._slot_rid >= 0).any():
                # idle: background maintenance, then jump (or sleep) to the
                # next arrival
                if self._background is not None:
                    self._background()
                nxt = float(arrivals[order[i]])
                if realtime:
                    time.sleep(max(0.0, nxt - (time.perf_counter() - t0)))
                else:
                    clock = nxt
                continue
            tick_t0 = time.perf_counter()
            finished = self.tick(now=clock)
            if realtime:
                clock = time.perf_counter() - t0
            elif tick_cost is not None:
                clock += tick_cost
            else:
                clock += time.perf_counter() - tick_t0
            for r in finished:
                r.t_done = clock
                results[r.rid] = r
        return [results[j] for j in range(n_req)]


class SlotScheduler(SchedulerHost):
    """Slot-recycling continuous-batching searcher over a neighborhood graph.

    Parameters
    ----------
    dist : search distance (PairDistance gather contract)
    graph_fn : () -> GraphView — re-read every tick; array SHAPES must stay
        fixed across calls (capacity-padded for mutable indexes)
    dim : query vector dimensionality
    slots : S, concurrent in-flight queries (the continuous batch)
    ef, k : beam width / results per query (ef >= k)
    frontier : max beam candidates expanded per slot per lock-step
    adaptive : per-slot adaptive frontier width (see module docstring)
    patience : stalled steps before the adaptive width starts regrowing
    steps_per_sync : lock-steps run per host tick; >1 amortizes dispatch
        overhead, at the cost of retire/refill granularity
    use_pallas : scoring path, same semantics as ``make_step_searcher`` —
        None routes single-matmul ``Distance`` scoring through the fused
        gather kernel wrapper (einsum off-TPU, Pallas on TPU), False forces
        the generic pytree path (the parity reference)
    k_c, rerank_fn : the full-symmetrization rerank scenario (``RetrievalSpec``
        with ``search_policy != none``): ``dist`` is the BOUND search policy
        guiding the beam, and at retire time the slot's best ``k_c``
        candidates are re-ranked under the ORIGINAL distance by
        ``rerank_fn(q, cand_ids) -> (dists (k,), ids (k,))`` — a host
        callback per retired request (fixed B=1 shape, so it compiles
        once), counted into ``n_evals`` exactly like the batch searcher's
        rerank path
    ladder : optional list of ``Rung`` (or kwargs dicts) — the QoS demotion
        ladder, full-fidelity first, cheapest last.  Rung 0 must be the
        scheduler's own operating point; every rung needs
        ``max(k, k_c) <= rung.ef <= ef``.  Defaults to the single
        full-fidelity rung (QoS machinery compiled out, legacy behavior)
    slo_ms : default SLO budget per request (admission control ON when set;
        per-request ``submit(slo_ms=...)`` overrides)
    shed : drop requests that no rung can save (False = serve best-effort
        at the cheapest rung instead)
    tenant_weights : tenant id -> DRR weight (> 0); unlisted tenants get 1.0
    background_fn : zero-arg callable invoked once per idle tick — the hook
        for incremental index maintenance (``OnlineIndex.compact_slice``)
    service_alpha, service_prior : EWMA smoothing / optional initial mean
        service seconds for the admission controller's rate estimate
    admission_margin : planning slack factor on predicted service times
        (see ``AdmissionController``); 1.0 plans on the bare EWMA mean
    """

    def __init__(self, dist, graph_fn: Callable[[], GraphView], *, dim: int,
                 slots: int = 32, ef: int = 96, k: int = 10, frontier: int = 4,
                 compact: int = 32, adaptive: bool = False, patience: int = 1,
                 max_steps: Optional[int] = None, steps_per_sync: int = 1,
                 use_pallas=None, k_c: Optional[int] = None,
                 rerank_fn: Optional[Callable] = None,
                 ladder: Optional[list] = None, slo_ms: Optional[float] = None,
                 shed: bool = True, tenant_weights: Optional[dict] = None,
                 background_fn: Optional[Callable[[], Any]] = None,
                 service_alpha: float = 0.25,
                 service_prior: Optional[float] = None,
                 admission_margin: float = 1.0):
        if ef < k:
            raise ValueError(f"ef {ef} < k {k}")
        if frontier < 1:
            raise ValueError(f"frontier must be >= 1, got {frontier}")
        if (k_c is None) != (rerank_fn is None):
            raise ValueError("k_c and rerank_fn must be provided together")
        if k_c is not None and not (k <= k_c <= ef):
            raise ValueError(f"need k {k} <= k_c {k_c} <= ef {ef}")
        self.k_c = None if k_c is None else int(k_c)
        self._rerank_fn = rerank_fn
        g = graph_fn()
        n, M = g.neighbors.shape
        self.dist = dist
        self.graph_fn = graph_fn
        self.dim = int(dim)
        self.S = int(slots)
        self.ef = int(ef)
        self.k = int(k)
        self.T = int(min(frontier, ef))
        self.C = frontier_compact_width(self.T, M, compact)
        self.adaptive = bool(adaptive)
        self.patience = int(max(1, patience))
        self.max_steps = int(n if max_steps is None else max_steps)
        self.steps_per_sync = int(max(1, steps_per_sync))
        self._masked = g.alive is not None
        # beam columns a retire takes from the step's record (the masked
        # backfill reads the whole beam instead, on retiring ticks only)
        self._width = self.k_c or self.k
        self._n = n
        self._dtype = jax.tree.leaves(g.consts)[0].dtype
        self._use_pallas = use_pallas
        self._kernel_ok = isinstance(dist, Distance) and use_pallas is not False
        self._rows_of, self._rows = None, None  # see _kernel_rows

        # ---- QoS: demotion ladder, admission control, tenant fairness
        rungs = [r if isinstance(r, Rung) else Rung(**r) for r in ladder or []]
        if not rungs:
            rungs = [Rung(ef=self.ef, adaptive=self.adaptive, name="full")]
        if rungs[0].ef != self.ef or rungs[0].adaptive != self.adaptive:
            raise ValueError(
                "ladder rung 0 must be the scheduler's own operating point "
                f"(ef={self.ef}, adaptive={self.adaptive}), got {rungs[0]}")
        floor = self.k_c or self.k
        for r in rungs:
            if not floor <= r.ef <= self.ef:
                raise ValueError(
                    f"ladder rung ef {r.ef} outside [{floor}, {self.ef}]")
        if any(rungs[i].ef < rungs[i + 1].ef for i in range(len(rungs) - 1)):
            raise ValueError("ladder rungs must be cheapest-last "
                             "(ef non-increasing)")
        self.rungs = rungs
        self.slo_s = None if slo_ms is None else float(slo_ms) / 1e3
        # static compile flags: a single-rung ladder without an SLO keeps
        # the jitted admit/step graphs byte-for-byte the legacy ones
        self._qos = len(rungs) > 1 or self.slo_s is not None
        self._any_adaptive = self.adaptive or any(r.adaptive for r in rungs)
        self.admission = AdmissionController(
            rungs, self.S, shed=shed, alpha=service_alpha,
            prior=service_prior, margin=admission_margin)
        self._background = background_fn
        self._init_host(tenant_weights)
        # admission decisions count into the tick log's counters
        self.admission.counters = self.log.counters
        self._build_jits()
        self.reset()

    # ------------------------------------------------------------- jit setup

    def _kernel_rows(self, consts):
        """The kernel's row view of ``consts`` (None on the jnp path), laid
        out once per corpus snapshot: a static index hands back the same
        consts every tick, a mutable one new consts after a mutation."""
        if not self._kernel_ok:
            return None
        if consts is not self._rows_of:
            from repro.kernels.ops import kernel_rows

            self._rows_of = consts
            self._rows = kernel_rows(self.dist, consts, self._use_pallas)
        return self._rows

    def _score_fn(self, consts, rows, qc):
        dist = self.dist
        if self._kernel_ok:
            from repro.kernels.ops import frontier_gather_scores

            def score_rows(ids):
                return frontier_gather_scores(
                    dist, ids, qc["rep"], qc["bias"], consts["rep"],
                    consts["bias"], x_rows=rows,
                )
        else:

            def score_rows(ids):
                rows = jax.tree.map(lambda a: a[ids], consts)
                return jax.vmap(dist.score)(rows, qc)

        return score_rows

    def _build_jits(self):
        S, ef, T, C = self.S, self.ef, self.T, self.C
        dist, n, max_steps = self.dist, self._n, self.max_steps
        width = self._width
        patience = self.patience
        qos, any_adaptive = self._qos, self._any_adaptive

        # named scopes label the device ops of each program (op metadata
        # only: the compiled code is the same)
        @jax.named_scope("admit")
        def admit(state: SlotState, Q_new, write, consts, rows, entries,
                  alive, ef_new, ad_new):
            qc_new = jax.vmap(dist.prep_query)(Q_new)
            score_rows = self._score_fn(consts, rows, qc_new)
            fresh = seed_beams(score_rows, entries, S, ef, n, alive=alive)
            if qos:
                # demoted slots seed exactly like an ef_new-wide engine:
                # void seeded entries beyond the rung's effective width
                off = (jnp.arange(ef, dtype=jnp.int32)[None, :]
                       >= ef_new[:, None])
                fresh = fresh._replace(
                    beam_d=jnp.where(off, INF, fresh.beam_d),
                    beam_i=jnp.where(off, -1, fresh.beam_i),
                    expanded=fresh.expanded | off,
                )

            def sel(a, b):
                w = write.reshape((S,) + (1,) * (a.ndim - 1))
                return jnp.where(w, a, b)

            # adaptive slots start at width 1: admission begins the
            # fill/descent phase, where sequential-order expansion is the
            # whole point of the policy
            t_new = jnp.where(ad_new, 1, T) if any_adaptive else T
            core = jax.tree.map(sel, fresh, state.core)
            return SlotState(
                core=core,
                retire=retire_record(core, width),
                qc=jax.tree.map(sel, qc_new, state.qc),
                t_cur=jnp.where(write, t_new, state.t_cur),
                stall=jnp.where(write, 0, state.stall),
                worst=jnp.where(write, INF, state.worst),
                ef_act=jnp.where(write, ef_new, state.ef_act),
                adapt=jnp.where(write, ad_new, state.adapt),
            )

        @jax.named_scope("step")
        def step(state: SlotState, neighbors, consts, rows):
            score_rows = self._score_fn(consts, rows, state.qc)
            core, t_cur, stall, worst = (state.core, state.t_cur, state.stall,
                                         state.worst)
            ef_act = state.ef_act if qos else None
            for _ in range(self.steps_per_sync):
                t_act = t_cur if any_adaptive else None
                core = beam_step(core, neighbors, score_rows, ef, T, C,
                                 max_steps, t_active=t_act, ef_active=ef_act)
                if any_adaptive:
                    # shared with the offline adaptive while_loop: expand
                    # sequentially while the slot's beam radius improves,
                    # drain fat once it stalls (see adaptive_width_update).
                    # Demoted slots watch the radius at their effective
                    # beam width; non-adaptive rungs stay pinned at T.
                    radius = None
                    if qos:
                        wi = jnp.clip(state.ef_act - 1, 0, ef - 1)[:, None]
                        radius = jnp.take_along_axis(core.beam_d, wi,
                                                     axis=1)[:, 0]
                    t_cur, stall, worst = adaptive_width_update(
                        core, t_cur, stall, worst, T, patience, radius=radius
                    )
                    t_cur = jnp.where(state.adapt, t_cur, T)
            return state._replace(core=core,
                                  retire=retire_record(core, width),
                                  t_cur=t_cur, stall=stall, worst=worst)

        self._admit = jax.jit(admit)
        self._step = jax.jit(step)

    # ----------------------------------------------------------- state mgmt

    def reset(self):
        """Clear all slots, the pending queue, and per-request bookkeeping."""
        S, ef = self.S, self.ef
        nw = -(-self._n // 32)
        core = BatchBeamState(
            beam_d=jnp.full((S, ef), INF, jnp.float32),
            beam_i=jnp.full((S, ef), -1, jnp.int32),
            expanded=jnp.ones((S, ef), bool),
            visited=jnp.zeros((S, nw), jnp.uint32),
            n_evals=jnp.zeros((S,), jnp.int32),
            hops=jnp.zeros((S,), jnp.int32),
            done=jnp.ones((S,), bool),
        )
        # uniform histogram placeholder: valid under every registry distance,
        # so idle slots never score NaNs (their rows are masked anyway)
        q0 = jnp.full((S, self.dim), 1.0 / self.dim, self._dtype)
        qc = jax.vmap(self.dist.prep_query)(q0)
        self.state = SlotState(
            core=core,
            retire=retire_record(core, self._width),
            qc=qc,
            t_cur=jnp.full((S,), self.T, jnp.int32),
            stall=jnp.zeros((S,), jnp.int32),
            worst=jnp.full((S,), INF, jnp.float32),
            ef_act=jnp.full((S,), self.ef, jnp.int32),
            adapt=jnp.full((S,), self.adaptive, bool),
        )
        # the learned service-rate estimate survives reset (it describes
        # the hardware, not the request stream); the per-run counters of
        # the tick log do not
        self._clear_host()
        self._slot_rid = np.full((S,), -1, np.int64)
        self._slot_level = np.zeros((S,), np.int64)
        # raw per-slot query rows, kept host-side for the retire-time rerank
        self._slot_q = np.zeros((S, self.dim), np.float32)
        # rid -> (arrival, admit time, admission epoch, tenant, priority,
        # rung level, admit tick)
        self._meta: dict[int, tuple] = {}

    @property
    def qos_stats(self) -> dict:
        """Per-run admission counters (the tick log's, zeroed by ``reset``)
        and the service-rate estimate."""
        est = self.admission.estimator
        return {
            "demoted": self.log.counters["demoted"],
            "shed": self.log.counters["shed"],
            "mean_service_s": est.mean,
            "rate_per_slot": est.rate_per_slot,
        }

    # -------------------------------------------------------------- serving

    def _read(self, *arrays) -> tuple:
        """Copy slot-state ``arrays`` to the host in one call: the tick's
        only device-to-host reads go through here."""
        return jax.device_get(arrays)

    def _tick(self, now: float, t) -> list[SlotResult]:
        """Admit pending requests into free slots (DRR across tenants,
        SLO admission control per request), run ``steps_per_sync``
        lock-steps, retire every converged slot.  Returns retired results
        plus any load-shed responses (``t_done`` left for the caller's
        clock).  ``t`` is the tick's open log row: each part runs in its
        span, in the order of ``repro.core.telemetry.SPANS``."""
        g = self.graph_fn()
        counters = self.log.counters
        shed_out: list[SlotResult] = []
        free = np.flatnonzero(self._slot_rid < 0)
        if len(free) and self._n_pending:
            with t.span("select"):
                Q_new = np.full((self.S, self.dim), 1.0 / self.dim,
                                np.float32)
                write = np.zeros((self.S,), bool)
                ef_new = np.full((self.S,), self.ef, np.int32)
                ad_new = np.full((self.S,), self.adaptive, bool)
                fi = 0
                # shed decisions free no slot, so keep drawing from the DRR
                # queues until the free slots are filled or the queues drain
                while fi < len(free) and self._n_pending:
                    for req in self._drr_select(len(free) - fi):
                        lvl = req.level
                        if lvl is None:
                            lvl = self.admission.decide(
                                elapsed=now - req.t_arrival, slo_s=req.slo_s,
                                base_level=min(req.priority,
                                               len(self.rungs) - 1),
                            )
                        if lvl is None:
                            # load-shed: answer immediately without burning
                            # a slot — demotion was already ruled out by
                            # decide()
                            shed_out.append(SlotResult(
                                rid=req.rid,
                                dists=np.full((self.k,), np.inf, np.float32),
                                ids=np.full((self.k,), -1, np.int64),
                                n_evals=0, hops=0, t_arrival=req.t_arrival,
                                t_admit=now, tenant=req.tenant,
                                priority=req.priority, level=-1, shed=True,
                            ))
                            continue
                        rung = self.rungs[lvl]
                        s = free[fi]
                        fi += 1
                        Q_new[s] = req.q
                        write[s] = True
                        ef_new[s] = rung.ef
                        ad_new[s] = rung.adaptive
                        self._slot_rid[s] = req.rid
                        self._slot_q[s] = req.q
                        self._slot_level[s] = lvl
                        self._meta[req.rid] = (req.t_arrival, now, g.epoch,
                                               req.tenant, req.priority, lvl,
                                               t.index)
                counters["admitted"] += fi
            if fi:
                with t.span("put"):
                    Q_dev = jnp.asarray(Q_new, self._dtype)
                    write_dev = jnp.asarray(write)
                    ef_dev = jnp.asarray(ef_new)
                    ad_dev = jnp.asarray(ad_new)
                with t.span("admit"):
                    self.state = self._admit(
                        self.state, Q_dev, write_dev, g.consts,
                        self._kernel_rows(g.consts), g.entries, g.alive,
                        ef_dev, ad_dev,
                    )
        if (self._background is not None and not self._n_pending
                and (self._slot_rid < 0).any()):
            # idle capacity this tick: hang one slice of background index
            # maintenance (incremental compaction)
            with t.span("background"):
                self._background()
        occupied = int((self._slot_rid >= 0).sum())
        t.set("occupied", occupied)
        if not occupied:
            return shed_out

        with t.span("step"):
            self.state = self._step(self.state, g.neighbors, g.consts,
                                    self._kernel_rows(g.consts))

        with t.span("sync"):
            # the tick's one read: waits out the step, returns its record
            rec, = self._read(self.state.retire)
        finished = rec[:, 0].astype(bool) & (self._slot_rid >= 0)
        if not finished.any():
            return shed_out
        # the retired rows are picked on the host from the fixed-shape
        # record.  Masked serving takes the FULL ef-wide beam instead, so
        # voided top-k entries backfill from the alive candidates the
        # search already ranked at k..ef: a read only a retiring tick makes.
        with t.span("retire_read"):
            idx = np.flatnonzero(finished)
            evals, hops, d, ids = unpack_retire(rec[idx], self._width)
            if self._masked:
                d, ids = self._read(self.state.core.beam_d,
                                    self.state.core.beam_i)
                d, ids = d[idx], ids[idx].astype(np.int64)
        with t.span("retire"):
            metas = [self._meta.pop(int(self._slot_rid[s]),
                                    (0.0, 0.0, 0, 0, 0, 0, t.index))
                     for s in idx]
            counters["retired"] += len(idx)
            counters["held_ticks"] += sum(t.index - m[6] + 1 for m in metas)
            if self._masked and g.alive is not None:
                # points tombstoned while this query was in flight must not
                # surface: void them and compact each row (stable order).
                # The killed-epoch guard additionally catches slots that
                # died AND were reused for a different point since this
                # request's admission — `alive` alone would vouch for the
                # impostor.
                safe = np.where(ids >= 0, ids, 0)
                dead = ~np.asarray(g.alive)[safe]
                if g.killed_epoch is not None:
                    admit_epoch = np.asarray([m[2] for m in metas])[:, None]
                    dead |= g.killed_epoch[safe] > admit_epoch
                dead &= ids >= 0
                if dead.any():
                    d = np.where(dead, np.inf, d)
                    ids = np.where(dead, -1, ids)
                    order = np.argsort(np.where(np.isfinite(d), 0, 1),
                                       axis=1, kind="stable")
                    d = np.take_along_axis(d, order, axis=1)
                    ids = np.take_along_axis(ids, order, axis=1)
            if self.k_c is None:
                d, ids = d[:, : self.k], ids[:, : self.k]
        if self.k_c is not None:
            # full-symmetrization scenario: the beam ran under the bound
            # search policy; re-rank its k_c best candidates under the
            # ORIGINAL distance at retire time (one fixed-shape B=1 call
            # per retired request, so serving never recompiles)
            with t.span("rerank"):
                d, ids = d[:, : self.k_c], ids[:, : self.k_c]
                rr_d = np.empty((len(idx), self.k), np.float32)
                rr_i = np.empty((len(idx), self.k), np.int64)
                for j, s in enumerate(idx):
                    rr_d[j], rr_i[j] = self._rerank_fn(self._slot_q[s],
                                                       ids[j])
                d, ids = rr_d, rr_i
                evals = evals + self.k_c

        out = []
        with t.span("retire"):
            for j, s in enumerate(idx):
                rid = int(self._slot_rid[s])
                t_arr, t_adm, _, tenant, priority, lvl, _ = metas[j]
                if now > t_adm:
                    # feed the admission controller's per-rung service
                    # estimate
                    self.admission.estimator.observe(now - t_adm, level=lvl)
                out.append(SlotResult(rid=rid, dists=d[j], ids=ids[j],
                                      n_evals=int(evals[j]),
                                      hops=int(hops[j]), t_arrival=t_arr,
                                      t_admit=t_adm, tenant=tenant,
                                      priority=priority, level=lvl))
                # the slot stays frozen on the device (its ``done`` holds)
                # until admission writes over it
                self._slot_rid[s] = -1
        return shed_out + out
