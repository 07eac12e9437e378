"""Retrieval serving driver: build a (sharded) non-metric index, answer
batched k-NN queries - the paper's system as a service loop.

Single-host mode runs the full pipeline on one device; with >1 local
devices it builds per-shard subgraphs and serves scatter-gather queries
through repro.core.distributed (the 1000-node architecture, DESIGN.md
SS2.4, exercised at container scale).

Mutation endpoints (``--churn-rounds`` > 0): the index is built with a
``--capacity`` slot budget and kept LIVE through rounds of interleaved
``insert`` / ``delete`` / query traffic (the online mutable index,
repro.core.online); each round reports mutation throughput and query
latency, and the loop ends with a ``compact()`` + recall audit against an
exact scan of the surviving set.

Continuous batching (``--continuous``): instead of fixed dispatch batches,
requests stream in as a Poisson process (rate = ``--utilization`` x the
measured static-batch capacity) and are served by the slot-recycling
scheduler (``repro.core.scheduler``): each of ``--slots`` slots retires its
query the moment it converges and is refilled from the admission queue, so
straggler queries stop inflating every co-batched request's latency.  The
driver reports p50/p95/p99 latency for all three disciplines (static,
dispatch-on-idle dynamic batching, continuous) over the identical arrival
trace, plus the per-query adaptive-frontier evaluation counts when
``--adaptive-frontier`` is set.

SLO-aware admission & multi-tenant QoS (``--slo-ms``, with ``--continuous``):
each request carries a latency budget; the scheduler's admission controller
predicts queue wait from a running service-rate estimate and *demotes*
requests that would miss their SLO to cheaper operating points (lower-``ef``
rungs from ``repro.core.spec.demotion_ladder`` — drawn from a tuned-spec
artifact's Pareto frontier when ``--spec`` names one) before resorting to
load shedding.  ``--tenants N`` splits the offered load into N independent
per-tenant Poisson traces served under deficit-round-robin fairness;
``--priority`` gives the class mix (e.g. ``0.6,0.4``) — class ``p`` starts
life at ladder rung ``p``.  The driver reports in-SLO fraction and goodput
for the admission-controlled run against a FIFO baseline over the identical
trace, per class and per tenant.

Declarative scenarios (``--spec spec.json``): a serialized ``RetrievalSpec``
fully defines the retrieval scenario — base distance, graph-construction
policy (incl. the ``blend``/``max``/``rankblend`` combinators), search
policy + rerank ``k_c``, builder/engine and scheduler knobs — while the CLI
keeps the workload/traffic knobs (sizes, batch, churn, utilization).  A
rerank spec (``search_policy != "none"``) is served through BOTH the batch
searcher and the slot scheduler (retire-time rerank).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.core import (ANNIndex, RetrievalSpec, dispatch_cache_size,
                        get_distance, knn_scan, recall_at_k)
from repro.core import telemetry
from repro.core.metrics import speedup_model
from repro.data.synthetic import lda_like_histograms, split_queries
from repro.launch.mesh import make_auto_mesh


# the checkout's own compile cache: a fixed path, so a later run (or a
# later process of the same run) finds what an earlier one compiled
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself); otherwise the cache goes to ``CACHE_DIR`` in the checkout.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


# ---------------------------------------------------------------------------
# arrival processes + serving-discipline simulators (shared with bench_serve)
# ---------------------------------------------------------------------------


def poisson_arrivals(n: int, rate: float, rng=None) -> np.ndarray:
    """Cumulative arrival times (seconds) of a rate-``rate`` Poisson process."""
    rng = rng or np.random.default_rng(0)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def multi_tenant_arrivals(n: int, rate: float, tenants: int, rng=None,
                          weights=None):
    """Merge independent per-tenant Poisson traces into one arrival stream.

    Each tenant runs its own Poisson process; tenant ``t`` gets
    ``weights[t] / sum(weights)`` of the total ``rate`` (uniform by
    default) and ``round(n * share)`` of the requests.  Returns
    ``(arrivals (n,), tenant_ids (n,))`` sorted by arrival time — the
    superposition the scheduler's deficit-round-robin queues see.
    """
    rng = rng or np.random.default_rng(0)
    tenants = max(1, int(tenants))
    w = np.ones((tenants,), float) if weights is None else np.asarray(
        weights, float)
    w = w / w.sum()
    counts = np.maximum(1, np.round(n * w).astype(int))
    while counts.sum() > n:
        counts[int(np.argmax(counts))] -= 1
    while counts.sum() < n:
        counts[int(np.argmin(counts))] += 1
    arr = np.concatenate([
        poisson_arrivals(int(c), rate * w[t], rng)
        for t, c in enumerate(counts)
    ])
    tid = np.concatenate([
        np.full((int(c),), t, np.int64) for t, c in enumerate(counts)
    ])
    order = np.argsort(arr, kind="stable")
    return arr[order], tid[order]


def qos_summary(results, slo_s: float, *, n_classes: int = 1,
                n_tenants: int = 1) -> dict:
    """In-SLO / goodput accounting over a list of ``SlotResult``.

    A request is in-SLO when it was served (not shed) within ``slo_s`` of
    its arrival; shed requests count as misses.  Goodput is in-SLO
    completions per second of trace makespan.  Adds per-class / per-tenant
    in-SLO breakdowns when more than one exists.
    """
    lat = np.asarray([r.latency for r in results], float)
    shed = np.asarray([r.shed for r in results], bool)
    ok = ~shed & (lat <= slo_s)
    t_end = max(r.t_done for r in results)
    t_start = min(r.t_arrival for r in results)
    out = {
        "n": len(results),
        "in_slo": round(float(ok.mean()), 4),
        "goodput_qps": round(float(ok.sum()) / max(t_end - t_start, 1e-9), 1),
        "shed_frac": round(float(shed.mean()), 4),
    }
    if n_classes > 1:
        prio = np.asarray([r.priority for r in results])
        out["in_slo_by_class"] = {
            int(c): round(float(ok[prio == c].mean()), 4)
            for c in range(n_classes) if (prio == c).any()
        }
    if n_tenants > 1:
        ten = np.asarray([r.tenant for r in results])
        out["in_slo_by_tenant"] = {
            int(t): round(float(ok[ten == t].mean()), 4)
            for t in range(n_tenants) if (ten == t).any()
        }
    return out


def latency_stats(lat_s, prefix: str = "") -> dict:
    """p50/p95/p99 latency percentiles (ms) of per-request latencies."""
    lat_s = np.asarray(lat_s, float)
    return {
        f"{prefix}p50_ms": round(1e3 * float(np.percentile(lat_s, 50)), 3),
        f"{prefix}p95_ms": round(1e3 * float(np.percentile(lat_s, 95)), 3),
        f"{prefix}p99_ms": round(1e3 * float(np.percentile(lat_s, 99)), 3),
    }


def simulate_static_batches(search, Q, arrivals, batch: int):
    """Static-batching baseline on a virtual clock, real measured compute.

    Requests are grouped into dispatch batches of ``batch`` in arrival
    order; a batch dispatches when its last member has arrived AND the
    single server is free (each batch then occupies the server for its
    measured ``search`` wall time — the lock-step engine runs every query
    until the SLOWEST one converges).  Latency of request r is
    ``t_batch_done - t_arrival[r]``: the fill wait + queue wait + straggler
    wait that continuous batching removes.  The virtual clock advances only
    by measured compute, so percentiles are free of host sleep jitter.

    Returns (latencies (n,), ids (n, k), n_evals (n,)) in request order.
    """
    Q = np.asarray(Q)
    arrivals = np.asarray(arrivals, float)
    n = Q.shape[0]
    order = np.argsort(arrivals, kind="stable")
    lat = np.zeros((n,), float)
    evals = np.zeros((n,), np.int64)
    rows = {}
    t_free = 0.0
    for lo in range(0, n, batch):
        sel = order[lo:lo + batch]
        t0 = time.perf_counter()
        out = search(Q[sel])
        jax.block_until_ready(out[0])
        service = time.perf_counter() - t0
        t_disp = max(t_free, float(arrivals[sel].max()))
        t_done = t_disp + service
        t_free = t_done
        lat[sel] = t_done - arrivals[sel]
        batch_ids = np.asarray(out[1])
        batch_evals = np.asarray(out[2])
        for j, r in enumerate(sel):
            rows[int(r)] = batch_ids[j]
            evals[r] = batch_evals[j]
    ids_out = np.stack([rows[j] for j in range(n)])
    return lat, ids_out, evals


def simulate_dynamic_batches(search, Q, arrivals, max_batch: int):
    """Dispatch-on-idle dynamic batching: the stronger classical baseline.

    Unlike static batching, a batch never waits to FILL: the moment the
    single server frees (or a request arrives at an idle server), every
    waiting request — up to ``max_batch`` — dispatches immediately.  What
    remains is the queue wait behind the in-service batch and the straggler
    wait inside it (the two the slot scheduler also removes).  Ragged
    dispatch sizes are padded up to power-of-two buckets so the jitted
    engine never recompiles mid-trace (each bucket is warmed first); the
    padded rows' compute is honestly charged to the batch, exactly like a
    fixed-shape production server.

    Returns (latencies (n,), ids (n, k), n_evals (n,)) in request order —
    the same contract as ``simulate_static_batches``.
    """
    Q = np.asarray(Q)
    arrivals = np.asarray(arrivals, float)
    n = Q.shape[0]
    order = np.argsort(arrivals, kind="stable")
    buckets = []
    b = 1
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch)
    for b in buckets:  # warm every dispatch shape outside the timed region
        # tile rows rather than slice: a bucket can exceed n (a dispatch of
        # n waiting requests pads UP to the bucket), and an unwarmed shape
        # would put its compile inside the timed region
        jax.block_until_ready(search(Q[np.arange(b) % n])[0])
    lat = np.zeros((n,), float)
    evals = np.zeros((n,), np.int64)
    rows = {}
    t_free = 0.0
    i = 0
    while i < n:
        # server idle: dispatch everything that has arrived by now
        t_disp = max(t_free, float(arrivals[order[i]]))
        j = i
        while j < n and arrivals[order[j]] <= t_disp and j - i < max_batch:
            j += 1
        sel = order[i:j]
        bucket = next(b for b in buckets if b >= len(sel))
        pad = np.concatenate([sel, np.repeat(sel[:1], bucket - len(sel))])
        t0 = time.perf_counter()
        out = search(Q[pad])
        jax.block_until_ready(out[0])
        service = time.perf_counter() - t0
        t_free = t_disp + service
        lat[sel] = t_free - arrivals[sel]
        batch_ids = np.asarray(out[1])
        batch_evals = np.asarray(out[2])
        for p, r in enumerate(sel):
            rows[int(r)] = batch_ids[p]
            evals[r] = batch_evals[p]
        i = j
    ids_out = np.stack([rows[j] for j in range(n)])
    return lat, ids_out, evals


def run_continuous(idx, Q, arrivals, *, k: int, ef_search: int, slots: int,
                   frontier: int, adaptive: bool = False,
                   steps_per_sync: int = 4, realtime: bool = False):
    """Serve the arrival trace through the slot scheduler.

    Returns (latencies (n,), ids (n, k), n_evals (n,)) in request order —
    the same contract as ``simulate_static_batches`` so callers can compare
    the two disciplines on identical traffic.
    """
    sched = idx.scheduler(k, ef_search, slots=slots, frontier=frontier,
                          adaptive=adaptive, steps_per_sync=steps_per_sync)
    res = sched.run_stream(np.asarray(Q), arrivals, realtime=realtime)
    lat = np.asarray([r.latency for r in res])
    ids = np.stack([r.ids for r in res])
    evals = np.asarray([r.n_evals for r in res])
    return lat, ids, evals


def run_churn(idx, Q, pool, *, rounds: int, insert_n: int, delete_n: int,
              batch: int, k: int, ef_search: int, frontier: int,
              verbose: bool = True):
    """Steady-state mutation endpoints: insert/delete/query churn rounds.

    ``pool``: (rounds * insert_n, m) fresh points to stream in.  Deletes
    draw uniformly from the currently alive ids.  Returns per-phase
    throughput plus a post-churn, post-compact recall audit against an
    exact scan of the surviving set.
    """
    online = idx.ensure_online()
    dist = idx.dist
    search = idx.searcher(k, ef_search, frontier=frontier, adaptive=False)
    jax.block_until_ready(search(Q[:batch])[0])  # steady-state timings
    rng = np.random.default_rng(0)
    ins_t, del_t, q_t, n_ins, n_del = 0.0, 0.0, [], 0, 0
    for r in range(rounds):
        chunk = pool[r * insert_n:(r + 1) * insert_n]
        t0 = time.time()
        jax.block_until_ready(idx.insert(chunk))
        ins_t += time.time() - t0
        n_ins += chunk.shape[0]

        alive_ids = np.flatnonzero(np.asarray(online.alive))
        victims = rng.choice(alive_ids, size=min(delete_n, len(alive_ids)),
                             replace=False)
        t0 = time.time()
        idx.delete(victims)
        jax.block_until_ready(online.alive)
        del_t += time.time() - t0
        n_del += len(victims)

        qb = Q[(r * batch) % max(1, Q.shape[0] - batch):][:batch]
        t0 = time.time()
        jax.block_until_ready(search(qb)[0])
        q_t.append((time.time() - t0) / qb.shape[0])

    t0 = time.time()
    compact_stats = idx.compact()
    compact_s = time.time() - t0

    # recall audit on the surviving set (exact scan ground truth)
    surv = np.flatnonzero(np.asarray(online.alive))
    _, true_pos = knn_scan(dist, Q, online.X[surv], k)
    true_global = surv[np.asarray(true_pos)]
    _, ids, _, _ = search(Q)
    stats = {
        "rounds": rounds,
        "inserted": n_ins,
        "deleted": n_del,
        "inserts_per_s": round(n_ins / max(ins_t, 1e-9), 1),
        "deletes_per_s": round(n_del / max(del_t, 1e-9), 1),
        "churn_p50_latency_ms": round(1e3 * float(np.percentile(q_t, 50)), 3),
        "compact_s": round(compact_s, 3),
        "compact_repaired": compact_stats["repaired"],
        "recall@k_after_churn": round(
            recall_at_k(np.asarray(ids), true_global), 4),
        "n_alive": online.n_alive,
        "capacity_used": online.n_total,
    }
    if verbose:
        print(f"[serve/churn] {stats}")
    return stats


def build_and_serve(*, spec: RetrievalSpec | None = None,
                    distance: str = "kl", n_db: int = 20_000, dim: int = 32,
                    n_queries: int = 256, batch: int = 64, k: int = 10,
                    ef_search: int = 96, index_sym: str = "none",
                    builder: str = "nndescent", build_engine: str = "wave",
                    wave: int = 64, engine: str = "batched",
                    frontier: int = 4, n_entries: int = 4,
                    capacity: int | None = None, churn_rounds: int = 0,
                    churn_insert: int = 256, churn_delete: int = 200,
                    continuous: bool = False, slots: int = 48,
                    cont_frontier: int = 12, adaptive_frontier: bool = False,
                    utilization: float = 0.4, slo_ms: float | None = None,
                    tenants: int = 1, priority_mix=None, ladder_source=None,
                    seed: int = 0, verbose: bool = True):
    if spec is None:
        spec = RetrievalSpec(
            distance=distance, build_policy=index_sym, builder=builder,
            build_engine=build_engine, wave=wave, NN=15, ef_construction=100,
            n_entries=n_entries, capacity=capacity, k=k, ef_search=ef_search,
            engine=engine, frontier=frontier, slots=slots,
            sched_frontier=cont_frontier, adaptive=adaptive_frontier,
            steps_per_sync=4,
        )
    else:
        # the spec IS the scenario; the CLI keeps workload/traffic knobs
        distance, k, ef_search = spec.distance, spec.k, spec.ef_search
        engine, frontier = spec.engine, spec.frontier
        slots, cont_frontier = spec.slots, spec.sched_frontier
        adaptive_frontier, capacity = spec.adaptive, spec.capacity
    key = jax.random.PRNGKey(seed)
    pool_n = churn_rounds * churn_insert
    data = lda_like_histograms(key, n_db + n_queries + pool_n, dim)
    Q, rest = split_queries(data, n_queries, jax.random.fold_in(key, 1))
    X, pool = rest[:n_db], rest[n_db:]
    dist = get_distance(distance)
    if churn_rounds > 0 and capacity is None:
        capacity = n_db + pool_n
    if capacity != spec.capacity:
        spec = spec.replace(capacity=capacity)
    if capacity is not None and engine != "batched":
        raise ValueError("mutable (--capacity / --churn-rounds) serving "
                         "requires --engine batched")

    t0 = time.time()
    idx = ANNIndex.build(X, dist, spec=spec, key=jax.random.fold_in(key, 2))
    build_s = time.time() - t0
    # the batch/static/dynamic serving phases are the fixed-frontier
    # BASELINE: pin adaptive off so a spec (or --adaptive-frontier) that
    # turns on the per-query width policy changes only the continuous path,
    # never the yardstick the gated ratios divide by
    search = idx.searcher(k, ef_search, engine=engine, frontier=frontier,
                          adaptive=False)
    # warm the jit cache on every batch shape served (full batches plus a
    # possible ragged tail) so latency percentiles reflect steady state,
    # not compilation
    t0 = time.time()
    jax.block_until_ready(search(Q[:batch])[0])
    tail = n_queries % batch
    if tail:
        jax.block_until_ready(search(Q[:tail])[0])
    compile_s = time.time() - t0

    # ground truth for quality accounting
    _, true_ids = knn_scan(dist, Q, X, k)

    served, evals, lat, batch_s = 0, [], [], []
    all_ids = []
    for lo in range(0, n_queries, batch):
        qb = Q[lo:lo + batch]
        t0 = time.time()
        d, ids, n_evals, hops = search(qb)
        jax.block_until_ready(d)
        batch_s.append(time.time() - t0)
        lat.append(batch_s[-1] / qb.shape[0])
        served += qb.shape[0]
        evals.append(np.asarray(n_evals))
        all_ids.append(np.asarray(ids))

    recall = recall_at_k(np.concatenate(all_ids), np.asarray(true_ids))
    stats = {
        "build_s": round(build_s, 2),
        "compile_s": round(compile_s, 2),
        "engine": engine,
        "served": served,
        "recall@k": round(recall, 4),
        "eval_reduction": round(speedup_model(n_db, np.concatenate(evals)), 1),
        "p50_latency_ms": round(1e3 * float(np.percentile(lat, 50)), 3),
        "p99_latency_ms": round(1e3 * float(np.percentile(lat, 99)), 3),
        "spec": spec.to_dict(),
        "spec_fingerprint": spec.fingerprint(),
    }
    if verbose:
        print(f"[serve] dist={distance} build={spec.build_policy} "
              f"search={spec.search_policy} n={n_db} -> {stats}")

    if continuous:
        # Poisson load at `utilization` x the measured static capacity, so
        # the offered traffic adapts to the machine running the driver
        rate = utilization * batch / float(np.median(batch_s))
        if adaptive_frontier:
            # the adaptive engine trades steps for evaluations (sequential
            # expansion while the beam improves): anchor its offered load
            # to ITS measured capacity, or the queue saturates and reports
            # queueing delay instead of scheduler latency
            probe = idx.scheduler(k, ef_search, slots=slots,
                                  frontier=cont_frontier, adaptive=True,
                                  steps_per_sync=4)
            n_probe = min(96, n_queries)
            res = probe.run_stream(np.asarray(Q[:n_probe]))
            # the stream's virtual clock counts tick compute only (warmup
            # compiles are excluded), so max t_done is the drain time
            rate = min(rate, utilization * n_probe /
                       max(r.t_done for r in res))
        arrivals = poisson_arrivals(n_queries, rate, np.random.default_rng(1))
        s_lat, s_ids, _ = simulate_static_batches(search, Q, arrivals, batch)
        d_lat, d_ids, _ = simulate_dynamic_batches(search, Q, arrivals, batch)
        # the slot engine's latency is (steps x tick), not batch service, so
        # it prefers a fatter frontier than the dispatch-batched engine
        c_lat, c_ids, c_evals = run_continuous(
            idx, Q, arrivals, k=k, ef_search=ef_search, slots=slots,
            frontier=cont_frontier, adaptive=adaptive_frontier,
        )
        # the scheduler's tick log (the newest): each part's mean and max
        phases = telemetry.latest().summary()
        cont = {
            "offered_qps": round(rate, 1),
            "slots": slots,
            "frontier": cont_frontier,
            "adaptive_frontier": adaptive_frontier,
            "recall@k": round(recall_at_k(c_ids, np.asarray(true_ids)), 4),
            "eval_reduction": round(speedup_model(n_db, c_evals), 1),
            **latency_stats(c_lat),
            "static_p99_ms": latency_stats(s_lat)["p99_ms"],
            "dynamic_p99_ms": latency_stats(d_lat)["p99_ms"],
            "dynamic_recall@k": round(
                recall_at_k(d_ids, np.asarray(true_ids)), 4),
            "p99_speedup_vs_static": round(
                float(np.percentile(s_lat, 99) / np.percentile(c_lat, 99)), 2),
            "p99_speedup_vs_dynamic": round(
                float(np.percentile(d_lat, 99) / np.percentile(c_lat, 99)), 2),
            "tick_phases": {
                "ticks": phases["ticks"],
                **{k: {p: round(v, 4) for p, v in phases[k].items()}
                   for k in ("mean_ms", "max_ms")}},
        }
        stats["continuous"] = cont
        if verbose:
            print(f"[serve/continuous] {cont}")

        if slo_ms is not None:
            from repro.core.spec import demotion_ladder

            ladder = demotion_ladder(spec, ladder_source)
            mix = np.asarray([1.0] if not priority_mix else priority_mix,
                             float)
            mix = mix / mix.sum()
            rng_q = np.random.default_rng(7)
            q_arr, t_ids = multi_tenant_arrivals(
                n_queries, rate, tenants, rng_q)
            prios = rng_q.choice(len(mix), size=n_queries, p=mix)
            sched = idx.scheduler(
                spec=spec, ladder=ladder, slo_ms=slo_ms,
                background=idx.online is not None)
            res = sched.run_stream(Q, q_arr, tenants=t_ids, priorities=prios)
            # FIFO baseline: same trace, no admission control / demotion
            res_f = idx.scheduler(spec=spec).run_stream(Q, q_arr)
            fifo = qos_summary(res_f, slo_ms * 1e-3)
            qos = {
                "slo_ms": slo_ms,
                "tenants": max(1, int(tenants)),
                "ladder": [r.name for r in sched.rungs],
                **qos_summary(res, slo_ms * 1e-3, n_classes=len(mix),
                              n_tenants=tenants),
                "demoted": sched.qos_stats["demoted"],
                "shed": sched.qos_stats["shed"],
                "fifo_in_slo": fifo["in_slo"],
                "fifo_goodput_qps": fifo["goodput_qps"],
            }
            stats["qos"] = qos
            if verbose:
                print(f"[serve/qos] {qos}")

    if churn_rounds > 0:
        stats["churn"] = run_churn(
            idx, Q, pool, rounds=churn_rounds, insert_n=churn_insert,
            delete_n=churn_delete, batch=batch, k=k, ef_search=ef_search,
            frontier=frontier, verbose=verbose,
        )
    return stats


def build_and_serve_sharded(*, distance: str = "kl", n_db: int = 4096,
                            dim: int = 32, n_queries: int = 256, k: int = 10,
                            ef_search: int = 96, slots: int = 32,
                            shards: int = 4, steps_per_sync: int = 1,
                            drop_shards: int = 0, NN: int = 15,
                            nnd_iters: int = 8, compare_replicated: bool = True,
                            seed: int = 0, verbose: bool = True):
    """Scatter-gather serving: the slot scheduler over a SHARDED corpus.

    Each of ``shards`` devices owns ``n_db / shards`` rows (padded when not
    divisible) and its own local subgraph; every scheduler tick advances all
    shards' beams in lock-step under ``shard_map`` and ends in an all_gather
    + merge sync that rebuilds each slot's replicated global top-k.  All
    device state is fixed-shape, so steady-state serving keeps exactly one
    executable per jitted path (reported in the stats).

    When ``compare_replicated`` is set the same trace is also served by the
    replicated single-device ``SlotScheduler`` over one global graph of the
    union corpus, reporting the recall gap the serving gate bounds (0.005).
    """
    from repro.core.distributed import (ShardedSlotScheduler,
                                        build_local_subgraphs)

    if len(jax.devices()) < shards:
        raise RuntimeError(
            f"--shards {shards} needs {shards} devices, found "
            f"{len(jax.devices())}; on CPU re-run with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={shards}")
    mesh = make_auto_mesh((shards,), ("data",))
    key = jax.random.PRNGKey(seed)
    data = lda_like_histograms(key, n_db + n_queries, dim)
    Q, X = split_queries(data, n_queries, jax.random.fold_in(key, 1))
    X = X[:n_db]
    dist = get_distance(distance)

    t0 = time.time()
    nbrs = build_local_subgraphs(mesh, dist, X, NN=NN, nnd_iters=nnd_iters,
                                 key=jax.random.fold_in(key, 2))
    sched = ShardedSlotScheduler(
        mesh, dist, X, neighbors=nbrs, slots=slots, ef=ef_search, k=k,
        steps_per_sync=steps_per_sync, drop_shards=drop_shards)
    build_s = time.time() - t0

    _, true_ids = knn_scan(dist, Q, X, k)
    res = sched.run_stream(np.asarray(Q))
    ids = np.stack([r.ids for r in res])
    lat = np.asarray([r.latency for r in res])
    evals = np.asarray([r.n_evals for r in res])
    stats = {
        "shards": shards,
        "n_db": n_db,
        "rows_per_shard": sched.n_local,
        "build_s": round(build_s, 2),
        "slots": slots,
        "steps_per_sync": steps_per_sync,
        "drop_shards": drop_shards,
        "recall@k": round(recall_at_k(ids, np.asarray(true_ids)), 4),
        "eval_reduction": round(speedup_model(n_db, evals), 1),
        **latency_stats(lat),
        # the zero-recompile contract, made observable
        "step_executables": dispatch_cache_size(sched._step),
        "admit_executables": dispatch_cache_size(sched._admit),
        # distinct devices holding a shard of the corpus reps (== shards
        # when each shard sits on its own device)
        "shard_devices": len({
            sh.device for sh in
            jax.tree.leaves(sched._consts)[0].addressable_shards}),
    }
    if compare_replicated:
        idx = ANNIndex.build(X, dist, builder="nndescent", NN=NN,
                             nnd_iters=nnd_iters,
                             key=jax.random.fold_in(key, 3))
        repl = idx.scheduler(k=k, ef_search=ef_search, slots=slots)
        res_r = repl.run_stream(np.asarray(Q))
        ids_r = np.stack([r.ids for r in res_r])
        r_repl = recall_at_k(ids_r, np.asarray(true_ids))
        stats["replicated_recall@k"] = round(r_repl, 4)
        stats["recall_gap"] = round(r_repl - recall_at_k(
            ids, np.asarray(true_ids)), 4)
    if verbose:
        print(f"[serve/sharded] dist={distance} n={n_db} x{shards} -> {stats}")
    return stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", default=None,
                    help="path to a RetrievalSpec JSON file OR an autotune "
                         "tuned-spec artifact (bench_autotune / "
                         "TuneResult.save — verified by fingerprint); fully "
                         "defines the retrieval scenario (distance, "
                         "build/search policies, builder/engine/scheduler "
                         "knobs) — the remaining flags keep workload/traffic "
                         "control and may not be combined with it")
    # scenario flags: default None so an explicit use can be detected and
    # rejected when --spec already defines the scenario (a silently-ignored
    # --ef would make the user believe they swept something they didn't)
    ap.add_argument("--distance", default=None)
    ap.add_argument("--n-db", type=int, default=20_000)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--ef", type=int, default=None, dest="ef_search")
    ap.add_argument("--index-sym", default=None)
    ap.add_argument("--builder", default=None, choices=["nndescent", "swgraph"])
    ap.add_argument("--build-engine", default=None, choices=["wave", "sequential"],
                    help="swgraph construction engine (wave-parallel vs reference)")
    ap.add_argument("--wave", type=int, default=None,
                    help="points inserted per construction wave (swgraph builder)")
    ap.add_argument("--engine", default=None, choices=["batched", "reference"])
    ap.add_argument("--frontier", type=int, default=None,
                    help="beam candidates expanded per lock-step (batched engine)")
    ap.add_argument("--entries", type=int, default=None, dest="n_entries",
                    help="entry points seeded per query (medoid + random)")
    ap.add_argument("--capacity", type=int, default=None,
                    help="mutable-index slot budget (enables insert/delete; "
                         "defaults to n_db + total churn inserts)")
    ap.add_argument("--churn-rounds", type=int, default=0,
                    help="rounds of steady-state insert/delete/query churn "
                         "after the initial serve phase")
    ap.add_argument("--churn-insert", type=int, default=256,
                    help="points inserted per churn round")
    ap.add_argument("--churn-delete", type=int, default=200,
                    help="points tombstoned per churn round")
    ap.add_argument("--continuous", action="store_true",
                    help="also serve a Poisson arrival trace through the "
                         "slot-recycling scheduler and compare latency "
                         "percentiles against static batching")
    ap.add_argument("--slots", type=int, default=None,
                    help="concurrent in-flight queries in the scheduler")
    ap.add_argument("--cont-frontier", type=int, default=None,
                    help="per-slot frontier for the continuous scheduler "
                         "(fatter than --frontier: slot latency is steps x "
                         "tick, not batch service)")
    ap.add_argument("--adaptive-frontier", action="store_true", default=None,
                    help="per-slot adaptive frontier width (fewer distance "
                         "evaluations at equal recall)")
    ap.add_argument("--utilization", type=float, default=0.4,
                    help="Poisson arrival rate as a fraction of the measured "
                         "static-batch capacity")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-request latency budget (ms): serve the "
                         "continuous trace through SLO-aware admission "
                         "control (demote-then-shed) and report in-SLO "
                         "fraction / goodput vs a FIFO baseline")
    ap.add_argument("--tenants", type=int, default=1,
                    help="independent per-tenant Poisson traces merged into "
                         "the offered load, served under deficit-round-"
                         "robin fairness (QoS path, needs --slo-ms)")
    ap.add_argument("--priority", default=None,
                    help="comma-separated QoS class mix, highest class "
                         "first (e.g. 0.6,0.4): class p starts at demotion-"
                         "ladder rung p (QoS path, needs --slo-ms)")
    ap.add_argument("--shards", type=int, default=0,
                    help="serve scatter-gather from N corpus shards through "
                         "the sharded slot scheduler (one device per shard; "
                         "on CPU set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N in the environment)")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed of the generated corpus and queries")
    ap.add_argument("--drop-shards", type=int, default=0,
                    help="freeze the last s shards at admission (bounded-"
                         "staleness straggler model, sharded path)")
    ap.add_argument("--steps-per-sync", type=int, default=1,
                    help="beam lock-steps per cross-shard sync point "
                         "(sharded path)")
    args = ap.parse_args(argv)
    if args.shards:
        bad = [f for f, v in [("--spec", args.spec),
                              ("--continuous", args.continuous or None),
                              ("--churn-rounds", args.churn_rounds or None),
                              ("--slo-ms", args.slo_ms)] if v]
        if bad:
            ap.error(f"--shards is its own serving path; incompatible "
                     f"with {bad}")
        init_compile_cache()
        return build_and_serve_sharded(
            n_db=args.n_db, dim=args.dim, n_queries=args.queries,
            shards=args.shards, drop_shards=args.drop_shards,
            steps_per_sync=args.steps_per_sync, seed=args.seed,
            **{k: v for k, v in [("distance", args.distance),
                                 ("ef_search", args.ef_search),
                                 ("slots", args.slots)] if v is not None})
    if args.slo_ms is not None and not args.continuous:
        ap.error("--slo-ms needs --continuous (it shapes the arrival trace)")
    if (args.tenants != 1 or args.priority) and args.slo_ms is None:
        ap.error("--tenants / --priority need --slo-ms (the QoS path)")
    priority_mix = None
    if args.priority:
        try:
            priority_mix = [float(x) for x in args.priority.split(",")]
        except ValueError:
            ap.error(f"--priority expects comma-separated fractions, "
                     f"got {args.priority!r}")
        if not priority_mix or min(priority_mix) <= 0:
            ap.error("--priority fractions must be positive")
    scenario = {
        "distance": args.distance, "ef_search": args.ef_search,
        "index_sym": args.index_sym, "builder": args.builder,
        "build_engine": args.build_engine, "wave": args.wave,
        "engine": args.engine, "frontier": args.frontier,
        "n_entries": args.n_entries, "capacity": args.capacity,
        "slots": args.slots, "cont_frontier": args.cont_frontier,
        "adaptive_frontier": args.adaptive_frontier,
    }
    spec = None
    ladder_source = None
    if args.spec:
        clash = sorted(k for k, v in scenario.items() if v is not None)
        if clash:
            ap.error(f"--spec defines the scenario; conflicting flags: {clash}")
        from repro.core import load_spec

        # accepts both a plain RetrievalSpec JSON and a tuned-spec artifact
        # (kind "repro.autotune/tuned-spec@1", fingerprint-verified)
        spec = load_spec(args.spec)
        with open(args.spec) as fh:
            doc = json.load(fh)
        if isinstance(doc, dict) and "frontier" in doc:
            # a tuned artifact's Pareto frontier feeds the demotion ladder
            ladder_source = doc
    init_compile_cache()
    return build_and_serve(
        spec=spec, seed=args.seed,
        n_db=args.n_db, dim=args.dim, n_queries=args.queries,
        batch=args.batch, churn_rounds=args.churn_rounds,
        churn_insert=args.churn_insert, churn_delete=args.churn_delete,
        continuous=args.continuous, utilization=args.utilization,
        slo_ms=args.slo_ms, tenants=args.tenants,
        priority_mix=priority_mix, ladder_source=ladder_source,
        **{k: v for k, v in scenario.items() if v is not None})


if __name__ == "__main__":
    main()
