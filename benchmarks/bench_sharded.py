"""Sharded serving benchmark: scatter-gather slot scheduler vs one device.

The ISSUE-8 acceptance workload: a corpus of 4x one shard's rows served by
the ``ShardedSlotScheduler`` (4 shards under ``shard_map``, per-shard local
subgraphs, all_gather + merge at every sync point), compared against

  * single_shard — the replicated ``SlotScheduler`` over ONE shard's worth
                   of rows on one device: the "single-device number" the
                   p99 gate is anchored to.  A shard of the scatter-gather
                   system does exactly this much per-tick work, so when
                   each shard owns a device the sharded tick costs the
                   same and any latency excess is extra ticks (stragglers
                   + sync granularity).
  * replicated   — the replicated ``SlotScheduler`` over the FULL union
                   corpus with one global graph: the recall yardstick the
                   serving gate (0.005) is measured against.

Latency is measured on the DETERMINISTIC virtual tick clock (every
scheduler tick costs ``TICK_COST``, the overload bench's mode): the
lock-step tick runs fixed-shape full-batch compute on every shard
regardless of occupancy, so ticks-to-retire is the machine-independent
latency unit, and it equals wall clock when each shard owns its own
device.  Wall-clock percentiles are recorded UNGATED — on CI's forced
host devices (one physical core) the shards serialize, so sharded wall
clock is ~n_shards x the per-shard number by construction.

Gated metrics (``compare_bench.py`` "sharded" schema): recall@10 of the
sharded and replicated runs (abs tolerance) and ``p99_headroom`` =
1.5 x p99_single / p99_sharded on the tick clock (relative tolerance;
>= 1 means the acceptance bound "p99 within 1.5x of the single-device
number" holds, and the bench hard-asserts it).  The bench also
hard-asserts the recall gate and the zero-recompile contract (exactly one
executable per jitted path after two full streams).  Results land in
BENCH_sharded.json; CI compares the quick run against
benchmarks/baselines/BENCH_sharded.quick.json.

Where the backend already has ``SHARDS`` devices (a 4-chip host) the
measurement runs in this process: a chip belongs to one process, and the
parent holds it.  The CPU rehearsal runs it in a SUBPROCESS instead:
``--xla_force_host_platform_device_count`` is read once at backend
initialisation, and by the time ``benchmarks.run`` reaches this bench an
earlier bench has usually already initialised a single-device backend.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

SHARDS = 4
K, EF_S, NN, NND_ITERS = 10, 64, 10, 6
# identical frontier on every scheduler: the replicated SlotScheduler's
# default is the fatter spec.sched_frontier, and a frontier mismatch would
# turn the gated tick ratio into a frontier comparison
SLOTS, FRONTIER, STEPS_PER_SYNC = 16, 8, 1
TICK_COST = 1e-3  # one virtual millisecond per scheduler tick
P99_BOUND = 1.5  # acceptance: sharded p99 <= 1.5x the single-device p99


def run_sharded(out_path: str = "BENCH_sharded.json", quick: bool = False):
    """Measure in-process on >= SHARDS devices; on a CPU backend spawn the
    measurement child with the forced device count, collect."""
    import jax

    if jax.device_count() >= SHARDS:
        return _measure(out_path, quick)
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"the sharded bench needs {SHARDS} devices, found "
            f"{jax.device_count()} {jax.default_backend()} device(s)")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={SHARDS}")
    cmd = [sys.executable, "-m", "benchmarks.bench_sharded", "--child",
           "--out", out_path]
    if quick:
        cmd.append("--quick")
    subprocess.run(cmd, env=env, check=True)
    with open(out_path) as fh:
        return json.load(fh)


def _measure(out_path: str, quick: bool):
    import jax
    import numpy as np

    from repro.core import (ANNIndex, dispatch_cache_size, knn_scan,
                            recall_at_k, recompile_guard)
    from repro.core.distributed import (ShardedSlotScheduler,
                                        build_local_subgraphs)
    from repro.core.metrics import speedup_model
    from repro.data.synthetic import lda_like_histograms, split_queries
    from repro.launch.mesh import make_auto_mesh
    from repro.launch.serve import latency_stats

    n, n_req, dim = (2048, 96, 32) if quick else (4096, 192, 32)
    n_local = n // SHARDS
    key = jax.random.PRNGKey(0)
    data = lda_like_histograms(key, n + n_req, dim)
    Q, X = split_queries(data, n_req, jax.random.fold_in(key, 1))
    Qn, X = np.asarray(Q), X[:n]
    from repro.core import get_distance

    dist = get_distance("kl")
    mesh = make_auto_mesh((SHARDS,), ("data",))

    def serve(sched):
        """Two full streams on the tick clock + one wall-clock stream."""
        res = sched.run_stream(Qn, tick_cost=TICK_COST)
        res2 = sched.run_stream(Qn[::-1].copy(), tick_cost=TICK_COST)
        wall = sched.run_stream(Qn)
        ids = np.stack([r.ids for r in res])
        lat = np.asarray([r.latency for r in res + res2])
        wall_lat = np.asarray([r.latency for r in wall])
        evals = np.asarray([r.n_evals for r in res])
        return ids, lat, wall_lat, evals

    # --- sharded: 4 shards, local subgraphs, scatter-gather serving
    nbrs = build_local_subgraphs(mesh, dist, X, NN=NN, nnd_iters=NND_ITERS,
                                 key=jax.random.fold_in(key, 2))
    sched = ShardedSlotScheduler(mesh, dist, X, neighbors=nbrs, slots=SLOTS,
                                 ef=EF_S, k=K, frontier=FRONTIER,
                                 steps_per_sync=STEPS_PER_SYNC)
    # zero-recompile contract: one executable per jitted path across three
    # full streams (raises RecompileError on violation)
    with recompile_guard(sched._step, sched._admit):
        s_ids, s_lat, s_wall, s_evals = serve(sched)

    # --- single_shard: one shard's rows, one device (the latency anchor)
    idx_1 = ANNIndex.build(X[:n_local], dist, builder="nndescent", NN=NN,
                           nnd_iters=NND_ITERS,
                           key=jax.random.fold_in(key, 3))
    one = idx_1.scheduler(k=K, ef_search=EF_S, slots=SLOTS,
                          frontier=FRONTIER, steps_per_sync=STEPS_PER_SYNC)
    _, o_lat, o_wall, _ = serve(one)

    # --- replicated: one global graph of the union corpus (recall anchor)
    idx_r = ANNIndex.build(X, dist, builder="nndescent", NN=NN,
                           nnd_iters=NND_ITERS,
                           key=jax.random.fold_in(key, 4))
    repl = idx_r.scheduler(k=K, ef_search=EF_S, slots=SLOTS,
                           frontier=FRONTIER, steps_per_sync=STEPS_PER_SYNC)
    r_ids, r_lat, r_wall, _ = serve(repl)

    _, true_ids = knn_scan(dist, Qn, X, K)
    true_np = np.asarray(true_ids)
    r_sharded = recall_at_k(s_ids, true_np)
    r_repl = recall_at_k(r_ids, true_np)
    assert r_sharded >= r_repl - 0.005, (
        f"sharded recall {r_sharded:.4f} below replicated {r_repl:.4f} "
        f"- 0.005 (the serving gate)")

    p99_s = float(np.percentile(s_lat, 99))
    p99_1 = float(np.percentile(o_lat, 99))
    ratio = p99_s / p99_1
    assert ratio <= P99_BOUND, (
        f"sharded p99 {ratio:.2f}x the single-device number "
        f"(bound {P99_BOUND}x, tick clock)")

    single_shard = {
        "n_db": n_local,
        **latency_stats(o_lat, "tick_"),
        **latency_stats(o_wall, "wall_"),
    }
    replicated = {
        "n_db": n,
        "recall@10": round(r_repl, 4),
        **latency_stats(r_lat, "tick_"),
        **latency_stats(r_wall, "wall_"),
    }
    sharded = {
        "n_db": n,
        "shards": SHARDS,
        "rows_per_shard": sched.n_local,
        "recall@10": round(r_sharded, 4),
        "recall_gap_vs_replicated": round(r_repl - r_sharded, 4),
        "eval_reduction": round(speedup_model(n, s_evals), 1),
        "p99_ratio_vs_single": round(ratio, 3),
        "p99_headroom": round(P99_BOUND / ratio, 3),
        "step_executables": dispatch_cache_size(sched._step),
        "admit_executables": dispatch_cache_size(sched._admit),
        **latency_stats(s_lat, "tick_"),
        **latency_stats(s_wall, "wall_"),
    }
    print(f"[sharded] single_shard: n={n_local} "
          f"tick_p99={single_shard['tick_p99_ms']:.1f}ms")
    print(f"[sharded] replicated  : n={n} recall={r_repl:.4f} "
          f"tick_p99={replicated['tick_p99_ms']:.1f}ms")
    print(f"[sharded] sharded     : n={n} x{SHARDS} recall={r_sharded:.4f} "
          f"tick_p99={sharded['tick_p99_ms']:.1f}ms "
          f"({ratio:.2f}x single-device, bound {P99_BOUND}x; "
          f"headroom {sharded['p99_headroom']:.2f})")

    result = {
        "workload": {"distance": "kl", "n_db": n, "n_requests": n_req,
                     "dim": dim, "k": K, "NN": NN, "nnd_iters": NND_ITERS,
                     "ef_search": EF_S, "slots": SLOTS, "frontier": FRONTIER,
                     "steps_per_sync": STEPS_PER_SYNC, "shards": SHARDS,
                     "tick_cost_s": TICK_COST,
                     "backend": jax.default_backend(),
                     "devices": jax.device_count()},
        "single_shard": single_shard,
        "replicated": replicated,
        "sharded": sharded,
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true",
                    help="run the measurement in THIS process (the parent "
                         "sets the forced device count in XLA_FLAGS first)")
    ap.add_argument("--out", default="BENCH_sharded.json")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        _measure(args.out, args.quick)
    else:
        run_sharded(args.out, args.quick)


if __name__ == "__main__":
    main()
