"""Smoke run of the retrieval service on a TPU: the served path, end to end.

    python chip_smoke.py                 # one chip: wiki-128/KL, n = 500,000
    python chip_smoke.py --shards 4 --n-db 250000   # four chips: sharded

One chip: the wiki-128/KL deployment (``configs/paper_swgraph.WIKI128_KL``,
graph built by the SW-graph wave engine, see ``_spec``) is built and served
through ``repro.launch.serve.build_and_serve`` with ``continuous=True`` —
``ANNIndex``/``RetrievalSpec`` build, the batch searcher and the slot
scheduler — on a seeded LDA-like corpus, and both
paths must reach recall@10 >= 0.90 against the exact (HIGHEST-precision)
``knn_scan``.  A first, short phase at n = 20,000 checks that the search
and scheduler steps compile to the Pallas kernel (``tpu_custom_call``) and
that the kernel path (``use_pallas=None``) agrees with the jnp path
(``use_pallas=False``): distances within 1e-4 relative, recall within 0.005.

``--shards 4`` runs only ``build_and_serve_sharded`` and its replicated
comparator: recall gap <= 0.005, one executable per jitted path, and one
corpus shard on each of the four devices.

Every check is a hard assert.  The lines printed before the last are a
smoke run's record, not a benchmark.  The last line is a JSON object naming
the device.  With no TPU the script exits non-zero before any work.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
MIN_N_DB = 250_000  # the smallest corpus the one-chip run may be cut to
# The deployment's corpus is 10^6 rows; the run is cut to 500,000, the
# config's own n_db, because at 10^6 the SW-graph build and serving did not
# finish inside the run's 1,200 s limit on one v5e chip (NN-descent, which
# builds 10^6 rows in ~100 s, reaches recall@10 0.43 there).
N_DB = 500_000
CHECK_N_DB = 20_000  # the kernel-vs-jnp phase
CHECK_EF = 128  # its beam width: the phase checks agreement, not recall


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _cache_counter():
    """Counts persistent compile-cache hits and misses in this process."""
    import jax

    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listen(event, **_):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listen)
    return counts


def _spec(ef_search: int):
    """WIKI128_KL's distance, NN, ef_construction and k, built by the
    paper's SW-graph (wave engine).  The config's NN-descent graph at NN = 15
    reaches recall@10 0.43 at n = 10^6 even at ef = 2048 on this corpus;
    the SW-graph reaches 0.93 at n = 250,000 (ef = 2048) and 0.97 (4096)."""
    from repro.configs.paper_swgraph import WIKI128_KL as cfg
    from repro.core import RetrievalSpec

    return RetrievalSpec(
        distance=cfg.distance, builder="swgraph", build_engine="wave",
        wave=128, NN=cfg.NN, ef_construction=cfg.ef_construction, k=cfg.k,
        ef_search=ef_search, engine="batched", frontier=4, slots=48,
        sched_frontier=12, steps_per_sync=4, n_entries=4,
    )


def serve_phase(spec, n_db: int, n_queries: int, seed: int) -> dict:
    from repro.launch.serve import build_and_serve

    t0 = time.time()
    stats = build_and_serve(spec=spec, n_db=n_db, dim=128,
                            n_queries=n_queries, batch=64, continuous=True,
                            seed=seed, verbose=False)
    r_batch = stats["recall@k"]
    r_sched = stats["continuous"]["recall@k"]
    _say(f"serve: n_db={n_db} build_s={stats['build_s']} "
         f"compile_s={stats['compile_s']} recall@10 batch={r_batch} "
         f"scheduler={r_sched} wall_s={time.time() - t0:.1f}")
    assert r_batch >= 0.90, f"batch searcher recall@10 {r_batch} < 0.90"
    assert r_sched >= 0.90, f"slot scheduler recall@10 {r_sched} < 0.90"
    return stats


def kernel_phase(spec, seed: int) -> None:
    """Kernel path vs jnp path on one corpus and one query set."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ANNIndex, knn_scan, recall_at_k
    from repro.core.batched_beam import make_step_searcher
    from repro.data.synthetic import lda_like_histograms, split_queries
    from repro.kernels.ops import frontier_gather_scores, kernel_rows

    spec = spec.replace(ef_search=CHECK_EF)
    key = jax.random.PRNGKey(seed)
    data = lda_like_histograms(key, CHECK_N_DB + 256, 128)
    Q, X = split_queries(data, 256, jax.random.fold_in(key, 1))
    idx = ANNIndex.build(X, spec=spec, key=jax.random.fold_in(key, 2))
    dist = idx.dist
    _, true_ids = knn_scan(dist, Q, X, spec.k)

    def searcher(use_pallas):
        return make_step_searcher(dist, idx.neighbors, idx.X, spec.ef_search,
                                  spec.k, entries=idx.entries,
                                  frontier=spec.frontier,
                                  use_pallas=use_pallas)

    kern, ref = searcher(None), searcher(False)
    hlo = kern.func.lower(*kern.args, Q).compile().as_text()
    assert "tpu_custom_call" in hlo, "search step did not compile to the kernel"
    sched = idx.scheduler(spec=spec)
    g = sched.graph_fn()
    hlo = sched._step.lower(sched.state, g.neighbors, g.consts,
                            sched._kernel_rows(g.consts)).compile().as_text()
    assert "tpu_custom_call" in hlo, "scheduler step did not compile to the kernel"

    # the scoring call itself, on a (B, R) id block with -1 padding
    consts = dist.prep_scan(X)
    qc = jax.vmap(dist.prep_query)(Q)
    ids = jax.random.randint(jax.random.fold_in(key, 3), (256, 120), -1,
                             CHECK_N_DB)
    s_kern, s_ref = (np.asarray(frontier_gather_scores(
        dist, ids, qc["rep"], qc["bias"], consts["rep"], consts["bias"],
        x_rows=kernel_rows(dist, consts, p))) for p in (None, False))
    np.testing.assert_array_equal(np.isinf(s_kern), np.asarray(ids < 0))
    ok = np.asarray(ids >= 0)
    np.testing.assert_allclose(s_kern[ok], s_ref[ok], rtol=1e-4, atol=1e-6)

    d_k, i_k, _, _ = kern(Q)
    d_r, i_r, _, _ = ref(Q)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), rtol=1e-4,
                               atol=1e-6)
    truth = np.asarray(true_ids)
    r_k = recall_at_k(np.asarray(i_k), truth)
    r_r = recall_at_k(np.asarray(i_r), truth)
    score_err = float(np.max(np.abs(s_kern[ok] - s_ref[ok])
                             / np.maximum(np.abs(s_ref[ok]), 1e-6)))
    _say(f"kernel check: n_db={CHECK_N_DB} tpu_custom_call in search and "
         f"scheduler steps; score max rel err={score_err:.3g}; recall@10 "
         f"kernel={r_k} jnp={r_r}; ids equal="
         f"{bool(jnp.all(i_k == i_r))}")
    assert abs(r_k - r_r) <= 0.005, f"kernel/jnp recall gap {r_k - r_r}"


def sharded_phase(shards: int, n_db: int, n_queries: int, seed: int) -> None:
    from repro.launch.serve import build_and_serve_sharded

    st = build_and_serve_sharded(distance="kl", n_db=n_db, dim=128,
                                 n_queries=n_queries, shards=shards,
                                 seed=seed, verbose=False)
    _say(f"sharded: {json.dumps(st)}")
    assert st["recall_gap"] <= 0.005, f"recall gap {st['recall_gap']} > 0.005"
    assert st["step_executables"] == st["admit_executables"] == 1, st
    assert st["shard_devices"] == shards, (
        f"corpus shards sit on {st['shard_devices']} device(s), not {shards}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n-db", type=int, default=N_DB)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ef", type=int, default=4096,
                    help="ef_search of the served spec")
    ap.add_argument("--shards", type=int, default=0,
                    help="run only the sharded path on this many chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {platform!r}); not running",
              file=sys.stderr)
        return 1
    need = max(1, args.shards)
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    if not args.shards and args.n_db < MIN_N_DB:
        ap.error(f"--n-db may not be cut below {MIN_N_DB}")

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.serve import init_compile_cache

    cache_dir = init_compile_cache()
    cache = _cache_counter()
    dev = devices[0]
    _say(f"smoke run, not a benchmark: device={dev.device_kind} "
         f"count={len(devices)} compile_cache={cache_dir}")

    if args.shards:
        sharded_phase(args.shards, args.n_db, args.queries, args.seed)
    else:
        spec = _spec(args.ef)
        _say(f"spec: {json.dumps(spec.to_dict(), sort_keys=True)}")
        kernel_phase(spec, args.seed)
        peak = dev.memory_stats().get("peak_bytes_in_use")
        serve_phase(spec, args.n_db, args.queries, args.seed)
        _say(f"peak_bytes_in_use after kernel check={peak} "
             f"after serve={dev.memory_stats().get('peak_bytes_in_use')}")
    _say(f"compile cache {cache_dir}: hits={cache['hits']} "
         f"misses={cache['misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
