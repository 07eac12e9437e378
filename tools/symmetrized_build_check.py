"""One-off chip check of a graph built under a symmetrized distance.

    python tools/symmetrized_build_check.py --workload wiki128-kl-avgbuild.stream \
        --seed 4294967311 [--parts distances,graphs] \
        [--out chiprun_out/symmetrized_build_check.json]

For a benchmark cell whose configuration builds under ``avg`` over a linear
base (KL), at the cell's size and on the cell's data from ``--seed``:

* **distances**: 4,096 seeded (row, query) pairs scored by the gather
  kernel (forced: by default it takes only m' <= 128) under the lowered
  avg distance (one matmul-form ``Distance``, m' = 256), against the
  definition ``0.5 (d(x, q) + d(q, x))`` in float32 on the device
  (``bench/reference``), by ``bench/check.py``'s relative gap; and the same
  lowering as one XLA matmul at ``Precision.HIGHEST`` and at
  ``Precision.DEFAULT``, the second of which has to fail the
  configuration's ``dist_gap_max``;
* **graphs**: the cell's build (``ANNIndex.build``: the lowered distance,
  scored by XLA's gather) against the composite ``SymmetrizedDistance``
  build (jnp scoring of both argument orders) with the same knobs: the
  share of identical neighbour lists, each build's seconds, and recall@10
  and distance evaluations per query of each graph, served by the cell's
  slot scheduler over the same ``--queries`` queries submitted at once.

Prints one JSON line per part and writes them all to ``--out``.  Needs a
TPU for the kernel (off the chip the jnp path scores, at ``--rehearsal N``
rows).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def _say(out: list, **kw):
    out.append(kw)
    print(json.dumps(kw), flush=True)


def _serve(idx, spec, Q):
    """Every query submitted at once to the cell's slot scheduler, ticked
    until all are answered: (ids (B, k), evals (B,))."""
    import numpy as np

    sched = idx.scheduler(spec=spec)
    sched.warmup(Q[0])
    for i, q in enumerate(Q):
        sched.submit(q, rid=i)
    ids = np.full((len(Q), spec.k), -1, np.int64)
    evals = np.zeros(len(Q), np.int64)
    t0 = time.perf_counter()
    while sched.n_pending or sched.n_inflight:
        for r in sched.tick(time.perf_counter() - t0):
            ids[r.rid], evals[r.rid] = r.ids, r.n_evals
    return ids, evals


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="wiki128-kl-avgbuild.stream")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pairs", type=int, default=4096)
    ap.add_argument("--queries", type=int, default=1024)
    ap.add_argument("--rehearsal", type=int, default=None, metavar="N")
    ap.add_argument("--parts", default="distances,graphs")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "symmetrized_build_check.json"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import check, data, harness
    from bench.reference import scan as ref
    from repro.core import ANNIndex, RetrievalSpec, get_distance, telemetry
    from repro.core.build_engine import build_swgraph_wave
    from repro.core.symmetrize import SymmetrizedDistance, symmetrized
    from repro.kernels.ops import frontier_gather_scores, kernel_rows

    out: list = []
    files = harness.load_cell(ROOT, args.workload)
    config, mix = files["config"], files["traffic"]
    harness.open_compile_cache(ROOT)
    n = int(args.rehearsal or config["n_db"])
    spec = RetrievalSpec(**config["spec"])
    if args.rehearsal:
        spec = spec.replace(**config.get("rehearsal_spec", {}))
    if str(spec.build_policy) != "avg":
        raise SystemExit(f"{args.workload} does not build under avg")
    base = get_distance(spec.distance)
    lowered, composite = symmetrized(base, "avg"), SymmetrizedDistance(base, "avg")
    refd = ref.distance(config["distance"])
    X, Qpool = data.make_corpus(config["data"], n, int(mix["pool"]), args.seed)
    dev = jax.devices()[0]
    _say(out, what="device", platform=dev.platform, kind=dev.device_kind, n=n)

    parts = set(args.parts.split(","))
    if "distances" in parts:
        # -- distances: kernel at m' = 256 against the definition --------------
        rng = data.host_rng(args.seed, 17)
        G = 8
        B = args.pairs // G
        qi = rng.choice(Qpool.shape[0], B, replace=False)
        ids = jnp.asarray(rng.integers(0, n, (B, G)), jnp.int32)
        Q = Qpool[jnp.asarray(qi)]
        consts = lowered.prep_scan(X)
        qc = jax.vmap(lowered.prep_query)(Q)
        x_rows = kernel_rows(lowered, consts, use_pallas=dev.platform == "tpu" or None)
        on_kernel = x_rows is not None
        got = frontier_gather_scores(lowered, ids, qc["rep"], qc["bias"], consts["rep"],
                                     consts["bias"], x_rows=x_rows)

        def as_matmul(precision):
            """The lowering as one (B * G, m') x (m', B) MXU matmul (a batched
            matvec would run on the vector unit in float32 whatever the
            precision), its (b, g, b) entries."""
            full = jnp.dot(consts["rep"][ids].reshape(B * G, -1), qc["rep"].T,
                           precision=precision).reshape(B, G, B)
            s = full[jnp.arange(B), :, jnp.arange(B)]
            return np.asarray(s + consts["bias"][ids] + qc["bias"][:, None])

        low_highest = as_matmul(jax.lax.Precision.HIGHEST)
        low_default = as_matmul(jax.lax.Precision.DEFAULT)
        del consts, x_rows

        @jax.jit
        def definition(x, q):
            x, q = jnp.maximum(x, ref.EPS), jnp.maximum(q, ref.EPS)
            return 0.5 * (refd.pair(x, q, jnp) + refd.pair(q, x, jnp))

        want = np.concatenate([
            np.asarray(definition(X[ids[b:b + 64]], Q[b:b + 64, None, :]))
            for b in range(0, B, 64)])
        limit = float(config["checks"]["dist_gap_max"])
        gap = check.dist_gap(np.asarray(got), want)
        gap_default = check.dist_gap(low_default, want)
        _say(out, what="distances", pairs=int(B * G), m_prime=int(qc["rep"].shape[1]),
             kernel=on_kernel, gap_kernel=gap,
             gap_matmul_highest=check.dist_gap(low_highest, want),
             gap_default=gap_default, limit=limit,
             highest_passes=gap <= limit, default_fails=gap_default > limit,
             median_def=float(np.median(np.abs(want))))
        del qc, got, low_default

    if "graphs" in parts:
        # -- graphs: the cell's build against the composite build --------------
        key = jax.random.fold_in(data.seed_key(args.seed), 2)
        t = time.perf_counter()
        idx = ANNIndex.build(X, spec=spec, key=key)
        jax.block_until_ready(idx.neighbors)
        t_low = time.perf_counter() - t
        rec = telemetry.latest_build().as_dict()
        t = time.perf_counter()
        comp_nb, _ = build_swgraph_wave(composite, X, NN=spec.NN,
                                        ef_construction=spec.ef_construction,
                                        M_max=spec.M_max, wave=spec.wave,
                                        frontier=spec.build_frontier)
        comp_nb = jax.block_until_ready(comp_nb)
        t_comp = time.perf_counter() - t
        a, b = np.asarray(idx.neighbors), np.asarray(comp_nb)
        same_order = np.all(a == b, axis=1)
        same_set = np.all(np.sort(a, axis=1) == np.sort(b, axis=1), axis=1)
        _say(out, what="graphs", seconds_lowered=t_low, seconds_composite=t_comp,
             record=rec, identical_lists=float(same_set.mean()),
             identical_in_order=float(same_order.mean()),
             rows_differing=int((~same_set).sum()))

        Qs = np.asarray(Qpool[: args.queries])
        truth = check.truth(refd, X, Qs, spec.k)
        for name, nb in (("lowered", idx.neighbors), ("composite", comp_nb)):
            served = dataclasses.replace(idx, neighbors=nb)
            found, evals = _serve(served, spec, Qs)
            _say(out, what="served", graph=name, queries=len(Qs),
                 recall_at_10=check.recall(found, truth),
                 evals_per_query=float(evals.mean()))

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
