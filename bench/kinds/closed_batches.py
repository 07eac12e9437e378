"""One batch job: calls of ``batch`` queries each, made back to back, the
next when the previous has returned, for the length of the window.
Driven through ``ANNIndex.searcher``, one device loop per call.

Mix parameters: ``batch``, ``pool``.
"""

import time

import numpy as np

from bench.traffic import Plan


def plan(mix, seconds, rng):
    pool, batch = int(mix["pool"]), int(mix["batch"])
    if batch > pool:
        raise ValueError(f"batch {batch} > pool {pool}")
    return Plan("closed_batches", pool, rng.permutation(pool), batch=batch)


def batch_rows(plan, b: int) -> np.ndarray:
    """Pool rows of the ``b``-th call (wrapping round the pool once it is
    used up)."""
    idx = (b * plan.batch + np.arange(plan.batch)) % plan.pool
    return plan.order[idx]


def start(idx, spec, Qh, plan):
    """The searcher, warmed up on the window's call shape."""
    import jax

    search = idx.searcher(spec=spec)
    jax.block_until_ready(search(Qh[batch_rows(plan, 0)]))
    return search


def drive(search, Qh, plan, seconds, cap, k):
    """Calls back to back until the window has elapsed; each call's answers
    are read back to the host.  A traced run profiles the first call and
    stops the profiler after it."""
    import jax

    rows_all, ids, dists, evals, spans = [], [], [], [], []
    t0 = time.perf_counter()
    b = 0
    while time.perf_counter() - t0 < seconds:
        rows = batch_rows(plan, b)
        if b == 0:
            cap.begin()
        ts = time.perf_counter() - t0
        with cap.span("batch"):
            out = search(Qh[rows])
            jax.block_until_ready(out)
        with cap.span("readback"):
            d, i_, ev = (np.asarray(out[0]), np.asarray(out[1]),
                         np.asarray(out[2]))
        spans.append((ts, time.perf_counter() - t0, len(rows)))
        if b == 0:
            cap.end_window()
            cap.stop()
        rows_all.append(rows)
        ids.append(i_.astype(np.int64)[:, :k])
        dists.append(d.astype(np.float64)[:, :k])
        evals.append(ev.astype(np.int64))
        b += 1
    wall = time.perf_counter() - t0
    n = sum(s[2] for s in spans)
    traced = np.zeros(n, bool)
    traced[: spans[0][2]] = cap.on
    return {"kind": "closed_batches", "rows": np.concatenate(rows_all),
            "ids": np.concatenate(ids), "dists": np.concatenate(dists),
            "evals": np.concatenate(evals), "batches": spans, "wall_s": wall,
            "attempted": n, "answered": np.ones(n, bool), "traced": traced}
