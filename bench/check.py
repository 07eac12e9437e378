"""The comparison that decides ``correct``.

What the timed path returned for every query it answered in the window
(ids and distances, at the timed sizes) is held against the plain
reference (``reference/scan.py``), run once the window has closed:

* ``unanswered``: requests due in the window that got no answer (limit 0);
* ``bad_answers``: answers with an id out of range or repeated, a distance
  that is not finite, or distances out of ascending order (limit 0);
* ``recall_at_10``: the share of the reference's exact top-k found
  (at least the configuration's stated ``recall_at_10_min``): the graph
  and the beam;
* ``dist_gap``: the widest gap between a returned distance and the
  reference's distance of the returned id, computed from its definition in
  float32 on the device, relative to that distance or to the median one,
  whichever is larger (at most the configuration's ``dist_gap_max``): the
  gather kernel's and the beam's scores.

The same gap against the definition in float64 on the host,
``dist_gap_f64``, is reported beside them and not compared: the device's
own float32 logarithm departs from float64 by more than the control does
(PERF.md), so no limit on it separates the program from the control.

``control`` puts the reference in the program's place at the next lower
precision (``bf16x3``, a TPU's ``Precision.HIGH``): its answers have to
fail one of these numbers.
"""

from __future__ import annotations

import numpy as np

from bench.reference import scan as ref


def recall(found, truth) -> float:
    """Average |found ∩ truth| / |truth| over the queries."""
    hits = total = 0
    for f, t in zip(np.asarray(found), np.asarray(truth)):
        t_set = {int(x) for x in t if x >= 0}
        hits += len(t_set & {int(x) for x in f if x >= 0})
        total += len(t_set)
    return hits / max(total, 1)


def _bad_rows(ids, d, n: int) -> np.ndarray:
    ids = np.asarray(ids)
    d = np.asarray(d, np.float64)
    out_of_range = ((ids < 0) | (ids >= n)).any(axis=1)
    srt = np.sort(ids, axis=1)
    repeated = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    not_finite = ~np.isfinite(d).all(axis=1)
    unordered = (np.diff(d, axis=1) < 0).any(axis=1)
    return out_of_range | repeated | not_finite | unordered


def dist_gap(d, ref_d) -> float:
    d = np.asarray(d, np.float64)
    r = np.asarray(ref_d, np.float64)
    ok = np.isfinite(d) & np.isfinite(r)
    if not ok.any():
        return float("inf")
    scale = np.maximum(np.abs(r[ok]), np.median(np.abs(r[ok])))
    return float(np.max(np.abs(d[ok] - r[ok]) / scale))


def compare(dist, X, Q, ids, dists, truth_ids, limits: dict,
            unanswered: int) -> tuple[bool, dict]:
    """Hold the answers ``(ids, dists)`` (B, k) to queries ``Q`` (B, d) to
    the reference under the distance module ``dist``
    (``reference.scan.distance``).  Returns ``(correct, checks)``: each
    check's value beside its limit."""
    ids = np.asarray(ids, np.int64)
    dists = np.asarray(dists, np.float64)
    n = X.shape[0]
    ref_d = ref.distances_of(dist, X, Q, ids)[0] if len(ids) else ids
    checks = {
        "unanswered": (int(unanswered), 0),
        "bad_answers": (int(_bad_rows(ids, dists, n).sum()) if len(ids)
                        else 0, 0),
        "recall_at_10": (recall(ids, truth_ids) if len(ids) else 0.0,
                         float(limits["recall_at_10_min"])),
        "dist_gap": (dist_gap(dists, ref_d) if len(ids) else 0.0,
                     float(limits["dist_gap_max"])),
    }
    ok = {
        "unanswered": checks["unanswered"][0] <= 0,
        "bad_answers": checks["bad_answers"][0] <= 0,
        "recall_at_10": checks["recall_at_10"][0] >= checks["recall_at_10"][1],
        "dist_gap": checks["dist_gap"][0] <= checks["dist_gap"][1],
    }
    out = {k: {"value": v, "limit": lim, "ok": ok[k]}
           for k, (v, lim) in checks.items()}
    return all(ok.values()) and len(ids) > 0, out


def gap_f64(dist, X, Q, ids, dists) -> float | None:
    """``dist_gap`` against the definition in float64 on the host."""
    if not len(ids):
        return None
    return dist_gap(dists, ref.distances_of(dist, X, Q, ids)[1])


def truth(dist, X, Q, k: int):
    """The reference's exact top-k ids of each query (HIGHEST precision)."""
    return ref.scan(dist, X, Q, k)[1]


def control(dist, X, Q, k: int):
    """The control's answers: the reference at the next lower precision."""
    return ref.scan(dist, X, Q, k, precision="bf16x3")
