"""The trace reduction on a small trace recorded on one TPU v5e chip: a
piece of the ``wiki128-kl.stream`` cell's traced slice (``devtrace.sample``,
names cut to 160 characters)."""

import os

import numpy as np
import pytest

from bench import devtrace

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "stream_trace.json")


def _busy_by_sweep(events, lo, hi):
    """Busy time by counting open intervals at each edge (no merging)."""
    edges = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            edges += [(s, 1), (t, -1)]
    edges.sort()
    busy, depth, last = 0.0, 0, None
    for x, d in edges:
        if depth > 0:
            busy += x - last
        depth += d
        last = x
    return busy / 1e9


def test_recorded_trace_reduces_consistently():
    tr = devtrace.read_json(PATH)
    lo, hi = devtrace.window_of(tr)
    dev = tr.device["/device:TPU:0"]
    r = devtrace.reduce(tr)
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert r["busy_s"] == pytest.approx(_busy_by_sweep(dev, lo, hi))
    assert 0 < r["busy_s"] < r["window_s"]
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-9
    # every idle gap is put down to what the harness was doing
    for name, _ in r["idle_gaps"]:
        assert name.split("/")[0] in ("tick", "submit", "sleep", "other")
    # the gather kernel: every frontier_scores custom call, under one name
    kern = [e for e in dev if e.name.startswith("%frontier_scores")
            and "custom-call(" in e.name and e.end > lo and e.start < hi]
    assert kern
    want = sum(min(e.end, hi) - max(e.start, lo) for e in kern) / 1e9
    assert r["ops"]["frontier_scores"] == pytest.approx(want)
    assert not any(k.startswith("frontier_scores.") for k in r["ops"])
    # no op's time exceeds the window
    assert max(r["ops"].values()) <= r["window_s"]
    assert np.isfinite(list(r["ops"].values())).all()
