"""The check against the lower-precision control and the planted faults.

Each test drives a whole rehearsal run off the chip (the harness's look for
a chip skipped, a small corpus) with the timed path broken underneath, and
sees ``correct`` come out false; the first shows the unbroken run passes.
"""

import json
import os

import numpy as np
import pytest

from bench import control, harness
from repro.core import index as index_mod
from repro.core import scheduler as sched_mod

N = 1500
STREAM, BATCH = "wiki128-kl.stream", "randhist32-renyi2.batch"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The repository's benchmark plus the closed-batch cell, which waits
    out of ``BENCHMARK.json`` for a fault of the program on the chip (see
    PERF.md); its files are here, and the harness drives it the same way."""
    tmp = tmp_path_factory.mktemp("root")
    for name in ("src", "bench"):
        os.symlink(os.path.join(harness.ROOT, name), tmp / name)
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    bench["configs"].append({"name": "randhist32-renyi2", "source": "-",
                             "file": "bench/configs/randhist32-renyi2.json",
                             "reduced": ["n_db"], "why": "-"})
    bench["workloads"].append({"name": BATCH, "config": "randhist32-renyi2",
                               "traffic": "batch", "chips": 1, "why": "-"})
    bench["end_to_end"].append({"name": "qps", "unit": "queries/s",
                                "better": "higher", "bound": 0.023,
                                "source": "host_clock", "workloads": [BATCH]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp)


def _run(root, cell, seed, keep=None):
    return harness.run_cell(cell, seed, 0.5, False, root=root, rehearsal=N,
                            drain_s=3.0, keep=keep)


@pytest.mark.parametrize("cell", [STREAM, BATCH])
def test_sound_run_is_correct_and_the_control_is_not(root, cell):
    keep = {}
    line = _run(root, cell, 21, keep)
    assert line["correct"], line["checks"]
    ok, checks, f64 = control.control_checks(keep)
    assert not ok, checks
    assert not checks["dist_gap"]["ok"]
    # off the chip the float64 gaps separate too
    assert f64 > 3 * line["dist_gap_f64"]


def test_step_that_returns_its_state_unchanged(root, monkeypatch):
    warm = sched_mod.SlotScheduler.warmup

    def broken_warmup(self, q=None):
        warm(self, q)
        self._step = lambda state, *args: state

    monkeypatch.setattr(sched_mod.SlotScheduler, "warmup", broken_warmup)
    line = _run(root, STREAM, 22)
    assert not line["correct"]
    assert line["failed"] == line["attempted"] > 0
    assert not line["checks"]["unanswered"]["ok"]


def test_answer_altered_where_it_is_produced(root, monkeypatch):
    tick = sched_mod.SlotScheduler.tick
    altered = []

    def broken_tick(self, now=0.0):
        out = tick(self, now)
        if out and now > 0 and not altered:
            r = out[0]
            r.ids = r.ids.copy()
            r.ids[0] = (r.ids[0] + 1) % self._n
            altered.append(r.rid)
        return out

    monkeypatch.setattr(sched_mod.SlotScheduler, "tick", broken_tick)
    line = _run(root, STREAM, 23)
    assert altered and not line["correct"]


@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered"])
def test_batch_faults(root, monkeypatch, fault):
    searcher = index_mod.ANNIndex.searcher

    def broken_searcher(self, *a, **kw):
        search = searcher(self, *a, **kw)

        def run(Q):
            d, ids, ev, hops = (np.asarray(x) for x in search(Q))
            d, ids = d.copy(), ids.copy()
            if fault == "half_left_out":
                # only the first half searched; the rest get its answers
                h = len(ids) // 2
                d[h:2 * h], ids[h:2 * h] = d[:h], ids[:h]
            else:
                ids[0, 0] = (ids[0, 0] + 1) % self.X.shape[0]
            return d, ids, ev, hops

        return run

    monkeypatch.setattr(index_mod.ANNIndex, "searcher", broken_searcher)
    line = _run(root, BATCH, 24)
    assert not line["correct"]
    assert not line["checks"]["dist_gap"]["ok"]
