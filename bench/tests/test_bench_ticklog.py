"""The per-layer readers of the program's tick log, on hand-made logs."""

import sys

import pytest

import repro.core
from bench import harness
from repro.core import telemetry

ROOT = harness.ROOT
READERS = ("tick_sync_ms", "tick_retire_read_ms", "tick_dispatch_ms",
           "tick_host_ms", "tick_max_sync_ms", "ticks_per_query.stream",
           "slot_occupancy.stream")
UNTIL = 2.5  # the profiler started here: later ticks are not read

# three ticks inside the window, one after it that would change every mean
ROWS = [
    dict(now=0.0, tick=0.010, select=0.001, put=0.0005, admit=0.0005,
         step=0.001, sync=0.005, occupied=24, admitted=3),
    dict(now=1.0, tick=0.012, step=0.001, sync=0.006, retire_read=0.002,
         retire=0.0005, release=0.0005, occupied=30, retired=2,
         held_ticks=80),
    dict(now=2.0, tick=0.020, select=0.001, step=0.001, sync=0.009,
         retire_read=0.003, release=0.001, occupied=36, retired=1,
         held_ticks=41),
    dict(now=3.0, tick=1.0, step=0.001, sync=0.9, retire_read=0.05,
         occupied=48, retired=10, held_ticks=1000),
]
WANT = {
    "tick_sync_ms": (5 + 6 + 9) / 3,
    "tick_retire_read_ms": (0 + 2 + 3) / 3,
    "tick_dispatch_ms": (2.0 + 1.5 + 2.0) / 3,
    "tick_host_ms": (3.0 + 2.5 + 6.0) / 3,
    "tick_max_sync_ms": 9.0,
    "ticks_per_query.stream": (80 + 41) / 3,
    "slot_occupancy.stream": 100 * (24 + 30 + 36) / 3 / 48,
}


def _run(kind="open_loop", until=UNTIL):
    return {"kind": kind, "rec": {"host_until": until},
            "config": {"spec": {"slots": 48}}}


def _log(rows=ROWS):
    log = telemetry.TickLog()  # the newest log: the one the readers find
    for r in rows:
        log.append(**r)
    return log


@pytest.mark.parametrize("name", READERS)
def test_reads_the_window_of_a_hand_made_log(name):
    _log()
    assert harness.load_reader(ROOT, name)(_run()) == pytest.approx(WANT[name])


def test_parts_add_up_to_the_mean_tick():
    _log()
    parts = sum(harness.load_reader(ROOT, name)(_run()) for name in READERS[:4])
    assert parts == pytest.approx(1e3 * (0.010 + 0.012 + 0.020) / 3)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read(name, monkeypatch):
    read = harness.load_reader(ROOT, name)
    _log()
    assert read(_run(kind="closed_batches")) is None
    assert read(_run(until=0.0)) is None  # no tick in the window
    _log([])
    assert read(_run()) is None  # an empty log
    # a log that overwrote the window's first ticks
    _log([dict(now=0.001 * i, tick=0.01, sync=0.005, occupied=8, retired=1,
               held_ticks=40) for i in range(telemetry.CAPACITY + 1)])
    assert read(_run(until=1e9)) is None
    # a program that keeps no tick log (the module cannot be imported)
    _log()
    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert read(_run()) is None


def test_no_retired_request_no_ticks_per_query():
    _log(ROWS[:1])
    assert harness.load_reader(ROOT, "ticks_per_query.stream")(_run()) is None
