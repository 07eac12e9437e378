"""Telemetry of the slot schedulers' host loop (spans and a tick log) and
of the index build (spans and a build record).

One mechanism with two outputs, both fed by the same span objects:

* **Spans.**  A scheduler ``tick`` runs inside a ``repro.tick`` span, and
  each of its parts inside a child span ``repro.tick.<part>`` (``SPANS``,
  in the order a tick runs them).  Every span is a
  ``jax.profiler.TraceAnnotation``: it is recorded only while a profiler
  session is active, on the profiler's clock and host line, beside the
  device's own events.  With no profiler running a span costs about a
  microsecond of host time.
* **The tick log.**  A ring of ``CAPACITY`` rows, one per tick, always
  on: the caller's clock (``now``), the tick's duration, each part's
  duration (zero for a part the tick skipped), the occupied slots at the
  step dispatch, and what the tick added to each running counter
  (``COUNTERS``).  The counters are the scheduler's only counters:
  requests admitted, shed and demoted by admission control, requests
  retired, and the ticks the retired requests held their slots (admit tick
  to retire tick, inclusive).  ``reset`` empties the log and zeroes the
  counters.

A tick's self time is its duration less the sum of its parts.  ``latest``
returns the log of the most recently constructed scheduler, so an
exporter finds it without a handle on the scheduler.

The index build (``ANNIndex.build``) runs the same way inside a
``repro.build`` span with the child spans ``repro.build.<part>``
(``BUILD_PARTS``: binding and prepping the constants, the wave scan, and
everything after it), each ended by a ``block_until_ready`` so a part's
host-clock seconds hold its device work.  Its ``BuildRecord`` keeps those
seconds and what the build reports of itself (waves, the rep width the
build scored at, whether the gather kernel scored it, the distances it
computed); ``latest_build`` returns the newest.
"""

from __future__ import annotations

import collections
import time
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

SPANS = ("select", "put", "admit", "background", "step", "sync",
         "retire_read", "rerank", "retire")
# parts no tick runs any longer (retirement moved into the step program):
# their columns stay, reading 0, for readers of the log that sum them
GONE = ("release",)
COUNTERS = ("admitted", "shed", "demoted", "retired", "held_ticks")
COLUMNS = ("now", "tick", *SPANS, *GONE, "occupied", *COUNTERS)
CAPACITY = 16_384  # rows kept: the newest overwrite the oldest

BUILD_PARTS = ("prep", "waves", "finish")

_COL = {name: i for i, name in enumerate(COLUMNS)}
_LABEL = {name: "repro.tick." + name for name in SPANS}

_latest: Optional["TickLog"] = None
_latest_build: Optional["BuildRecord"] = None


def latest() -> Optional["TickLog"]:
    """The most recently constructed log (None before the first)."""
    return _latest


def latest_build() -> Optional["BuildRecord"]:
    """The record of the most recently finished build (None before it)."""
    return _latest_build


class _Span:
    """One part of a tick or a build: a profiler annotation, and its
    duration added to the row's column ``col``."""

    __slots__ = ("_row", "_col", "_ann", "_t0")

    def __init__(self, row: list, col: int, label: str):
        self._row, self._col = row, col
        self._ann = TraceAnnotation(label)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._row[self._col] += time.perf_counter() - self._t0
        self._ann.__exit__(*exc)


class Tick:
    """The open row of one ``tick`` call: ``span(part)`` times a part,
    ``set`` records a gauge, ``index`` is the tick's number since the last
    reset.  Leaving the block writes the row."""

    __slots__ = ("_log", "row", "index", "_base", "_ann", "_t0")

    def __init__(self, log: "TickLog", now: float):
        self._log = log
        self.index = log.n
        self.row = [0.0] * len(COLUMNS)
        self.row[0] = float(now)

    def span(self, part: str) -> _Span:
        return _Span(self.row, _COL[part], _LABEL[part])

    def set(self, name: str, value) -> None:
        self.row[_COL[name]] = value

    def __enter__(self):
        counters = self._log.counters
        self._base = [counters[c] for c in COUNTERS]
        self._ann = TraceAnnotation("repro.tick")
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.row[1] = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        counters = self._log.counters
        for j, c in enumerate(COUNTERS):
            self.row[_COL[c]] = counters[c] - self._base[j]
        self._log._write(self.row)


class TickLog:
    """Fixed-capacity ring of per-tick rows plus the running counters.

    ``counters`` is a ``collections.Counter`` keyed by ``COUNTERS``.  ``n``
    counts the rows written since the last ``reset``; once it passes
    ``CAPACITY`` the oldest rows are overwritten and ``dropped`` says how
    many.  Constructing a log makes it the one ``latest`` returns."""

    def __init__(self):
        global _latest
        self._data = np.zeros((CAPACITY, len(COLUMNS)), np.float64)
        self.counters: collections.Counter = collections.Counter()
        self.n = 0
        _latest = self

    def reset(self) -> None:
        self.n = 0
        self.counters.clear()

    def tick(self, now: float) -> Tick:
        return Tick(self, now)

    def _write(self, row: list) -> None:
        self._data[self.n % CAPACITY] = row
        self.n += 1

    def append(self, **values) -> None:
        """Write one row by column name (absent columns read 0), as a tick
        writes it when it ends; counters are not touched."""
        row = [0.0] * len(COLUMNS)
        for name, v in values.items():
            row[_COL[name]] = v
        self._write(row)

    @property
    def dropped(self) -> int:
        """Rows overwritten since the last reset."""
        return max(0, self.n - CAPACITY)

    def rows(self, until: Optional[float] = None) -> dict:
        """The rows held, oldest first, as one array per column; with
        ``until``, only the ticks called with ``now < until``."""
        held = min(self.n, CAPACITY)
        data = self._data[np.arange(self.n - held, self.n) % CAPACITY]
        if until is not None:
            data = data[np.flatnonzero(data[:, 0] < until)]
        return {c: data[:, i] for i, c in enumerate(COLUMNS)}

    def summary(self) -> dict:
        """Each part's mean and maximum, in ms, over the rows held
        (``tick`` the whole call, ``self`` its time outside every part)."""
        r = self.rows()
        parts = {"tick": r["tick"], **{s: r[s] for s in SPANS},
                 "self": r["tick"] - sum(r[s] for s in SPANS)}
        n = len(r["tick"])
        return {
            "ticks": self.n,
            "mean_ms": {k: 1e3 * float(v.mean()) if n else 0.0
                        for k, v in parts.items()},
            "max_ms": {k: 1e3 * float(v.max()) if n else 0.0
                       for k, v in parts.items()},
        }


class BuildRecord:
    """One index build: ``with record:`` runs it in the ``repro.build`` span
    and ``record.part(name)`` times a part of it (``BUILD_PARTS``).

    ``seconds`` holds each part's host-clock seconds and ``total`` the
    whole span's; what the build reports of itself goes in ``waves`` (the
    wave count, None for builders without waves), ``m_prime`` (the rep
    width it scored at), ``kernel`` (whether the gather kernel scored it)
    and ``build_evals`` (the distances it computed).  Leaving the block
    makes the record the one ``latest_build`` returns."""

    def __init__(self):
        self._row = [0.0] * len(BUILD_PARTS)
        self.total = 0.0
        self.waves: Optional[int] = None
        self.m_prime: Optional[int] = None
        self.kernel = False
        self.build_evals: Optional[int] = None

    @property
    def seconds(self) -> dict:
        return dict(zip(BUILD_PARTS, self._row))

    def part(self, name: str) -> _Span:
        return _Span(self._row, BUILD_PARTS.index(name), "repro.build." + name)

    def __enter__(self):
        self._ann = TraceAnnotation("repro.build")
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        global _latest_build
        self.total = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if exc[0] is None:
            _latest_build = self

    def as_dict(self) -> dict:
        return {"seconds": self.seconds, "total": self.total,
                "waves": self.waves, "m_prime": self.m_prime,
                "kernel": self.kernel, "build_evals": self.build_evals}
