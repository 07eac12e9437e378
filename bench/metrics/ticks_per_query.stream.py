"""Beam engine (``core/batched_beam.py``) under the slot scheduler.

Mean ticks a retired request held its slot, from the tick that admitted
it to the tick that retired it, both counted: the program's counters
``held_ticks`` over ``retired`` (``repro.core.telemetry``), summed over
the ticks called with ``now`` before the profiler started.  The median
latency is about the queue wait plus these ticks times ``tick_ms``.
Stream cells only; nothing is read where the program keeps no tick log,
where no request retired in those ticks, or where the log overwrote the
window's first ticks.
"""

import numpy as np


def read(run):
    if run["kind"] != "open_loop":
        return None
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    log = telemetry.latest()
    if log is None or log.dropped:
        return None
    rows = log.rows(until=run["rec"]["host_until"])
    retired = float(np.sum(rows["retired"]))
    if retired <= 0:
        return None
    return float(np.sum(rows["held_ticks"])) / retired
