"""Slot scheduler device sync (``core/scheduler.py`` ``tick``) in the
longest tick.

The ``repro.tick.sync`` span, in ms, of the single longest tick (by its
``repro.tick`` span): read beside ``tick_max_ms``, it says whether a
stalling tick waits on the device.  Read from the program's tick log
(``repro.core.telemetry``), over the ticks called with ``now`` before the
profiler started (the ticks ``tick_ms`` counts).  Stream cells only;
nothing is read where the program keeps no tick log, where the log is
empty, or where it overwrote the window's first ticks.
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
    if not len(rows["now"]):
        return None
    return 1e3 * float(rows["sync"][int(np.argmax(rows["tick"]))])
