"""Slot scheduler retire reads (``core/scheduler.py`` ``tick``).

Mean time, in ms, of the ``repro.tick.retire_read`` span per tick: the
four device-to-host reads of the retired slots' beams, ids, evaluation
counts and hops (0 on a tick that retires nothing).  Read from the
program's tick log (``repro.core.telemetry``), over the ticks called with
``now`` before the profiler started (the ticks ``tick_ms`` counts).
Stream cells only; nothing is read where the program keeps no tick log,
where the log is empty, or where it overwrote the window's first ticks.
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
    return 1e3 * float(np.mean(rows["retire_read"]))
