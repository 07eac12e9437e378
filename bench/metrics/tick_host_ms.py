"""Slot scheduler host loop (``core/scheduler.py`` ``tick``): the tick's
own Python.

Mean time, in ms, per tick of the ``repro.tick`` span less its spans that
wait on or hand work to the device (``sync``, ``retire_read``, ``put``,
``admit``, ``step``, ``release``): selection and admission control, the
retire bookkeeping and the rest of the loop.  With ``tick_sync_ms``,
``tick_retire_read_ms`` and ``tick_dispatch_ms`` it adds up to the mean
tick.  Read from the program's tick log (``repro.core.telemetry``), over
the ticks called with ``now`` before the profiler started (the ticks
``tick_ms`` counts).  Stream cells only; nothing is read where the program
keeps no tick log, where the log is empty, or where it overwrote the
window's first ticks.
"""

import numpy as np

DEVICE_SPANS = ("sync", "retire_read", "put", "admit", "step", "release")


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
    host = rows["tick"] - sum(rows[s] for s in DEVICE_SPANS)
    return 1e3 * float(np.mean(host))
