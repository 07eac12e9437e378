"""Beam engine (``core/batched_beam.py``) under the slot scheduler: the
step program's useful share.

The occupied slots at each tick's step dispatch over the configuration's
``slots``, in %, mean over the ticks called with ``now`` before the
profiler started.  The step program works on every slot, occupied or not.
Read from the program's tick log (``repro.core.telemetry``).  Stream cells
only; nothing is read where the program keeps no tick log, where the log
is empty, or where it overwrote the window's first ticks.
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
    slots = int(run["config"]["spec"]["slots"])
    return 100.0 * float(np.mean(rows["occupied"])) / slots
