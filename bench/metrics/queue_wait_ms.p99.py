"""Slot scheduler admission (``core/scheduler.py`` ``submit``/``tick``).

99th percentile, in ms, of the wait from a request's due time to the tick
that admitted it into a slot: ``SlotResult.t_admit`` is the harness's own
clock reading that it passed to that ``tick``.  Over the requests due
before the profiler started (the whole window in an untraced run), so that
starting the profiler does not count.  Stream cells only.
"""

import numpy as np


def read(run):
    rec = run["rec"]
    if run["kind"] != "open_loop":
        return None
    ok = rec["answered"] & (rec["due_s"] < rec["host_until"])
    if not ok.any():
        return None
    return 1e3 * float(np.percentile(rec["admit_s"][ok] - rec["due_s"][ok], 99))
