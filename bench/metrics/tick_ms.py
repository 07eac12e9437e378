"""Slot scheduler host loop (``core/scheduler.py`` ``tick``).

Mean host-clock time, in ms, of one ``tick`` call (admit, the step
dispatch, the blocking read of ``done``, the retire reads and the release),
over the ticks that ended before the profiler started (the whole window in
an untraced run).  Stream cells only.
"""


def read(run):
    rec = run["rec"]
    if run["kind"] != "open_loop" or not rec["ticks"]:
        return None
    return 1e3 * rec["tick_s"] / rec["ticks"]
