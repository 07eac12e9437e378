"""Slot scheduler host loop (``core/scheduler.py`` ``tick``): the longest
single ``tick`` call, in ms, among the ticks that ended before the profiler
started (the whole window in an untraced run).  A tick normally takes
~11 ms on one v5e chip; a stall in one tick holds every request behind it.
Stream cells only.
"""


def read(run):
    rec = run["rec"]
    if run["kind"] != "open_loop" or not rec["longest_ticks"]:
        return None
    return 1e3 * rec["longest_ticks"][0][0]
