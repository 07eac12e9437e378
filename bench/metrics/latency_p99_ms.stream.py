"""The served path's tail: the 99th percentile, in ms, of the latency from
a request's due time to its answer, over the requests due before the
profiler started (the whole window in an untraced run).  Single ticks of
the scheduler that stall for 0.1-2.4 s (``tick_max_ms``) set it in some
runs, which is why the bounded end-to-end metric is the median.  Stream
cells only.
"""

import numpy as np

from bench.traffic import latencies


def read(run):
    rec = run["rec"]
    if run["kind"] != "open_loop":
        return None
    before = rec["due_s"] < rec["host_until"]
    if not before.any():
        return None
    return 1e3 * float(np.percentile(latencies(rec)[before], 99))
