"""Gather kernel (``kernels/frontier_gather.py``): device time per query.

The summed device time, in ms, of the kernel's events in the traced window
(the window's first call) over the queries of that call.  The kernel's
events are the custom call named ``frontier_scores`` (the Pallas kernel's
name in the trace: ``%frontier_scores.N = f32[...] custom-call(...)``,
``devtrace.short_name`` drops the ``.N``).  Batch cells only; nothing is
read where no event matches.
"""

import re

import numpy as np

KERNEL = r"^frontier_scores$"


def read(run):
    tr = run["trace"]
    if run["kind"] != "closed_batches" or not tr:
        return None
    total = sum(s for name, s in tr["ops"].items() if re.search(KERNEL, name))
    n = int(np.sum(run["rec"]["traced"]))
    if total <= 0 or not n:
        return None
    return 1e3 * total / n
