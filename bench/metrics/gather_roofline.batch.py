"""Gather kernel (``kernels/frontier_gather.py``) against its roofline.

The least time the chip could take to read what the scoring needs — each
distance evaluation (the program's ``n_evals``) reads one corpus row of the
configuration's own width m' in float32 plus 8 B of id and bias — at the
device's peak HBM bandwidth (``bench/peaks.json``), as a share (%) of the
device's busy time over the traced window (the window's first call).  It counts the same work
whatever implements the scoring.  Batch cells only.
"""

import numpy as np


def useful_bytes(n_evals: int, m_prime: int) -> int:
    return int(n_evals) * (4 * int(m_prime) + 8)


def read(run):
    tr = run["trace"]
    if run["kind"] != "closed_batches" or not tr or tr["busy_s"] <= 0:
        return None
    rec = run["rec"]
    evals = int(np.sum(rec["evals"][rec["traced"]]))
    if evals <= 0:
        return None
    least_s = useful_bytes(evals, run["config"]["m_prime"]) / float(
        run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / tr["busy_s"]
