"""Batch searcher (``core/batched_beam.py``).

Mean distance evaluations per query: the program's ``n_evals`` counter, as
the searcher returns it for every query of every call.  Batch cells only.
"""

import numpy as np


def read(run):
    rec = run["rec"]
    if run["kind"] != "closed_batches" or not len(rec["evals"]):
        return None
    return float(np.mean(rec["evals"]))
