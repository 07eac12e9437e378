"""Beam engine under the slot scheduler (``core/batched_beam.py``).

Mean distance evaluations per answered query: the program's ``n_evals``
counter, as each ``SlotResult`` returns it.  Stream cells only.
"""

import numpy as np


def read(run):
    rec = run["rec"]
    if run["kind"] != "open_loop" or not rec["answered"].any():
        return None
    return float(np.mean(rec["evals"][rec["answered"]]))
