"""Device: the share (%) of the traced window in which no operation ran on
the chip, 1 - busy / window, with busy the union of the device's operation
intervals (``devtrace.reduce``).  Batch cells only.
"""


def read(run):
    tr = run["trace"]
    if run["kind"] != "closed_batches" or not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
