"""Answer quality: the share of the benchmark's own exact top-10 found, over
every query answered in the window (the comparison's own number)."""


def read(run):
    return run["checks"]["recall_at_10"]["value"]
