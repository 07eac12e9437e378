"""Queries completed over the wall time of all the calls run in the window.
Closed-batch cells."""


def read(run):
    rec = run["rec"]
    return rec["attempted"] / rec["wall_s"]
