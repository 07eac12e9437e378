"""Median latency, in ms, over all requests due in the window, from each
one's due time to the moment the harness receives its answer (a request
never answered waits until the harness gave up).  Open-loop cells."""

from bench.traffic import latencies, latency_stats


def read(run):
    return latency_stats(latencies(run["rec"]))["p50_ms"]
