"""The one general traffic generator: a mix file's parameters -> a plan.

A traffic mix is a JSON file under ``traffic/`` (see ``README.md``).  Its
``kind`` names ``kinds/<kind>.py``, which plans the requests from the
mix's parameters (``plan``), starts and warms up the system under test
(``start``), drives it over the window (``drive``) and may add numbers of
its own to the result line (``extras``).  The kinds so far:

* ``open_loop``: independent users arriving as a Poisson process;
* ``closed_batches``: one batch job of back-to-back calls.

Queries are drawn without replacement from the held-out pool of ``pool``
rows, in an order drawn from the seed.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import lookup


def kind(name: str, root: str = lookup.ROOT):
    """The module ``kinds/<name>.py``."""
    return lookup.module("kinds", name, root)


def poisson_arrivals(n: int, rate: float, rng=None) -> np.ndarray:
    """Cumulative arrival times (seconds) of a rate-``rate`` Poisson process."""
    rng = rng or np.random.default_rng(0)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def arrivals_in_window(n: int, seconds: float, rng) -> np.ndarray:
    """``n`` arrival times in [0, seconds): a Poisson process conditioned on
    ``n`` arrivals (the first ``n`` of ``n + 1`` arrivals, scaled so that the
    last falls at ``seconds``)."""
    t = poisson_arrivals(n + 1, 1.0, rng)
    return t[:n] * (seconds / t[n])


def latency_stats(lat_s, prefix: str = "") -> dict:
    """p50/p95/p99 latency percentiles (ms) of per-request latencies."""
    lat_s = np.asarray(lat_s, float)
    return {
        f"{prefix}p50_ms": 1e3 * float(np.percentile(lat_s, 50)),
        f"{prefix}p95_ms": 1e3 * float(np.percentile(lat_s, 95)),
        f"{prefix}p99_ms": 1e3 * float(np.percentile(lat_s, 99)),
    }


def latencies(rec) -> np.ndarray:
    """Seconds from each request's due time to its answer; a request never
    answered counts as waiting until the harness gave up."""
    return np.where(rec["answered"], rec["recv_s"] - rec["due_s"],
                    rec["wall_s"] - rec["due_s"])


@dataclasses.dataclass
class Plan:
    kind: str
    pool: int
    order: np.ndarray  # pool indices, in the order the requests use them
    due_s: np.ndarray | None = None  # open_loop: due time of request i
    batch: int = 0  # closed_batches: queries per call
    drain_s: float = 60.0  # how long past the close answers are awaited
    trace_s: float = 1.5  # open_loop: the traced slice, the window's last


def make_plan(mix: dict, seconds: float, rng, root: str = lookup.ROOT) -> Plan:
    """The requests of one window of the mix, drawn from ``rng``."""
    return kind(mix["kind"], root).plan(mix, seconds, rng)
