"""Independent interactive users: ``rate_per_s`` requests a second arrive as
a Poisson process, whatever the system does, and each is timed from its
due time to the moment the harness receives its answer.  Driven through
``ANNIndex.scheduler``: the harness calls ``submit`` and ``tick`` itself on
the wall clock.

Every seed gets the same number of requests, ``round(rate_per_s *
seconds)``, spread as a Poisson process conditioned on that count, so the
seed changes which queries come and when, not how many.

Mix parameters: ``rate_per_s``, ``pool``; ``drain_s`` (how long past the
close answers are awaited), ``trace_s`` (the traced slice, the window's
last seconds).
"""

import collections
import os
import sys
import threading
import time

import numpy as np

from bench.traffic import Plan, arrivals_in_window


def plan(mix, seconds, rng):
    pool = int(mix["pool"])
    order = rng.permutation(pool)
    n = int(round(float(mix["rate_per_s"]) * seconds))
    if n > pool:
        raise ValueError(f"{n} requests in the window but a pool of {pool}")
    return Plan("open_loop", pool, order[:n],
                due_s=arrivals_in_window(n, seconds, rng),
                drain_s=float(mix.get("drain_s", 60.0)),
                trace_s=float(mix.get("trace_s", 1.5)))


def start(idx, spec, Qh, plan):
    """The slot scheduler, warmed up (its admit, step and release programs)."""
    sched = idx.scheduler(spec=spec)
    sched.warmup(Qh[plan.order[0]])
    return sched


def _site(frame) -> str:
    """``file:line function`` of the innermost frame of the program's code
    (``repro``), then of the innermost frame of all."""
    def say(f):
        return (f"{os.path.basename(f.f_code.co_filename)}:{f.f_lineno} "
                f"{f.f_code.co_name}")

    inner, f = say(frame), frame
    while f is not None and f"{os.sep}repro{os.sep}" not in f.f_code.co_filename:
        f = f.f_back
    return (say(f) if f is not None else "-") + " < " + inner


class StallWatch:
    """Where the driver's thread is while one ``tick`` runs long.

    A thread wakes every ``every_s`` seconds; while a ``tick`` call has run
    longer than ``after_s``, it reads the driver thread's stack and counts
    the site (``_site``).  Each count stands for about ``every_s`` seconds
    of a long tick.  Between long ticks it only wakes and sleeps."""

    def __init__(self, after_s: float = 0.05, every_s: float = 0.02):
        self.after_s, self.every_s = after_s, every_s
        self.sites = collections.Counter()
        self.since = None  # perf_counter() at the start of the running tick
        self._main = threading.get_ident()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()

    def _run(self):
        while not self._stop.wait(self.every_s):
            since = self.since
            if since is None or time.perf_counter() - since < self.after_s:
                continue
            frame = sys._current_frames().get(self._main)
            if frame is not None:
                self.sites[_site(frame)] += 1
            del frame


def drive(sched, Qh, plan, seconds, cap, k):
    """Submit each request at its due time, tick the scheduler, stamp each
    answer when the harness receives it.  A traced run profiles the
    window's last ``plan.trace_s`` seconds and stops the profiler once every
    request is answered."""
    n = len(plan.due_s)
    due, rows = plan.due_s, plan.order
    rec = {
        "recv_s": np.full(n, np.nan), "admit_s": np.full(n, np.nan),
        "submit_s": np.full(n, np.nan), "evals": np.zeros(n, np.int64),
        "ids": np.full((n, k), -1, np.int64),
        "dists": np.full((n, k), np.inf, np.float64),
    }
    ticks, tick_s, i, answered = 0, 0.0, 0, 0
    trace_from = max(0.0, seconds - plan.trace_s) if cap.on else seconds
    longest = []  # (seconds, start) of the longest ticks before trace_from
    deadline = seconds + plan.drain_s
    with StallWatch() as watch:
        t0 = time.perf_counter()
        while answered < n:
            now = time.perf_counter() - t0
            if now >= trace_from and now < seconds:
                cap.begin()
            if now >= seconds:
                cap.end_window()
            if now > deadline:
                break
            j = i
            while i < n and due[i] <= now:
                i += 1
            if i > j:
                with cap.span("submit"):
                    for r in range(j, i):
                        sched.submit(Qh[rows[r]], rid=r,
                                     t_arrival=float(due[r]))
                    rec["submit_s"][j:i] = now
            if not sched.n_pending and not sched.n_inflight:
                if i >= n:
                    break
                with cap.span("sleep"):
                    time.sleep(max(0.0, due[i] - (time.perf_counter() - t0)))
                continue
            ts = watch.since = time.perf_counter()
            with cap.span("tick"):
                out = sched.tick(now)
            t = time.perf_counter()
            watch.since = None
            if t - t0 < trace_from:
                ticks += 1
                tick_s += t - ts
                longest = sorted(longest + [(t - ts, ts - t0)])[-5:]
            for r in out:
                if r.shed:
                    continue
                rec["recv_s"][r.rid] = t - t0
                rec["admit_s"][r.rid] = r.t_admit
                rec["evals"][r.rid] = r.n_evals
                rec["ids"][r.rid] = r.ids
                rec["dists"][r.rid] = r.dists
                answered += 1
        cap.end_window()
        cap.stop()
        wall = time.perf_counter() - t0
    rec.update(kind="open_loop", due_s=due, rows=rows, ticks=ticks,
               tick_s=tick_s, host_until=trace_from,
               longest_ticks=longest[::-1], stall_sites=watch.sites,
               stall_every_s=watch.every_s,
               answered=~np.isnan(rec["recv_s"]), attempted=n, wall_s=wall)
    return rec


def extras(rec, rehearsal: bool) -> dict:
    """The longest ticks (ms, and when they began), where the driver's thread
    was during ticks over 50 ms (site and count of 20 ms samples), and how
    late the harness submitted requests against their due times (ms)."""
    if rehearsal:
        return {}
    late = rec["submit_s"] - rec["due_s"]
    late = late[np.isfinite(late)]
    return {
        "longest_ticks_ms": [[1e3 * d, t] for d, t in rec["longest_ticks"]],
        "stall_sites": rec["stall_sites"].most_common(8),
        "generator_late_ms": {
            "p99": 1e3 * float(np.percentile(late, 99)) if len(late) else None,
            "max": 1e3 * float(np.max(late)) if len(late) else None},
    }
