"""Device trace of a window: capture, and reduction to numbers.

A traced run profiles a slice of its window with ``jax.profiler``
(``Capture``): a ``bench.window`` span marks the slice, and host spans
named ``bench.<activity>`` mark what the harness does in it.
``load`` reads the profiler's ``.xplane.pb`` into plain event lists, and
``reduce`` turns them into the numbers the per-layer readers take:

* ``window_s``: the length of the ``bench.window`` span;
* ``busy_s``: the union of the device's operation intervals inside it,
  averaged over the chips traced;
* ``ops``: the device time of each operation by its short name (custom
  calls, the kernels, summed over their calls), leaving out events that
  contain others, such as a while loop's own span;
* ``device_ops`` and ``idle_gaps``: the ``breakdown`` of a result line —
  the ten device operations that took most time, and the device's idle
  time inside the window by what the host was doing then (the harness's
  activity / the innermost host event).

Everything after ``load`` works on plain lists, so it can be checked on a
small recorded trace (``tests/``).
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import json
import os
import re

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns, on the trace's one clock
    end: float


@dataclasses.dataclass
class Trace:
    device: dict  # plane name -> [Event] of its op line
    host: list  # [Event] of the host thread that holds bench.window


class Capture:
    """Host spans and the traced slice of one run; inert when ``on`` is
    False, so the untraced run pays nothing.

    A driver calls ``begin`` where the slice starts (profiler on, the
    ``bench.window`` span opened), ``end_window`` where it ends, and
    ``stop`` once it may stall: stopping writes the trace, which takes
    seconds, so a driver stops only where no request is waiting."""

    def __init__(self, on: bool, log_dir: str):
        self.on = bool(on)
        self.log_dir = log_dir
        self.path = None
        self._window = None
        self.stop_s = None

    def span(self, name: str):
        if not self.on or self._window is None:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def begin(self):
        if not self.on or self.path is not None or self._window is not None:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans and runtime events only
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()

    def end_window(self):
        if self._window is not None:
            self._window.__exit__(None, None, None)

    def stop(self):
        """Stop tracing (once); the trace's path is then ``self.path``."""
        if self._window is None:
            return
        import time

        import jax

        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t
        self._window = None
        paths = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        self.path = paths[-1] if paths else ""


def load(path: str) -> Trace:
    """Read a profiler ``.xplane.pb`` into a ``Trace``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(Event(e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns, e.end_ns) for e in line.events]
                if any(e.name == WINDOW for e in evs):
                    host = evs
    return Trace(device, host)


def dump(trace: Trace, path: str) -> None:
    """Write a trace as JSON (the form of the recorded test trace)."""
    def rows(evs):
        return [[e.name, e.start, e.end] for e in evs]

    with open(path, "w") as f:
        json.dump({"device": {k: rows(v) for k, v in trace.device.items()},
                   "host": rows(trace.host)}, f)


def sample(trace: Trace, max_events: int = 3000) -> Trace:
    """A small piece of a trace for a recorded test: the device events of
    the window's first ``max_events``, the host events over the same time,
    and a ``bench.window`` span cut to it."""
    lo, hi = window_of(trace)
    dev = {k: sorted((e for e in v if e.end > lo and e.start < hi),
                     key=lambda e: e.start)[:max_events]
           for k, v in trace.device.items()}
    end = max((v[-1].end for v in dev.values() if v), default=hi)
    end = min(end, hi)
    host = [e for e in trace.host if e.name != WINDOW
            and e.end > lo and e.start < end]
    return Trace(dev, [Event(WINDOW, lo, end)] + host)


def read_json(path: str) -> Trace:
    with open(path) as f:
        raw = json.load(f)
    return Trace({k: [Event(*r) for r in v] for k, v in raw["device"].items()},
                 [Event(*r) for r in raw["host"]])


def short_name(name: str) -> str:
    """An op's name without its HLO text: ``%fusion.62 = f32[...] ...`` ->
    ``fusion.62``; a custom call (a kernel) loses its numeric suffix, so
    its calls add up under one name."""
    head = name.split(" = ", 1)[0].lstrip("%")
    if "custom-call(" in name:
        head = re.sub(r"\.\d+$", "", head)
    return head


def leaves(events: list) -> list:
    """The events that contain no other event (a while loop's own span
    contains its body's ops)."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt.start >= e.end or nxt.end > e.end:
            out.append(e)
    return out


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint ones, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def window_of(trace: Trace) -> tuple[float, float]:
    spans = [e for e in trace.host if e.name == WINDOW]
    if not spans:
        raise ValueError("the trace holds no bench.window span")
    return spans[0].start, spans[0].end


def _gap_splitter(host: list):
    """(start, end) -> [(what the host was doing, ns)] over that interval:
    the harness's activity (``bench.<activity>``) and, inside it, the
    innermost host event at the middle of the part it covers."""
    acts = sorted((e for e in host
                   if e.name.startswith("bench.") and e.name != WINDOW),
                  key=lambda e: e.start)
    starts = [e.start for e in acts]
    others = sorted((e for e in host if not e.name.startswith("bench.")),
                    key=lambda e: e.start)
    o_starts = [e.start for e in others]

    def inner(act, t: float) -> str:
        name = act.name[len("bench."):]
        lo = bisect.bisect_left(o_starts, act.start)
        hi = bisect.bisect_right(o_starts, t)
        cover = [e for e in others[lo:hi] if e.end >= t]
        if cover:
            name += "/" + min(cover, key=lambda e: e.end - e.start).name
        return name

    def split(s: float, e: float) -> list:
        out, t = [], s
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while t < e:
            while i < len(acts) and acts[i].end <= t:
                i += 1
            if i >= len(acts) or acts[i].start >= e:
                out.append(("other", e - t))
                break
            act = acts[i]
            if act.start > t:
                out.append(("other", act.start - t))
                t = act.start
            hi = min(act.end, e)
            out.append((inner(act, (t + hi) / 2), hi - t))
            t = hi
        return out

    return split


def reduce(trace: Trace) -> dict:
    """The window's device numbers (see the module's docstring)."""
    lo, hi = window_of(trace)
    window_s = (hi - lo) / 1e9
    busy, ops, gaps = [], {}, {}
    split = _gap_splitter(trace.host)
    for evs in trace.device.values():
        inside = [e for e in evs if e.end > lo and e.start < hi]
        merged = union(clip([(e.start, e.end) for e in inside], lo, hi))
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for e in leaves(inside):
            d = (min(e.end, hi) - max(e.start, lo)) / 1e9
            name = short_name(e.name)
            ops[name] = ops.get(name, 0.0) + d
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                for key, ns in split(s, e):
                    gaps[key] = gaps.get(key, 0.0) + ns / 1e9
    n = max(len(busy), 1)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n,
        "ops": {k: v / n for k, v in ops.items()},
        "device_ops": [[k, v / n] for k, v in top],
        "idle_gaps": [[k, v / n] for k, v in idle],
        "n_devices": len(busy),
    }
