"""One run of one cell: set-up, the measured window, the check, the line.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric is a file of its own, found by the name that ``BENCHMARK.json``
gives it (see ``README.md``):

* ``bench/configs/<config>.json``: the deployment (data, distance, sizes,
  the ``RetrievalSpec`` it is built and served with, the limits of the
  check); its data generator ``bench/generators/<generator>.py`` and its
  distance's reference ``bench/reference/<distance>.py``;
* ``bench/traffic/<traffic>.json``: the traffic mix, and the
  ``bench/kinds/<kind>.py`` that plans and drives it (``traffic.py``);
* ``bench/e2e/<metric>.py`` and ``bench/metrics/<metric>.py``: an
  end-to-end and a per-layer metric's reader;
* ``bench/peaks.json``: the device's peaks, by ``device_kind``.

From the program the harness takes only the system under test:
``ANNIndex.build``, ``ANNIndex.scheduler`` (``submit``/``tick``) and
``ANNIndex.searcher``, and the counters they return.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import tempfile
import time

import numpy as np

from bench import check, data, devtrace, lookup, traffic
from bench.reference import scan as ref

ROOT = lookup.ROOT
# metrics computed from counts alone: a rehearsal off the chip may print them
COUNT_SOURCES = ("program_counter",)
COUNT_METRICS = ("recall_at_10",)


class NoDevice(RuntimeError):
    """The run cannot measure here: no chip, too few, or unknown peaks."""


# ---------------------------------------------------------------- lookup


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> dict:
    """The cell's entries from ``BENCHMARK.json`` and the files they name."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _json(os.path.join(root, cfg_entry["file"]))
    mix = _json(os.path.join(root, "bench", "traffic", cell["traffic"] + ".json"))

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell, "config": config, "traffic": mix,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def load_reader(root: str, metric: str, folder: str = "metrics"):
    """``read(run) -> float | None`` of ``bench/<folder>/<metric>.py``:
    ``metrics`` for a per-layer metric, ``e2e`` for an end-to-end one."""
    return lookup.module(folder, metric, root).read


def device_info(root: str, chips: int, rehearsal: bool) -> dict:
    """The devices JAX reports, checked against the cell and the peaks."""
    import jax

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if platform != "tpu" and not rehearsal:
        raise NoDevice(f"JAX finds no TPU (platform {platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    peaks = _json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if kind not in peaks and not rehearsal:
        raise NoDevice(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return {"platform": platform, "kind": kind, "count": len(devs),
            "devices": devs, "peaks": peaks.get(kind)}


def open_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``.jax_cache`` of the checkout."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


class GcClock:
    """Collections of the cyclic garbage collector while in the block,
    and the seconds they took."""

    def __init__(self):
        self.seconds, self.count, self._t = 0.0, 0, None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.seconds += time.perf_counter() - self._t
            self.count += 1

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


class CompileClock:
    """Seconds JAX spends compiling (or loading compiled programs), and how
    many programs, since the last ``reset``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0

        def listen(event, duration, **_):
            if event == self.EVENT:
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    def reset(self):
        self.seconds, self.count = 0.0, 0


# ------------------------------------------------------------------- run


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, rehearsal: int | None = None,
             t_start: float | None = None, drain_s: float | None = None,
             keep: dict | None = None) -> dict:
    """Run one cell once and return its result line (a dict).

    ``rehearsal=n`` runs off the chip at ``n`` corpus rows: it checks the
    paths and the line's shape, and prints no device metric.  ``keep``, if
    given, receives the run's state for a caller that checks more after the
    window (the control)."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    spec_files = load_cell(root, workload)
    cell, config, mix = (spec_files["cell"], spec_files["config"],
                         spec_files["traffic"])
    dev = device_info(root, int(cell["chips"]), rehearsal is not None)
    open_compile_cache(root)
    compiles = CompileClock()

    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.core import ANNIndex, RetrievalSpec

    n = int(rehearsal or config["n_db"])
    spec = RetrievalSpec(**config["spec"])
    if rehearsal:
        spec = spec.replace(**config.get("rehearsal_spec", {}))
    k = spec.k
    dist = ref.distance(config["distance"], root)
    kind = traffic.kind(mix["kind"], root)
    X, Q = data.make_corpus(config["data"], n, int(mix["pool"]), seed, root)
    Qh = np.asarray(Q)
    plan = kind.plan(mix, seconds, data.host_rng(seed, 1))
    if drain_s is not None:
        plan.drain_s = drain_s

    compiles.reset()
    t_build = time.perf_counter()
    idx = ANNIndex.build(X, spec=spec, key=jax.random.fold_in(
        data.seed_key(seed), 2))
    jax.block_until_ready(idx.neighbors)
    build_s = time.perf_counter() - t_build - compiles.seconds

    system = kind.start(idx, spec, Qh, plan)
    setup_s = time.perf_counter() - t_start

    gc_clock = GcClock()
    with tempfile.TemporaryDirectory() as tdir:
        cap = devtrace.Capture(trace, tdir)
        compiles.reset()
        with gc_clock:
            rec = kind.drive(system, Qh, plan, seconds, cap, k)
        compiled_in_window = compiles.count
        reduced = None
        if cap.path:
            loaded = devtrace.load(cap.path)
            reduced = devtrace.reduce(loaded)
            if keep is not None:
                keep["trace"] = loaded
    stats = dev["devices"][0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if keep is not None:
        keep.update(X=X, Q=Qh, rec=rec, config=config, k=k, dist=dist)
    del system, idx
    gc.collect()

    # the check, once the window has closed and the program's state is freed
    ans = rec["answered"]
    rows_a = rec["rows"][ans]
    Qa = Qh[rows_a]
    truth_ids = []
    if ans.any():
        uniq, inv = np.unique(rows_a, return_inverse=True)
        truth_ids = check.truth(dist, X, Qh[uniq], k)[inv]
    correct, checks = check.compare(
        dist, X, Qa, rec["ids"][ans], rec["dists"][ans],
        truth_ids, config["checks"], int((~ans).sum()))
    f64 = check.gap_f64(dist, X, Qa, rec["ids"][ans], rec["dists"][ans])
    if keep is not None:
        keep.update(truth_ids=truth_ids, checks=checks)

    run = {"rec": rec, "n": n, "build_s": build_s, "setup_s": setup_s,
           "checks": checks, "config": config, "peaks": dev["peaks"],
           "kind": plan.kind, "trace": reduced}
    metrics = {}
    wanted = spec_files["per_layer"] if trace else spec_files["end_to_end"]
    for m in wanted:
        value = load_reader(root, m["name"],
                            "metrics" if trace else "e2e")(run)
        if value is None:
            continue
        if rehearsal and not (m["source"] in COUNT_SOURCES
                              or m["name"] in COUNT_METRICS):
            value = None  # off the chip: not measured
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": int(rec["attempted"]),
            "failed": int((~ans).sum()), "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = None if rehearsal else reduced["busy_s"]
        device["window_s"] = None if rehearsal else reduced["window_s"]
        if not rehearsal:
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
    line["compiles_in_window"] = compiled_in_window
    line["gc_in_window"] = {"count": gc_clock.count,
                            "s": None if rehearsal else gc_clock.seconds}
    if cap.stop_s is not None and not rehearsal:
        line["trace_stop_s"] = cap.stop_s
    line.update(getattr(kind, "extras", lambda rec, rehearsal: {})(
        rec, rehearsal is not None))
    line["dist_gap_f64"] = f64
    line["checks"] = checks
    return line
