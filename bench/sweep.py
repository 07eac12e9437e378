"""Find a cell's operating point on the chip, once; not part of a run.

    python3 bench/sweep.py --workload wiki128-kl.stream --seed 11 \
        --fractions 0.6,0.8,0.9,1.0,1.2 --seconds 8
    python3 bench/sweep.py --workload randhist32-renyi2.batch --seed 12 \
        --efs 512,1024,2048,4096

Stream cells: builds the index once, measures the scheduler's throughput
at saturation (every slot always full), then offers open-loop Poisson load
at each fraction of it and reports latency, admission wait and the backlog
left when the window closes.  The traffic file's ``rate_per_s`` is 0.8 of
the highest rate whose backlog did not grow.

Batch cells: builds once and reports recall@10 and time per call at each
``ef_search``; the configuration takes the smallest that reaches its
recall target.

``--trace-out DIR`` also traces one short window (``--trace-seconds``) and
writes a small sample of it there (``devtrace.sample``), to read the
device's event names by hand and to record a trace for the tests.
Prints one JSON line per measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _say(**kw):
    print(json.dumps(kw), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--fractions", default="0.6,0.8,0.9,1.0,1.2")
    ap.add_argument("--efs", default="512,1024,2048,4096")
    ap.add_argument("--saturate", type=int, default=1200,
                    help="requests submitted at once to measure throughput")
    ap.add_argument("--trace-out", default=None)
    ap.add_argument("--trace-seconds", type=float, default=1.0)
    ap.add_argument("--rehearsal", type=int, default=None, metavar="N")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import check, data, devtrace, harness, traffic
    from bench.reference import scan as ref

    root = harness.ROOT
    files = harness.load_cell(root, args.workload)
    config, mix = files["config"], files["traffic"]
    dev = harness.device_info(root, int(files["cell"]["chips"]),
                              args.rehearsal is not None)
    harness.open_compile_cache(root)
    sys.path.insert(0, os.path.join(root, "src"))
    from repro.core import ANNIndex, RetrievalSpec

    n = int(args.rehearsal or config["n_db"])
    spec = RetrievalSpec(**config["spec"])
    if args.rehearsal:
        spec = spec.replace(**config.get("rehearsal_spec", {}))
    X, Q = data.make_corpus(config["data"], n, int(mix["pool"]), args.seed)
    Qh = np.asarray(Q)
    t = time.perf_counter()
    idx = ANNIndex.build(X, spec=spec, key=jax.random.fold_in(
        data.seed_key(args.seed), 2))
    jax.block_until_ready(idx.neighbors)
    _say(what="build", n=n, seconds=time.perf_counter() - t,
         device=dev["kind"])
    k = spec.k
    rng = data.host_rng(args.seed, 9)
    kind = traffic.kind(mix["kind"])

    if mix["kind"] == "open_loop":
        sched = idx.scheduler(spec=spec)
        sched.warmup(Qh[0])
        # saturation: every slot stays full while the queue lasts
        m = min(args.saturate, Qh.shape[0])
        for r in range(m):
            sched.submit(Qh[r], rid=r)
        done_t, ticks = [], 0
        t0 = time.perf_counter()
        while sched.n_pending or sched.n_inflight:
            out = sched.tick(time.perf_counter() - t0)
            ticks += 1
            done_t.extend([time.perf_counter() - t0] * len(out))
        lo, hi = int(0.2 * m), int(0.8 * m)
        cap = (hi - lo) / (done_t[hi] - done_t[lo])
        _say(what="saturation", requests=m, per_s=cap,
             tick_ms=1e3 * done_t[-1] / ticks, seconds=done_t[-1])
        sched.reset()
        for frac in [float(f) for f in args.fractions.split(",")]:
            mix2 = dict(mix, rate_per_s=frac * cap, pool=Qh.shape[0],
                        drain_s=30.0)
            plan = traffic.make_plan(mix2, args.seconds, rng)
            rec = kind.drive(sched, Qh, plan, args.seconds,
                             devtrace.Capture(False, ""), k)
            ok = rec["answered"]
            lat = rec["recv_s"][ok] - rec["due_s"][ok]
            wait = rec["admit_s"][ok] - rec["due_s"][ok]
            # backlog at the close: due before it, not yet answered by it
            backlog = int(np.sum(~(rec["recv_s"] <= args.seconds)))
            half = int(np.sum((rec["due_s"] < args.seconds / 2)
                              & ~(rec["recv_s"] <= args.seconds / 2)))
            _say(what="open_loop", fraction=frac, rate=frac * cap,
                 requests=len(plan.due_s), answered=int(ok.sum()),
                 backlog_half=half, backlog_close=backlog,
                 **traffic.latency_stats(lat),
                 wait_p99_ms=1e3 * float(np.percentile(wait, 99)),
                 tick_ms=1e3 * rec["tick_s"] / max(rec["ticks"], 1),
                 drain_s=rec["wall_s"] - args.seconds)
            sched.reset()
        system = sched
    else:
        Qa = Qh[: 2 * mix["batch"]]
        truth = check.truth(ref.distance(config["distance"]), X, Qa, k)
        for ef in [int(e) for e in args.efs.split(",")]:
            search = idx.searcher(spec=spec.replace(ef_search=ef))
            t = time.perf_counter()
            jax.block_until_ready(search(Qa[: mix["batch"]]))
            compile_s = time.perf_counter() - t
            ids, per = [], []
            for b in range(2):
                t = time.perf_counter()
                out = search(Qa[b * mix["batch"]:(b + 1) * mix["batch"]])
                ids.append(np.asarray(out[1]))
                per.append(time.perf_counter() - t)
            _say(what="ef", ef=ef, recall=check.recall(np.concatenate(ids),
                                                       truth),
                 call_s=per, first_call_s=compile_s,
                 evals=float(np.mean(np.asarray(out[2]))))
        system = search

    if args.trace_out:
        os.makedirs(args.trace_out, exist_ok=True)
        with tempfile.TemporaryDirectory() as tdir:
            cap = devtrace.Capture(True, tdir)
            if mix["kind"] == "open_loop":
                mix2 = dict(mix, pool=Qh.shape[0], trace_s=args.trace_seconds)
                plan = traffic.make_plan(mix2, args.trace_seconds, rng)
            else:
                plan = traffic.make_plan(mix, args.trace_seconds, rng)
            kind.drive(system, Qh, plan, args.trace_seconds, cap, k)
            size = os.path.getsize(cap.path)
            tr = devtrace.load(cap.path)
        red = devtrace.reduce(tr)
        devtrace.dump(devtrace.sample(tr),
                      os.path.join(args.trace_out, "sample.json"))
        _say(what="trace", stop_s=cap.stop_s, bytes=size,
             planes=sorted(tr.device), host_events=len(tr.host),
             device_events={p: len(v) for p, v in tr.device.items()},
             busy_s=red["busy_s"], window_s=red["window_s"],
             device_ops=red["device_ops"], idle_gaps=red["idle_gaps"],
             host_names=sorted({e.name for e in tr.host})[:60])
    return 0


if __name__ == "__main__":
    sys.exit(main())
