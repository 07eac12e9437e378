"""The lower-precision control of a cell, on the chip; not part of a run.

    python3 bench/control.py --workload wiki128-kl.stream --seconds 20 \
        --seeds 201,202,203

For each seed it makes one whole run of the cell (the same as
``run.py``, printing the same result line), then puts the
reference in the program's place at the next lower precision (``bf16x3``,
a TPU's ``Precision.HIGH``): the control answers the same queries that the
window answered, and its answers go through the same comparison.  The
control has to come out not correct; its numbers are the upper readings
that the limits in the configuration's ``checks`` were set below.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def control_checks(keep: dict) -> tuple[bool, dict]:
    from bench import check

    rec, cfg = keep["rec"], keep["config"]
    ans = rec["answered"]
    Qa = keep["Q"][rec["rows"][ans]]
    d, ids = check.control(keep["dist"], keep["X"], Qa, keep["k"])
    ok, checks = check.compare(keep["dist"], keep["X"], Qa, ids, d,
                               keep["truth_ids"], cfg["checks"], 0)
    return ok, checks, check.gap_f64(keep["dist"], keep["X"], Qa, ids, d)


def control_only(workload: str, seed: int, seconds: float) -> dict:
    """The control's numbers without a run: the queries a window would send
    (every request of an open-loop window; every distinct pool row of a
    closed-batch one), at the cell's size, on the chip."""
    import numpy as np

    from bench import check, data, harness, traffic
    from bench.reference import scan as ref

    files = harness.load_cell(harness.ROOT, workload)
    cfg, mix = files["config"], files["traffic"]
    harness.device_info(harness.ROOT, int(files["cell"]["chips"]), False)
    harness.open_compile_cache(harness.ROOT)
    X, Q = data.make_corpus(cfg["data"], int(cfg["n_db"]), int(mix["pool"]),
                            seed)
    plan = traffic.make_plan(mix, seconds, data.host_rng(seed, 1))
    Qa = np.asarray(Q)[np.sort(plan.order)]
    k = int(cfg["spec"]["k"])
    dist = ref.distance(cfg["distance"])
    truth_ids = check.truth(dist, X, Qa, k)
    d, ids = check.control(dist, X, Qa, k)
    ok, checks = check.compare(dist, X, Qa, ids, d, truth_ids,
                               cfg["checks"], 0)
    return {"control": True, "seed": seed, "correct": ok, "queries": len(Qa),
            "dist_gap_f64": check.gap_f64(dist, X, Qa, ids, d),
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sample", default=None, metavar="DIR",
                    help="with --trace 1: write a small sample of each "
                         "run's trace there (devtrace.sample)")
    ap.add_argument("--rehearsal", type=int, default=None, metavar="N")
    ap.add_argument("--control-only", action="store_true",
                    help="no run: the control on the queries a window sends")
    args = ap.parse_args(argv)

    from bench import devtrace, harness

    for seed in [int(s) for s in args.seeds.split(",")]:
        if args.control_only:
            print(json.dumps(control_only(args.workload, seed, args.seconds)),
                  flush=True)
            continue
        keep = {}
        line = harness.run_cell(args.workload, seed, args.seconds,
                                bool(args.trace), rehearsal=args.rehearsal,
                                t_start=time.perf_counter(), keep=keep)
        print(json.dumps(line), flush=True)
        if args.sample and "trace" in keep:
            os.makedirs(args.sample, exist_ok=True)
            devtrace.dump(devtrace.sample(keep["trace"]), os.path.join(
                args.sample, f"{args.workload}.{seed}.json"))
        ok, checks, f64 = control_checks(keep)
        print(json.dumps({"control": True, "seed": seed, "correct": ok,
                          "dist_gap_f64": f64, "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
