"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload wiki128-kl.stream --seed 7 \
        --seconds 20 --trace 0

From the root of a checkout.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``
and, traced, ``breakdown``; ``checks`` comes last, each number of the
comparison with its limit, and the same numbers are the last lines of
standard error.  With no TPU, too few chips, or a device missing from
``bench/peaks.json`` it prints no result and exits 2.

``--rehearsal N`` runs off the chip (``JAX_PLATFORMS=cpu``) at N corpus
rows: it checks the paths and the line's shape, and every device metric
reads null (not measured).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", type=int, default=None, metavar="N")
    args = ap.parse_args(argv)

    from bench import harness

    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), rehearsal=args.rehearsal,
                                t_start=T_START)
    except harness.NoDevice as e:
        print(f"bench: {e}; not running", file=sys.stderr)
        return 2
    print(f"reported dist_gap_f64: {line['dist_gap_f64']!r} (no limit)",
          file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
