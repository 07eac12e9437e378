"""A configuration, a data generator, a distance, a traffic mix of a new
kind, and an end-to-end and a per-layer metric, each added as new files
alone: the harness finds each by its name and runs the cell."""

import json
import os
import shutil

from bench import harness

ROOT = harness.ROOT

CONFIG = {
    "name": "tiny-sqkl", "source": "test only",
    "data": {"generator": "sparse_test", "d": 16, "alpha": 0.3,
             "keep": 0.5},
    "distance": "kl_test", "n_db": 600, "m_prime": 16,
    "spec": {"distance": "kl", "builder": "swgraph", "build_engine": "wave",
             "wave": 16, "NN": 8, "ef_construction": 40, "k": 10,
             "ef_search": 48, "frontier": 2, "slots": 8, "sched_frontier": 4,
             "steps_per_sync": 2},
    "checks": {"recall_at_10_min": 0.5, "dist_gap_max": 1e-4},
}
# histograms with about half their bins at the floor
GENERATOR = '''
import jax
import jax.numpy as jnp


def make(key, n, pool, *, d, alpha, keep):
    def rows(k, m):
        k1, k2 = jax.random.split(k)
        x = jax.random.dirichlet(k1, jnp.full((d,), alpha), (m,))
        x = jnp.where(jax.random.uniform(k2, (m, d)) < keep, x, 0.0)
        x = jnp.maximum(x, 1e-6)
        return x / jnp.sum(x, axis=-1, keepdims=True)

    kx, kq = jax.random.split(key)
    return rows(kx, n), rows(kq, pool)
'''
# KL written a second way, as a new distance's file would be
DISTANCE = '''
import jax.numpy as jnp


def pair(x, q, xp):
    return xp.sum(x * xp.log(x / q), axis=-1)


def terms(X, Q):
    ent = jnp.sum(X * jnp.log(X), axis=-1)
    return X, -jnp.log(Q), lambda s: s + ent[None, :]
'''
# bursts: the open-loop kind with every arrival pulled into the first half
# of each second
KIND = '''
import os

from bench import lookup

base = lookup.module("kinds", "open_loop", os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
start, drive = base.start, base.drive


def plan(mix, seconds, rng):
    p = base.plan(mix, seconds, rng)
    whole = p.due_s // 1.0
    p.due_s = whole + (p.due_s - whole) * float(mix["on_share"])
    return p
'''
MIX = {"kind": "bursts_test", "rate_per_s": 40.0, "pool": 64,
       "drain_s": 30.0, "on_share": 0.5}
READER = '''
def read(run):
    return float(run["rec"]["answered"].mean())
'''
E2E = '''
def read(run):
    return float(run["rec"]["attempted"])
'''


def test_new_files_make_a_new_cell(tmp_path):
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    new = {"configs/tiny-sqkl.json": json.dumps(CONFIG),
           "generators/sparse_test.py": GENERATOR,
           "reference/kl_test.py": DISTANCE,
           "kinds/bursts_test.py": KIND,
           "traffic/bursts.json": json.dumps(MIX),
           "metrics/answered_share.test.py": READER,
           "e2e/requests_test.py": E2E}
    for rel, text in new.items():
        (tmp_path / "bench" / rel).write_text(text)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = "tiny-sqkl.bursts"
    bench["configs"].append({"name": "tiny-sqkl", "source": "test only",
                             "file": "bench/configs/tiny-sqkl.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "tiny-sqkl",
                               "traffic": "bursts", "chips": 1, "why": "t"})
    bench["end_to_end"].append({"name": "requests_test", "unit": "requests",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock", "workloads": [cell]})
    bench["per_layer"].append({"name": "answered_share.test", "unit": "ratio",
                               "better": "higher", "source": "program_counter",
                               "layer": "test", "moves": "requests_test",
                               "workloads": [cell]})
    for m in bench["end_to_end"]:
        if m["name"] == "p50_ms":
            m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    keep = {}
    line = harness.run_cell(cell, 5, 1.0, True, root=str(tmp_path),
                            rehearsal=600, keep=keep)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 40 and line["failed"] == 0
    assert line["metrics"]["answered_share.test"]["value"] == 1.0
    # a metric that lists its cells is read in those alone
    assert set(line["metrics"]) == {"answered_share.test"}
    assert list(line)[-1] == "checks"
    # the new kind planned the window, the new generator made the data
    assert all((keep["rec"]["due_s"] % 1.0) < 0.5)
    assert (keep["X"] < 1e-5).mean() > 0.4
    assert keep["dist"].__file__.endswith("kl_test.py")
    line = harness.run_cell(cell, 5, 1.0, False, root=str(tmp_path),
                            rehearsal=600)
    assert set(line["metrics"]) == {"recall_at_10", "p50_ms",
                                    "build_points_per_s", "setup_s",
                                    "requests_test"}
    assert line["metrics"]["p50_ms"]["value"] is None  # not measured off chip
    assert line["metrics"]["recall_at_10"]["value"] >= 0.5
