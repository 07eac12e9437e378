"""Serving driver endpoints: in-process smoke over the full service loop.

Covers the serving surfaces of ``repro.launch.serve`` on one tiny
workload: plain batched search, the ``--churn-*`` mutation endpoints
(insert/delete/query rounds + compact + recall audit), the
continuous-batching scheduler path (Poisson trace served by static,
dispatch-on-idle dynamic, and slot disciplines; the request -> queue ->
slot -> response mapping itself is asserted in tests/test_scheduler.py),
and the declarative ``--spec`` path — including a rerank spec
(``search_policy="min"``) served end to end.
"""

import numpy as np

from repro.core import RetrievalSpec
from repro.launch.serve import build_and_serve, main, poisson_arrivals


def test_poisson_arrivals_shape_and_rate():
    arr = poisson_arrivals(4000, 100.0, np.random.default_rng(0))
    assert arr.shape == (4000,)
    assert np.all(np.diff(arr) > 0)
    # mean inter-arrival ~ 1/rate (law of large numbers, loose bound)
    assert 0.008 < float(np.diff(arr).mean()) < 0.012


def test_serve_endpoints_search_churn_continuous():
    stats = build_and_serve(
        distance="kl", n_db=400, dim=16, n_queries=64, batch=16, k=10,
        ef_search=48, builder="swgraph", build_engine="wave", wave=16,
        churn_rounds=2, churn_insert=32, churn_delete=24,
        continuous=True, slots=8, utilization=0.5, verbose=False,
    )
    # -- plain batched serving
    assert stats["served"] == 64
    assert stats["recall@k"] >= 0.85

    # -- every response is self-described by the spec it was served under
    spec = RetrievalSpec.from_dict(stats["spec"])
    assert stats["spec_fingerprint"] == spec.fingerprint()
    assert spec.builder == "swgraph" and spec.wave == 16

    # -- continuous-batching path: same traffic, slot scheduler
    cont = stats["continuous"]
    assert cont["slots"] == 8
    assert cont["recall@k"] >= stats["recall@k"] - 0.02
    assert cont["p50_ms"] > 0 and cont["p99_ms"] >= cont["p50_ms"]
    assert cont["offered_qps"] > 0
    # dispatch-on-idle baseline served over the identical trace
    assert cont["dynamic_p99_ms"] > 0
    assert cont["dynamic_recall@k"] >= stats["recall@k"] - 0.02
    assert cont["p99_speedup_vs_dynamic"] > 0
    # the scheduler's tick log: each part's mean and max, in ms
    ph = cont["tick_phases"]
    assert ph["ticks"] > 0 and ph["mean_ms"]["tick"] > 0
    assert ph["max_ms"]["sync"] >= ph["mean_ms"]["sync"] > 0
    assert ph["mean_ms"]["self"] >= 0

    # -- churn mutation endpoints (online mutable index underneath)
    churn = stats["churn"]
    assert churn["inserted"] == 64 and churn["deleted"] == 48
    assert churn["inserts_per_s"] > 0 and churn["deletes_per_s"] > 0
    assert churn["recall@k_after_churn"] >= 0.8
    assert churn["n_alive"] == 400 + 64 - 48
    # free-list reuse keeps the footprint below naive append-only growth
    assert churn["capacity_used"] <= 400 + 64


def test_serve_cli_spec_path(tmp_path):
    """`--spec spec.json` drives the whole driver: the CLI smoke the ISSUE-5
    CI satellite asks for.  The spec fully defines the scenario (swgraph
    builder, blend construction policy); the flags keep workload control."""
    spec = RetrievalSpec(distance="kl", build_policy="blend(0.25)",
                         builder="swgraph", build_engine="wave", wave=16,
                         NN=10, ef_construction=48, k=10, ef_search=48,
                         frontier=2)
    path = tmp_path / "spec.json"
    spec.to_json(str(path))
    stats = main(["--spec", str(path), "--n-db", "320", "--dim", "16",
                  "--queries", "32", "--batch", "16"])
    assert stats["served"] == 32
    assert stats["recall@k"] >= 0.8
    # the recorded spec is the file's spec (capacity untouched: no churn)
    assert RetrievalSpec.from_dict(stats["spec"]) == spec
    # scenario flags may not silently fight the spec: fail loud
    import pytest

    with pytest.raises(SystemExit):
        main(["--spec", str(path), "--ef", "256", "--n-db", "320"])


def test_serve_rerank_spec_through_searcher_and_scheduler():
    """A rerank spec (search_policy=min) serves through BOTH the batch path
    and the continuous scheduler (ISSUE-5: the scheduler no longer raises
    on query_sym != none)."""
    spec = RetrievalSpec(distance="kl", build_policy="min",
                         search_policy="min", k_c=24, builder="nndescent",
                         NN=10, nnd_iters=4, k=10, ef_search=48, frontier=2,
                         slots=8, sched_frontier=4, steps_per_sync=2)
    stats = build_and_serve(spec=spec, n_db=400, dim=16, n_queries=48,
                            batch=16, continuous=True, utilization=0.5,
                            verbose=False)
    assert stats["recall@k"] >= 0.85
    cont = stats["continuous"]
    # the scheduler's retire-time rerank serves the same quality
    assert cont["recall@k"] >= stats["recall@k"] - 0.02
