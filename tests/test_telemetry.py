"""Tick telemetry of the slot schedulers (``repro.core.telemetry``).

What must hold:
  * one log row per ``tick`` call: the caller's ``now``, the tick's
    duration, and each part's duration, the parts adding up to no more
    than the tick (its self time is the rest);
  * the log's counters agree with the traffic: admitted + shed equals the
    submits, retired equals the returned non-shed results, and the ticks
    held add up to what the caller saw; ``qos_stats`` reads the same
    counters;
  * ``reset`` (and so ``warmup``) empties the log; the ring keeps the
    newest ``CAPACITY`` rows and says how many it dropped;
  * under a profiler the spans land on the host line of the enclosing
    annotation, and the sharded scheduler records the same names;
  * the build record's parts add up to the build, and its ``build_evals``
    is every distance the build computed.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import ANNIndex, RetrievalSpec, get_distance, telemetry
from repro.data.synthetic import lda_like_histograms, split_queries

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # repo root, for `bench`
from bench import devtrace  # noqa: E402

N_DB, N_Q, DIM, K, EF = 420, 24, 16, 10, 48
PARTS = set(telemetry.SPANS)


@pytest.fixture(scope="module")
def setup():
    spec = RetrievalSpec(distance="kl", builder="swgraph", NN=10,
                         ef_construction=48, wave=16, k=K, ef_search=EF,
                         slots=6, sched_frontier=4, steps_per_sync=2)
    X = lda_like_histograms(jax.random.PRNGKey(0), N_DB + N_Q, DIM)
    Q, db = split_queries(X, N_Q, jax.random.PRNGKey(1))
    idx = ANNIndex.build(db, spec=spec, key=jax.random.PRNGKey(2))
    return idx, spec, np.asarray(Q)


def _drive(sched, Q, slo_ms=None, dt=0.001):
    """Submit every query, tick until all are answered (tick n at clock
    ``n * dt``); returns the results, the tick number (0-based) at which
    each came back, and the number of ticks."""
    for i, q in enumerate(Q):
        sched.submit(q, rid=i, slo_ms=None if slo_ms is None else slo_ms[i])
    got, at, n = {}, {}, 0
    while sched.n_pending or sched.n_inflight:
        for r in sched.tick(now=dt * n):
            got[r.rid], at[r.rid] = r, n
        n += 1
    return got, at, n


def test_rows_add_up_to_the_tick(setup):
    idx, spec, Q = setup
    sched = idx.scheduler(spec=spec)
    got, _, n = _drive(sched, Q)
    assert len(got) == len(Q)
    log = sched.log
    assert log is telemetry.latest() and log.n == n and not log.dropped
    r = log.rows()
    np.testing.assert_allclose(r["now"], 0.001 * np.arange(n))
    parts = np.stack([r[s] for s in telemetry.SPANS])
    assert (parts >= 0).all() and (r["tick"] > 0).all()
    self_s = r["tick"] - parts.sum(axis=0)
    assert (self_s >= 0).all()
    np.testing.assert_allclose(parts.sum(axis=0) + self_s, r["tick"])
    # every tick here ran a step, waited on it, and had slots to fill
    assert (r["occupied"] > 0).all() and (r["occupied"] <= spec.slots).all()
    assert (r["step"] > 0).all() and (r["sync"] > 0).all()
    assert (r["rerank"] == 0).all() and (r["background"] == 0).all()
    s = log.summary()
    assert s["ticks"] == n and set(s["mean_ms"]) == PARTS | {"tick", "self"}
    assert s["mean_ms"]["self"] >= 0
    assert s["max_ms"]["tick"] >= s["mean_ms"]["tick"] > 0
    assert sum(s["mean_ms"][p] for p in PARTS | {"self"}) == pytest.approx(
        s["mean_ms"]["tick"])


def test_counts_match_submits_and_results(setup):
    """A third of the requests shed (1 ms budget), a third demoted (a
    budget only the cheaper rung fits), a third served in full."""
    idx, spec, Q = setup
    ladder = [spec, spec.replace(ef_search=24)]
    # a 10 s prior prices rung 0 at 10 s and rung 1 at 5 s; the ticks'
    # clock stands still, so no service time is observed and the estimate
    # stays at the prior
    sched = idx.scheduler(spec=spec, ladder=ladder, slo_ms=60_000.0,
                          service_prior=10.0)
    budgets = [(1.0, 7_000.0, None)[i % 3] for i in range(len(Q))]
    got, _, _ = _drive(sched, Q, slo_ms=budgets, dt=0.0)
    shed = [r for r in got.values() if r.shed]
    served = [r for r in got.values() if not r.shed]
    assert len(shed) == len(Q) // 3
    assert sorted(r.level for r in served) == [0] * 8 + [1] * 8
    c = sched.log.counters
    assert c["admitted"] + c["shed"] == len(Q)
    assert (c["admitted"], c["shed"], c["demoted"]) == (16, 8, 8)
    assert c["retired"] == len(served)
    assert sched.qos_stats["shed"] == c["shed"] == sched.admission.n_shed
    assert sched.qos_stats["demoted"] == c["demoted"]
    # each row holds what its tick added to the counters
    r = sched.log.rows()
    for name in telemetry.COUNTERS:
        assert r[name].sum() == c[name], name


def test_held_ticks_sum_what_the_caller_saw(setup):
    """With a slot for every query, all are admitted on tick 0, so a
    request answered on tick i held its slot i + 1 ticks."""
    idx, spec, Q = setup
    sched = idx.scheduler(spec=spec.replace(slots=len(Q)))
    got, at, _ = _drive(sched, Q)
    r = sched.log.rows()
    assert r["admitted"][0] == len(Q) and r["admitted"][1:].sum() == 0
    assert r["held_ticks"].sum() == sum(i + 1 for i in at.values())
    assert r["retired"].sum() == len(got) == len(Q)
    done = np.flatnonzero(r["retired"])
    np.testing.assert_array_equal(done, sorted(set(at.values())))


def test_reset_and_warmup_empty_the_log(setup):
    idx, spec, Q = setup
    sched = idx.scheduler(spec=spec)
    _drive(sched, Q[:8])
    assert sched.log.n > 0 and sched.log.counters["retired"] == 8
    sched.reset()
    assert sched.log.n == 0 and not any(sched.log.counters.values())
    assert len(sched.log.rows()["tick"]) == 0
    sched.warmup(Q[0])  # submits, drains, resets
    assert sched.log.n == 0 and sched.log.counters["admitted"] == 0
    sched.tick(now=1.0)  # an idle tick is a row of its own
    r = sched.log.rows()
    assert sched.log.n == 1 and r["now"][0] == 1.0 and r["occupied"][0] == 0


def test_ring_wraps_at_capacity():
    log = telemetry.TickLog()
    assert telemetry.latest() is log
    cap = telemetry.CAPACITY
    for i in range(cap + 5):
        log.append(now=float(i), tick=0.01, sync=0.004)
    assert log.n == cap + 5 and log.dropped == 5
    r = log.rows()
    assert len(r["now"]) == cap
    assert r["now"][0] == 5.0 and r["now"][-1] == cap + 4.0
    assert np.all(np.diff(r["now"]) == 1.0)  # oldest first across the seam
    assert len(log.rows(until=10.0)["now"]) == 5  # the rows of now 5..9
    log.reset()
    assert log.n == 0 and log.dropped == 0 and not len(log.rows()["now"])


def _tick_span_names(trace) -> set:
    return {e.name for e in trace.host if e.name.startswith("repro.tick")}


def test_spans_land_on_the_profiled_host_line(setup, tmp_path):
    """Under a profiler, each tick's spans are events of the host line that
    holds the enclosing annotation, nested inside it."""
    idx, spec, Q = setup
    sched = idx.scheduler(spec=spec)
    sched.warmup(Q[0])
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            _drive(sched, Q)
    finally:
        jax.profiler.stop_trace()
    paths = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert len(paths) == 1
    trace = devtrace.load(str(paths[0]))
    lo, hi = devtrace.window_of(trace)
    names = _tick_span_names(trace)
    want = {"repro.tick"} | {"repro.tick." + p for p in
                             ("select", "put", "admit", "step", "sync",
                              "retire_read", "retire")}
    assert names == want
    ticks = [e for e in trace.host if e.name == "repro.tick"]
    assert len(ticks) == sched.log.n
    assert all(lo <= e.start and e.end <= hi for e in ticks)
    for e in trace.host:
        if e.name.startswith("repro.tick."):
            assert any(t.start <= e.start and e.end <= t.end for t in ticks)


def test_sharded_scheduler_writes_the_same_spans():
    """The scatter-gather scheduler logs and annotates the same parts
    (neither has a release: admission writes over a retired slot)."""
    body = """
import glob, json, sys, tempfile
import jax, numpy as np
sys.path.insert(0, %r)
from bench import devtrace
from repro.core import get_distance, telemetry
from repro.core.distributed import ShardedSlotScheduler, build_local_subgraphs
from repro.data.synthetic import lda_like_histograms
mesh = jax.make_mesh((4, 2), ("data", "model"))
dist = get_distance("kl")
X = lda_like_histograms(jax.random.PRNGKey(0), 512, 16)
Q = np.asarray(lda_like_histograms(jax.random.PRNGKey(1), 12, 16))
nbrs = build_local_subgraphs(mesh, dist, X, NN=10, nnd_iters=6)
sched = ShardedSlotScheduler(mesh, dist, X, neighbors=nbrs, slots=4, ef=48,
                             k=10, steps_per_sync=2)
sched.warmup(Q[0])
assert telemetry.latest() is sched.log and sched.log.n == 0
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
with jax.profiler.TraceAnnotation(devtrace.WINDOW):
    res = sched.run_stream(Q, warm=False)
jax.profiler.stop_trace()
trace = devtrace.load(glob.glob(d + "/plugins/profile/*/*.xplane.pb")[0])
r = sched.log.rows()
print(json.dumps({
    "names": sorted({e.name for e in trace.host
                     if e.name.startswith("repro.tick")}),
    "ticks": sched.log.n,
    "annotated": sum(e.name == "repro.tick" for e in trace.host),
    "admitted": int(r["admitted"].sum()), "retired": int(r["retired"].sum()),
    "results": len(res), "held": int(r["held_ticks"].sum()),
    "self_ok": bool((r["tick"] >= sum(r[s] for s in telemetry.SPANS)).all()),
}))
""" % REPO
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    want = {"repro.tick"} | {"repro.tick." + p for p in
                             ("select", "put", "admit", "step", "sync",
                              "retire_read", "retire")}
    assert set(got["names"]) == want
    assert got["annotated"] == got["ticks"] > 0
    assert got["admitted"] == got["retired"] == got["results"] == 12
    assert got["held"] >= got["retired"] and got["self_ok"]


@dataclasses.dataclass(frozen=True)
class _CountingKL:
    """KL with a ``score`` that adds the size of every block it scores to
    ``counts`` as the build runs (a composite: the jnp path scores it)."""

    counts: list = dataclasses.field(compare=False, hash=False)
    base: object = dataclasses.field(default_factory=lambda: get_distance("kl"))

    name = "kl"
    needs_simplex = True

    def matrix(self, U, V):
        return self.base.matrix(U, V)

    def query_matrix(self, Q, X, mode="left"):
        return self.base.query_matrix(Q, X, mode)

    def pairwise(self, u, v):
        return self.base.pairwise(u, v)

    def prep_scan(self, X):
        return self.base.prep_scan(X)

    def prep_query(self, q):
        return self.base.prep_query(q)

    def score(self, rows, qc):
        out = self.base.score(rows, qc)
        jax.debug.callback(lambda o: self.counts.append(o.size), out)
        return out


@pytest.mark.parametrize("wave,frontier", [(16, None), (1, None), (8, 2)])
def test_build_record_counts_every_distance(wave, frontier):
    """``build_evals`` equals the distances a wrapped ``score`` saw over a
    tiny build, and the record's parts add up to the build's time."""
    X = lda_like_histograms(jax.random.PRNGKey(3), 150, 16)
    spec = RetrievalSpec(distance="kl", builder="swgraph", NN=6,
                         ef_construction=24, wave=wave, build_frontier=frontier)
    dist = _CountingKL([])
    ANNIndex.build(X, dist, spec=spec)
    rec = telemetry.latest_build()
    assert rec.build_evals == sum(dist.counts) > 0
    assert rec.waves == -(-149 // wave) and rec.m_prime == 16 and not rec.kernel
    parts = rec.seconds
    assert set(parts) == set(telemetry.BUILD_PARTS) and min(parts.values()) > 0
    assert sum(parts.values()) <= rec.total <= sum(parts.values()) + 0.05


@pytest.mark.parametrize("policy,base,kernel,m_prime", [
    ("avg", "kl", True, 32), ("blend(0.25)", "itakura_saito", True, 32),
    ("reverse", "kl", True, 16), ("none", "kl", True, 16),
    ("min", "kl", False, 32), ("max", "kl", False, 32),
    ("avg", "renyi_2", False, 32), ("rankblend(0.5,1.0)", "kl", False, 32),
])
def test_build_record_names_the_scoring_path(policy, base, kernel, m_prime,
                                             monkeypatch):
    """Which path scores a build: linear policies over a linear base lower
    to one Distance that the gather kernel can take, at m' = 2m for a blend;
    every other policy or base stays composite and scores through jnp on
    any backend (its branches' widths add up in ``m_prime``).  On the chip
    the kernel takes by default only reps of one 128-lane row.  Off the
    chip the record says jnp for all of them."""
    from repro.core.spec import DistancePolicy
    from repro.kernels import ops

    bound = DistancePolicy.parse(policy).bind(get_distance(base))
    assert ops.kernel_scores(bound, m_prime, use_pallas=True) is kernel
    assert ops.kernel_scores(bound, m_prime, use_pallas=False) is False
    with monkeypatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)
        assert ops.kernel_scores(bound, m_prime) is kernel
        assert ops.kernel_scores(bound, 2 * ops.LANES) is False
    X = lda_like_histograms(jax.random.PRNGKey(4), 40, 16)
    spec = RetrievalSpec(distance=base, build_policy=policy, builder="swgraph",
                         NN=4, ef_construction=8, wave=8, n_entries=1)
    ANNIndex.build(X, spec=spec)
    rec = telemetry.latest_build()
    assert (rec.kernel, rec.m_prime) == (False, m_prime)


def test_no_build_record_for_a_failed_build():
    before = telemetry.latest_build()
    X = lda_like_histograms(jax.random.PRNGKey(4), 20, 16)
    with pytest.raises(ValueError):
        ANNIndex.build(X, spec=RetrievalSpec(distance="kl", builder="swgraph",
                                             NN=8, M_max=4))
    assert telemetry.latest_build() is before
