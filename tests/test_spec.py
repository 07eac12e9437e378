"""RetrievalSpec / DistancePolicy: the declarative scenario currency.

Contract (ISSUE 5): specs JSON-round-trip exactly (hypothesis property),
policies parse from their canonical string forms, the legacy
``index_sym``/``query_sym`` kwargs shim constructs an equivalent spec with
BIT-IDENTICAL build and search results (plus a DeprecationWarning), and
``grid`` sweeps the cartesian product deterministically.
"""

import json
import warnings

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    ANNIndex,
    Blend,
    DistancePolicy,
    MaxSym,
    RankBlend,
    RetrievalSpec,
    get_distance,
)
from repro.data.synthetic import lda_like_histograms, split_queries

N_DB, N_Q, DIM, K = 420, 16, 16, 10


@pytest.fixture(scope="module")
def data():
    X = lda_like_histograms(jax.random.PRNGKey(0), N_DB + N_Q, DIM)
    Q, db = split_queries(X, N_Q, jax.random.PRNGKey(1))
    return Q, db


# ---------------------------------------------------------------------------
# DistancePolicy
# ---------------------------------------------------------------------------


def test_policy_parse_roundtrip_canonical_forms():
    for p in (DistancePolicy("none"), DistancePolicy("avg"), MaxSym(),
              Blend(0.25), RankBlend(0.6), RankBlend(0.7, 2.0)):
        assert DistancePolicy.parse(str(p)) == p
    assert DistancePolicy.parse(None) == DistancePolicy("none")
    assert DistancePolicy.parse(Blend(0.5)) == Blend(0.5)


def test_policy_validation():
    with pytest.raises(ValueError, match="unknown policy"):
        DistancePolicy("bogus")
    with pytest.raises(ValueError, match="alpha"):
        Blend(1.5)
    with pytest.raises(ValueError, match="no parameters"):
        DistancePolicy("avg", alpha=0.5)
    with pytest.raises(ValueError, match="tau"):
        RankBlend(0.5, tau=-1.0)
    with pytest.raises(ValueError, match="malformed|unknown"):
        DistancePolicy.parse("blend(")
    # tau silently dropped would break parse(str(p)) == p: reject it
    with pytest.raises(ValueError, match="no tau"):
        DistancePolicy("blend", alpha=0.3, tau=5.0)
    with pytest.raises(ValueError, match="no tau"):
        DistancePolicy.parse("blend(0.3,5)")


def test_blend_special_cases_lower_to_legacy_wrappers():
    """Over a linear base (KL) avg, reverse and every blend lower to one
    matmul-form ``Distance``; over Renyi the legacy wrappers stay."""
    from repro.core.distances import Distance
    from repro.core.symmetrize import (CombinedDistance, ReversedDistance,
                                       SymmetrizedDistance, symmetrized)

    dist = get_distance("kl")
    assert Blend(1.0).bind(dist) is dist
    assert type(Blend(0.5).bind(dist)) is Distance
    assert Blend(0.5).bind(dist) is symmetrized(dist, "avg")
    assert Blend(0.5).bind(dist).name == "kl-avg"
    assert type(Blend(0.0).bind(dist)) is Distance
    assert type(Blend(0.25).bind(dist)) is Distance

    renyi = get_distance("renyi_2")
    assert Blend(1.0).bind(renyi) is renyi
    assert isinstance(Blend(0.5).bind(renyi), SymmetrizedDistance)
    assert isinstance(Blend(0.0).bind(renyi), ReversedDistance)
    assert isinstance(Blend(0.25).bind(renyi), CombinedDistance)


# ---------------------------------------------------------------------------
# RetrievalSpec serialization
# ---------------------------------------------------------------------------


def test_spec_json_roundtrip_through_file(tmp_path):
    spec = RetrievalSpec(distance="itakura_saito", build_policy=Blend(0.25),
                         search_policy="min", k_c=40, builder="swgraph",
                         wave=16, capacity=1000, adaptive=True)
    path = tmp_path / "spec.json"
    spec.to_json(str(path))
    back = RetrievalSpec.from_json(str(path))
    assert back == spec
    assert back.fingerprint() == spec.fingerprint()
    # and from a raw JSON string
    assert RetrievalSpec.from_json(spec.to_json()) == spec


def test_spec_rejects_unknown_fields_and_bad_values():
    with pytest.raises(ValueError, match="unknown RetrievalSpec fields"):
        RetrievalSpec.from_dict({"efSearch": 50})
    with pytest.raises(ValueError, match="builder"):
        RetrievalSpec(builder="hnswlib")
    with pytest.raises(ValueError, match="k_c"):
        RetrievalSpec(k=10, k_c=5)


@settings(max_examples=25, deadline=None)
@given(
    distance=st.sampled_from(["kl", "itakura_saito", "renyi_0.25", "l2"]),
    build_kind=st.sampled_from(["none", "avg", "min", "reverse", "max"]),
    alpha=st.floats(min_value=0.0, max_value=1.0),
    use_blend=st.booleans(),
    builder=st.sampled_from(["nndescent", "swgraph"]),
    ef=st.integers(min_value=16, max_value=512),
    k=st.integers(min_value=1, max_value=16),
    wave=st.integers(min_value=1, max_value=128),
    adaptive=st.booleans(),
)
def test_property_spec_json_roundtrip(distance, build_kind, alpha, use_blend,
                                      builder, ef, k, wave, adaptive):
    """Property: any spec survives dict -> json -> dict bit-exactly, and the
    fingerprint is a pure function of the serialized form."""
    bp = Blend(alpha) if use_blend else DistancePolicy(build_kind)
    spec = RetrievalSpec(distance=distance, build_policy=bp, builder=builder,
                         ef_search=ef, k=k, wave=wave, adaptive=adaptive)
    wire = json.loads(json.dumps(spec.to_dict()))
    back = RetrievalSpec.from_dict(wire)
    assert back == spec
    assert back.fingerprint() == spec.fingerprint()
    assert back.to_dict() == spec.to_dict()


def test_grid_sweeps_cartesian_product():
    base = RetrievalSpec()
    specs = base.grid(build_policy=[Blend(a) for a in (0.0, 0.5, 1.0)],
                      ef_search=[32, 96])
    assert len(specs) == 6
    assert len({s.fingerprint() for s in specs}) == 6
    assert specs[0].build_policy == Blend(0.0) and specs[0].ef_search == 32
    assert all(s.builder == base.builder for s in specs)
    assert base.grid() == [base]


# ---------------------------------------------------------------------------
# the deprecation shim: legacy kwargs == spec, bit for bit
# ---------------------------------------------------------------------------


def test_legacy_kwargs_shim_bit_identical_and_warns(data):
    Q, db = data
    dist = get_distance("kl")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        legacy = ANNIndex.build(db, dist, index_sym="min", query_sym="min",
                                builder="nndescent", NN=10, nnd_iters=6,
                                key=jax.random.PRNGKey(2))
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    spec = RetrievalSpec(distance="kl", build_policy="min", search_policy="min",
                         builder="nndescent", NN=10, nnd_iters=6)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)  # spec path: quiet
        fresh = ANNIndex.build(db, spec=spec, key=jax.random.PRNGKey(2))
    np.testing.assert_array_equal(np.asarray(legacy.neighbors),
                                  np.asarray(fresh.neighbors))
    np.testing.assert_array_equal(np.asarray(legacy.entries),
                                  np.asarray(fresh.entries))
    out_l = legacy.searcher(K, 48, k_c=32)(Q)
    out_s = fresh.searcher(K, 48, k_c=32)(Q)
    for a, b in zip(out_l, out_s):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_spec_and_legacy_kwargs_conflict_raises(data):
    _, db = data
    spec = RetrievalSpec(NN=10, nnd_iters=4)
    with pytest.raises(ValueError, match="not both"):
        ANNIndex.build(db, get_distance("kl"), spec=spec, NN=12)


def test_searcher_resolves_spec_first_with_explicit_overrides(data):
    Q, db = data
    spec = RetrievalSpec(distance="kl", NN=10, nnd_iters=6, ef_search=48,
                         k=5, frontier=2)
    idx = ANNIndex.build(db, spec=spec, key=jax.random.PRNGKey(2))
    d, ids, _, _ = idx.searcher()(Q)  # all knobs from the build spec
    assert ids.shape == (N_Q, 5)
    d2, ids2, _, _ = idx.searcher(k=K)(Q)  # explicit override wins
    assert ids2.shape == (N_Q, K)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids2[:, :5]))


def test_searcher_rejects_spec_with_mismatched_search_policy(data):
    """The search distance is bound at build time: a later spec that flips
    search_policy must fail loud instead of silently serving the wrong
    scenario (knob-only overrides on a matching spec remain fine)."""
    Q, db = data
    spec = RetrievalSpec(distance="kl", NN=10, nnd_iters=4)
    idx = ANNIndex.build(db, spec=spec, key=jax.random.PRNGKey(2))
    with pytest.raises(ValueError, match="search policy"):
        idx.searcher(spec=spec.replace(search_policy="min", k_c=40))
    with pytest.raises(ValueError, match="search policy"):
        idx.scheduler(spec=spec.replace(search_policy="min", k_c=40))
    # same policy, different knobs: allowed
    d, ids, _, _ = idx.searcher(spec=spec.replace(ef_search=32, k=5))(Q)
    assert ids.shape == (N_Q, 5)


def test_build_info_records_spec_fingerprint(data):
    _, db = data
    spec = RetrievalSpec(distance="kl", build_policy=Blend(0.25), NN=10,
                         nnd_iters=4)
    idx = ANNIndex.build(db, spec=spec, key=jax.random.PRNGKey(2))
    assert idx.build_info["spec_fingerprint"] == spec.fingerprint()
    assert RetrievalSpec.from_dict(idx.build_info["spec"]) == spec
    assert idx.build_info["index_sym"] == "blend(0.25)"
    # the spec rides into the online index on conversion
    idx.ensure_online()
    assert idx.online.spec == spec


# ---------------------------------------------------------------------------
# data-calibrated RankBlend tau (ISSUE 6)
# ---------------------------------------------------------------------------


def test_rankblend_tau_none_means_auto_and_roundtrips():
    p = RankBlend(0.6, tau=None)
    assert p.tau is None
    assert str(p) == "rankblend(0.6)"
    assert DistancePolicy.parse("rankblend(0.6)") == p
    # the function DEFAULT keeps the historical fixed scale: existing specs
    # and their fingerprints are untouched by the auto-tau feature
    assert RankBlend(0.6).tau == 1.0
    assert str(RankBlend(0.6)) == "rankblend(0.6,1.0)"


def test_rankblend_explicit_tau_bit_parity(data):
    """Explicit ``tau=`` reproduces the pre-calibration behavior bit-for-bit
    (the old code always bound the fixed scale constant)."""
    Q, db = data
    from repro.core.symmetrize import CombinedDistance

    dist = get_distance("kl")
    ref = CombinedDistance(dist, "rankblend", alpha=0.6, tau=1.0)
    for p in (RankBlend(0.6), RankBlend(0.6, tau=1.0)):
        bound = p.bind(dist)
        assert bound == ref
        np.testing.assert_array_equal(np.asarray(ref.matrix(Q, db)),
                                      np.asarray(bound.matrix(Q, db)))


def test_rankblend_tau_auto_calibrates_from_data(data):
    _, db = data
    from repro.core.symmetrize import calibrate_tau

    dist = get_distance("kl")
    expected = calibrate_tau(dist, db)
    assert expected > 0.0 and expected != 1.0
    # deterministic: same data, same scale
    assert calibrate_tau(dist, db) == expected
    p = RankBlend(0.6, tau=None)
    assert p.resolve(dist, db).tau == pytest.approx(expected)
    bound = p.bind(dist, data=db)
    assert bound.tau == pytest.approx(expected)
    # no calibration data: the fixed historical scale is the fallback
    assert p.resolve(dist, None).tau == 1.0
    assert p.bind(dist).tau == 1.0
    # explicit tau is never overridden by resolution
    assert RankBlend(0.6, tau=2.5).resolve(dist, db).tau == 2.5


def test_build_resolves_auto_tau_but_spec_stays_unresolved(data):
    """``ANNIndex.build`` calibrates tau against X, records the concrete
    policy in build_info, and keeps the spec AS WRITTEN so later
    ``searcher(spec=...)`` calls with the same auto-tau spec still match."""
    Q, db = data
    spec = RetrievalSpec(distance="kl", search_policy=RankBlend(0.6, tau=None),
                         k_c=24, builder="nndescent", NN=10, nnd_iters=4)
    idx = ANNIndex.build(db, spec=spec, key=jax.random.PRNGKey(2))
    assert idx.build_info["query_sym"] == "rankblend(0.6)"
    resolved = idx.build_info["query_sym_resolved"]
    assert resolved.startswith("rankblend(0.6,") and resolved != "rankblend(0.6)"
    from repro.core.symmetrize import calibrate_tau

    assert idx.search_dist.tau == pytest.approx(
        calibrate_tau(get_distance("kl"), db))
    # the unresolved spec keeps matching the bound index
    d, ids, _, _ = idx.searcher(spec=spec.replace(ef_search=48))(Q)
    assert ids.shape == (N_Q, K)


def test_blend_build_policy_end_to_end_recall(data):
    """A graph built under Blend(0.25) serves the ORIGINAL distance well —
    the paper's construction-distance research line through the spec API."""
    Q, db = data
    from repro.core import knn_scan, recall_at_k

    dist = get_distance("kl")
    _, true_ids = knn_scan(dist, Q, db, K)
    spec = RetrievalSpec(distance="kl", build_policy=Blend(0.25),
                         builder="nndescent", NN=10, nnd_iters=6,
                         ef_search=80, k=K)
    idx = ANNIndex.build(db, spec=spec, key=jax.random.PRNGKey(3))
    _, ids, _, _ = idx.searcher(spec=spec)(Q)
    r = recall_at_k(np.asarray(ids), np.asarray(true_ids))
    assert r >= 0.85, f"Blend(0.25) recall={r}"
