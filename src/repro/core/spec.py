"""RetrievalSpec: the declarative distance-policy API (ISSUE 5).

The paper closes by observing that building the graph under a *modified*
distance while searching under the original one "paves a way to designing
index-specific graph-construction distance functions".  Until this module
that scenario lived in two string knobs (``index_sym``/``query_sym``) plus a
dozen loose kwargs threaded differently through every layer.  Here the
scenario itself becomes a first-class object, in two layers:

``DistancePolicy`` — a composable combinator describing HOW a base distance
is transformed before use.  The legacy symmetrization modes
(none/avg/min/reverse/l2/natural) are named policies; the parametric
combinators implement the paper's open research line:

    Blend(alpha)            alpha*d(u,v) + (1-alpha)*d(v,u)
                            (avg / reverse / the original distance are the
                            alpha = 0.5 / 0 / 1 special cases, lowered to
                            the dedicated wrappers for bit-parity)
    MaxSym()                max(d(u,v), d(v,u))
    RankBlend(alpha, tau)   convex mix of the forward distance with a
                            monotone compressive proxy of the reversed rank

Every policy ``bind``s against a base PairDistance and lowers to the same
matmul-form contract (``prep_scan``/``prep_query``/``score``), so the
batched engines and Pallas kernels run any policy unchanged.

``RetrievalSpec`` — a frozen dataclass capturing the WHOLE scenario: base
distance by registry name, build/search/rerank policies + ``k_c``, builder
and engine knobs, and scheduler knobs.  It JSON round-trips
(``to_dict``/``from_dict``), fingerprints itself for self-describing bench
artifacts, and sweeps (``grid``) — the single currency consumed by
``ANNIndex.build/searcher/scheduler``, ``OnlineIndex``, ``launch/serve.py
--spec`` and the benchmark harnesses.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import re
from typing import Callable, Optional

from .symmetrize import (SYM_MODES, CombinedDistance, linear_blend, lowers_linearly,
                         reverse_of, symmetrized)

# ---------------------------------------------------------------------------
# DistancePolicy
# ---------------------------------------------------------------------------

POLICY_KINDS = SYM_MODES + ("max", "blend", "rankblend", "learned")

_POLICY_RE = re.compile(r"^([a-z0-9_]+)(?:\(([^)]*)\))?$")
_LEARNED_REF_RE = re.compile(r"^[0-9a-f]{12}$")


@dataclasses.dataclass(frozen=True)
class DistancePolicy:
    """A named, optionally parametric graph-construction distance policy.

    ``bind(base, natural=None)`` lowers the policy over a concrete
    PairDistance; ``str(policy)`` is the canonical serialized form
    (``"blend(0.25)"``), parsed back by ``DistancePolicy.parse``.
    """

    kind: str
    alpha: Optional[float] = None  # blend / rankblend mix weight
    tau: Optional[float] = None  # rankblend proxy scale; None = data-calibrated
    ref: Optional[str] = None  # learned-weights fingerprint (kind == "learned")

    def __post_init__(self):
        if self.kind not in POLICY_KINDS:
            raise ValueError(f"unknown policy kind {self.kind!r}; known: {POLICY_KINDS}")
        if self.kind == "learned":
            if self.ref is None or not _LEARNED_REF_RE.match(self.ref):
                raise ValueError(
                    f"learned needs a 12-hex weights fingerprint ref, got {self.ref!r}"
                )
            if self.alpha is not None or self.tau is not None:
                raise ValueError("learned takes only a weights ref")
            return
        if self.ref is not None:
            raise ValueError(f"policy {self.kind!r} takes no weights ref")
        if self.kind in ("blend", "rankblend"):
            if self.alpha is None or not 0.0 <= self.alpha <= 1.0:
                raise ValueError(f"{self.kind} needs alpha in [0, 1], got {self.alpha}")
        elif self.alpha is not None or self.tau is not None:
            raise ValueError(f"policy {self.kind!r} takes no parameters")
        if self.kind == "blend" and self.tau is not None:
            # silently dropping it would break parse(str(p)) == p
            raise ValueError("blend takes no tau")
        if self.kind == "rankblend" and self.tau is not None and self.tau <= 0:
            raise ValueError(f"rankblend needs tau > 0, got {self.tau}")

    # -- identity ------------------------------------------------------------

    @property
    def is_none(self) -> bool:
        return self.kind == "none"

    def __str__(self) -> str:
        # repr() is the shortest float form that round-trips exactly, so
        # parse(str(p)) == p for ANY parameter value
        if self.kind == "blend":
            return f"blend({self.alpha!r})"
        if self.kind == "rankblend":
            if self.tau is None:  # data-calibrated at bind/resolve time
                return f"rankblend({self.alpha!r})"
            return f"rankblend({self.alpha!r},{self.tau!r})"
        if self.kind == "learned":
            return f"learned({self.ref})"
        return self.kind

    # -- serialization -------------------------------------------------------

    @classmethod
    def parse(cls, spec) -> "DistancePolicy":
        """Coerce a policy from its serialized form (or pass one through)."""
        if isinstance(spec, DistancePolicy):
            return spec
        if spec is None:
            return cls("none")
        if not isinstance(spec, str):
            raise TypeError(f"cannot parse a policy from {type(spec).__name__}")
        m = _POLICY_RE.match(spec.strip())
        if not m:
            raise ValueError(f"malformed policy {spec!r}")
        kind, args = m.group(1), m.group(2)
        if kind == "learned":
            # the argument is a weights fingerprint, not a float
            if not args or not args.strip():
                raise ValueError(f"learned policy needs a weights ref: {spec!r}")
            return cls("learned", ref=args.strip())
        params = [float(a) for a in args.split(",") if a.strip()] if args else []
        if len(params) > 2:
            raise ValueError(f"too many parameters in policy {spec!r}")
        return cls(
            kind,
            alpha=params[0] if params else None,
            tau=params[1] if len(params) > 1 else None,
        )

    # -- lowering ------------------------------------------------------------

    def resolve(self, base=None, data=None) -> "DistancePolicy":
        """Make any data-calibrated parameter concrete.

        Only ``rankblend`` with ``tau=None`` resolves today: given ``base``
        and a database sample ``data``, tau becomes the median
        reversed-distance scale (``symmetrize.calibrate_tau`` — deterministic
        in the data); without data it falls back to the historical fixed
        constant 1.0.  Every other policy returns itself unchanged, so
        ``resolve`` is idempotent and safe to call unconditionally.
        """
        if self.kind == "rankblend" and self.tau is None:
            from .symmetrize import calibrate_tau

            tau = (calibrate_tau(base, data)
                   if base is not None and data is not None else 1.0)
            return dataclasses.replace(self, tau=tau)
        return self

    def bind(self, base, natural: Optional[Callable] = None, data=None):
        """Lower the policy over ``base``, returning a PairDistance.

        The exact special cases of ``Blend`` lower like the legacy modes,
        so ``Blend(0.5)`` is bit-identical to ``avg``, ``Blend(0)`` to
        ``reverse`` and ``Blend(1)`` to the original distance.  Over a
        linear base (KL, Itakura-Saito) every ``Blend``, ``avg`` and
        ``reverse`` lowers to one matmul-form ``Distance``
        (``symmetrize.linear_blend``), scored like a registry distance;
        every other policy or base binds to its composite wrapper.

        ``data`` — optional (n, m) database sample used to ``resolve``
        data-calibrated parameters (RankBlend tau) before lowering; an
        explicit ``tau=`` always wins and reproduces the old fixed-constant
        behavior bit-for-bit.
        """
        if self.kind in SYM_MODES:
            return symmetrized(base, self.kind, natural=natural)
        if self.kind == "learned":
            from .symmetrize import LearnedDistance, get_learned_weights

            return LearnedDistance.from_weights(
                base, get_learned_weights(self.ref), fingerprint=self.ref
            )
        if self.kind == "max":
            return CombinedDistance(base, "max")
        if self.kind == "blend":
            if self.alpha == 1.0:
                return base
            if self.alpha == 0.5:
                return symmetrized(base, "avg")
            if self.alpha == 0.0:
                return reverse_of(base)
            if lowers_linearly(base):
                return linear_blend(base, self.alpha)
            return CombinedDistance(base, "blend", alpha=self.alpha)
        p = self.resolve(base, data)
        return CombinedDistance(base, "rankblend", alpha=p.alpha, tau=p.tau)


def Blend(alpha: float) -> DistancePolicy:  # noqa: N802 - combinator constructor
    """alpha*d(u,v) + (1-alpha)*d(v,u): the paper's open line as one knob."""
    return DistancePolicy("blend", alpha=float(alpha))


def MaxSym() -> DistancePolicy:  # noqa: N802
    """max(d(u,v), d(v,u)) — pessimistic symmetrization."""
    return DistancePolicy("max")


def RankBlend(alpha: float, tau: Optional[float] = 1.0) -> DistancePolicy:  # noqa: N802
    """Convex mix of d(u,v) with a monotone proxy of the reversed rank.

    ``tau`` sets the scale where the reversed-distance proxy switches from
    linear to logarithmic compression.  The default keeps the historical
    fixed constant 1.0; pass ``tau=None`` (serialized ``"rankblend(a)"``)
    for the DATA-CALIBRATED tau — the median reversed-distance scale of the
    database sample supplied at bind time (``calibrate_tau``), falling back
    to 1.0 when no data is available.
    """
    return DistancePolicy("rankblend", alpha=float(alpha),
                          tau=None if tau is None else float(tau))


def Learned(weights_or_ref) -> DistancePolicy:  # noqa: N802
    """The learned construction distance (ISSUE 9), referenced by content.

    Accepts EITHER a learned-weights dict (``repro.core.learned`` output —
    registered on the spot, the policy records its content fingerprint) or
    a bare 12-hex fingerprint whose weights are already registered (e.g.
    by ``load_learned_artifact``).  ``bind`` resolves the fingerprint
    through the process-local registry and lowers to
    ``symmetrize.LearnedDistance``.
    """
    from .symmetrize import register_learned_weights

    if isinstance(weights_or_ref, dict):
        ref = register_learned_weights(weights_or_ref)
    else:
        ref = str(weights_or_ref)
    return DistancePolicy("learned", ref=ref)


NONE_POLICY = DistancePolicy("none")


# ---------------------------------------------------------------------------
# RetrievalSpec
# ---------------------------------------------------------------------------

_BUILDERS = ("nndescent", "swgraph")
_BUILD_ENGINES = ("wave", "sequential")
_ENGINES = ("batched", "reference")


@dataclasses.dataclass(frozen=True)
class RetrievalSpec:
    """One frozen object describing a complete retrieval scenario.

    Defaults mirror the historical kwarg defaults layer by layer, so a spec
    constructed by the deprecation shim reproduces the old behavior
    bit-for-bit.  ``search_policy != none`` is the full-symmetrization
    scenario: the beam runs under the bound search policy and ``k_c``
    candidates are re-ranked under the original distance — by the batch
    searcher AND (since this spec) the slot scheduler at retire time.
    """

    # -- distance scenario
    distance: str = "kl"  # base distance registry name
    build_policy: DistancePolicy = NONE_POLICY  # graph-construction distance
    search_policy: DistancePolicy = NONE_POLICY  # beam-guidance distance
    k_c: Optional[int] = None  # rerank candidates (search_policy != none)

    # -- construction
    builder: str = "nndescent"
    build_engine: str = "wave"
    wave: int = 32
    build_frontier: Optional[int] = None
    NN: int = 15
    ef_construction: int = 100
    M_max: Optional[int] = None
    nnd_iters: int = 8
    n_entries: int = 4
    capacity: Optional[int] = None

    # -- search
    k: int = 10
    ef_search: int = 96
    engine: str = "batched"
    frontier: int = 2
    adaptive: bool = False
    patience: int = 1

    # -- scheduler (continuous batching)
    slots: int = 32
    sched_frontier: int = 4
    steps_per_sync: int = 1
    compact: int = 32

    def __post_init__(self):
        # coerce serialized policies so replace()/grid() accept strings
        for f in ("build_policy", "search_policy"):
            v = getattr(self, f)
            if not isinstance(v, DistancePolicy):
                object.__setattr__(self, f, DistancePolicy.parse(v))
        if self.builder not in _BUILDERS:
            raise ValueError(f"unknown builder {self.builder!r}; known: {_BUILDERS}")
        if self.build_engine not in _BUILD_ENGINES:
            raise ValueError(
                f"unknown build_engine {self.build_engine!r}; known: {_BUILD_ENGINES}"
            )
        if self.engine not in _ENGINES:
            raise ValueError(f"unknown engine {self.engine!r}; known: {_ENGINES}")
        for f in ("wave", "NN", "ef_construction", "nnd_iters", "n_entries", "k",
                  "ef_search", "frontier", "patience", "slots", "sched_frontier",
                  "steps_per_sync", "compact"):
            if int(getattr(self, f)) < 1:
                raise ValueError(f"{f} must be >= 1, got {getattr(self, f)}")
        if self.k_c is not None and self.k_c < self.k:
            raise ValueError(f"k_c {self.k_c} < k {self.k}")

    # -- distance lowering ---------------------------------------------------

    def base_distance(self):
        from .distances import get_distance

        return get_distance(self.distance)

    def bind_build(self, base=None, natural: Optional[Callable] = None,
                   data=None):
        """Lower ``build_policy`` over the base distance (graph construction).

        ``data`` — optional database sample forwarded to
        ``DistancePolicy.bind`` so data-calibrated parameters (auto
        RankBlend tau) resolve against the corpus being indexed.
        """
        base = base if base is not None else self.base_distance()
        return self.build_policy.bind(base, natural=natural, data=data)

    def bind_search(self, base=None, natural: Optional[Callable] = None,
                    data=None):
        """Lower ``search_policy`` over the base distance (beam guidance)."""
        base = base if base is not None else self.base_distance()
        return self.search_policy.bind(base, natural=natural, data=data)

    @property
    def needs_rerank(self) -> bool:
        """True when the beam runs under a modified distance and the results
        must be re-ranked under the original one (paper's full-sym path)."""
        return not self.search_policy.is_none

    # -- serialization -------------------------------------------------------

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["build_policy"] = str(self.build_policy)
        d["search_policy"] = str(self.search_policy)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "RetrievalSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown RetrievalSpec fields: {sorted(unknown)}")
        return cls(**d)

    def to_json(self, path: Optional[str] = None) -> str:
        s = json.dumps(self.to_dict(), indent=1, sort_keys=True)
        if path is not None:
            with open(path, "w") as f:
                f.write(s + "\n")
        return s

    @classmethod
    def from_json(cls, src: str) -> "RetrievalSpec":
        """Parse a spec from a JSON string or a path to a JSON file."""
        if "{" not in src:
            with open(src) as f:
                src = f.read()
        return cls.from_dict(json.loads(src))

    def fingerprint(self) -> str:
        """Stable short hash of the canonical serialized form — recorded in
        every bench artifact so baselines are self-describing."""
        canon = json.dumps(self.to_dict(), sort_keys=True)
        return hashlib.sha256(canon.encode()).hexdigest()[:12]

    # -- composition ---------------------------------------------------------

    def replace(self, **changes) -> "RetrievalSpec":
        return dataclasses.replace(self, **changes)

    def grid(self, **axes) -> list["RetrievalSpec"]:
        """Cartesian sweep helper: ``spec.grid(ef_search=[32, 96],
        build_policy=[Blend(a) for a in (0, 0.5, 1)])`` returns one spec per
        combination, in deterministic (itertools.product) order."""
        if not axes:
            return [self]
        names = list(axes)
        out = []
        for combo in itertools.product(*(axes[n] for n in names)):
            out.append(self.replace(**dict(zip(names, combo))))
        return out


# ---------------------------------------------------------------------------
# Pareto dominance / frontier helpers (the auto-tuner's objective algebra)
# ---------------------------------------------------------------------------


def dominates(a: dict, b: dict, *, maximize=(), minimize=()) -> bool:
    """True iff objective point ``a`` Pareto-dominates ``b``.

    ``a`` dominates ``b`` when it is at least as good on EVERY listed
    objective (``maximize`` keys: higher is better; ``minimize`` keys:
    lower is better) and strictly better on at least one.  Points are
    plain dicts so the helper serves hand-built test points, bench rows
    and ``autotune`` candidates alike.  Missing keys raise ``KeyError`` —
    a silent default would make an incomparable point look dominated.
    """
    if not maximize and not minimize:
        raise ValueError("dominates() needs at least one objective key")
    as_good = all(a[m] >= b[m] for m in maximize) and all(
        a[m] <= b[m] for m in minimize
    )
    strictly = any(a[m] > b[m] for m in maximize) or any(
        a[m] < b[m] for m in minimize
    )
    return as_good and strictly


def pareto_frontier(points, *, maximize=(), minimize=(), key=None) -> list:
    """Non-dominated subset of ``points``, input order preserved.

    ``key(point) -> dict`` extracts the objective dict (identity by
    default, so plain dicts work directly).  Ties on every objective keep
    ALL tied points — neither dominates the other.  O(n^2) pairwise scan:
    tuner frontiers are tens of points, not millions.
    """
    key = key if key is not None else (lambda p: p)
    objs = [key(p) for p in points]
    out = []
    for i, p in enumerate(points):
        if not any(
            dominates(objs[j], objs[i], maximize=maximize, minimize=minimize)
            for j in range(len(points))
            if j != i
        ):
            out.append(p)
    return out


# ---------------------------------------------------------------------------
# tuned-spec artifact: the auto-tuner's output, consumable by serve/build
# ---------------------------------------------------------------------------

# schema version ships in every artifact so loaders can reject a future
# incompatible layout instead of mis-parsing it
TUNED_ARTIFACT_KIND = "repro.autotune/tuned-spec@1"


def tuned_artifact(spec: "RetrievalSpec", objectives: dict, *, frontier=(),
                   calibration: Optional[dict] = None,
                   provenance: Optional[dict] = None) -> dict:
    """Assemble the tuned-spec JSON artifact (fingerprint provenance inside).

    Args:
        spec: the chosen tuned ``RetrievalSpec`` (fully concrete — the
            tuner resolves data-calibrated parameters before choosing).
        objectives: the chosen spec's measured objectives
            (``recall`` / ``evals_per_query`` / ``build_cost``).
        frontier: iterable of ``(spec, objectives)`` pairs — the full
            Pareto frontier the choice was made from.
        calibration: workload/sample description the tuner ran on.
        provenance: tool metadata (grid size, rungs, seed).

    Returns:
        A JSON-serializable dict.  ``spec_fingerprint`` is recorded next to
        the spec itself so a hand-edited artifact is rejected at load time
        (``load_tuned_artifact``) instead of silently serving a scenario
        that was never tuned.
    """
    return {
        "kind": TUNED_ARTIFACT_KIND,
        "tuned_spec": spec.to_dict(),
        "spec_fingerprint": spec.fingerprint(),
        "objectives": dict(objectives),
        "frontier": [
            {"spec": s.to_dict(), "spec_fingerprint": s.fingerprint(), **o}
            for s, o in frontier
        ],
        "calibration": dict(calibration or {}),
        "provenance": {"tool": "repro.core.autotune", **(provenance or {})},
    }


def load_tuned_artifact(src) -> tuple["RetrievalSpec", dict]:
    """Load a tuned-spec artifact from a path / JSON string / parsed dict.

    Returns ``(spec, artifact_dict)``.  Raises ``ValueError`` when the
    ``kind`` tag is unknown or the recorded ``spec_fingerprint`` does not
    match the embedded spec — the fingerprint is the artifact's provenance
    seal, so any edit to the spec must go through re-tuning (or an
    explicit plain-spec JSON, which carries no tuning claim).
    """
    if isinstance(src, dict):
        doc = src
    else:
        if "{" not in src:
            with open(src) as f:
                src = f.read()
        doc = json.loads(src)
    kind = doc.get("kind")
    if kind != TUNED_ARTIFACT_KIND:
        raise ValueError(
            f"not a tuned-spec artifact (kind={kind!r}; "
            f"expected {TUNED_ARTIFACT_KIND!r})"
        )
    spec = RetrievalSpec.from_dict(doc["tuned_spec"])
    if spec.fingerprint() != doc.get("spec_fingerprint"):
        raise ValueError(
            f"tuned-spec fingerprint mismatch: artifact says "
            f"{doc.get('spec_fingerprint')!r} but the embedded spec hashes "
            f"to {spec.fingerprint()!r} — the artifact was edited after "
            f"tuning; re-run the tuner or pass a plain spec JSON instead"
        )
    return spec, doc


# ---------------------------------------------------------------------------
# learned-weights artifact: repro.core.learned's output, consumable by serve
# ---------------------------------------------------------------------------

LEARNED_ARTIFACT_KIND = "repro.learned/construction-distance@1"


def learned_artifact(spec: "RetrievalSpec", weights: dict, objectives: dict, *,
                     anchor: Optional[dict] = None, candidates=(),
                     calibration: Optional[dict] = None,
                     provenance: Optional[dict] = None) -> dict:
    """Assemble the learned-construction-distance artifact.

    Seals the learned weights AND the spec that references them: the
    spec's ``build_policy`` must be ``learned(<fp>)`` where ``<fp>`` is
    the weights' content fingerprint, so the spec fingerprint transitively
    covers the weights.  ``candidates`` (NOT named "frontier": serve.py
    treats any doc with a "frontier" key as a demotion-ladder source) is
    the measured candidate family the selection was made from.
    """
    from .symmetrize import learned_weights_fingerprint

    wfp = learned_weights_fingerprint(weights)
    if spec.build_policy.kind != "learned" or spec.build_policy.ref != wfp:
        raise ValueError(
            f"spec build_policy {spec.build_policy} does not reference the "
            f"sealed weights (fingerprint {wfp})"
        )
    return {
        "kind": LEARNED_ARTIFACT_KIND,
        "spec": spec.to_dict(),
        "spec_fingerprint": spec.fingerprint(),
        "weights": dict(weights),
        "weights_fingerprint": wfp,
        "objectives": dict(objectives),
        "anchor": dict(anchor or {}),
        "candidates": [dict(c) for c in candidates],
        "calibration": dict(calibration or {}),
        "provenance": {"tool": "repro.core.learned", **(provenance or {})},
    }


def load_learned_artifact(src) -> tuple["RetrievalSpec", dict]:
    """Load + verify a learned-weights artifact; registers the weights.

    Returns ``(spec, artifact_dict)``.  Three seals are checked: the
    recorded ``weights_fingerprint`` must equal the recomputed content
    fingerprint of the embedded weights, the spec's ``build_policy`` ref
    must point at exactly those weights, and the recorded
    ``spec_fingerprint`` must match the embedded spec.  On success the
    weights are registered in the process-local registry, so the returned
    spec binds (``ANNIndex.build(spec=...)``) with no further setup.
    """
    from .symmetrize import learned_weights_fingerprint, register_learned_weights

    if isinstance(src, dict):
        doc = src
    else:
        if "{" not in src:
            with open(src) as f:
                src = f.read()
        doc = json.loads(src)
    kind = doc.get("kind")
    if kind != LEARNED_ARTIFACT_KIND:
        raise ValueError(
            f"not a learned-weights artifact (kind={kind!r}; "
            f"expected {LEARNED_ARTIFACT_KIND!r})"
        )
    weights = doc.get("weights")
    if not isinstance(weights, dict):
        raise ValueError("learned artifact carries no weights dict")
    wfp = learned_weights_fingerprint(weights)
    if wfp != doc.get("weights_fingerprint"):
        raise ValueError(
            f"learned weights fingerprint mismatch: artifact says "
            f"{doc.get('weights_fingerprint')!r} but the embedded weights "
            f"hash to {wfp!r} — the artifact was edited after training"
        )
    spec = RetrievalSpec.from_dict(doc["spec"])
    if spec.build_policy.kind != "learned" or spec.build_policy.ref != wfp:
        raise ValueError(
            f"learned artifact spec build_policy {spec.build_policy} does "
            f"not reference the sealed weights ({wfp})"
        )
    if spec.fingerprint() != doc.get("spec_fingerprint"):
        raise ValueError(
            f"learned-spec fingerprint mismatch: artifact says "
            f"{doc.get('spec_fingerprint')!r} but the embedded spec hashes "
            f"to {spec.fingerprint()!r} — re-run the trainer instead of "
            f"hand-editing the artifact"
        )
    register_learned_weights(weights, fingerprint=wfp)
    return spec, doc


def load_spec(src) -> "RetrievalSpec":
    """Load a ``RetrievalSpec`` from ANY serialized form.

    Accepts a path or JSON string holding a plain spec (``to_json``
    output), a tuned-spec artifact (``tuned_artifact`` output, fingerprint
    verified) or a learned-weights artifact (``learned_artifact`` output,
    weights + spec fingerprints verified and the weights registered) — the
    single entry point ``launch/serve.py --spec`` uses, so both tuner and
    trainer output files are directly servable.
    """
    if not isinstance(src, dict):
        if "{" not in src:
            with open(src) as f:
                src = f.read()
        src = json.loads(src)
    if src.get("kind") == TUNED_ARTIFACT_KIND:
        return load_tuned_artifact(src)[0]
    if src.get("kind") == LEARNED_ARTIFACT_KIND:
        return load_learned_artifact(src)[0]
    return RetrievalSpec.from_dict(src)


# ---------------------------------------------------------------------------
# QoS demotion ladders (per-request class -> operating-point mapping)
# ---------------------------------------------------------------------------

# the knobs a demotion rung may vary: everything else — distance scenario,
# construction, k/k_c, scheduler shape — is pinned to the serving spec
_LADDER_SEARCH_FIELDS = ("ef_search", "frontier", "adaptive", "patience")


def _ladder_key(spec: "RetrievalSpec") -> str:
    d = spec.to_dict()
    for f in _LADDER_SEARCH_FIELDS:
        d.pop(f)
    return json.dumps(d, sort_keys=True)


def demotion_ladder(spec: "RetrievalSpec", source=None, *, max_rungs: int = 3,
                    floor_ef: Optional[int] = None) -> list["RetrievalSpec"]:
    """Ordered QoS operating points for SLO-aware admission, full first.

    Rung 0 is ``spec`` itself (the full-fidelity serving point); later
    rungs are strictly cheaper search-side operating points the scheduler's
    admission controller may demote a request to when its SLO budget no
    longer fits the full-fidelity service time.

    ``source`` (optional) is a tuned-spec artifact — path, JSON string, or
    parsed dict (``tuned_artifact`` layout): its Pareto frontier supplies
    the cheaper points, filtered to entries whose build side (and k/k_c)
    match ``spec`` exactly and whose ``ef_search`` lies in
    ``[floor_ef, spec.ef_search)``, ordered most-expensive first.  Without
    a source (or when no frontier entry qualifies) the ladder is
    synthesized by halving ``ef_search`` down to ``floor_ef``.

    ``floor_ef`` defaults to ``max(k, k_c, 16)`` — a rung can never return
    fewer than the contracted result (or rerank-candidate) count.
    """
    floor = max(spec.k, spec.k_c or spec.k,
                16 if floor_ef is None else int(floor_ef))
    rungs = [spec]
    if source is not None:
        if not (isinstance(source, dict) and "frontier" in source):
            _, source = load_tuned_artifact(source)
        key = _ladder_key(spec)
        cands: dict = {}
        for entry in source.get("frontier", ()):
            try:
                s = RetrievalSpec.from_dict(entry["spec"])
            except (KeyError, TypeError, ValueError):
                continue
            if _ladder_key(s) != key or not floor <= s.ef_search < spec.ef_search:
                continue
            cands.setdefault((s.ef_search, s.adaptive), s)
        for ef_a in sorted(cands, key=lambda t: (-t[0], t[1])):
            if len(rungs) >= max_rungs:
                break
            rungs.append(cands[ef_a])
    if len(rungs) == 1:
        e = spec.ef_search // 2
        while len(rungs) < max_rungs and e >= floor:
            rungs.append(spec.replace(ef_search=e))
            e //= 2
    return rungs


def class_spec(ladder: list["RetrievalSpec"], priority: int) -> "RetrievalSpec":
    """Per-request QoS class -> operating-point spec.

    Priority class ``p`` (0 = highest) starts at demotion-ladder rung
    ``min(p, len(ladder) - 1)`` — lower classes begin life already demoted,
    and admission control can only move them further down the ladder.
    """
    return ladder[min(max(int(priority), 0), len(ladder) - 1)]
