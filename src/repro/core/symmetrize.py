"""Distance symmetrization and quasi-symmetrization (SS2/SS3 of the paper).

The paper's central experimental knob: the distance used to CONSTRUCT the
neighborhood graph may differ from the distance used to SEARCH it.

    none    : the original distance d(u, v)
    avg     : (d(u, v) + d(v, u)) / 2                      (Eq. 2)
    min     : min(d(u, v), d(v, u))                        (Eq. 3)
    reverse : d(v, u)              (argument-reversed quasi-symmetrization)
    l2      : squared Euclidean    (quasi-symmetrization proxy)
    natural : distance-specific natural symmetrization; for BM25 both sides
              are vectorized as TF * sqrt(IDF)             (Eq. 4)

All wrappers implement the same PairDistance interface as
``repro.core.distances.Distance``:

    matrix(U, V)                D[i,j] = d(U[i], V[j])
    query_matrix(Q, X, mode)    (B, N) query-vs-database distances
    pairwise(u, v)              pointwise oracle
    prep_scan(X) / prep_query(q) / score(rows, qc)
                                gather-able per-row constants for beam search

so graph builders and searchers are agnostic to the symmetrization mode.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .distances import POST_LINEAR, Distance, l2_squared

SYM_MODES = ("none", "avg", "min", "reverse", "l2", "natural")


# ---------------------------------------------------------------------------
# wrappers
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ReversedDistance:
    """d_rev(u, v) = d(v, u)."""

    base: Distance

    @property
    def name(self):
        return f"{self.base.name}-reverse"

    @property
    def needs_simplex(self):
        return self.base.needs_simplex

    @property
    def symmetric(self):
        return getattr(self.base, "symmetric", False)

    def matrix(self, U, V):
        return self.base.matrix(V, U).T

    def query_matrix(self, Q, X, mode: str = "left"):
        # left mode: D[b,i] = d_rev(X[i], Q[b]) = d(Q[b], X[i]) = base right mode
        return self.base.query_matrix(Q, X, mode="right" if mode == "left" else "left")

    def pairwise(self, u, v):
        return self.base.pairwise(v, u)

    def pairwise_batch(self, U, V):
        return jax.vmap(self.pairwise)(U, V)

    def prep_scan(self, X):
        return {"rep": self.base.prep_right(X), "bias": self.base.bias_right(X)}

    def prep_query(self, q):
        return {
            "rep": self.base.prep_left(q[None, :])[0],
            "bias": self.base.bias_left(q[None, :])[0],
        }

    def score(self, rows, qc):
        from .distances import HIGHEST, apply_post

        s = jnp.matmul(rows["rep"], qc["rep"], precision=HIGHEST)
        # left-mode d_rev(x, q) = d(q, x): q is the LEFT argument of base.
        return apply_post(self.base.post_id, s, qc["bias"], rows["bias"], self.base.c0)


@dataclasses.dataclass(frozen=True)
class SymmetrizedDistance:
    """avg- or min-based symmetrization (Eqs. 2-3).

    Works over ANY PairDistance (including ViewedDistance / BM25): it pairs
    the base with its argument-reversal and combines - two matmul-form
    evaluations per block.  ``symmetrized`` lowers avg over a linear base
    to ``linear_blend`` instead; min is never linear.
    """

    base: object  # any PairDistance
    mode: str  # "avg" | "min"

    def __post_init__(self):
        if self.mode not in ("avg", "min"):
            raise ValueError(self.mode)

    @property
    def _rev(self):
        return reverse_of(self.base)

    @property
    def name(self):
        return f"{self.base.name}-{self.mode}"

    @property
    def needs_simplex(self):
        return self.base.needs_simplex

    @property
    def symmetric(self):
        return True  # symmetric by construction (Eqs. 2-3)

    def _combine(self, a, b):
        return (a + b) * 0.5 if self.mode == "avg" else jnp.minimum(a, b)

    def matrix(self, U, V):
        return self._combine(self.base.matrix(U, V), self.base.matrix(V, U).T)

    def query_matrix(self, Q, X, mode: str = "left"):
        del mode  # symmetric by construction
        return self._combine(
            self.base.query_matrix(Q, X, mode="left"),
            self.base.query_matrix(Q, X, mode="right"),
        )

    def pairwise(self, u, v):
        return self._combine(self.base.pairwise(u, v), self.base.pairwise(v, u))

    def pairwise_batch(self, U, V):
        return jax.vmap(self.pairwise)(U, V)

    def prep_scan(self, X):
        return {"f": self.base.prep_scan(X), "r": self._rev.prep_scan(X)}

    def prep_query(self, q):
        return {"f": self.base.prep_query(q), "r": self._rev.prep_query(q)}

    def score(self, rows, qc):
        return self._combine(
            self.base.score(rows["f"], qc["f"]),
            self._rev.score(rows["r"], qc["r"]),
        )


@dataclasses.dataclass(frozen=True)
class ViewedDistance:
    """A distance evaluated over role-dependent representations.

    Used for BM25-style asymmetric vectorization: ``left_view`` maps a raw
    record matrix to its left-argument (document) representation and
    ``right_view`` to its right-argument (query) representation.  The
    ``natural`` symmetrization of Eq. (4) is a ViewedDistance whose two views
    coincide (TF * sqrt(IDF) on both sides).
    """

    base: Distance
    left_view: Callable
    right_view: Callable
    view_name: str = "viewed"

    @property
    def name(self):
        return f"{self.base.name}-{self.view_name}"

    @property
    def needs_simplex(self):
        return False

    def matrix(self, U, V):
        return self.base.matrix(self.left_view(U), self.right_view(V))

    def query_matrix(self, Q, X, mode: str = "left"):
        if mode == "left":
            return self.base.query_matrix(self.right_view(Q), self.left_view(X), mode="left")
        return self.base.query_matrix(self.left_view(Q), self.right_view(X), mode="right")

    def pairwise(self, u, v):
        return self.base.pairwise(self.left_view(u[None])[0], self.right_view(v[None])[0])

    def pairwise_batch(self, U, V):
        return jax.vmap(self.pairwise)(U, V)

    def prep_scan(self, X):
        return self.base.prep_scan(self.left_view(X))

    def prep_query(self, q):
        return self.base.prep_query(self.right_view(q[None])[0])

    def score(self, rows, qc):
        return self.base.score(rows, qc)


@dataclasses.dataclass(frozen=True)
class CombinedDistance:
    """Parametric two-branch combinator over a PairDistance (ISSUE 5).

    Evaluates both argument orders of ``base`` and combines them pointwise —
    the generalisation of ``SymmetrizedDistance`` that the paper's closing
    observation calls for ("index-specific graph-construction distance
    functions").  Combine modes:

        blend      alpha * d(u, v) + (1 - alpha) * d(v, u)
                   (avg at alpha=0.5, reverse at 0, the original at 1 —
                   those exact cases are lowered like the legacy modes by
                   ``DistancePolicy.bind`` for bit-parity, and over a
                   linear base every blend lowers to ``linear_blend``)
        max        max(d(u, v), d(v, u))  — the pessimistic symmetrization
        rankblend  alpha * d(u, v) + (1 - alpha) * proxy(d(v, u)) where
                   ``proxy(x) = tau * sign(x) * log1p(|x| / tau)`` is a
                   monotone compressive stand-in for the reversed RANK:
                   it preserves the reverse ordering while taming the heavy
                   tail that strongly asymmetric divergences put on the
                   reverse direction (ranks discard exactly that tail)

    Same PairDistance contract as every other wrapper: two matmul-form
    evaluations per block, ``prep_scan`` carries both branches as a pytree,
    so the batched engines run it unchanged; the gather kernel takes only a
    plain ``Distance``, so it scores on the jnp path.
    """

    base: object  # any PairDistance
    combine: str  # "blend" | "max" | "rankblend"
    alpha: float = 0.5
    tau: float = 1.0

    def __post_init__(self):
        if self.combine not in ("blend", "max", "rankblend"):
            raise ValueError(f"unknown combine {self.combine!r}")
        if self.combine in ("blend", "rankblend") and not 0.0 <= self.alpha <= 1.0:
            raise ValueError(f"alpha must be in [0, 1], got {self.alpha}")
        if self.combine == "rankblend" and self.tau <= 0.0:
            raise ValueError(f"tau must be > 0, got {self.tau}")

    @property
    def _rev(self):
        return reverse_of(self.base)

    @property
    def name(self):
        if self.combine == "max":
            return f"{self.base.name}-max"
        if self.combine == "blend":
            return f"{self.base.name}-blend({self.alpha:g})"
        return f"{self.base.name}-rankblend({self.alpha:g},{self.tau:g})"

    @property
    def needs_simplex(self):
        return self.base.needs_simplex

    @property
    def symmetric(self):
        # blend is symmetric only at the avg point; rankblend never is
        # (the proxy breaks the exchange symmetry even at alpha=0.5)
        return self.combine == "max" or (self.combine == "blend" and self.alpha == 0.5)

    def _combine(self, fwd, rev):
        if self.combine == "max":
            return jnp.maximum(fwd, rev)
        if self.combine == "rankblend":
            rev = self.tau * jnp.sign(rev) * jnp.log1p(jnp.abs(rev) / self.tau)
        return self.alpha * fwd + (1.0 - self.alpha) * rev

    def matrix(self, U, V):
        return self._combine(self.base.matrix(U, V), self.base.matrix(V, U).T)

    def query_matrix(self, Q, X, mode: str = "left"):
        fwd = self.base.query_matrix(Q, X, mode=mode)
        rev = self.base.query_matrix(Q, X, mode="right" if mode == "left" else "left")
        return self._combine(fwd, rev)

    def pairwise(self, u, v):
        return self._combine(self.base.pairwise(u, v), self.base.pairwise(v, u))

    def pairwise_batch(self, U, V):
        return jax.vmap(self.pairwise)(U, V)

    def prep_scan(self, X):
        return {"f": self.base.prep_scan(X), "r": self._rev.prep_scan(X)}

    def prep_query(self, q):
        return {"f": self.base.prep_query(q), "r": self._rev.prep_query(q)}

    def score(self, rows, qc):
        return self._combine(
            self.base.score(rows["f"], qc["f"]),
            self._rev.score(rows["r"], qc["r"]),
        )


# ---------------------------------------------------------------------------
# learned construction distances (ISSUE 9)
# ---------------------------------------------------------------------------

# process-local registry of learned-weight dicts, keyed by content
# fingerprint.  ``Learned(ref)`` policies resolve their weights here at
# bind time; ``load_learned_artifact`` populates it when a sealed artifact
# is loaded, so a spec shipped inside an artifact is self-contained.
_LEARNED_WEIGHTS: dict = {}


def learned_weights_fingerprint(weights: dict) -> str:
    """Content fingerprint of a learned-weights dict (sorted-key JSON,
    sha256, first 12 hex chars) — same convention as spec fingerprints."""
    blob = json.dumps(weights, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def register_learned_weights(weights: dict, *, fingerprint: Optional[str] = None) -> str:
    """Register a learned-weights dict; returns its fingerprint.

    ``weights`` must be plain JSON data: ``alpha`` (float), ``beta``
    (float), ``tau`` (float or None) and ``L`` (nested lists, the low-rank
    Mahalanobis map, or None).  When ``fingerprint`` is given it is checked
    against the recomputed content fingerprint — a mismatch means the
    weights were tampered with after sealing.
    """
    for field in ("alpha", "beta", "tau", "L"):
        if field not in weights:
            raise ValueError(f"learned weights missing field {field!r}")
    fp = learned_weights_fingerprint(weights)
    if fingerprint is not None and fingerprint != fp:
        raise ValueError(
            f"learned weights fingerprint mismatch: recorded {fingerprint}, "
            f"recomputed {fp}"
        )
    _LEARNED_WEIGHTS[fp] = weights
    return fp


def get_learned_weights(ref: str) -> dict:
    """Look up a registered learned-weights dict by fingerprint."""
    try:
        return _LEARNED_WEIGHTS[ref]
    except KeyError:
        raise KeyError(
            f"no learned weights registered under {ref!r}; load the sealed "
            "artifact first (repro.core.spec.load_learned_artifact / "
            "load_spec) or call register_learned_weights"
        ) from None


@dataclasses.dataclass(frozen=True)
class LearnedDistance:
    """A learned construction distance (ISSUE 9).

    The trained family is a superset of ``CombinedDistance``'s blend:

        d_learned(u, v) = alpha * d(u, v) + (1 - alpha) * proxy(d(v, u))
                          + beta * ||L^T u - L^T v||^2

    where ``proxy`` is identity when ``tau is None`` and the rankblend
    compression ``tau * sign(x) * log1p(|x| / tau)`` otherwise, and ``L``
    is a low-rank Mahalanobis map fit by margin-ranking against true-NN
    pairs under the ORIGINAL distance (``repro.core.learned``).  Unused
    branches are gated STATICALLY (``alpha == 1`` skips the reverse
    branch, ``beta == 0`` skips the Mahalanobis branch), and with no proxy
    over a linear base the blend part is ``linear_blend`` itself, so the
    degenerate weights ``(alpha=a, beta=0, tau=None)`` are arithmetically
    identical to what ``Blend(a)`` binds to — the trainer's
    by-construction anchor guarantee relies on this bit-parity.

    ``L`` lives inside ``maha`` (an internal ``ViewedDistance`` whose view
    closes over the array), keeping this dataclass hashable as a static
    jit argument.  Same PairDistance contract as every other wrapper:
    ``prep_scan`` carries up to three branches as a pytree, so the batched
    engines run it unchanged, on the jnp path (the kernels take only a
    plain ``Distance``).
    """

    base: object  # any PairDistance
    alpha: float = 1.0
    beta: float = 0.0
    tau: Optional[float] = None
    maha: Optional[object] = None  # ViewedDistance(l2, M -> M @ L); None iff beta == 0
    weights_fingerprint: str = ""

    def __post_init__(self):
        if not 0.0 <= self.alpha <= 1.0:
            raise ValueError(f"alpha must be in [0, 1], got {self.alpha}")
        if self.tau is not None and self.tau <= 0.0:
            raise ValueError(f"tau must be > 0, got {self.tau}")
        if (self.beta != 0.0) != (self.maha is not None):
            raise ValueError("maha branch must be present exactly when beta != 0")

    @classmethod
    def from_weights(cls, base, weights: dict, *, fingerprint: Optional[str] = None):
        """Build from a plain-JSON weights dict (see register_learned_weights)."""
        fp = register_learned_weights(weights, fingerprint=fingerprint)
        beta = float(weights["beta"])
        maha = None
        if beta != 0.0:
            if weights["L"] is None:
                raise ValueError("beta != 0 requires a Mahalanobis map L")
            L = jnp.asarray(weights["L"], jnp.float32)
            view = lambda M: M @ L  # noqa: E731 — closure keeps the dataclass hashable
            maha = ViewedDistance(l2_squared(), left_view=view, right_view=view,
                                  view_name=f"maha({fp})")
        tau = weights["tau"]
        return cls(base, alpha=float(weights["alpha"]), beta=beta,
                   tau=None if tau is None else float(tau),
                   maha=maha, weights_fingerprint=fp)

    @property
    def _rev(self):
        return reverse_of(self.base)

    @property
    def _blend(self):
        """The blend part as ``Blend(alpha)`` binds it where that is one
        lowered ``Distance`` (a linear base, no proxy), so the degenerate
        weights keep their bit-parity with the hand blend; else None."""
        if self.tau is None and self.alpha != 1.0 and lowers_linearly(self.base):
            return linear_blend(self.base, self.alpha)
        return None

    @property
    def name(self):
        return f"{self.base.name}-learned({self.weights_fingerprint})"

    @property
    def needs_simplex(self):
        return self.base.needs_simplex

    @property
    def symmetric(self):
        # the Mahalanobis term is symmetric; the blend part is symmetric
        # only at the avg point with an identity proxy
        blend_sym = self.alpha == 0.5 and self.tau is None
        return (blend_sym or self.alpha == 1.0 and getattr(self.base, "symmetric", False))

    def _combine(self, fwd, rev, m):
        if rev is not None and self.tau is not None:
            rev = self.tau * jnp.sign(rev) * jnp.log1p(jnp.abs(rev) / self.tau)
        out = fwd if rev is None else self.alpha * fwd + (1.0 - self.alpha) * rev
        if m is not None:
            out = out + self.beta * m
        return out

    def _plus_maha(self, out, m):
        return out if m is None else out + self.beta * m

    def matrix(self, U, V):
        m = self.maha.matrix(U, V) if self.beta != 0.0 else None
        if self._blend is not None:
            return self._plus_maha(self._blend.matrix(U, V), m)
        rev = self.base.matrix(V, U).T if self.alpha != 1.0 else None
        return self._combine(self.base.matrix(U, V), rev, m)

    def query_matrix(self, Q, X, mode: str = "left"):
        # the Mahalanobis term is symmetric, so its mode is irrelevant
        m = self.maha.query_matrix(Q, X, mode=mode) if self.beta != 0.0 else None
        if self._blend is not None:
            return self._plus_maha(self._blend.query_matrix(Q, X, mode=mode), m)
        fwd = self.base.query_matrix(Q, X, mode=mode)
        rev = None
        if self.alpha != 1.0:
            rev = self.base.query_matrix(Q, X, mode="right" if mode == "left" else "left")
        return self._combine(fwd, rev, m)

    def pairwise(self, u, v):
        rev = self.base.pairwise(v, u) if self.alpha != 1.0 else None
        m = self.maha.pairwise(u, v) if self.beta != 0.0 else None
        return self._combine(self.base.pairwise(u, v), rev, m)

    def pairwise_batch(self, U, V):
        return jax.vmap(self.pairwise)(U, V)

    def prep_scan(self, X):
        if self._blend is not None:
            out = {"b": self._blend.prep_scan(X)}
        else:
            out = {"f": self.base.prep_scan(X)}
            if self.alpha != 1.0:
                out["r"] = self._rev.prep_scan(X)
        if self.beta != 0.0:
            out["m"] = self.maha.prep_scan(X)
        return out

    def prep_query(self, q):
        if self._blend is not None:
            out = {"b": self._blend.prep_query(q)}
        else:
            out = {"f": self.base.prep_query(q)}
            if self.alpha != 1.0:
                out["r"] = self._rev.prep_query(q)
        if self.beta != 0.0:
            out["m"] = self.maha.prep_query(q)
        return out

    def score(self, rows, qc):
        m = self.maha.score(rows["m"], qc["m"]) if self.beta != 0.0 else None
        if self._blend is not None:
            return self._plus_maha(self._blend.score(rows["b"], qc["b"]), m)
        fwd = self.base.score(rows["f"], qc["f"])
        rev = self._rev.score(rows["r"], qc["r"]) if self.alpha != 1.0 else None
        return self._combine(fwd, rev, m)


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------


def calibrate_tau(base, X, *, max_rows: int = 256) -> float:
    """Data-calibrated ``RankBlend`` proxy scale: median reversed-distance.

    The ``rankblend`` proxy ``tau * sign(x) * log1p(|x| / tau)`` switches
    from near-linear to logarithmic compression around ``|x| ~ tau``, so
    ``tau`` should sit at the TYPICAL scale of the reversed distance — not
    at the hand-tuned constant 1.0, which is only right when the workload
    happens to produce O(1) divergences.  This estimates that scale as the
    median of ``|d(v, u)|`` over all ordered pairs of an evenly-strided
    sample of ``X`` (at most ``max_rows`` rows, one ``matrix`` call).

    Args:
        base: any PairDistance (the distance being rank-blended).
        X: (n, m) database sample to calibrate against.
        max_rows: sample-size cap; the estimate is deterministic (strided,
            no RNG) so the same data always yields the same tau.

    Returns:
        The median reversed-distance magnitude as a positive float; falls
        back to 1.0 (the historical fixed constant) when the sample is
        degenerate (fewer than 2 rows, all-zero, or non-finite median).
    """
    X = jnp.asarray(X)
    n = int(X.shape[0])
    if n < 2:
        return 1.0
    stride = max(1, n // max_rows)
    S = X[::stride][:max_rows]
    m = int(S.shape[0])
    # d(v, u) over the sample: same multiset as the transposed forward matrix
    D = base.matrix(S, S).T
    off = ~jnp.eye(m, dtype=bool)
    med = float(jnp.median(jnp.abs(D[off])))
    if not (med > 0.0 and jnp.isfinite(med)):
        return 1.0
    return med


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------


def lowers_linearly(base) -> bool:
    """Whether a linear blend of ``base``'s two argument orders is itself
    one matmul-form ``Distance`` (``linear_blend``): a plain ``Distance``
    whose post-combine is ``s + bias_l + bias_r`` (KL, Itakura-Saito)."""
    return isinstance(base, Distance) and base.post_id == POST_LINEAR


@functools.lru_cache(maxsize=64)
def linear_blend(base: Distance, alpha: float) -> Distance:
    """alpha * d(u, v) + (1 - alpha) * d(v, u) as ONE matmul-form Distance.

    For a ``POST_LINEAR`` base, d(u, v) = L(u) . R(v) + bl(u) + br(v), so

        alpha d(u, v) + (1 - alpha) d(v, u)
            = [alpha L(u) | (1 - alpha) R(u)] . [R(v) | L(v)]
              + (alpha bl(u) + (1 - alpha) br(u))
              + (alpha br(v) + (1 - alpha) bl(v))

    exactly: one matmul over m' = 2m (m' = m for the pure reversal,
    alpha = 0, which swaps the roles), so the batched engines score it like
    any registry distance (the gather kernel too, where m' fits one of its
    128-lane rows: ``kernels.ops.kernel_scores``).  ``pairwise`` stays
    the definition, the blend of the base's ``pairwise`` in both orders.
    Names follow the wrappers it replaces (``kl-avg``, ``kl-reverse``,
    ``kl-blend(0.25)``).  Cached, so binding the same base twice gives the
    same object (one jit cache entry).
    """
    if not lowers_linearly(base):
        raise ValueError(f"{base.name} has no linear post-combine")
    if not 0.0 <= alpha < 1.0:
        raise ValueError(f"alpha must be in [0, 1), got {alpha}")
    b = base
    if alpha == 0.0:
        return Distance(
            name=f"{b.name}-reverse", post_id=POST_LINEAR,
            prep_left=b.prep_right, prep_right=b.prep_left,
            bias_left=b.bias_right, bias_right=b.bias_left,
            pairwise=lambda u, v: b.pairwise(v, u),
            symmetric=b.symmetric, needs_simplex=b.needs_simplex,
        )
    a, r = float(alpha), 1.0 - float(alpha)
    name = f"{b.name}-avg" if alpha == 0.5 else f"{b.name}-blend({alpha:g})"
    return Distance(
        name=name, post_id=POST_LINEAR,
        prep_left=lambda U: jnp.concatenate([a * b.prep_left(U), r * b.prep_right(U)], -1),
        prep_right=lambda V: jnp.concatenate([b.prep_right(V), b.prep_left(V)], -1),
        bias_left=lambda U: a * b.bias_left(U) + r * b.bias_right(U),
        bias_right=lambda V: a * b.bias_right(V) + r * b.bias_left(V),
        pairwise=lambda u, v: a * b.pairwise(u, v) + r * b.pairwise(v, u),
        symmetric=alpha == 0.5 or b.symmetric, needs_simplex=b.needs_simplex,
    )


def reverse_of(base):
    """Argument reversal for any PairDistance.  ViewedDistance reverses by
    swapping its role views AND reversing the inner distance:
    vd_rev(u, v) = vd(v, u) = inner(L(v), R(u)) = inner_rev(R(u), L(v)).
    A linear base lowers to one ``Distance`` with its roles swapped."""
    if lowers_linearly(base):
        return linear_blend(base, 0.0)
    if isinstance(base, ViewedDistance):
        return ViewedDistance(
            ReversedDistance(base.base),
            left_view=base.right_view,
            right_view=base.left_view,
            view_name=base.view_name + "-rev",
        )
    return ReversedDistance(base)


def symmetrized(base, mode: str, natural: Optional[Callable] = None):
    """Wrap ``base`` (a PairDistance) with a symmetrization mode.

    ``natural`` — optional callable returning the distance-specific natural
    symmetrization (e.g. built from dataset IDF statistics, Eq. 4).
    """
    if mode == "none":
        return base
    if mode == "reverse":
        return reverse_of(base)
    if mode == "avg" and lowers_linearly(base):
        return linear_blend(base, 0.5)
    if mode in ("avg", "min"):
        return SymmetrizedDistance(base, mode)
    if mode == "l2":
        return l2_squared()
    if mode == "natural":
        if natural is None:
            raise ValueError("natural symmetrization requires a dataset-supplied distance")
        return natural()
    raise ValueError(f"unknown symmetrization mode {mode!r}; known: {SYM_MODES}")
