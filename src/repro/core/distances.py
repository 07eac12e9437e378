"""Distance zoo for non-metric k-NN retrieval (Boytsov & Nyberg 2019).

Every distance used by the paper factors into a *matmul form*

    d(u, v) = post( prep_left(u) . prep_right(v) , bias_left(u), bias_right(v) )

where ``u`` is the LEFT argument and ``v`` the RIGHT argument of ``d``.
The paper's *left queries* compute ``d(x, q)`` with the data point ``x`` on
the left, so a query-vs-database scan is

    D[b, i] = d(X[i], Q[b]) = post( prep_right(Q) @ prep_left(X)^T )[b, i]

i.e. a single MXU matmul after the database has been pre-transformed ONCE at
index time.  This decomposition is the TPU adaptation of the paper's scalar
CPU distance evaluations (see DESIGN.md SS2.1) and is the contract implemented
by the Pallas kernel in ``repro.kernels.distance_matrix``.

Post-combine functions are identified by a static integer id so kernels can
specialise on them:

    POST_LINEAR : s + bias_l + bias_r            (KL, Itakura-Saito)
    POST_RENYI  : log(max(s, tiny)) * c0         (Renyi, c0 = 1/(alpha-1))
    POST_NEG    : -s                             (BM25 / negative inner product)
    POST_L2     : bias_l - 2 s + bias_r          (squared Euclidean)
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# post-combine registry (static ids shared with the Pallas kernels)
# ---------------------------------------------------------------------------

POST_LINEAR = 0
POST_RENYI = 1
POST_NEG = 2
POST_L2 = 3

_TINY = 1e-30
EPS = 1e-6  # histogram floor; matches the data generators

# Every matmul that decides a ranking runs at full f32 precision.  XLA's
# default on a TPU passes f32 operands through bf16, and a KL distance is a
# small remainder of two large terms (x . -log q + sum x log x): at bf16 the
# exact scan that every recall figure divides by would rank wrongly.
HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def apply_post(post_id: int, s, bias_l, bias_r, c0: float = 0.0):
    """Apply a post-combine. ``bias_l``/``bias_r`` broadcast against ``s``.

    ``s`` has shape (..., L, R) when computed as prep_left @ prep_right^T with
    bias_l shaped (L, 1)-broadcastable and bias_r shaped (R,)-broadcastable
    (callers are responsible for orienting the biases to match ``s``).
    """
    if post_id == POST_LINEAR:
        return s + bias_l + bias_r
    if post_id == POST_RENYI:
        return jnp.log(jnp.maximum(s, _TINY)) * c0
    if post_id == POST_NEG:
        return -s
    if post_id == POST_L2:
        return bias_l - 2.0 * s + bias_r
    raise ValueError(f"unknown post id {post_id}")


# ---------------------------------------------------------------------------
# Distance definition
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Distance:
    """A (possibly non-symmetric, non-metric) distance in matmul form.

    ``prep_left``/``prep_right`` map a batch of raw vectors (N, m) to the
    transformed representation (N, m'); ``bias_left``/``bias_right`` map the
    same batch to per-row scalar biases (N,).  ``pairwise`` is the pointwise
    oracle d(u, v) used for tests and for the paper-faithful scalar path.
    """

    name: str
    post_id: int
    prep_left: Callable
    prep_right: Callable
    bias_left: Callable
    bias_right: Callable
    pairwise: Callable  # (m,), (m,) -> scalar
    c0: float = 0.0
    symmetric: bool = False
    needs_simplex: bool = True  # defined over positive histograms

    # -- full matrices ------------------------------------------------------

    def matrix(self, U, V):
        """D[i, j] = d(U[i], V[j]) via one matmul."""
        s = _mm(self.prep_left(U), self.prep_right(V).T)
        return apply_post(
            self.post_id, s, self.bias_left(U)[:, None], self.bias_right(V)[None, :], self.c0
        )

    def query_matrix(self, Q, X, mode: str = "left"):
        """Distances between a query batch Q (B, m) and database X (N, m).

        mode="left"  (paper default): D[b, i] = d(X[i], Q[b])
        mode="right"                : D[b, i] = d(Q[b], X[i])
        Result is (B, N) either way.
        """
        if mode == "left":
            s = _mm(self.prep_right(Q), self.prep_left(X).T)
            return apply_post(
                self.post_id, s, self.bias_left(X)[None, :], self.bias_right(Q)[:, None], self.c0
            )
        elif mode == "right":
            s = _mm(self.prep_left(Q), self.prep_right(X).T)
            return apply_post(
                self.post_id, s, self.bias_left(Q)[:, None], self.bias_right(X)[None, :], self.c0
            )
        raise ValueError(f"unknown query mode {mode!r}")

    # -- pointwise oracle over batches ---------------------------------------

    def pairwise_batch(self, U, V):
        """d(U[i], V[i]) elementwise over two equal-length batches."""
        return jax.vmap(self.pairwise)(U, V)

    # -- gather-able per-row constants (beam-search contract) ----------------
    #
    # ``prep_scan(X)`` pre-transforms the database ONCE; ``score`` evaluates
    # left-mode distances d(X[rows], q) for a gathered subset of rows.  Both
    # the jnp beam search and the Pallas fused gather kernel consume this.

    def prep_scan(self, X):
        return {"rep": self.prep_left(X), "bias": self.bias_left(X)}

    def prep_query(self, q):
        """Per-query constants matching ``prep_scan`` (q: (m,) raw vector)."""
        return {"rep": self.prep_right(q[None, :])[0], "bias": self.bias_right(q[None, :])[0]}

    def score(self, rows, qc):
        """rows: pytree from prep_scan gathered to (B, ...); qc: from prep_query."""
        s = _mm(rows["rep"], qc["rep"])
        return apply_post(self.post_id, s, rows["bias"], qc["bias"], self.c0)


# ---------------------------------------------------------------------------
# Concrete distances (Table 2 of the paper)
# ---------------------------------------------------------------------------


def _safe(x):
    return jnp.maximum(x, EPS)


def kl_divergence() -> Distance:
    """KL(u || v) = sum u log(u/v).  Non-symmetric, non-metric (Bregman)."""

    def pairwise(u, v):
        u, v = _safe(u), _safe(v)
        return jnp.sum(u * (jnp.log(u) - jnp.log(v)))

    return Distance(
        name="kl",
        post_id=POST_LINEAR,
        prep_left=lambda U: _safe(U),
        prep_right=lambda V: -jnp.log(_safe(V)),
        bias_left=lambda U: jnp.sum(_safe(U) * jnp.log(_safe(U)), axis=-1),
        bias_right=lambda V: jnp.zeros(V.shape[:-1], V.dtype),
        pairwise=pairwise,
    )


def itakura_saito() -> Distance:
    """IS(u, v) = sum [ u/v - log(u/v) - 1 ].  Strongly non-symmetric."""

    def pairwise(u, v):
        u, v = _safe(u), _safe(v)
        r = u / v
        return jnp.sum(r - jnp.log(r) - 1.0)

    def bias_left(U):
        m = U.shape[-1]
        return -jnp.sum(jnp.log(_safe(U)), axis=-1) - float(m)

    return Distance(
        name="itakura_saito",
        post_id=POST_LINEAR,
        prep_left=lambda U: _safe(U),
        prep_right=lambda V: 1.0 / _safe(V),
        bias_left=bias_left,
        bias_right=lambda V: jnp.sum(jnp.log(_safe(V)), axis=-1),
        pairwise=pairwise,
    )


def renyi_divergence(alpha: float) -> Distance:
    """Renyi_a(u||v) = log( sum u^a v^(1-a) ) / (a - 1), a > 0, a != 1.

    Non-symmetric except at a = 1/2; degree of asymmetry grows as a moves
    away from 1/2 (the paper stress-tests with a in {0.25, 0.75, 2}).
    """
    if alpha <= 0 or alpha == 1.0:
        raise ValueError("Renyi divergence needs alpha > 0, alpha != 1")
    c0 = 1.0 / (alpha - 1.0)

    def pairwise(u, v):
        u, v = _safe(u), _safe(v)
        s = jnp.sum(u**alpha * v ** (1.0 - alpha))
        return jnp.log(jnp.maximum(s, _TINY)) * c0

    return Distance(
        name=f"renyi_{alpha:g}",
        post_id=POST_RENYI,
        prep_left=lambda U: _safe(U) ** alpha,
        prep_right=lambda V: _safe(V) ** (1.0 - alpha),
        bias_left=lambda U: jnp.zeros(U.shape[:-1], U.dtype),
        bias_right=lambda V: jnp.zeros(V.shape[:-1], V.dtype),
        pairwise=pairwise,
        c0=c0,
        symmetric=(alpha == 0.5),
    )


def neg_inner_product(name: str = "negdot") -> Distance:
    """Negative inner product: the BM25 similarity as a distance (Eq. 1).

    The asymmetry of BM25 lives in the *vectorization* (query-side TF vs
    document-side TF x IDF); the distance itself is a negated dot product
    over the already-vectorized representations.  The dataset object supplies
    the role-dependent views (see repro.data.synthetic.TextCollection).
    """

    def pairwise(u, v):
        return -jnp.sum(u * v)

    return Distance(
        name=name,
        post_id=POST_NEG,
        prep_left=lambda U: U,
        prep_right=lambda V: V,
        bias_left=lambda U: jnp.zeros(U.shape[:-1], U.dtype),
        bias_right=lambda V: jnp.zeros(V.shape[:-1], V.dtype),
        pairwise=pairwise,
        symmetric=False,
        needs_simplex=False,
    )


def l2_squared() -> Distance:
    """Squared Euclidean - the quasi-symmetrization proxy of the paper."""

    def pairwise(u, v):
        w = u - v
        return jnp.sum(w * w)

    return Distance(
        name="l2",
        post_id=POST_L2,
        prep_left=lambda U: U,
        prep_right=lambda V: V,
        bias_left=lambda U: jnp.sum(U * U, axis=-1),
        bias_right=lambda V: jnp.sum(V * V, axis=-1),
        pairwise=pairwise,
        symmetric=True,
        needs_simplex=False,
    )


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_FACTORIES = {
    "kl": kl_divergence,
    "itakura_saito": itakura_saito,
    "renyi_0.25": lambda: renyi_divergence(0.25),
    "renyi_0.75": lambda: renyi_divergence(0.75),
    "renyi_2": lambda: renyi_divergence(2.0),
    "negdot": neg_inner_product,
    "bm25": neg_inner_product,  # alias: BM25-as-distance over vectorized reps
    "l2": l2_squared,
}


def get_distance(name: str) -> Distance:
    if name.startswith("renyi_"):
        alpha = float(name.split("_", 1)[1])
        return renyi_divergence(alpha)
    try:
        return _FACTORIES[name]()
    except KeyError:
        raise ValueError(f"unknown distance {name!r}; known: {sorted(_FACTORIES)}") from None


def available_distances():
    return sorted(_FACTORIES)
