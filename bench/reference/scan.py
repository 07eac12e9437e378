"""Plain reference: exact k-NN by a blocked scan, for each distance by name.

Written from the distances' definitions alone; it imports nothing of the
program.  Each distance is a file of its own, ``reference/<distance>.py``
(found by ``distance(name)``), with its definition over any array module
(``pair``) and its terms for the scan (``terms``).  The argument order is
the program's documented one (the paper's *left queries*): the corpus row
``x`` is the LEFT argument and the query ``q`` the RIGHT one, and these
distances are not symmetric.  Both arguments are floored at ``EPS`` first,
as the program documents for histogram distances.

``distances_of`` gives the distance of each returned id from the
definition: in float32 on the device, and in float64 on the host.  ``scan``
ranks the whole corpus: one matrix product per block of
rows, float32 at ``Precision.HIGHEST``.  ``precision="bf16x3"`` runs the
same products as three bfloat16 passes (high x high + high x low + low x
high, float32 accumulation), which is what a TPU computes at
``Precision.HIGH``: the lower-precision control, spelled out so that it
computes the same on any backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import lookup

EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("highest", "bf16x3")


def distance(name: str, root: str = lookup.ROOT):
    """The module ``reference/<name>.py``: ``pair(x, q, xp)`` and
    ``terms(X, Q)``."""
    return lookup.module("reference", name, root)


def _terms(dist, X, Q):
    """``dist.terms`` of the floored rows: (left rows (N, d), right rows
    (B, d), combine(s (B, N)) -> D (B, N)) with D[b, i] = d(X[i], Q[b]) and
    s = right @ left^T."""
    return dist.terms(jnp.maximum(X, EPS), jnp.maximum(Q, EPS))


def _bf16(a):
    """``a`` rounded to bfloat16's 8-bit mantissa, kept in float32.
    ``reduce_precision`` and not a float32 -> bfloat16 -> float32 round
    trip: a compiler allowed excess precision may drop the round trip."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    lo = _bf16(a - hi)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def matmul_t(r, l, precision: str):
    """r @ l^T in float32 at the named precision."""
    if precision == "highest":
        return jnp.matmul(r, l.T, precision=HIGHEST)
    if precision == "bf16x3":
        (rh, rl), (lh, ll) = _split(r), _split(l)

        def mm(a, b):  # bfloat16 operands, float32 products and sums
            return jnp.matmul(a, b.T, preferred_element_type=jnp.float32)

        # the barrier keeps the compiler from merging the three products
        # into one (dot(a, b) + dot(a, c) = dot(a, b + c)), which would
        # round b + c back to one bfloat16 pass
        parts = jax.lax.optimization_barrier((mm(rh, lh), mm(rh, ll),
                                              mm(rl, lh)))
        return parts[0] + parts[1] + parts[2]
    raise ValueError(f"unknown precision {precision!r}; known: {PRECISIONS}")


@functools.partial(jax.jit, static_argnames=("dist", "k", "precision"))
def _block(best_d, best_i, Q, Xb, base, n, *, dist, k, precision):
    left, right, combine = _terms(dist, Xb, Q)
    D = combine(matmul_t(right, left, precision))
    ids = base + jnp.arange(Xb.shape[0], dtype=jnp.int32)
    D = jnp.where(ids[None, :] < n, D, jnp.inf)
    cat_d = jnp.concatenate([best_d, D], axis=1)
    cat_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, D.shape)], axis=1)
    neg, pos = jax.lax.top_k(-cat_d, k)
    return -neg, jnp.take_along_axis(cat_i, pos, axis=1)


def scan(dist, X, Q, k: int, *, precision: str = "highest",
         block_rows: int = 32768, block_queries: int = 512):
    """Exact top-k (distances ascending, ids) of each query over all of X
    under the distance module ``dist``.

    Returns NumPy arrays (B, k) float32 and (B, k) int64.  Runs in blocks of
    ``block_queries`` queries by ``block_rows`` corpus rows, so its memory is
    bounded whatever the corpus size.
    """
    X = jnp.asarray(X, jnp.float32)
    Q = np.asarray(Q, np.float32)
    n = X.shape[0]
    rows = min(block_rows, n)
    pad = -(-n // rows) * rows - n
    if pad:
        X = jnp.concatenate([X, jnp.ones((pad, X.shape[1]), X.dtype)], axis=0)
    out_d, out_i = [], []
    for lo in range(0, Q.shape[0], block_queries):
        qb = Q[lo:lo + block_queries]
        nq = qb.shape[0]
        qb = jnp.asarray(np.pad(qb, ((0, block_queries - nq), (0, 0)),
                                constant_values=1.0 / Q.shape[1]))
        best_d = jnp.full((block_queries, k), jnp.inf, jnp.float32)
        best_i = jnp.full((block_queries, k), -1, jnp.int32)
        for base in range(0, X.shape[0], rows):
            best_d, best_i = _block(best_d, best_i, qb, X[base:base + rows],
                                    jnp.int32(base), jnp.int32(n), dist=dist,
                                    k=k, precision=precision)
        out_d.append(np.asarray(best_d)[:nq])
        out_i.append(np.asarray(best_i)[:nq].astype(np.int64))
    return np.concatenate(out_d), np.concatenate(out_i)


@functools.partial(jax.jit, static_argnames=("dist",))
def _pair_rows(X, Q, ids, *, dist):
    x = jnp.maximum(X[ids], EPS)
    q = jnp.maximum(Q, EPS)[:, None, :]
    return dist.pair(x, q, jnp)


def distances_of(dist, X, Q, ids):
    """d(X[ids[b, j]], Q[b]) from the definition, NaN where an id is out of
    range: (float32 on the device, float64 on the host)."""
    ids = np.asarray(ids, np.int64)
    n = X.shape[0]
    ok = (ids >= 0) & (ids < n)
    safe = np.where(ok, ids, 0)
    d32 = np.asarray(_pair_rows(jnp.asarray(X), jnp.asarray(Q, jnp.float32),
                                jnp.asarray(safe, jnp.int32), dist=dist))
    x = np.maximum(np.asarray(X)[safe].astype(np.float64), EPS)
    q = np.maximum(np.asarray(Q, np.float64), EPS)[:, None, :]
    return np.where(ok, d32, np.nan), np.where(ok, dist.pair(x, q, np), np.nan)
