"""Pure-jnp oracles for the Pallas kernels.

Contract note: kernels operate on ALREADY-PREPPED representations (the
elementwise pre-transforms of DESIGN.md SS2.1 are applied once at index time
outside the kernel); the kernel hot loop is the tiled matmul + post-combine.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.distances import apply_post

# f32 matmuls at full precision: on a TPU the default passes through bf16,
# and KL's small remainder of two large terms would rank neighbours wrongly
_HIGHEST = jax.lax.Precision.HIGHEST


def distance_matrix_ref(q_rep, x_rep, q_bias, x_bias, post_id: int, c0: float = 0.0):
    """(B, N) left-query distances from prepped reps.

    q_rep (B, m') = prep_right(Q);  x_rep (N, m') = prep_left(X);
    q_bias (B,), x_bias (N,) the matching scalar biases.
    D[b, i] = post(q_rep[b] . x_rep[i], bias_l=x_bias[i], bias_r=q_bias[b]).
    """
    s = jnp.dot(q_rep, x_rep.T, precision=_HIGHEST, preferred_element_type=jnp.float32)
    return apply_post(post_id, s, x_bias[None, :].astype(jnp.float32),
                      q_bias[:, None].astype(jnp.float32), c0)


def gather_scores_ref(ids, q_rep, x_rep, q_bias, x_bias, post_id: int, c0: float = 0.0):
    """Fused beam-step oracle: distances of gathered neighbor rows per query.

    ids (B, M) int32 row indices into x_rep (n, m'); -1 = padding -> +inf.
    Returns (B, M) float32 distances.
    """
    safe = jnp.where(ids >= 0, ids, 0)
    rows = x_rep[safe]  # (B, M, m')
    s = jnp.einsum("bmf,bf->bm", rows.astype(jnp.float32), q_rep.astype(jnp.float32),
                   precision=_HIGHEST)
    d = apply_post(post_id, s, x_bias[safe].astype(jnp.float32),
                   q_bias[:, None].astype(jnp.float32), c0)
    return jnp.where(ids >= 0, d, jnp.inf)
