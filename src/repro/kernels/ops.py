"""Jitted public wrappers around the Pallas kernels.

``use_pallas`` selects the execution path:
  * None (default): Pallas in interpret mode off-TPU, compiled on TPU —
    i.e. the kernel body is always the code under test (the frontier
    gather takes the jnp path off-TPU, see ``frontier_gather_scores``);
  * True: the kernel, interpreted off-TPU;
  * False: the pure-jnp reference path (XLA fusion decides the schedule).

``_on_tpu`` is the one backend check every engine routes through.

Higher layers (brute_force, beam_search) call through these wrappers so the
kernel and the jnp path are interchangeable per call site.
"""

from __future__ import annotations

import jax

from repro.core.distances import Distance
from . import ref as _ref
from .distance_matrix import distance_matrix as _dm_kernel
from .frontier_gather import frontier_scores as _fs_kernel
from .frontier_gather import row_view


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def query_distance_matrix(dist: Distance, Q, X, use_pallas=None, block_q=256, block_x=256):
    """(B, N) left-query distances d(X[i], Q[b]) for a single-matmul Distance."""
    q_rep = dist.prep_right(Q)
    x_rep = dist.prep_left(X)
    q_bias = dist.bias_right(Q)
    x_bias = dist.bias_left(X)
    if use_pallas is False:
        return _ref.distance_matrix_ref(q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0)
    return _dm_kernel(
        q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0,
        block_q=block_q, block_x=block_x, interpret=not _on_tpu(),
    )


def beam_gather_scores(dist: Distance, ids, Q, X, use_pallas=None):
    """(B, M) distances of neighbor rows ids under left-query convention."""
    q_rep = dist.prep_right(Q)
    x_rep = dist.prep_left(X)
    q_bias = dist.bias_right(Q)
    x_bias = dist.bias_left(X)
    if use_pallas is False:
        return _ref.gather_scores_ref(ids, q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0)
    return _fs_kernel(
        ids, q_rep, q_bias, row_view(x_rep), x_bias, dist.post_id, dist.c0,
        interpret=not _on_tpu(),
    )


def kernel_rows(dist, consts, use_pallas=None):
    """The corpus reps ``consts["rep"]`` (from ``dist.prep_scan``) laid out
    for the fused gather kernel (``row_view``), or None where the jnp path
    scores.

    ``use_pallas=None`` picks the kernel only on TPU (the interpret path is
    a per-tile Python loop — correct but slow off-TPU); True forces it,
    False never takes it.  Composite distances always score through jnp.
    Engines call this ONCE where they prep the corpus and hand the result
    to every ``frontier_gather_scores`` call.
    """
    if isinstance(dist, Distance) and (
            use_pallas is True or (use_pallas is None and _on_tpu())):
        return row_view(consts["rep"])
    return None


def frontier_gather_scores(dist: Distance, ids, q_rep, q_bias, x_rep, x_bias,
                           x_rows=None):
    """(B, R) distances of frontier rows from ALREADY-PREPPED reps.

    The batched beam engine calls this once per lock-step with the full
    (B, frontier*M) candidate block; NN-descent construction calls it once
    per refinement round with the (n, C) candidate join (every database row
    acting as its own query, reps prepped once per build).  With ``x_rows``
    from ``kernel_rows`` the fused DMA kernel scores; without, the jnp path.
    """
    if x_rows is not None:
        return _fs_kernel(
            ids, q_rep, q_bias, x_rows, x_bias, dist.post_id, dist.c0,
            interpret=not _on_tpu(),
        )
    return _ref.gather_scores_ref(ids, q_rep, x_rep, q_bias, x_bias, dist.post_id, dist.c0)
