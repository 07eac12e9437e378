"""Pallas TPU kernel: fused frontier gather + distance for the batched engine.

One grid step = ``G`` (=8) queries.  Their (G, R) candidate ids arrive as an
SMEM block (prefetched by the pipeline while the previous step runs) and
drive R single-row DMAs per query from the HBM-resident database into a
double-buffered VMEM scratch: the rows of query g+1 are in flight while
query g is scored with ONE (1, m') x (R, m')^T MXU contraction into a
(1, R) row.  The post-combine epilogue then runs once on the whole (G, R)
tile, with the gathered per-row biases brought in as a (G, R) block and the
query biases as a (G, 1) column — so no 1-D vector or lane-broadcast column
slice ever reaches the chip's compiler.

Layout rules the chip's compiler (Mosaic) enforces here:

* a DMA may slice ONE row only from an HBM array whose rows are contiguous,
  i.e. exactly one 128-lane tile wide.  The corpus is therefore handed to
  the kernel as an (n * K, 128) view (``row_view``) — zero-padded to 128
  lanes when m' < 128 (the (8, 128) tiling pads such rows in HBM anyway),
  reshaped to K = ceil(m' / 128) rows per data point when m' > 128 — and
  every data point costs K row DMAs.  At m' = 128 the view is the corpus
  itself.  The engines lay the view out once, where they prep the corpus,
  so no kernel call copies it;
* every block is (8, ·) or spans its whole dimension.

This is the kernel behind ``repro.core.batched_beam``: R = frontier * M ids
per query per step, and behind NN-descent's (n, C) candidate scoring.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .distance_matrix import _epilogue

G = 8  # queries per grid step: the sublane count of an f32 tile
LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(ids_ref, q_ref, xb_ref, qb_ref, x_hbm, o_ref, rows, sems, *,
            post_id: int, c0: float, R: int, K: int):
    # ids_ref (G, R) SMEM; q_ref (G, K*L); xb_ref (G, R); qb_ref (G, 1);
    # x_hbm (n*K, L) in HBM; rows (2, K, R, L) VMEM; sems: one per buffer
    def copies(g, r, slot):
        base = ids_ref[g, r] * K
        return [
            pltpu.make_async_copy(x_hbm.at[pl.ds(base + c, 1)],
                                  rows.at[slot, c, pl.ds(r, 1)], sems.at[slot])
            for c in range(K)
        ]

    def issue(g, slot, op):
        def body(r, carry):
            for cp in copies(g, r, slot):
                getattr(cp, op)()
            return carry

        jax.lax.fori_loop(0, R, body, 0)

    L = q_ref.shape[1] // K
    issue(0, 0, "start")
    for g in range(G):
        if g + 1 < G:
            issue(g + 1, (g + 1) % 2, "start")
        issue(g, g % 2, "wait")
        s = None
        for c in range(K):
            t = jax.lax.dot_general(
                q_ref[pl.ds(g, 1), pl.ds(c * L, L)], rows[g % 2, c],
                (((1,), (1,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=jnp.float32,
            )
            s = t if s is None else s + t
        o_ref[pl.ds(g, 1), :] = s

    o_ref[...] = _epilogue(post_id, o_ref[...], xb_ref[...], qb_ref[...], c0)


def row_view(x_rep):
    """(n * K, 128) f32 view of the (n, m') corpus reps that the kernel
    DMAs rows from, K = ceil(m' / 128)."""
    n, m = x_rep.shape
    x = x_rep.astype(jnp.float32)
    mp = -(-m // LANES) * LANES
    if mp != m:
        x = jnp.pad(x, ((0, 0), (0, mp - m)))
    return x.reshape(n * (mp // LANES), LANES)


@functools.partial(jax.jit, static_argnames=("post_id", "c0", "interpret"))
def frontier_scores(
    ids,  # (B, R) int32 candidate row indices (-1 padding)
    q_rep,  # (B, m') prepped query reps
    q_bias,  # (B,)
    x_rows,  # (n * K, 128) prepped DB reps laid out by ``row_view``
    x_bias,  # (n,)
    post_id: int,
    c0: float = 0.0,
    interpret: bool = True,
):
    """(B, R) f32 left-query distances of the gathered rows (inf where id < 0)."""
    B, R = ids.shape
    m = q_rep.shape[1]
    K = -(-m // LANES)
    if x_rows.shape != (x_bias.shape[0] * K, LANES):
        raise ValueError(f"x_rows {x_rows.shape} is not row_view of an "
                         f"({x_bias.shape[0]}, {m}) corpus")
    Bp = -(-B // G) * G
    safe = jnp.where(ids >= 0, ids, 0)
    xb = x_bias.astype(jnp.float32)[safe]
    q = q_rep.astype(jnp.float32)
    if K * LANES != m:
        q = jnp.pad(q, ((0, 0), (0, K * LANES - m)))

    def rows_pad(a):
        return jnp.pad(a, ((0, Bp - B), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, post_id=post_id, c0=c0, R=R, K=K),
        grid=(Bp // G,),
        in_specs=[
            pl.BlockSpec((G, R), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((G, K * LANES), lambda i: (i, 0)),
            pl.BlockSpec((G, R), lambda i: (i, 0)),
            pl.BlockSpec((G, 1), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # database stays in HBM
        ],
        out_specs=pl.BlockSpec((G, R), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, R), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, K, R, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(rows_pad(safe), rows_pad(q), rows_pad(xb),
      rows_pad(q_bias.astype(jnp.float32)[:, None]), x_rows)
    return jnp.where(ids >= 0, out[:B], jnp.inf)
