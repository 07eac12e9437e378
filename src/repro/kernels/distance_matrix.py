"""Pallas TPU kernel: MXU-tiled non-metric distance matrix.

The brute-force scan / graph-construction hot spot.  One grid step computes a
(BQ, BX) distance tile from a (BQ, m') query-rep block and a (BX, m') DB-rep
block resident in VMEM:

    s_tile = q_blk @ x_blk^T          (MXU, f32 accumulation)
    d_tile = post(s_tile, x_bias_blk, q_bias_blk)   (VPU epilogue, fused)

Tiling: block sizes default to 256x256 over the (B, N) output - 256 is a
multiple of both the 128-wide MXU systolic dimension and the (8,128) f32
VMEM tile.  The reduction dim m' is kept whole in VMEM (paper data is
m <= 4096: 256x4096 f32 = 4 MiB per operand block, well under the ~16 MiB
v5e VMEM budget); a k-tiled accumulation variant is selected automatically
for larger m'.

Biases travel as (rows, 1) 2-D arrays - TPU Pallas prefers >=2-D refs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.distances import POST_L2, POST_LINEAR, POST_NEG, POST_RENYI

_TINY = 1e-30


def _epilogue(post_id: int, s, xb, qb, c0: float):
    """Fused post-combine on a (BQ, BX) tile. xb: (1, BX), qb: (BQ, 1)."""
    if post_id == POST_LINEAR:
        return s + xb + qb
    if post_id == POST_RENYI:
        return jnp.log(jnp.maximum(s, _TINY)) * c0
    if post_id == POST_NEG:
        return -s
    if post_id == POST_L2:
        return xb - 2.0 * s + qb
    raise ValueError(post_id)


def _kernel_whole_k(q_ref, x_ref, qb_ref, xb_ref, o_ref, *, post_id: int, c0: float):
    s = jnp.dot(
        q_ref[...].astype(jnp.float32),
        x_ref[...].astype(jnp.float32).T,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = _epilogue(post_id, s, xb_ref[...].T, qb_ref[...], c0)


def _kernel_tiled_k(q_ref, x_ref, qb_ref, xb_ref, o_ref, acc_ref, *, post_id: int,
                    c0: float, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        q_ref[...].astype(jnp.float32),
        x_ref[...].astype(jnp.float32).T,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = _epilogue(post_id, acc_ref[...], xb_ref[...].T, qb_ref[...], c0)


def _pad_to(a, mult, axis, value=0.0):
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


@functools.partial(
    jax.jit,
    static_argnames=("post_id", "c0", "block_q", "block_x", "block_k", "interpret"),
)
def distance_matrix(
    q_rep,
    x_rep,
    q_bias,
    x_bias,
    post_id: int,
    c0: float = 0.0,
    block_q: int = 256,
    block_x: int = 256,
    block_k: int = 2048,
    interpret: bool = True,
):
    """(B, N) f32 distance tile matrix. See module docstring for layout.

    ``interpret=True`` runs the kernel body on CPU (this container);
    on TPU pass ``interpret=False``.
    """
    B, m = q_rep.shape
    N, m2 = x_rep.shape
    assert m == m2, (m, m2)
    block_q = min(block_q, max(8, B))
    block_x = min(block_x, max(128, N))

    qp = _pad_to(q_rep, block_q, 0)
    xp = _pad_to(x_rep, block_x, 0)
    qbp = _pad_to(q_bias[:, None].astype(jnp.float32), block_q, 0)
    xbp = _pad_to(x_bias[:, None].astype(jnp.float32), block_x, 0)
    Bp, Np = qp.shape[0], xp.shape[0]

    if m <= block_k:
        grid = (Bp // block_q, Np // block_x)
        out = pl.pallas_call(
            functools.partial(_kernel_whole_k, post_id=post_id, c0=c0),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_q, m), lambda i, j: (i, 0)),
                pl.BlockSpec((block_x, m), lambda i, j: (j, 0)),
                pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((block_x, 1), lambda i, j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((block_q, block_x), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.float32),
            interpret=interpret,
        )(qp, xp, qbp, xbp)
    else:
        qp = _pad_to(qp, block_k, 1)
        xp = _pad_to(xp, block_k, 1)
        mk = qp.shape[1]
        nk = mk // block_k
        grid = (Bp // block_q, Np // block_x, nk)
        out = pl.pallas_call(
            functools.partial(_kernel_tiled_k, post_id=post_id, c0=c0, nk=nk),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_q, block_k), lambda i, j, k: (i, k)),
                pl.BlockSpec((block_x, block_k), lambda i, j, k: (j, k)),
                pl.BlockSpec((block_q, 1), lambda i, j, k: (i, 0)),
                pl.BlockSpec((block_x, 1), lambda i, j, k: (j, 0)),
            ],
            out_specs=pl.BlockSpec((block_q, block_x), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.float32),
            scratch_shapes=[pltpu.VMEM((block_q, block_x), jnp.float32)],
            interpret=interpret,
        )(qp, xp, qbp, xbp)
    return out[:B, :N]
