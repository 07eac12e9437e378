"""Sharded sparse embedding tables (the recsys hot path).

JAX has no EmbeddingBag / CSR - per the spec this IS part of the system:
lookups are ``jnp.take`` + ``jax.ops.segment_sum`` (for multi-hot bags).

Distribution = ROW sharding over the TP axis with mask-lookup + psum
(DESIGN.md SS5): every device holds a contiguous row range of each table,
looks up the (replicated) indices that fall in its range (zeros elsewhere),
and a single psum over the axis restores exact lookups.  This is the
classic "model-parallel embedding" of DLRM/TorchRec, expressed with
shard_map so the collective is explicit (one psum per lookup batch,
bytes = batch x n_fields x dim).

All per-field tables are CONCATENATED into one (sum(vocab), dim) matrix
with per-field row offsets - one kernel/gather for all fields, one psum.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding.api import current_mesh


import numpy as np


def field_offsets(vocab_sizes: Sequence[int]):
    off = np.cumsum((0,) + tuple(vocab_sizes[:-1]), dtype=np.int64)
    assert off[-1] + vocab_sizes[-1] < 2**31, "concatenated table exceeds int32"
    return jnp.asarray(off, jnp.int32)


def init_table(key, vocab_sizes: Sequence[int], dim: int, dtype=jnp.float32):
    total = int(sum(vocab_sizes))
    scale = dim**-0.5
    return (jax.random.normal(key, (total, dim)) * scale).astype(dtype)


def table_spec(tp_axis: str = "model", fsdp_axis: str = None):
    """Row-sharded over the TP axis, and over the FSDP axis too when given
    (10^7-10^8-row tables: rows/(16x16) keeps table+AdamW states ~100s MB
    per chip, and grad syncs become reduce-scatters to the row shards)."""
    if fsdp_axis:
        return P((tp_axis, fsdp_axis), None)
    return P(tp_axis, None)


def embedding_lookup(table, ids, offsets, *, row_axes=("model", "data")):
    """ids: (B, F) per-field local ids -> (B, F, dim) embeddings.

    Off-mesh: plain take.  On-mesh: EXPLICIT shard_map masked-take + one
    psum over the row-sharding axes.  (Letting GSPMD serve a gather from a
    row-sharded table all-gathers the TABLE - 60+GB for two-tower - whereas
    the psum moves only (B, F, dim); EXPERIMENTS.md SSPerf.)  The backward
    is the transpose: each shard scatter-adds into its own rows, no
    table-sized collective.
    """
    flat = ids + jnp.broadcast_to(offsets, ids.shape[-1:])[None, :]
    mesh = current_mesh()
    axes = tuple(a for a in row_axes if mesh is not None and a in mesh.axis_names)
    if not axes:
        return table[flat]

    n_row_shards = 1
    for a in axes:
        n_row_shards *= mesh.shape[a]
    B = flat.shape[0]
    # reduce-scatter the combine when the batch divides the shard count:
    # the full (B, F, dim) partial never leaves registers/accumulators -
    # psum would materialise it REPLICATED (17 GiB/device on two-tower
    # train_batch; EXPERIMENTS.md SSPerf).  Falls back to psum for tiny B.
    use_scatter = B % n_row_shards == 0 and B >= n_row_shards

    def local(table_local, flat_ids):
        shard = jnp.int32(0)
        for a in axes:  # row-major linearization = PartitionSpec tuple order
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        rows_local = table_local.shape[0]
        lo = shard * rows_local
        rel = flat_ids - lo
        inside = (rel >= 0) & (rel < rows_local)
        safe = jnp.clip(rel, 0, rows_local - 1)
        emb = table_local[safe] * inside[..., None].astype(table_local.dtype)
        if use_scatter:
            # scatter order (batch_axis, *others) keeps the final per-device
            # rows CONTIGUOUS after re-gathering the non-batch axes
            scatter_axes = (axes[-1],) + axes[:-1]
            part = jax.lax.psum_scatter(emb, scatter_axes, scatter_dimension=0,
                                        tiled=True)  # (B/nm, F, d) summed
            if len(axes) > 1:  # re-gather all but the batch-sharding axis
                part = jax.lax.all_gather(part, axes[:-1], axis=0, tiled=True)
            return part
        return jax.lax.psum(emb, axes)

    out_spec = P((axes[-1],), None, None) if use_scatter else P(None, None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(None, None)),
        out_specs=out_spec,
        check_vma=False,
    )(table, flat)


def embedding_bag(table, ids, segment_ids, n_bags: int, mode: str = "sum",
                  weights=None):
    """EmbeddingBag: ragged multi-hot ids -> per-bag reduced embeddings.

    ids: (nnz,) rows; segment_ids: (nnz,) bag index (sorted); -> (n_bags, dim).
    """
    emb = table[jnp.where(ids >= 0, ids, 0)]
    if weights is not None:
        emb = emb * weights[:, None]
    emb = jnp.where((ids >= 0)[:, None], emb, 0.0)
    s = jax.ops.segment_sum(emb, segment_ids, n_bags)
    if mode == "sum":
        return s
    if mode == "mean":
        cnt = jax.ops.segment_sum((ids >= 0).astype(emb.dtype), segment_ids, n_bags)
        return s / jnp.maximum(cnt, 1.0)[:, None]
    if mode == "max":
        m = jax.ops.segment_max(jnp.where((ids >= 0)[:, None], emb, -jnp.inf),
                                segment_ids, n_bags)
        return jnp.where(jnp.isfinite(m), m, 0.0)
    raise ValueError(mode)
