"""Pallas TPU kernels for the distance hot path (DESIGN.md SS2.1-2.2).

distance_matrix: MXU-tiled brute-force/construction block (compute-bound)
frontier_gather: per-query DMA row gather + one MXU contraction for the
                 batched beam engine's (B, frontier*M) lock-step expansion
ops:             jitted wrappers (interpret off-TPU, compiled on TPU)
ref:             pure-jnp oracles every kernel is tested against
"""

from .ops import beam_gather_scores, frontier_gather_scores, query_distance_matrix
