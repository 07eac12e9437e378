"""Core non-metric neighborhood-graph retrieval library (the paper's contribution)."""

from .distances import (
    Distance,
    apply_post,
    available_distances,
    get_distance,
    itakura_saito,
    kl_divergence,
    l2_squared,
    neg_inner_product,
    renyi_divergence,
)
from .symmetrize import (
    SYM_MODES,
    CombinedDistance,
    LearnedDistance,
    ReversedDistance,
    SymmetrizedDistance,
    ViewedDistance,
    calibrate_tau,
    get_learned_weights,
    learned_weights_fingerprint,
    linear_blend,
    register_learned_weights,
    symmetrized,
)
from .spec import (
    LEARNED_ARTIFACT_KIND,
    TUNED_ARTIFACT_KIND,
    Blend,
    DistancePolicy,
    Learned,
    MaxSym,
    RankBlend,
    RetrievalSpec,
    dominates,
    learned_artifact,
    load_learned_artifact,
    load_spec,
    load_tuned_artifact,
    pareto_frontier,
    tuned_artifact,
)
from .brute_force import ground_truth, knn_scan
from .beam_search import beam_search_impl, make_batched_searcher
from .batched_beam import (
    BatchBeamState,
    batched_beam_search,
    beam_step,
    make_step_searcher,
    seed_beams,
    select_entries,
)
from .scheduler import GraphView, SlotResult, SlotScheduler
from .distributed import (
    ShardedSlotScheduler,
    build_local_subgraphs,
    pad_to_shards,
    sharded_graph_search,
    sharded_knn_scan,
)
from .swgraph import build_swgraph
from .build_engine import build_sharded, build_swgraph_wave, reverse_edge_merge
from .nndescent import build_nndescent
from .online import OnlineIndex
from .filter_refine import filter_and_refine, kc_sweep, rerank
from .index import ANNIndex
from .autotune import Candidate, TuneResult, autotune, build_cost_proxy, default_axes
from .metric_learning import fit_mahalanobis_map, learn_mahalanobis, true_neighbor_ids
from .learned import LearnedResult, fit_construction_distance, mahalanobis_weights
from .metrics import recall_at_k, speedup_model
from .runtime_checks import (
    RecompileError,
    dispatch_cache_size,
    enable_strict_mode,
    recompile_guard,
    strict_mode_requested,
)
