"""Ahead-of-time compiles for a described TPU v5e chip (no chip attached).

The chip's compiler refuses what interpret mode accepts (block shapes off
the (8, 128) tiling, sub-tile HBM row slices, lane broadcasts), so the
main-path kernels and one whole search step are compiled here at the
widths the engines use and at a deployment's corpus size (n = 10^6).  The
topology is described inside a fixture: describing it loads the TPU
compiler's library, which one process at a time may hold.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.batched_beam import make_step_searcher
from repro.core.distances import get_distance
from repro.kernels import ops
from repro.kernels.distance_matrix import distance_matrix
from repro.kernels.frontier_gather import LANES, frontier_scores

N = 1_000_000
WIDTHS = [8, 32, 128, 256, 2048]
# M_max = 2*NN = 30 at frontier 1, the serve default frontier 4, a
# scheduler frontier of 12, and NN-descent's K*K + K + n_random join
RS = [30, 120, 360, 15 * 15 + 15 + 8]
KL = get_distance("kl")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the installed libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer every engine onto its kernel branch (one backend check)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _rows(sharding, n, m):
    """The kernel's (n * K, 128) row view of an (n, m') corpus."""
    return _shape(sharding, (n * -(-m // LANES), LANES))


@pytest.mark.parametrize("R", RS)
@pytest.mark.parametrize("m", WIDTHS)
def test_frontier_gather_compiles(one_chip, m, R):
    f = jax.jit(lambda ids, q, qb, x, xb: frontier_scores(
        ids, q, qb, x, xb, KL.post_id, KL.c0, interpret=False))
    c = f.lower(_shape(one_chip, (64, R), jnp.int32), _shape(one_chip, (64, m)),
                _shape(one_chip, (64,)), _rows(one_chip, N, m),
                _shape(one_chip, (N,))).compile()
    assert "tpu_custom_call" in c.as_text()


def test_frontier_gather_compiles_nndescent_join(one_chip):
    """The whole-corpus candidate join: every row a query, B = n."""
    R = RS[-1]
    f = jax.jit(lambda ids, q, qb, x, xb: frontier_scores(
        ids, q, qb, x, xb, KL.post_id, KL.c0, interpret=False))
    c = f.lower(_shape(one_chip, (N, R), jnp.int32), _shape(one_chip, (N, 128)),
                _shape(one_chip, (N,)), _rows(one_chip, N, 128),
                _shape(one_chip, (N,))).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("m", WIDTHS)
def test_distance_matrix_compiles(one_chip, m):
    f = jax.jit(lambda q, x, qb, xb: distance_matrix(
        q, x, qb, xb, KL.post_id, KL.c0, interpret=False))
    c = f.lower(_shape(one_chip, (256, m)), _shape(one_chip, (8192, m)),
                _shape(one_chip, (256,)), _shape(one_chip, (8192,))).compile()
    assert "tpu_custom_call" in c.as_text()


def test_search_step_compiles_to_kernel(one_chip, on_tpu):
    """One whole jitted batch-searcher step at n = 10^6, m' = 128."""
    m, M = 128, 30
    tiny_x = np.full((8, m), 1.0 / m, np.float32)
    search = make_step_searcher(KL, np.zeros((8, M), np.int32), tiny_x, 128, 10,
                                entries=np.arange(4, dtype=np.int32), frontier=4)
    consts = {"rep": _shape(one_chip, (N, m)), "bias": _shape(one_chip, (N,))}
    c = search.func.lower(consts, _rows(one_chip, N, m),
                          _shape(one_chip, (N, M), jnp.int32),
                          _shape(one_chip, (4,), jnp.int32),
                          _shape(one_chip, (64, m))).compile()
    assert "tpu_custom_call" in c.as_text()
    # the corpus reaches the step as an argument, not a baked-in constant
    assert c.memory_analysis().argument_size_in_bytes >= N * m * 4


@pytest.mark.parametrize("m,n", [(8, N), (2048, 250_000)])
def test_search_step_lays_out_no_corpus_copy(one_chip, on_tpu, m, n):
    """Off m' = 128 the kernel's row view is a padded (m' < 128) or
    relaid (m' > 128) copy of the corpus: the searcher makes it once, so
    the compiled step takes it as an argument and copies nothing of
    corpus size — no pad or copy of it inside the beam loop."""
    M = 30
    tiny_x = np.full((8, m), 1.0 / m, np.float32)
    search = make_step_searcher(KL, np.zeros((8, M), np.int32), tiny_x, 128, 10,
                                entries=np.arange(4, dtype=np.int32), frontier=4)
    consts = {"rep": _shape(one_chip, (n, m)), "bias": _shape(one_chip, (n,))}
    rows = _rows(one_chip, n, m)
    c = search.func.lower(consts, rows, _shape(one_chip, (n, M), jnp.int32),
                          _shape(one_chip, (4,), jnp.int32),
                          _shape(one_chip, (64, m))).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text
    made = re.findall(r" = f32\[{},{}\]\{{[^}}]*\}} ([\w-]+)\(".format(*rows.shape),
                      text)
    assert made and set(made) <= {"parameter", "get-tuple-element"}, made
    assert c.memory_analysis().temp_size_in_bytes < rows.size * 4


@pytest.mark.parametrize("use_pallas", [None, True])
def test_symmetrized_build_compiles(one_chip, on_tpu, use_pallas):
    """The wave build under KL-avg, lowered to one Distance (m' = 256), at
    n = 250,000 and the wiki-128 knobs: the whole wave scan compiles for the
    chip.  By default it scores with XLA's gather (the kernel takes only
    reps of one 128-lane row); forced, with the gather kernel at K = 2 row
    DMAs a candidate."""
    from repro.core.build_engine import wave_insert, wave_knobs
    from repro.core.spec import DistancePolicy

    n = 250_000
    dist = DistancePolicy.parse("avg").bind(KL)
    consts = {"rep": _shape(one_chip, (n, 256)), "bias": _shape(one_chip, (n,))}
    c = wave_insert.lower(dist, consts, consts, use_pallas=use_pallas,
                          **wave_knobs(n, NN=15, ef_construction=100, wave=128)).compile()
    assert ("tpu_custom_call" in c.as_text()) is bool(use_pallas)
