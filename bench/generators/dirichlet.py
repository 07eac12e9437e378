"""Dirichlet histograms: ``alpha = 1`` is RandHist-d, uniform on the
d-simplex; ``alpha < 1`` is Wiki-d, LDA-like topic histograms (Boytsov and
Nyberg, arXiv:1910.03534, section 3).

Every row is floored at ``EPS`` and renormalised.  Corpus rows and query
rows are independent draws of the same distribution, which is the paper's
random split of one collection into indexed points and held-out queries.
"""

import jax
import jax.numpy as jnp

EPS = 1e-6  # histogram floor


def histograms(key, n: int, d: int, alpha: float):
    x = jax.random.dirichlet(key, jnp.full((d,), alpha, jnp.float32), (n,))
    x = jnp.maximum(x, EPS)
    return x / jnp.sum(x, axis=-1, keepdims=True)


def make(key, n: int, pool: int, *, d: int, alpha: float):
    """(corpus (n, d), query pool (pool, d)) float32."""
    kx, kq = jax.random.split(key)
    return histograms(kx, n, int(d), float(alpha)), histograms(kq, pool,
                                                               int(d),
                                                               float(alpha))
