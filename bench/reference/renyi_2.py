"""Renyi divergence of order alpha = 2, the corpus row ``x`` on the left:
d(x, q) = log(sum_j x_j^a q_j^(1-a)) / (a - 1)."""

import jax.numpy as jnp

ALPHA = 2.0


def pair(x, q, xp):
    """d(x, q) over the last axis in the array module ``xp``: NumPy
    (float64, on the host) or ``jax.numpy`` (float32, on the device)."""
    a = ALPHA
    return xp.log(xp.sum(x**a * q ** (1.0 - a), axis=-1)) / (a - 1.0)


def terms(X, Q):
    """(left (N, d), right (B, d), combine) with
    combine(right @ left^T)[b, i] = d(X[i], Q[b])."""
    a = ALPHA
    return X**a, Q ** (1.0 - a), lambda s: jnp.log(s) / (a - 1.0)
