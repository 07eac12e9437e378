"""KL divergence, the corpus row ``x`` on the left:
d(x, q) = sum_j x_j (log x_j - log q_j)."""

import jax.numpy as jnp


def pair(x, q, xp):
    """d(x, q) over the last axis in the array module ``xp``: NumPy
    (float64, on the host) or ``jax.numpy`` (float32, on the device)."""
    return xp.sum(x * (xp.log(x) - xp.log(q)), axis=-1)


def terms(X, Q):
    """(left (N, d), right (B, d), combine) with
    combine(right @ left^T)[b, i] = d(X[i], Q[b])."""
    neg_entropy = jnp.sum(X * jnp.log(X), axis=-1)
    return X, -jnp.log(Q), lambda s: s + neg_entropy[None, :]
