"""Corpus and query pool of a cell, made on the device from the seed.

The configuration's ``data`` block names its generator,
``generators/<generator>.py`` (the benchmark's own copies of the
repository's synthetic twins of the paper's collections, so that a later
change to the program cannot change the data it is measured on), and gives
its parameters.  A generator's ``make(key, n, pool, **params)`` returns the
corpus and the query pool; it runs as one jitted call on the device.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from bench import lookup


def seed_key(seed: int):
    """A PRNG key from a whole number of any size: all 64 low bits count."""
    seed = int(seed) % 2**64
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """A NumPy generator for host-side draws (arrival times, query order)."""
    return np.random.default_rng([int(seed) % 2**64, stream])


def make_corpus(data: dict, n: int, pool: int, seed: int,
                root: str = lookup.ROOT):
    """(X (n, d), Q (pool, d)) float32 on the default device, in one call.

    ``data`` is the configuration's ``data`` block:
    ``{"generator": <name>, <the generator's parameters>}``.
    """
    gen = lookup.module("generators", data["generator"], root)
    params = {k: v for k, v in data.items() if k != "generator"}
    make = jax.jit(functools.partial(gen.make, n=int(n), pool=int(pool),
                                     **params))
    return jax.block_until_ready(make(seed_key(seed)))
