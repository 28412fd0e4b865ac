"""Seeded inputs: keys from ``--seed`` and Gaussian-mixture datasets.

``make_dataset`` follows ``benchmarks/common.py:make_dataset`` (mixture
centres ×2.0, within-class noise ×0.7, at least 8 centres, per-feature
standardisation, labels folded onto the class count), drawn with
``jax.random`` on the device in one jitted call so that a 2^19-row set costs
no host time.  Each configuration file states its dataset's ``d`` and
``classes``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including ones wider than 32
    bits: the low word seeds the key and the high word is folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def derive(key: jax.Array, *path: int) -> jax.Array:
    """Independent sub-keys by a path of small integers."""
    for p in path:
        key = jax.random.fold_in(key, int(p))
    return key


@functools.partial(jax.jit, static_argnames=("n", "d", "classes"))
def _mixture(key, n: int, d: int, classes: int):
    k_eff = max(classes, 8)
    kc, kl, kn = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (k_eff, d), jnp.float32) * 2.0
    labels = jax.random.randint(kl, (n,), 0, k_eff)
    X = centers[labels] + jax.random.normal(kn, (n, d), jnp.float32) * 0.7
    X = (X - X.mean(0)) / (X.std(0) + 1e-9)
    return X, labels % max(classes, 2)


def make_dataset(config: dict, key: jax.Array, n: int):
    """(X (n, d) float32 standardised, labels (n,)) on the default device,
    with the configuration's ``d`` and ``classes``."""
    return _mixture(key, n=int(n), d=int(config["d"]),
                    classes=int(config["classes"]))
