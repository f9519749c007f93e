"""Seeded corpora at the shapes of the configurations' source data sets.

Copied from ``src/repro/core/datasets.py`` (``clustered``, ``mnist_like``,
``audio_like``) so that the benchmark's inputs cannot change with the
program. One change: the copy drops the final
``jax.random.permutation`` of the rows. Cluster labels are drawn i.i.d.
per row, so the rows are already in no cluster order, and the 70,000-row
permutation cost a 21.1 s cold compile on a v5e (PERF.md). The
distribution is the same.

Each corpus is one jitted program run on the device, from the seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one past 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _clustered(key, n: int, d: int, c: int, sep: float) -> jax.Array:
    """c Gaussian clusters with means ``sep`` * N(0, I), unit covariance."""
    k1, k2, k3 = jax.random.split(key, 3)
    means = sep * jax.random.normal(k1, (c, d), jnp.float32)
    which = jax.random.randint(k2, (n,), 0, c)
    return means[which] + jax.random.normal(k3, (n, d), jnp.float32)


@functools.partial(jax.jit, static_argnames=("n", "d", "clusters", "sep",
                                             "transform"))
def corpus(key, *, n: int, d: int, clusters: int, sep: float,
           transform: str) -> jax.Array:
    """(n, d) float32 rows. ``transform`` "mnist" maps the rows to
    clip(|x| / 4, 0, 1), like pixel intensities (``mnist_like``); "none"
    keeps them (``audio_like``)."""
    x = _clustered(key, n, d, clusters, sep)
    if transform == "mnist":
        return jnp.clip(jnp.abs(x) * 0.25, 0.0, 1.0)
    if transform == "none":
        return x
    raise ValueError(f"unknown transform {transform!r}")


def make_corpus(data: dict, seed: int) -> jax.Array:
    """The corpus a configuration's ``data`` block describes, from ``seed``."""
    return corpus(seed_key(seed), n=data["rows"], d=data["dim"],
                  clusters=data["clusters"], sep=float(data["sep"]),
                  transform=data["transform"])
