"""Memory-layout helpers (paper §3.3 "mem-align", TPU form).

The paper pads/aligns points to 256-bit AVX2 boundaries. The TPU analog is
(8, 128) VREG tiling and 128-lane MXU alignment: we pad the feature axis to
a multiple of 128 and the point axis to a multiple of 8 so every gather and
matmul tile is layout-native. Zero padding is exact for squared-l2.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


LANE = 128
SUBLANE = 8


def ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pad_features(x: jax.Array, lane: int = LANE) -> jax.Array:
    """Pad (n, d) -> (n, ceil(d/lane)*lane) with zeros (exact for sq-l2)."""
    n, d = x.shape
    dp = ceil_to(d, lane)
    if dp == d:
        return x
    return jnp.pad(x, ((0, 0), (0, dp - d)))


def pad_points(x: jax.Array, mult: int = SUBLANE) -> tuple[jax.Array, int]:
    """Pad point axis to a multiple; returns (padded, original_n).

    Padded rows are set to +large coordinates so they are never anyone's
    nearest neighbor while keeping distances finite (no inf propagation
    through the MXU path).
    """
    n, d = x.shape
    np_ = ceil_to(n, mult)
    if np_ == n:
        return x, n
    fill = jnp.full((np_ - n, d), 1e6, dtype=x.dtype)
    return jnp.concatenate([x, fill], axis=0), n


def flatten_rows(a: jax.Array, r: int = 1024) -> jax.Array:
    """``a.reshape(-1)`` for an (n, ...) array, ``r`` rows at a time.

    A row-major flatten of a narrow (n, w) array (w not a multiple of
    128 lanes) is a relayout that the TPU compiler takes time linear in
    n over: 28 s at 65,536 x 20 for a v5e. In r-row pieces the same
    flatten compiles in under a second."""
    n = a.shape[0]
    if n <= r or a[0].size == 1:
        return a.reshape(-1)
    npad = ceil_to(n, r)
    a = jnp.pad(a, [(0, npad - n)] + [(0, 0)] * (a.ndim - 1))
    out = jax.lax.map(lambda c: c.reshape(-1),
                      a.reshape((npad // r, r) + a.shape[1:]))
    return out.reshape(-1)[: a[0].size * n]


def chunked_rows(fn, args, r: int):
    """``fn`` over the leading axis of ``args`` in chunks of ``r`` rows
    (the tail padded with copies of row 0 and dropped): bounds the
    gathers inside ``fn`` to ``r`` rows at a time."""
    n = args[0].shape[0]
    npad = ((n + r - 1) // r) * r
    padded = tuple(
        jnp.concatenate([a, jnp.broadcast_to(a[:1], (npad - n,) + a.shape[1:])])
        .reshape((npad // r, r) + a.shape[1:]) for a in args)
    out = jax.lax.map(fn, padded)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((npad,) + o.shape[2:])[:n], out)
