"""Sort formulations that the TPU compiler turns around in seconds.

XLA's TPU backend compiles a sort in time that grows with the number of
sort operands and with float keys: described-chip compiles for a v5e of
a 2.4M-element ``jnp.lexsort((f32, i32))`` take about 270 s, a stable
``argsort`` of int32 keys about 35-50 s, and an unstable sort of one
packed int32 key with an index payload about 20 s. The build's
per-receiver groupings are such sorts, so they are written here as one
int32 key each:

  * ``packed_argsort`` — group by a small non-negative id, order within
    a group by a coarse int32 rank packed into the low bits. For the
    orderings that only sample or bound a buffer (turbosampling's
    random order, the inversion's overflow priority).
  * ``lex_argsort`` — the exact ``jnp.lexsort((minor, major))`` as two
    stable single-key argsorts (least significant key first). For the
    orderings that must stay exact (``compact_pairs``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sortable_bits(x: jax.Array) -> jax.Array:
    """Monotone int32 image of float32 values: ``a < b`` iff
    ``sortable_bits(a) < sortable_bits(b)`` (+-inf included; -0.0 sorts
    just below +0.0)."""
    i = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))


def lex_argsort(minor: jax.Array, major: jax.Array) -> jax.Array:
    """``jnp.lexsort((minor, major))`` for int32 keys: order by ``major``,
    then ``minor``, then position — two stable single-key sorts."""
    o1 = jnp.argsort(minor, stable=True)
    o2 = jnp.argsort(major[o1], stable=True)
    return o1[o2].astype(jnp.int32)


def packed_argsort(group: jax.Array, rank: jax.Array, n_groups: int):
    """Order by ``group`` (ids in ``[0, n_groups]``, the high bits of the
    key), then by the top bits of the int32 ``rank`` (the low bits left
    over; negative ranks, such as ``sortable_bits(-0.0)``, count as 0);
    ties fall in no set order. One single-key sort."""
    b = max(0, 31 - int(n_groups).bit_length())
    rank = jnp.maximum(rank, 0) >> (31 - b)
    key = (group.astype(jnp.int32) << b) | rank
    idx = jnp.arange(key.shape[0], dtype=jnp.int32)
    _, order = jax.lax.sort((key, idx), num_keys=1, is_stable=False)
    return order
