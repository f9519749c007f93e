"""Metric generality via input-side reductions to squared l2.

The entire fused kernel family (the norm-expansion distance tiles, the
partial top-C select, the quantized mirror scoring) is written for ONE
metric: squared l2. That is not a restriction in practice, because the
two metrics embedding-retrieval workloads actually ask for both reduce
to l2 by transforming the INPUTS — so every kernel, every store layout,
and the whole two-stage precision machinery are reused unchanged:

  * **cosine** — row-normalize. On unit vectors
    ``|q - x|^2 = 2 - 2*cos(q, x)``, so l2 order IS descending-cosine
    order and ``cos = 1 - d2/2`` recovers the similarity exactly.
    Corpus rows are normalized once at build/insert; queries once per
    batch at the search boundary.

  * **mips** (maximum inner product) — the augmented-coordinate
    reduction (Bachrach et al., RecSys'14): pick ``M >= max_i |x_i|``,
    append ``sqrt(M^2 - |x|^2)`` to every corpus row and a literal 0 to
    every query. Then ``|q^ - x^|^2 = |q|^2 + M^2 - 2<q, x>`` — constant
    per query plus a constant, minus twice the inner product — so
    ascending l2 over the augmented vectors IS descending inner product,
    and ``ip = (|q|^2 + M^2 - d2) / 2`` recovers it exactly. The store
    carries ``M`` (``MutableKNNStore.mips_m``, echoed by persistence);
    inserted rows with ``|x| > M`` get their augmented coordinate
    clamped to 0 with a RuntimeWarning — their recovered inner products
    stay exact (the clamp only weakens their l2 ORDER consistency by
    the overshoot, it never corrupts other rows).

  * **l2** — the identity; the default; what the paper benchmarks.

The transforms are *input-side*: ``transform_corpus`` runs once where
rows enter a store (``MutableKNNStore.from_graph`` / ``knn_insert``,
``build_knn_graph``), ``transform_queries`` runs once per batch inside
``graph_search`` — downstream of both, the blocked kernels see plain
rows and plain squared-l2 and cannot tell the metric apart. The
quantized mirror quantizes the TRANSFORMED rows, so int8/bf16 two-stage
search works per metric for free; the router's k-means clusters the
transformed rows, so routed seeding does too.

Returned distances are always the transformed-space squared l2 —
monotone in the native metric, so ranking consumers (recall, knn-LM
softmax weighting) need no conversion; ``similarity_from_dist`` converts
when the caller wants the native cosine / inner-product values.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

METRICS = ("l2", "cosine", "mips")

_EPS = 1e-12   # zero-row guard: a zero row normalizes to zero, not NaN


def check_metric(metric: str) -> str:
    if metric not in METRICS:
        raise ValueError(f"unknown metric {metric!r}; expected one of "
                         f"{METRICS}")
    return metric


def normalize_rows(x: jax.Array) -> jax.Array:
    """Row-normalize to unit l2 norm (the cosine reduction). Zero rows
    stay zero (eps floor) instead of going NaN; rows that are ALREADY
    exactly unit norm divide by exactly 1.0, so pre-normalized data is
    bit-identical under the transform (tests/test_property.py pins
    this)."""
    x = x.astype(jnp.float32)
    n2 = jnp.sum(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.maximum(n2, _EPS))


def mips_max_norm(x: jax.Array) -> float:
    """The augmentation bound M: the max row norm of the corpus."""
    if x.shape[0] == 0:
        return 0.0
    return float(jnp.sqrt(jnp.max(jnp.sum(
        x.astype(jnp.float32) ** 2, axis=-1))))


def mips_augment(x: jax.Array, m: float) -> jax.Array:
    """Append the augmented coordinate ``sqrt(M^2 - |x|^2)`` per corpus
    row (d -> d+1). Rows with ``|x| > M`` (inserts that outgrow the
    build-time bound) clamp the coordinate to 0 with a RuntimeWarning:
    the recovered inner products stay exact, only those rows' l2 order
    degrades by the overshoot."""
    x = x.astype(jnp.float32)
    n2 = jnp.sum(x * x, axis=-1)
    slack = m * m - n2
    if x.shape[0] and not isinstance(x, jax.core.Tracer):
        over = int(jnp.sum(slack < -1e-6 * max(m * m, 1.0)))
        if over:
            warnings.warn(
                f"mips insert: {over} row(s) exceed the store's "
                f"augmentation bound M={m:.4g}; their augmented "
                "coordinate is clamped to 0 (inner products stay exact, "
                "their traversal order degrades by the overshoot)",
                RuntimeWarning, stacklevel=3)
    aug = jnp.sqrt(jnp.maximum(slack, 0.0))
    return jnp.concatenate([x, aug[:, None]], axis=-1)


def transform_corpus(
    x: jax.Array, metric: str, *, mips_m: float | None = None
) -> tuple[jax.Array, float]:
    """Metric reduction of corpus rows (run ONCE where rows enter a
    store or a build — the transforms are not idempotent for mips).
    Returns ``(x_t, mips_m)``; ``mips_m`` is 0.0 except under mips,
    where it is the augmentation bound used (pass the store's bound for
    inserts so the batch shares the build-time M)."""
    check_metric(metric)
    if metric == "l2":
        return x.astype(jnp.float32), 0.0
    if metric == "cosine":
        return normalize_rows(x), 0.0
    m = mips_max_norm(x) if mips_m is None else mips_m
    return mips_augment(x, m), m


def transform_queries(q: jax.Array, metric: str) -> jax.Array:
    """Metric reduction of query rows: cosine normalizes (idempotent up
    to fp — exactly idempotent on unit rows), mips appends the literal 0
    coordinate (d -> d+1; no bound needed on the query side)."""
    check_metric(metric)
    q = q.astype(jnp.float32)
    if metric == "l2":
        return q
    if metric == "cosine":
        return normalize_rows(q)
    return jnp.concatenate(
        [q, jnp.zeros((*q.shape[:-1], 1), jnp.float32)], axis=-1)


def similarity_from_dist(
    dist: jax.Array,
    metric: str,
    *,
    q2: jax.Array | None = None,
    mips_m: float = 0.0,
) -> jax.Array:
    """Convert transformed-space squared-l2 distances back to the native
    similarity: cosine ``1 - d2/2``; mips ``(|q|^2 + M^2 - d2) / 2``
    (``q2`` = squared norms of the RAW queries, broadcast against
    ``dist``); l2 returns the distances unchanged (it has no similarity
    form). Empty slots (+inf distance) come back -inf similarity, so
    descending-similarity order keeps them last."""
    check_metric(metric)
    if metric == "l2":
        return dist
    if metric == "cosine":
        sim = 1.0 - dist / 2.0
    else:
        if q2 is None:
            raise ValueError("mips similarity needs q2 (raw-query "
                             "squared norms)")
        q2 = jnp.asarray(q2, jnp.float32)
        if q2.ndim == dist.ndim - 1:
            q2 = q2[..., None]
        sim = (q2 + mips_m * mips_m - dist) / 2.0
    return jnp.where(jnp.isfinite(dist), sim, -jnp.inf)


def transformed_dim(d: int, metric: str) -> int:
    """Logical feature dim after the reduction (mips appends one)."""
    check_metric(metric)
    return d + 1 if metric == "mips" else d


def filter_frac(filter_ids: jax.Array | None, n: int | None = None) -> float:
    """Fraction of corpus rows a filter mask admits (1.0 = unfiltered).
    Accepts the (n,) shared or (q, n) per-query layouts of
    ``graph_search(filter_ids=...)``; the stat serving/bench lanes
    report next to recall (selective filters cost recall — see
    docs/METRICS.md)."""
    if filter_ids is None:
        return 1.0
    return float(jnp.mean(jnp.asarray(filter_ids, bool)))


def exact_sq_l2(q: jax.Array, rows: jax.Array, ids: jax.Array):
    """Difference-form squared l2 of each query against its gathered
    rows: q (..., dp), rows (..., W, dp), ids (..., W) -> (..., W), +inf
    where ``ids`` < 0. Summing (r - q)^2 has none of the norm
    expansion's cancellation, so near neighbors get exact fp32
    distances — the form every returned distance is computed in."""
    diff = rows - q[..., None, :]
    return jnp.where(ids >= 0, jnp.sum(diff * diff, axis=-1), jnp.inf)
