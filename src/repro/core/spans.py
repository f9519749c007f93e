"""Host spans on the served search path, recorded into the JAX profiler's
own trace, and the named scopes of the fused search program.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``repro/<name>``: it is written only while the profiler runs, and costs
about a microsecond otherwise. Its arguments are host integers only; a
span must never read a device value, which would wait for the device.
Inside traced code (a ``shard_map`` body) a span runs at trace time
alone and costs nothing when the program runs.

``SPANS`` is the table of every span in the program (a test holds the
code to it): its layer, the span it nests in on a store's search, and
what reads it. The benchmark's trace reduction
(``benchmarks/chip/attribution.py``) puts each idle interval of the
device down to the innermost span over it, in ``idle_s`` per span name,
and reads the metrics named here from that.

``SCOPES`` names the ``jax.named_scope`` phases of
``graph_search._search_block``. A scope changes only the ``op_name``
metadata of the ops it holds, not the computation; the chip trace
carries that name in each device op's ``tf_op``, so the reduction adds
up device time per phase, whatever the compiler numbers its fusions.
"""
from __future__ import annotations

import jax

PREFIX = "repro/"

# name -> (layer, parent span or None, the metric that reads it)
SPANS = {
    "store.search": ("store", None, "idle_program.search"),
    "search.admit": ("search", "store.search", "idle_s"),
    "search.route": ("router", "store.search", "idle_route.search"),
    "search.dispatch": ("search", "store.search", "idle_s"),
}


def span(name: str, **args: int) -> jax.profiler.TraceAnnotation:
    """The host span ``repro/<name>``, for a ``name`` of ``SPANS``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


# phase of _search_block -> what it holds (the reduction reads each one's
# device seconds; gather and merge also as shares of the program)
SCOPES = {
    "seed": "entry scoring and the first merge",
    "select": "the top-E unexpanded pool slots, and the loop's test",
    "gather": "adjacency rows, alive/filter masks, candidate rows, norms",
    "score": "the candidate distance tile (knn_search_dists, q8, bf16)",
    "merge": "the pool-k-th prefilter select and the pool insert",
    "rerank": "the exact fp32 re-rank of the final pool and its select",
}
