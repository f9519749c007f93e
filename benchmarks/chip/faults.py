"""Faults planted under the timed path, for the test that sees
``correct`` come out false (tests/test_harness.py) and for readings on
the chip (``control.py --fault``). Each is a context manager that
patches the program for its duration.

  unchanged   the build's sampled iterations return their lists as they
              got them; the search's rounds leave the pool as its entry
              seeds filled it
  altered     one answer of each build or dispatch altered where it is
              produced: its first neighbour id moved to the next row
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patch(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _alter(idx, n):
    import jax.numpy as jnp
    return idx.at[0, 0].set(jnp.where(idx[0, 0] >= 0, (idx[0, 0] + 1) % n,
                                      idx[0, 0]))


def _modules():
    """The search and build modules (``repro.core`` re-exports functions
    under their names)."""
    import importlib
    return (importlib.import_module("repro.core.graph_search"),
            importlib.import_module("repro.core.nn_descent"))


@contextlib.contextmanager
def unchanged():
    import jax.numpy as jnp
    graph_search, nn_descent = _modules()
    orig = graph_search._search_block

    def iteration(key, x, x2, nl, cfg, qs=None):
        return nl, jnp.int32(0), jnp.int32(0)

    def block(x, x2, graph_idx, *args, **kw):
        # no neighbours to expand: every round leaves the pool as seeded
        return orig(x, x2, jnp.full_like(graph_idx, -1), *args, **kw)

    with _patch(nn_descent, "nn_descent_iteration", iteration), \
            _patch(graph_search, "_search_block", block):
        yield


@contextlib.contextmanager
def altered():
    import repro
    from repro.core import online
    build, search = repro.build_knn_graph, online.MutableKNNStore.search

    def build_altered(x, k=20, **kw):
        dist, idx, stats = build(x, k, **kw)
        return dist, _alter(idx, x.shape[0]), stats

    def search_altered(self, queries, **kw):
        dist, idx = search(self, queries, **kw)
        return dist, _alter(idx, self.n)

    with _patch(repro, "build_knn_graph", build_altered), \
            _patch(online.MutableKNNStore, "search", search_altered):
        yield


FAULTS = {"unchanged": unchanged, "altered": altered}
