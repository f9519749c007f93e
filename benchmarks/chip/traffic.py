"""The one traffic generator and window runner of the benchmark.

A traffic mix is a data file, ``traffic/<mix>.json``; its ``kind`` picks
one of the runners below and the rest of it are their parameters:

  build    NN-Descent builds of the whole corpus, back to back, each with
           a fresh key from the seed (``build_knn_graph``)
  batch    batch retrieval: ``batch``-query calls of
           ``MutableKNNStore.search`` back to back, ``ahead_s`` seconds of
           them dispatched ahead of the one waited for

Each runner makes its inputs from the seed (``setup``), warms every shape
its window will use, runs the window (``window``), releases the program's
state (``release``) and then compares what the window produced with the
plain reference (``check``). ``record`` holds the host spans and the
program's counters that the per-layer readers take.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import check, data

clock = time.perf_counter


def keep_first(into: list, also=None):
    """A ``build_knn_graph`` callback that copies the lists after the
    first sampled iteration to the host, before the reorder renumbers the
    rows: distances as the join scored them, before any re-rank. ``also``
    is called with every iteration's arguments."""
    def callback(it, upd, nl):
        if it == 0:
            into.append((np.asarray(nl.dist), np.asarray(nl.idx)))
        if also is not None:
            also(it, upd, nl)
    return callback


def join_err(exact, x, first: list) -> float:
    """``join_dist_err`` (check.py) over the first-iteration lists of
    every build in ``first``."""
    errs = []
    for dist, idx in first:
        pair = exact.pair(x, x, np.clip(idx, 0, None))
        errs.append(check.pair_err(dist, idx, pair, n=x.shape[0]))
    return max(errs)


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------

class Build:
    """NN-Descent builds of the whole corpus, back to back."""

    def __init__(self, mix: dict, config: dict, seed: int):
        self.mix, self.config, self.seed = mix, config, seed
        self.record: dict = {"builds": [], "traced_builds": []}
        # (rows, dist, idx) of each build; rows None = every row
        self.answers: list[tuple] = []
        self.first: list[tuple] = []            # keep_first, window builds

    def _build(self, i: int, tracer=None):
        """Build ``i`` (0 is the warm-up). The host clock is read at each
        iteration's end (``build_knn_graph``'s callback, after its host
        sync); with a ``tracer``, the profiler runs from the end of the
        second iteration to the end of the build: the part after the
        greedy reorder, whose single n*k-step device loop would write
        about ten million trace events. Window builds keep their first
        iteration's lists (``keep_first``)."""
        from repro import build_knn_graph
        from repro.core.nn_descent import DescentConfig
        k = self.config["k"]
        cfg = DescentConfig(**self.config["descent"])
        key = jax.random.fold_in(data.seed_key(self.seed), i)
        marks = [clock()]

        def mark(it, upd, nl):
            marks.append(clock())
            if tracer is not None and it == 1:
                tracer.start()

        first = self.first if i > 0 else []
        dist, idx, stats = build_knn_graph(self.x, k, cfg=cfg, key=key,
                                           callback=keep_first(first, mark))
        jax.block_until_ready((dist, idx))
        if tracer is not None:
            tracer.stop()
        return dist, idx, stats, marks

    def inputs(self) -> None:
        """The corpus and the rows whose lists are checked, from the seed."""
        self.x = data.make_corpus(self.config["data"], self.seed)
        self.x.block_until_ready()
        n = self.x.shape[0]
        rng = np.random.default_rng(self.seed)
        self.sample = np.sort(rng.choice(n, min(self.mix["sample_rows"], n),
                                         replace=False))

    def setup(self) -> None:
        self.inputs()
        self._build(0)                           # warm-up: every program

    def window(self, seconds: float, tracer=None) -> None:
        """Whole builds back to back while the next one would still end
        within ``seconds`` (at least one). With a ``tracer``, the first
        build is traced after its reorder (``_build``)."""
        n = self.x.shape[0]
        t0 = clock()
        i = 1
        while True:
            dist, idx, stats, marks = self._build(i, tracer if i == 1
                                                  else None)
            te = clock()
            row = {"seconds": te - marks[0], "rows": n, "iters": stats.iters,
                   "dist_evals": stats.dist_evals,
                   "iteration_ends": [t - marks[0] for t in marks[1:]]}
            self.record["builds"].append(row)
            if tracer is not None and i == 1:
                # iterations 3..iters ran under the profiler
                row["profiler_s"] = tracer.overhead_s
                self.record["traced_builds"].append(
                    dict(row, traced_iters=stats.iters - 2))
            self.answers.append((None, np.asarray(dist), np.asarray(idx)))
            del dist, idx
            i += 1
            if te - t0 + row["seconds"] > seconds:
                break           # the next build would not end in time
        self.elapsed = te - t0

    def end_to_end(self) -> dict:
        rows = sum(b["rows"] for b in self.record["builds"])
        return {"build_rows_per_s": rows / self.elapsed}

    def attempted(self) -> tuple[int, int]:
        return len(self.answers), 0

    def release(self) -> None:
        pass

    def check(self, exact) -> tuple[dict, float]:
        """Every build's lists: ``dist_err`` and ``invalid`` over all rows,
        ``dist_excess`` and recall over the sampled rows (the exact k-NN
        of every row would take longer than the window);
        ``join_dist_err`` over every row of each first-iteration list."""
        k, n = self.config["k"], self.x.shape[0]
        ref_d, ref_i = exact.knn(self.x[self.sample], self.x, k,
                                 self_ids=jnp.asarray(self.sample, jnp.int32))
        numbers, recalls = [], []
        for rows, dist, idx in self.answers:
            xr = self.x if rows is None else self.x[rows]
            pair = exact.pair(xr, self.x, np.clip(idx, 0, None))
            nums, rec = check.compare(
                dist, idx, pair, ref_d, ref_i, n=n,
                self_ids=np.arange(n) if rows is None else rows,
                ref_rows=self.sample if rows is None else None)
            numbers.append(nums)
            recalls.append(rec)
        worst = check.worst(numbers)
        worst["join_dist_err"] = join_err(exact, self.x, self.first)
        return worst, float(np.mean(recalls))


# ---------------------------------------------------------------------------
# batch retrieval: search calls dispatched ahead over one store
# ---------------------------------------------------------------------------

class Batch:
    """Offline batch retrieval over a served index. Set-up splits the
    corpus into base rows and held-out queries, builds the base once and
    puts it in a ``MutableKNNStore`` with its router; the window sends
    ``batch``-query calls of ``store.search`` back to back, cycling over
    the queries, and keeps ``ahead_s`` seconds of calls dispatched ahead
    of the one whose answers it reads, so that the chip stays fed while
    the host stands still."""

    def __init__(self, mix: dict, config: dict, seed: int):
        self.mix, self.config, self.seed = mix, config, seed
        self.record: dict = {"dispatches": [], "traced_dispatches": []}
        self.first: list[tuple] = []
        self.answers: list[tuple] = []          # (query rows, dist, idx)

    def inputs(self) -> None:
        """The base rows and the held-out queries, from the seed."""
        split = self.config["search_split"]
        x = data.make_corpus(self.config["data"], self.seed)
        self.base = x[:split["base"]]
        self.queries = x[split["base"]:split["base"] + split["queries"]]
        self.sent = 0

    def setup(self) -> None:
        from repro import MutableKNNStore, build_knn_graph
        from repro.core.graph_search import SearchConfig
        from repro.core.nn_descent import DescentConfig
        from repro.core.online import OnlineConfig
        from repro.core.router import RouterConfig

        self.inputs()
        k = self.config["k"]
        dist, idx, _ = build_knn_graph(
            self.base, k, cfg=DescentConfig(**self.config["descent"]),
            key=jax.random.fold_in(data.seed_key(self.seed), 0),
            callback=keep_first(self.first))
        fields = {f.name for f in dataclasses.fields(SearchConfig)}
        self.scfg = SearchConfig(**{k: v for k, v in
                                    self.config["search"].items()
                                    if k in fields})
        self.store = MutableKNNStore.from_graph(
            self.base, dist, idx, cfg=OnlineConfig(
                router=RouterConfig(), precision=self.scfg.precision))
        del dist, idx
        self.key = jax.random.fold_in(data.seed_key(self.seed), 1)
        # the first call compiles; the second times one call on the warm
        # programs, which sets how many are kept ahead
        jax.block_until_ready(self._call(0)[1:])
        ts = clock()
        jax.block_until_ready(self._call(1)[1:])
        self.ahead = max(1, math.ceil(self.mix["ahead_s"] / (clock() - ts)))
        self.record["ahead_calls"] = self.ahead

    def _call(self, b: int):
        """Search call ``b``: its query rows and the (dist, idx) it will
        return, dispatched and not waited for."""
        mb, nq = self.mix["batch"], self.queries.shape[0]
        rows = np.arange(b * mb, (b + 1) * mb) % nq
        q = jnp.take(self.queries, jnp.asarray(rows, jnp.int32), axis=0)
        dist, idx = self.store.search(q, k_out=self.config["search"]["k_out"],
                                      key=self.key, cfg=self.scfg)
        return rows, dist, idx

    def window(self, seconds: float, tracer=None) -> None:
        """Calls sent while ``seconds`` last, ``ahead`` in flight past the
        one waited for; at the close nothing more is sent, every call sent
        is waited for, and the clock is read after that wait: all of that
        work counts, over all of that time."""
        t0 = clock()
        tracing = tracer is not None
        if tracing:
            tracer.start()
        flight: collections.deque = collections.deque()
        b = 0
        while clock() - t0 < seconds:
            flight.append(self._call(b))
            b += 1
            if tracing:
                self.record["traced_dispatches"].append(b - 1)
            if len(flight) > self.ahead:
                self._answer(flight.popleft(), t0)
            if tracing and clock() - t0 >= self.mix["trace_seconds"]:
                tracer.stop()
                tracing = False
        while flight:
            self._answer(flight.popleft(), t0)
        te = clock()
        if tracing:
            tracer.stop()
        self.elapsed = te - t0
        self.sent = b * self.mix["batch"]

    def _answer(self, call, t0: float) -> None:
        rows, dist, idx = call
        self.answers.append((rows, np.asarray(dist), np.asarray(idx)))
        self.record["dispatches"].append(
            {"queries": len(rows), "done_at": clock() - t0})

    def end_to_end(self) -> dict:
        return {"search_qps": self.sent / self.elapsed}

    def attempted(self) -> tuple[int, int]:
        return self.sent, self.sent - sum(len(r) for r, _, _ in self.answers)

    def release(self) -> None:
        del self.store

    def check(self, exact) -> tuple[dict, float]:
        """Every answer against the exact k_out nearest of its query;
        ``join_dist_err`` of the set-up build's first iteration."""
        k_out = self.config["search"]["k_out"]
        nums = {"missing": float(self.attempted()[1]),
                "join_dist_err": join_err(exact, self.base, self.first)}
        if not self.answers:
            return nums, 0.0
        qi = np.concatenate([r for r, _, _ in self.answers])
        used = np.unique(qi)
        nq = self.queries.shape[0]
        ref_d = np.full((nq, k_out), np.inf, np.float32)
        ref_i = np.full((nq, k_out), -1, np.int32)
        ref_d[used], ref_i[used] = exact.knn(self.queries[used], self.base,
                                             k_out)
        dist = np.concatenate([d for _, d, _ in self.answers])
        idx = np.concatenate([i for _, _, i in self.answers])
        pair = exact.pair(self.queries[qi], self.base, np.clip(idx, 0, None))
        found, rec = check.compare(dist, idx, pair, ref_d[qi], ref_i[qi],
                                   n=self.base.shape[0])
        nums.update(found)
        return nums, rec


KINDS = {"build": Build, "batch": Batch}


def runner(mix: dict, config: dict, seed: int):
    """The runner of this mix's ``kind``."""
    if mix["kind"] not in KINDS:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    return KINDS[mix["kind"]](mix, config, seed)

