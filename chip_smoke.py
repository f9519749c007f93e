#!/usr/bin/env python3
"""Chip smoke test: the retrieval main path on TPU, judged by
chip-versus-reference parity.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --four-chips  # the routed sharded path, 4 chips

One chip. The deployment is the source paper's MNIST shape: 60,000 base
rows and 10,000 queries at d=784, squared l2, k=20, drawn from
``datasets.mnist_like`` with a fixed seed. The path is the one a user
calls: ``build_knn_graph`` -> ``MutableKNNStore`` with the default router
-> ``RetrievalScheduler`` -> fused ``graph_search`` -> kernels. Phases:

  (a) build   the graph; recall@20 on 1,000 sampled rows
  (b) serve   the 10,000 queries through the scheduler, 1,000 in the
              interactive lane and 9,000 in the batch lane, over the
              graph (a) built with backend="auto"; recall@10
  (c) mutate  insert 1,000 fresh rows, delete 1,000, search again
  (d) int8    one int8-scored batch of 1,024 queries

Four chips. The routed sharded path: ``build_knn_graph_sharded`` on a
4-device mesh (compared with ``build_knn_graph`` of the same corpus on
device 0), then ``graph_search_sharded`` over the shard-local graph,
replicated (route_p=0) and routed (route_p=2). The corpus is 131,072
rows at SIFT-128 width, 32,768 per chip, in whole equal-size clusters
per shard.

Every phase runs twice with the same keys and inputs: backend="auto"
(compiled Pallas kernels on TPU) and backend="ref" (the XLA-compiled jnp
oracles). Recall and distance errors are taken against an exact
reference computed here — fp32 difference form ||q - x||^2 over chunks,
sharing no code with either run. A check that fails, a phase that
raises, or a RuntimeWarning from this repository exits non-zero without
the last line. So does a run where JAX sees no TPU, or one outside a
checkout. Times are first calls (compilation included): bring-up
readings, not performance numbers.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when that is set,
and ``<repo>/.jax_cache`` otherwise.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import warnings

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

K = 20            # graph degree
K_OUT = 10        # search results per query
RTOL = 1e-4       # returned distance vs the exact reference


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


# ---------------------------------------------------------------------------
# the exact reference: difference form, chunked, no code shared with repro
# ---------------------------------------------------------------------------

def _exact_fns():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("k", "m"))
    def block(qc, qid, corpus, valid, *, k, m):
        """Exact top-k of a query chunk over ``corpus`` in chunks of m
        rows: (b, d) -> (dist (b, k) ascending, idx (b, k))."""
        n, d = corpus.shape
        pad = (-n) % m
        xs = jnp.pad(corpus, ((0, pad), (0, 0))).reshape(-1, m, d)
        vs = jnp.pad(valid, (0, pad)).reshape(-1, m)
        ids = jnp.arange(n + pad, dtype=jnp.int32).reshape(-1, m)

        def step(carry, c):
            bd, bi = carry
            xc, vc, ic = c
            diff = qc[:, None, :] - xc[None, :, :]
            dd = jnp.sum(diff * diff, axis=-1)
            dd = jnp.where(vc[None, :] & (ic[None, :] != qid[:, None]),
                           dd, jnp.inf)
            ad = jnp.concatenate([bd, dd], axis=1)
            ai = jnp.concatenate(
                [bi, jnp.broadcast_to(ic[None, :], dd.shape)], axis=1)
            neg, pos = jax.lax.top_k(-ad, k)
            return (-neg, jnp.take_along_axis(ai, pos, axis=1)), None

        b = qc.shape[0]
        init = (jnp.full((b, k), jnp.inf, jnp.float32),
                jnp.full((b, k), -1, jnp.int32))
        (dist, idx), _ = jax.lax.scan(step, init, (xs, vs, ids))
        return dist, idx

    @jax.jit
    def pair(q, corpus, ids):
        """Exact distances of each query to its listed rows."""
        rows = corpus[jnp.clip(ids, 0, corpus.shape[0] - 1)]
        diff = rows - q[:, None, :]
        return jnp.sum(diff * diff, axis=-1)

    return block, pair


class Exact:
    """Exact k-NN and pair distances in fp32 difference form."""

    def __init__(self):
        import jax
        self.jax = jax
        self._block, self._pair = _exact_fns()

    def knn(self, queries, corpus, k, *, valid=None, self_ids=None,
            chunk=128, m=2048):
        import jax.numpy as jnp
        import numpy as np
        nq = queries.shape[0]
        if valid is None:
            valid = jnp.ones((corpus.shape[0],), bool)
        if self_ids is None:
            self_ids = jnp.full((nq,), -1, jnp.int32)
        out = []
        with self.jax.default_matmul_precision("highest"):
            for s in range(0, nq, chunk):
                qc = queries[s:s + chunk]
                qi = self_ids[s:s + chunk]
                if qc.shape[0] < chunk:
                    padn = chunk - qc.shape[0]
                    qc = jnp.pad(qc, ((0, padn), (0, 0)))
                    qi = jnp.pad(qi, (0, padn), constant_values=-1)
                _, idx = self._block(qc, qi, corpus, valid, k=k, m=m)
                out.append(np.asarray(idx)[: min(chunk, nq - s)])
        return np.concatenate(out)

    def pair(self, queries, corpus, ids, chunk=1024):
        import jax.numpy as jnp
        import numpy as np
        out = []
        with self.jax.default_matmul_precision("highest"):
            for s in range(0, queries.shape[0], chunk):
                out.append(np.asarray(self._pair(
                    queries[s:s + chunk], corpus,
                    jnp.asarray(ids[s:s + chunk]))))
        return np.concatenate(out)


def overlap(a, b) -> float:
    """Mean per-row share of ``b``'s ids that ``a`` holds (-1 ignored)."""
    import numpy as np
    tot = 0.0
    for ra, rb in zip(np.asarray(a), np.asarray(b)):
        sb = set(int(v) for v in rb if v >= 0)
        sa = set(int(v) for v in ra if v >= 0)
        tot += len(sa & sb) / max(len(rb), 1)
    return tot / max(len(a), 1)


def dist_error(dist, ids, queries, corpus, exact: Exact) -> float:
    """Largest relative error of returned distances against the exact
    difference form (entries with id -1 skipped)."""
    import numpy as np
    dist = np.asarray(dist)
    ids = np.asarray(ids)
    ok = ids >= 0
    if not ok.any():
        return 0.0
    e = exact.pair(queries, corpus, np.where(ok, ids, 0))
    rel = np.abs(dist - e) / np.maximum(e, 1e-12)
    return float(rel[ok].max())


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    import jax
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# one chip: build, serve, mutate, int8
# ---------------------------------------------------------------------------

def one_chip(seed: int, *, n_base=60_000, n_query=10_000, n_fresh=1_000,
             n_int8=1_024, n_sample=1_000, n_inter=1_000,
             backends=("auto", "ref"), emit=print) -> None:
    """The one-chip phases at the deployment's sizes (smaller sizes are
    for rehearsals). ``backends[0]`` is judged against ``backends[1]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import MutableKNNStore, build_knn_graph, knn_delete, knn_insert
    from repro.core import datasets
    from repro.core.graph_search import SearchConfig
    from repro.core.nn_descent import DescentConfig
    from repro.core.online import OnlineConfig
    from repro.core.router import RouterConfig
    from repro.serve.scheduler import RetrievalScheduler, SchedulerConfig

    exact = Exact()
    data = datasets.mnist_like(jax.random.key(seed),
                               n_base + n_query + n_fresh)
    base = data[:n_base]
    queries = data[n_base:n_base + n_query]
    fresh = data[n_base + n_query:]
    fast, oracle = backends

    def search_cfg(backend, **kw):
        return SearchConfig(beam=256, rounds=256, backend=backend, **kw)

    # (a) build --------------------------------------------------------
    sample = np.arange(n_sample) * (n_base // n_sample)
    gt20 = exact.knn(base[sample], base, K,
                     self_ids=jnp.asarray(sample, jnp.int32))
    graph, rec, err, secs = {}, {}, {}, {}
    for b in backends:
        (dist, idx, _), secs[b] = timed(lambda: build_knn_graph(
            base, K, cfg=DescentConfig(k=K, backend=b),
            key=jax.random.key(seed + 1)))
        graph[b] = (dist, idx)
        rec[b] = overlap(np.asarray(idx)[sample], gt20)
        err[b] = dist_error(np.asarray(dist)[sample],
                            np.asarray(idx)[sample], base[sample], base,
                            exact)
    emit(phase_line("a build", secs, rec, err, "recall@20"))
    check(rec[fast] >= rec[oracle] - 0.01, "build: pallas recall below ref")
    check(max(err.values()) <= RTOL, "build: returned distances not exact")

    # (b) serve --------------------------------------------------------
    # both backends serve the same input: the graph phase (a) built with
    # the kernels under test
    gt10 = exact.knn(queries, base, K_OUT)
    stores, served, rec, err, secs = {}, {}, {}, {}, {}
    for b in backends:
        def serve():
            store = MutableKNNStore.from_graph(
                base, *graph[fast],
                cfg=OnlineConfig(router=RouterConfig(), backend=b))
            sched = RetrievalScheduler(
                lambda q, scfg: store.search(
                    q, k_out=K_OUT, key=jax.random.key(seed + 2),
                    cfg=scfg),
                base_cfg=search_cfg(b),
                cfg=SchedulerConfig(max_queue=n_query,
                                    max_batch=n_inter))
            reqs = [sched.submit(np.asarray(queries[i]),
                                 lane="interactive" if i < n_inter
                                 else "batch", qid=i)
                    for i in range(n_query)]
            sched.run_until_drained()
            return store, reqs, sched.stats()
        (store, reqs, stats), secs[b] = timed(serve)
        check(all(r.rejection is None and r.idx is not None for r in reqs),
              f"serve ({b}): a request was not served")
        check(stats["served"] == n_query, f"serve ({b}): served count")
        stores[b] = store
        served[b] = (np.stack([r.dist for r in reqs]),
                     np.stack([r.idx for r in reqs]))
        rec[b] = overlap(served[b][1], gt10)
        err[b] = dist_error(*served[b], queries, base, exact)
    ov = overlap(served[fast][1], served[oracle][1])
    emit(phase_line("b serve", secs, rec, err, "recall@10",
                            overlap=ov))
    check(rec[fast] >= rec[oracle] - 0.01, "serve: pallas recall below ref")
    check(max(err.values()) <= RTOL, "serve: returned distances not exact")
    check(ov >= 0.98, "serve: backends disagree")

    # (c) mutate -------------------------------------------------------
    dead = np.arange(n_fresh) * (n_base // n_fresh) + 7
    alive = np.ones(n_base + n_fresh, bool)
    alive[dead] = False
    corpus = jnp.concatenate([base, fresh])
    q1k = queries[:n_inter]
    gt_mut = exact.knn(q1k, corpus, K_OUT, valid=jnp.asarray(alive))
    found, rec, err, secs = {}, {}, {}, {}
    for b in backends:
        def mutate():
            st, _ = knn_insert(stores[b], fresh, key=jax.random.key(seed + 3))
            st, _ = knn_delete(st, jnp.asarray(dead, jnp.int32))
            return st.search(q1k, k_out=K_OUT, key=jax.random.key(seed + 4),
                             cfg=search_cfg(b))
        (d, i), secs[b] = timed(mutate)
        i = np.asarray(i)
        check(not np.isin(i, dead).any(), f"mutate ({b}): deleted id returned")
        check((i < n_base + n_fresh).all(), f"mutate ({b}): unallocated id")
        found[b] = i
        rec[b] = overlap(i, gt_mut)
        err[b] = dist_error(d, i, q1k, corpus, exact)
    emit(phase_line("c mutate", secs, rec, err, "recall@10",
                            overlap=overlap(found[fast], found[oracle])))
    check(rec[fast] >= rec[oracle] - 0.01, "mutate: pallas recall below ref")
    check(max(err.values()) <= RTOL, "mutate: returned distances not exact")

    # (d) int8 ---------------------------------------------------------
    qb = queries[:n_int8]
    found, rec, err, secs = {}, {}, {}, {}
    for b in backends:
        (d, i), secs[b] = timed(lambda: stores[b].search(
            qb, k_out=K_OUT, key=jax.random.key(seed + 5),
            cfg=search_cfg(b, precision="int8")))
        found[b] = np.asarray(i)
        rec[b] = overlap(found[b], gt10[:n_int8])
        err[b] = dist_error(d, i, qb, base, exact)
    ov = overlap(found[fast], found[oracle])
    emit(phase_line("d int8", secs, rec, err, "recall@10",
                            overlap=ov))
    check(rec[fast] >= rec[oracle] - 0.01, "int8: pallas recall below ref")
    check(max(err.values()) <= RTOL, "int8: returned distances not exact")
    check(ov >= 0.98, "int8: backends disagree")


def phase_line(name, secs, rec, err, metric, **extra) -> str:
    fields = {
        "phase": name,
        "first_call_s (bring-up, compile included)": {
            b: round(v, 3) for b, v in secs.items()},
        metric: {b: round(v, 4) for b, v in rec.items()},
        "max_rel_dist_err": {b: float(f"{v:.3g}") for b, v in err.items()},
    }
    fields.update({k: round(v, 4) for k, v in extra.items()})
    return json.dumps(fields)


# ---------------------------------------------------------------------------
# four chips: the routed sharded path
# ---------------------------------------------------------------------------

def aligned_corpus(rng, n: int, d: int, clusters: int):
    """n rows at width d in ``clusters`` Gaussian clusters of equal size,
    each cluster's rows contiguous, so equal contiguous shards hold whole
    clusters when ``clusters`` is a multiple of the shard count. Drawn on
    the host in bulk: a device-side draw of this size compiled for
    18.7 s on TPU."""
    import numpy as np
    per = n // clusters
    cent = 4.0 * rng.standard_normal((clusters, 1, d), dtype=np.float32)
    noise = rng.standard_normal((clusters, per, d), dtype=np.float32)
    return (cent + noise).reshape(n, d)


def four_chips(seed: int, *, n=131_072, d=128, n_query=1_000, n_sample=1_000,
               backend="auto", emit=print) -> None:
    """The routed sharded path on a 4-device mesh (smaller sizes are for
    rehearsals on virtual devices)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.distributed import build_knn_graph_sharded, graph_search_sharded
    from repro.core.graph_search import SearchConfig
    from repro.core.nn_descent import DescentConfig, build_knn_graph
    from repro.core.router import RouterConfig, build_router

    shards = 4
    devs = jax.devices()[:shards]
    mesh = jax.sharding.Mesh(np.array(devs), ("data",))
    n_local = n // shards
    exact = Exact()
    rng = np.random.default_rng(seed)
    x_np = aligned_corpus(rng, n, d, clusters=16 * shards)
    # queries: fresh draws around random corpus rows
    q_np = x_np[rng.integers(0, n, n_query)] + 0.5 * rng.standard_normal(
        (n_query, d), dtype=np.float32)
    # the whole corpus on the default device 0 (the exact reference and
    # the device-0 build), and sharded over the mesh
    x_host = jnp.asarray(x_np)
    queries = jnp.asarray(q_np)
    x = jax.device_put(x_np, NamedSharding(mesh, PartitionSpec("data")))
    homes = {dv for s in x.addressable_shards for dv in [s.device]}
    check(len(homes) == shards, f"corpus sits on {len(homes)} devices")

    cfg = DescentConfig(k=K, backend=backend, reorder=False)
    sample = np.arange(n_sample) * (n // n_sample)
    gt20 = exact.knn(x_host[sample], x_host, K,
                     self_ids=jnp.asarray(sample, jnp.int32))
    (sd, si, _), t_sh = timed(lambda: build_knn_graph_sharded(
        mesh, x, K, cfg=cfg, key=jax.random.key(seed + 3)))
    (od, oi, _), t_one = timed(lambda: build_knn_graph(
        x_host, K, cfg=cfg, key=jax.random.key(seed + 3)))
    si_h, sd_h = np.asarray(si), np.asarray(sd)
    rec_sh = overlap(si_h[sample], gt20)
    rec_one = overlap(np.asarray(oi)[sample], gt20)
    err_sh = dist_error(sd_h[sample], si_h[sample], x_host[sample], x_host,
                        exact)
    emit(json.dumps({
        "phase": "4 sharded build", "rows": n, "rows_per_chip": n_local,
        "reduced": "131,072 rows (SIFT-128 width), cut to fit the chip time",
        "devices": len(homes),
        "first_call_s (bring-up, compile included)": {
            "sharded": round(t_sh, 3), "device0": round(t_one, 3)},
        "recall@20": {"sharded": round(rec_sh, 4),
                      "device0": round(rec_one, 4)},
        "max_rel_dist_err": {"sharded": float(f"{err_sh:.3g}")}}))
    check(rec_sh >= rec_one - 0.02, "sharded build recall below device 0")
    check(err_sh <= RTOL, "sharded build: returned distances not exact")

    # restrict the global graph to shard-local edges, in local ids
    owner = np.arange(n) // n_local
    local = (si_h >= 0) & (si_h // n_local == owner[:, None])
    gl = jnp.asarray(np.where(local, si_h - (owner * n_local)[:, None], -1),
                     jnp.int32)
    router = build_router(x_host, cfg=RouterConfig(), key=jax.random.key(7),
                          backend=backend)
    scfg = SearchConfig(beam=64, rounds=64, backend=backend)
    key = jax.random.key(seed + 4)
    (rep, t_rep) = timed(lambda: graph_search_sharded(
        mesh, x, gl, queries, k_out=K_OUT, cfg=scfg, key=key,
        with_stats=True))
    (rt, t_rt) = timed(lambda: graph_search_sharded(
        mesh, x, gl, queries, k_out=K_OUT, cfg=scfg, key=key,
        router=router, route_p=2, with_stats=True))
    gt10 = exact.knn(queries, x_host, K_OUT)
    ov = overlap(rt[1], rep[1])
    st = rt[2]
    err = max(dist_error(rep[0], rep[1], queries, x_host, exact),
              dist_error(rt[0], rt[1], queries, x_host, exact))
    emit(json.dumps({
        "phase": "4 routed search", "queries": n_query,
        "first_call_s (bring-up, compile included)": {
            "replicated": round(t_rep, 3), "routed": round(t_rt, 3)},
        "recall@10": {"replicated": round(overlap(rep[1], gt10), 4),
                      "routed": round(overlap(rt[1], gt10), 4)},
        "overlap": round(ov, 4), "fanout": st["fanout"],
        "dropped_queries": st["dropped_queries"],
        "max_rel_dist_err": float(f"{err:.3g}")}))
    check(ov >= 0.95, "routed search disagrees with replicated")
    check(st["dropped_queries"] == 0, "routed search dropped queries")
    check(err <= RTOL, "sharded search: returned distances not exact")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the routed sharded phase on 4 chips (only)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print("chip_smoke.py: no src/repro next to this script — run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke.py: JAX finds no TPU (platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke.py: needs {need} chips, JAX finds {len(devs)}",
              file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        emit = functools.partial(print, flush=True)
        if args.four_chips:
            four_chips(args.seed, emit=emit)
        else:
            one_chip(args.seed, emit=emit)
    for w in caught:
        print(f"warning: {w.category.__name__}: {w.message} "
              f"({w.filename}:{w.lineno})", file=sys.stderr)
    ours = [w for w in caught if issubclass(w.category, RuntimeWarning)
            and os.path.abspath(w.filename).startswith(REPO)]
    check(not ours, f"{len(ours)} RuntimeWarning(s) from the repository")
    print(json.dumps({"total_s (bring-up, compile included)":
                      round(time.perf_counter() - t0, 1)}))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
