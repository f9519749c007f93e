"""Online subsystem (core/online.py + serve wiring): insert quality and
cost vs. a full rebuild, tombstone semantics, determinism, frontier
compaction (oracle parity of the chunked gather/scatter dispatch,
O(frontier) delete cost, frontier-vs-dense result parity), and the
growable kNN-LM datastore / scheduler capture path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DescentConfig,
    brute_force_knn,
    build_knn_graph,
    datasets,
    recall_at_k,
)
from repro.core.graph_search import expand_frontier
from repro.core.online import (
    MutableKNNStore,
    OnlineConfig,
    knn_delete,
    knn_insert,
)
from repro.kernels import ref
from repro.kernels.knn_merge import (
    knn_compact_blocked,
    knn_compact_rows_blocked,
    knn_merge_rows_blocked,
)
from repro.serve import ContinuousBatcher, MutableKNNDatastore, Request, knn_logits

K = 10
DCFG = DescentConfig(k=K, rho=1.0, max_iters=15)


@pytest.fixture(scope="module")
def blob_split():
    """~512-point Gaussian-blob corpus + a 10% insert batch (the paper's
    clustered setting, small enough for the fast tier)."""
    x = datasets.clustered(jax.random.key(3), 563, 16, 8)
    return x[:512], x[512:]


@pytest.fixture(scope="module")
def base_store(blob_split):
    x0, _ = blob_split
    dist, idx, _ = build_knn_graph(x0, k=K, cfg=DCFG, key=jax.random.key(1))
    return MutableKNNStore.from_graph(x0, dist, idx, cfg=OnlineConfig())


def test_insert_recall_and_cost(blob_split, base_store):
    """Acceptance criterion: inserting 10% new points reaches >= 0.85
    recall on the combined corpus at < 25% of the distance evaluations of
    a from-scratch build (both counted via DescentStats.dist_evals)."""
    x0, xn = blob_split
    store, ins = knn_insert(base_store, xn, key=jax.random.key(2))
    combined = jnp.concatenate([x0, xn], axis=0)
    _, _, rebuild = build_knn_graph(
        combined, k=K, cfg=DCFG, key=jax.random.key(1))
    _, true_idx = brute_force_knn(combined, combined, K)
    r = recall_at_k(store.nl.idx[:combined.shape[0]], true_idx)
    assert r >= 0.85, r
    assert ins.dist_evals < 0.25 * rebuild.dist_evals, (
        ins.dist_evals, rebuild.dist_evals)


def test_insert_grows_capacity(blob_split, base_store):
    _, xn = blob_split
    assert base_store.capacity == 512
    store, _ = knn_insert(base_store, xn, key=jax.random.key(2))
    assert store.capacity == 1024
    assert store.n == 563
    assert store.live_count() == 563


def test_insert_deterministic(blob_split, base_store):
    _, xn = blob_split
    a, sa = knn_insert(base_store, xn, key=jax.random.key(7))
    b, sb = knn_insert(base_store, xn, key=jax.random.key(7))
    assert jnp.array_equal(a.nl.idx, b.nl.idx)
    assert jnp.array_equal(a.nl.dist, b.nl.dist)
    assert sa.dist_evals == sb.dist_evals


def test_delete_never_returns_tombstoned(blob_split, base_store):
    x0, _ = blob_split
    dead = jnp.arange(0, 64, dtype=jnp.int32)
    store, _ = knn_delete(base_store, dead)
    # no list edge targets a dead node
    tgt = store.nl.idx
    bad = (tgt[:, :, None] == dead[None, None, :]).any(-1) & (tgt >= 0)
    assert int(bad.sum()) == 0
    # queries (including the deleted points themselves) never surface a
    # tombstoned id, and the patched graph still answers fully
    _, idx = store.search(x0[:96], k_out=5, key=jax.random.key(0))
    got = np.asarray(idx)
    assert not np.isin(got[got >= 0], np.asarray(dead)).any()
    assert (got >= 0).mean() == 1.0


def test_delete_then_insert_roundtrip(blob_split, base_store):
    """Tombstoned rows stay dead across later inserts."""
    x0, xn = blob_split
    dead = jnp.asarray([3, 99, 500], jnp.int32)
    store, _ = knn_delete(base_store, dead)
    store, _ = knn_insert(store, xn, key=jax.random.key(2))
    assert not bool(store.alive[dead].any())
    tgt = store.nl.idx
    bad = (tgt[:, :, None] == dead[None, None, :]).any(-1) & (tgt >= 0)
    assert int(bad.sum()) == 0


def test_delete_reconnects_orphaned_rows():
    """A live row whose entire neighborhood dies must keep a non-empty
    list (re-anchored to live rows) instead of dropping out of the graph."""
    key = jax.random.key(0)
    # two far-apart blobs; kill all of blob B except one point
    a = jax.random.normal(key, (96, 8))
    b = 100.0 + jax.random.normal(jax.random.fold_in(key, 1), (32, 8))
    x = jnp.concatenate([a, b])
    dist, idx, _ = build_knn_graph(x, k=8,
                                   cfg=DescentConfig(k=8, rho=1.0,
                                                     max_iters=10),
                                   key=jax.random.key(1))
    store = MutableKNNStore.from_graph(x, dist, idx)
    survivor = 96
    dead = jnp.arange(97, 128, dtype=jnp.int32)
    store, _ = knn_delete(store, dead)
    nbrs = store.nl.idx[survivor]
    assert int((nbrs >= 0).sum()) > 0          # reconnected, not orphaned
    assert bool(store.alive[jnp.clip(nbrs, 0, None)][nbrs >= 0].all())


def test_compact_kernel_matches_oracle():
    rng = np.random.RandomState(0)
    n, k = 37, 8
    d = np.sort(rng.rand(n, k).astype(np.float32), axis=1)
    i = rng.randint(-1, 50, size=(n, k)).astype(np.int32)
    # exercise the init_random placeholder distance (3e38, a valid entry
    # that must survive) and empty slots (inf)
    d[5, -1] = 3.0e38
    i[5, -1] = 42
    d[6, -1] = np.inf
    drop = rng.rand(n, k) < 0.3
    drop[5, -1] = False
    rd, ri, rr = ref.knn_compact(
        jnp.asarray(d), jnp.asarray(i), jnp.asarray(drop))
    kd, ki, kr = knn_compact_blocked(
        jnp.asarray(d), jnp.asarray(i), jnp.asarray(drop), tm=16,
        interpret=True)
    assert jnp.array_equal(ri, ki)
    assert jnp.array_equal(rr, kr)
    assert jnp.array_equal(jnp.isinf(rd), jnp.isinf(kd))
    assert jnp.array_equal(jnp.where(jnp.isinf(rd), 0.0, rd),
                           jnp.where(jnp.isinf(kd), 0.0, kd))


# ---------------------------------------------------------------------------
# frontier compaction (the chunked gather/scatter dispatch)
# ---------------------------------------------------------------------------


def _random_lists(rng, n, k, hi):
    d = np.sort(rng.rand(n, k).astype(np.float32), axis=1)
    i = rng.randint(-1, hi, size=(n, k)).astype(np.int32)
    return jnp.asarray(d), jnp.asarray(i)


def test_merge_rows_kernel_matches_oracle():
    """Chunked gather/scatter merge: pallas (interpret) vs. pure-jnp
    oracle, including padding slots and out-of-frontier passthrough."""
    rng = np.random.RandomState(1)
    n, k, f, c = 41, 6, 16, 9
    cur_d, cur_i = _random_lists(rng, n, k, 60)
    rows = np.full((f,), -1, np.int32)
    picks = rng.choice(n, size=f - 3, replace=False)
    rows[:f - 3] = np.sort(picks)
    cand_d = rng.rand(f, c).astype(np.float32)
    cand_i = rng.randint(-1, 60, size=(f, c)).astype(np.int32)
    args = (cur_d, cur_i, jnp.asarray(rows), jnp.asarray(cand_d),
            jnp.asarray(cand_i))
    rd, ri, ru = ref.knn_merge_rows(*args)
    kd, ki, ku = knn_merge_rows_blocked(*args, tm=8, interpret=True)
    assert jnp.array_equal(ri, ki)
    assert jnp.array_equal(ru, ku)
    assert jnp.allclose(jnp.where(jnp.isinf(rd), 0.0, rd),
                        jnp.where(jnp.isinf(kd), 0.0, kd))
    # rows off the frontier are bit-identical to the input
    off = np.setdiff1d(np.arange(n), rows[rows >= 0])
    assert jnp.array_equal(ri[off], cur_i[off])
    assert jnp.array_equal(rd[off], cur_d[off])


def test_compact_rows_kernel_matches_oracle():
    rng = np.random.RandomState(2)
    n, k, f = 29, 8, 12
    cur_d, cur_i = _random_lists(rng, n, k, 40)
    rows = np.full((f,), -1, np.int32)
    rows[:f - 2] = np.sort(rng.choice(n, size=f - 2, replace=False))
    drop = rng.rand(f, k) < 0.4
    args = (cur_d, cur_i, jnp.asarray(rows), jnp.asarray(drop))
    rd, ri, rr = ref.knn_compact_rows(*args)
    kd, ki, kr = knn_compact_rows_blocked(*args, tm=8, interpret=True)
    assert jnp.array_equal(ri, ki)
    assert jnp.array_equal(rr, kr)
    assert jnp.array_equal(jnp.isinf(rd), jnp.isinf(kd))
    off = np.setdiff1d(np.arange(n), rows[rows >= 0])
    assert jnp.array_equal(ri[off], cur_i[off])


def test_expand_frontier_closure():
    """1- and 2-hop closures over a known tiny graph, with truncation."""
    idx = jnp.asarray([[1, -1], [2, -1], [3, -1], [3, -1]], jnp.int32)
    seeds = jnp.asarray([0], jnp.int32)
    ids1, mask1 = expand_frontier(idx, seeds, hops=1, capacity=4)
    assert np.asarray(ids1).tolist() == [0, 1, -1, -1]
    ids2, mask2 = expand_frontier(idx, seeds, hops=2, capacity=4)
    assert np.asarray(ids2).tolist() == [0, 1, 2, -1]
    # alive filter drops rows; truncation keeps the smallest ids
    alive = jnp.asarray([True, False, True, True])
    ids3, _ = expand_frontier(idx, seeds, hops=3, capacity=2, alive=alive)
    assert np.asarray(ids3).tolist() == [0, 2]
    assert bool(mask1[1]) and not bool(mask1[2])
    assert bool(mask2[2])


def test_delete_refill_touches_o_frontier_rows(blob_split):
    """The tentpole's receipt: delete-refill processes O(frontier) rows —
    the padded-chunk row count tracks the affected set, not the store
    size. The same 8-row delete on a 4x bigger store must process the
    same number of padded rows (and far fewer than the store holds)."""
    x0, _ = blob_split                       # 512 points
    xbig = datasets.clustered(jax.random.key(9), 2048, 16, 8)
    cfg = OnlineConfig(chunk=64)
    dead = jnp.arange(17, 25, dtype=jnp.int32)
    for name, pts in (("small", x0), ("big", xbig)):
        dist, idx, _ = build_knn_graph(pts, k=K, cfg=DCFG,
                                       key=jax.random.key(1))
        store = MutableKNNStore.from_graph(pts, dist, idx, cfg=cfg)
        _, st = knn_delete(store, dead)
        assert st.frontier_rows <= st.padded_rows
        # padding never adds more than one chunk
        assert st.padded_rows <= st.frontier_rows + 64
        # the frontier is the dead rows plus their inbound pointers — a
        # degree-bounded set that does NOT scale with the store: the same
        # bound holds on the 512-row and the 2048-row store
        assert st.frontier_rows <= 4 * int(dead.shape[0]) * K, (
            name, st.frontier_rows)
        assert st.padded_rows < pts.shape[0] // 2, (name, st.padded_rows)


def test_delete_frontier_matches_dense(blob_split, base_store):
    """The dense baseline (frontier=False) and the compacted frontier
    path run the same per-row semantics — results must be identical."""
    dead = jnp.concatenate([
        jnp.arange(0, 40, dtype=jnp.int32),
        jnp.asarray([200, 201, 202, 511], jnp.int32),
    ])
    sf = dataclasses.replace(
        base_store, cfg=dataclasses.replace(base_store.cfg, frontier=True,
                                            chunk=128))
    sd = dataclasses.replace(
        base_store, cfg=dataclasses.replace(base_store.cfg, frontier=False,
                                            chunk=128))
    out_f, st_f = knn_delete(sf, dead)
    out_d, st_d = knn_delete(sd, dead)
    assert jnp.array_equal(out_f.nl.idx, out_d.nl.idx)
    assert jnp.array_equal(out_f.nl.dist, out_d.nl.dist)
    assert jnp.array_equal(out_f.alive, out_d.alive)
    # identical distance work, far fewer rows processed
    assert st_f.dist_evals == st_d.dist_evals
    assert st_f.padded_rows < st_d.padded_rows


def test_insert_reports_frontier_accounting(blob_split, base_store):
    _, xn = blob_split
    store, st = knn_insert(base_store, xn, key=jax.random.key(2))
    assert st.frontier_rows > 0
    assert st.padded_rows >= st.frontier_rows
    # one padded chunk per merge stage at most (the store is smaller than
    # the chunk quantum here, so every stage is capacity-bounded)
    cfg = base_store.cfg
    stages = 2 + 2 * cfg.refine_rounds   # seed + self-join + 2 per round
    assert st.padded_rows <= stages * store.capacity


def test_mutable_datastore_append_changes_retrieval():
    vocab, dk = 16, 8
    keys0 = jax.random.normal(jax.random.key(0), (128, dk))
    vals0 = jax.random.randint(jax.random.key(1), (128,), 0, vocab)
    ds = MutableKNNDatastore.build(keys0, vals0, k=8, key=jax.random.key(2))
    center = jnp.full((dk,), 5.0)
    newk = center + 0.05 * jax.random.normal(jax.random.key(3), (16, dk))
    ds2, _ = ds.append(newk, jnp.full((16,), 7, vals0.dtype),
                       key=jax.random.key(4))
    # the inserted cluster sits far from the base corpus (no inbound
    # edges), so reachability rides on the entry draw: thread an explicit
    # entry key like a serving loop would (see graph_search's key contract)
    lp = knn_logits(ds2, center[None], vocab, k=4, key=jax.random.key(5))
    assert int(jnp.argmax(lp[0])) == 7


def test_scheduler_capture_grows_datastore():
    vocab, dk = 16, 8
    keys0 = jax.random.normal(jax.random.key(0), (64, dk))
    vals0 = jax.random.randint(jax.random.key(1), (64,), 0, vocab)
    ds = MutableKNNDatastore.build(keys0, vals0, k=8, key=jax.random.key(2))
    proj = jax.random.normal(jax.random.key(5), (vocab, dk))

    def prefill_fn(toks):
        return jnp.ones((1, vocab)), None, toks.shape[1]

    def step_fn(cache, toks, lengths):
        lg = jax.nn.one_hot((toks[:, 0] * 3 + lengths) % vocab, vocab) * 4.0
        return lg, cache

    b = ContinuousBatcher(
        2, step_fn, prefill_fn, lambda c, i, o, l: c,
        knn_store=ds, knn_capture=lambda lg: lg @ proj, knn_chunk=8,
        knn_q_block=16)
    # the serving query-block knob rewrites the store's search quantum
    assert b.knn_store.store.cfg.q_block == 16
    for r in range(3):
        b.submit(Request(rid=r, prompt=np.array([1, 2, 3], np.int32),
                         max_new=8))
    b.run(None)
    # 3 requests x 8 tokens, minus the un-captured prefill token each
    assert b.knn_store.store.n == ds.store.n + 21
    assert b.knn_store.store.live_count() == ds.store.n + 21


def test_expand_frontier_overflow_prefers_near_hops():
    """Overflow regression for the frontier truncation: the kept rows
    must be the ones FEWEST hops from the seeds — the old smallest-id
    policy dropped the whole 1-hop ring here in favor of far 2-hop rows
    that happened to carry small ids."""
    idx = jnp.full((10, 2), -1, jnp.int32)
    idx = idx.at[0].set(jnp.asarray([8, 9]))     # seed -> high-id 1-hop
    idx = idx.at[8].set(jnp.asarray([1, 2]))     # ... -> low-id 2-hop
    idx = idx.at[9].set(jnp.asarray([3, -1]))
    seeds = jnp.asarray([0], jnp.int32)
    ids, mask = expand_frontier(idx, seeds, hops=2, capacity=3)
    # closure is {0, 8, 9, 1, 2, 3}; id-biased truncation kept {0, 1, 2}
    assert np.asarray(ids).tolist() == [0, 8, 9]
    # the mask stays exact regardless of truncation
    assert bool(mask[1]) and bool(mask[2]) and bool(mask[3])
    assert int(mask.sum()) == 6


# ---------------------------------------------------------------------------
# degenerate stores: empty / fully tombstoned (robustness hardening)
# ---------------------------------------------------------------------------


def test_empty_store_searches_empty_and_insert_is_first_build():
    """MutableKNNStore.empty: searches answer empty instead of raising,
    and the first insert acts as a first build — the batch self-join
    links the graph so the inserted points retrieve each other."""
    store = MutableKNNStore.empty(16, k=K)
    assert store.n == 0 and store.live_count() == 0
    q = jax.random.normal(jax.random.key(0), (6, 16))
    d, i = store.search(q, k_out=5, key=jax.random.key(1))
    assert (np.asarray(i) == -1).all()
    x = datasets.clustered(jax.random.key(2), 64, 16, 4)
    store, _ = knn_insert(store, x, key=jax.random.key(3))
    assert store.n == 64 and store.live_count() == 64
    _, idx = store.search(x[:16], k_out=1, key=jax.random.key(4))
    assert (np.asarray(idx)[:, 0] == np.arange(16)).all()


def test_empty_store_quantized_insert_roundtrip():
    store = MutableKNNStore.empty(
        16, k=K, cfg=OnlineConfig(precision="int8"))
    x = datasets.clustered(jax.random.key(2), 48, 16, 4)
    store, _ = knn_insert(store, x, key=jax.random.key(3))
    assert store.qs is not None and store.live_count() == 48
    _, idx = store.search(x[:8], k_out=1, key=jax.random.key(4))
    assert (np.asarray(idx)[:, 0] == np.arange(8)).all()


def test_fully_tombstoned_store_insert_relinks(blob_split, base_store):
    """Deleting EVERY row then inserting must behave like a first
    insert: no dead id ever resurfaces, the new batch is retrievable."""
    x0, xn = blob_split
    dead = jnp.arange(base_store.n, dtype=jnp.int32)
    store, _ = knn_delete(base_store, dead)
    assert store.live_count() == 0
    d, i = store.search(x0[:8], k_out=5, key=jax.random.key(0))
    assert (np.asarray(i) == -1).all()
    store, _ = knn_insert(store, xn, key=jax.random.key(1))
    assert store.live_count() == xn.shape[0]
    _, idx = store.search(xn, k_out=1, key=jax.random.key(2))
    got = np.asarray(idx)[:, 0]
    assert (got >= base_store.n).all()      # only the new rows surface
    assert (got == np.arange(xn.shape[0]) + base_store.n).all()


def test_store_search_spans(tmp_path, blob_split, base_store):
    """A CPU profile of one store search holds each span of
    ``spans.SPANS`` once, with its host-integer arguments, nested as the
    table says, all on the thread that called the search."""
    import glob

    from jax.profiler import ProfileData

    from repro.core import spans
    from repro.core.graph_search import SearchConfig
    q = blob_split[1][:40]
    cfg = SearchConfig(beam=16, rounds=16, q_block=32)
    jax.block_until_ready(base_store.search(q, k_out=5, cfg=cfg))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(base_store.search(q, k_out=5, cfg=cfg))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(spans.PREFIX):
                    found.setdefault(ev.name[len(spans.PREFIX):], []).append(
                        (line.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    assert sorted(found) == sorted(spans.SPANS)
    assert all(len(v) == 1 for v in found.values()), found
    assert len({v[0][0] for v in found.values()}) == 1     # one thread
    for name, [(_, start, end, _)] in found.items():
        parent = spans.SPANS[name][1]
        if parent is not None:
            _, p_start, p_end, _ = found[parent][0]
            assert p_start <= start <= end <= p_end, name
    args = {name: v[0][3] for name, v in found.items()}
    assert args["store.search"] == {"queries": 40}
    assert args["search.admit"] == {"rows": 40}
    assert args["search.dispatch"] == {"blocks": 2, "q_block": 32}
    assert args["search.route"] == {"width": 16}


def test_every_span_is_in_the_table():
    """Every ``span("<name>", ...)`` in the program names an entry of
    ``spans.SPANS``, and every entry is used."""
    import pathlib
    import re

    from repro.core import spans
    src = pathlib.Path(spans.__file__).parents[1]
    used = {m for f in src.rglob("*.py")
            for m in re.findall(r'\bspan\(\s*"([^"]+)"', f.read_text())}
    assert used == set(spans.SPANS)
