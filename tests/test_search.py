"""Fused batched graph search (kernels/knn_search.py +
core/graph_search.py): kernel-vs-oracle parity over odd shapes, fused
vs. backend="ref" behavior parity (tombstone masking, output invariants),
and the seeded 512-pt recall pin on the fused serving path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DescentConfig,
    SearchConfig,
    brute_force_knn,
    build_knn_graph,
    datasets,
    recall_at_k,
)
from repro.core.graph_search import graph_search
from repro.core.online import MutableKNNStore, OnlineConfig, knn_delete
from repro.kernels import ref
from repro.kernels.knn_search import knn_search_dists_blocked

K = 10
DCFG = DescentConfig(k=K, rho=1.0, max_iters=15)


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nq,w,dp,tq", [
    (37, 23, 16, 16),    # nq not a multiple of the query block, odd W
    (16, 64, 32, 16),    # exact blocks
    (5, 7, 8, 8),        # single padded block
])
def test_search_dists_kernel_matches_oracle(nq, w, dp, tq):
    rng = np.random.RandomState(nq + w)
    q = jnp.asarray(rng.randn(nq, dp).astype(np.float32))
    cg = jnp.asarray(rng.randn(nq, w, dp).astype(np.float32))
    ids = jnp.asarray(rng.randint(-1, 99, size=(nq, w)).astype(np.int32))
    ids = ids.at[2].set(-1)                     # an all-dead candidate row
    q2 = jnp.sum(q * q, axis=1)
    c2 = jnp.where(ids >= 0, jnp.sum(cg * cg, axis=-1), 0.0)
    rd = ref.knn_search_dists(q, q2, cg, c2, ids)
    kd = knn_search_dists_blocked(q, q2, cg, c2, ids, tq=tq,
                                  interpret=True)
    np.testing.assert_array_equal(np.isinf(rd), np.isinf(kd))
    np.testing.assert_allclose(np.where(np.isinf(rd), 0.0, rd),
                               np.where(np.isinf(kd), 0.0, kd),
                               rtol=1e-5, atol=1e-4)
    assert bool(jnp.isinf(kd[2]).all())


def test_search_dists_kernel_masks_match_brute():
    """Valid entries equal the plain pairwise distance."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(6, 12).astype(np.float32))
    x = jnp.asarray(rng.randn(30, 12).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, 30, size=(6, 9)).astype(np.int32))
    cg = x[ids]
    q2 = jnp.sum(q * q, axis=1)
    c2 = jnp.sum(cg * cg, axis=-1)
    got = ref.knn_search_dists(q, q2, cg, c2, ids)
    want = ref.pairwise_sq_l2(q, x)
    want = jnp.take_along_axis(want, ids, axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# fused search vs the ref loop
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def built_graph():
    x = datasets.clustered(jax.random.key(11), 512, 16, 8)
    dist, idx, _ = build_knn_graph(x, k=K, cfg=DCFG, key=jax.random.key(5))
    return x, dist, idx


def _invariants(d, i, alive=None):
    d = np.asarray(d)
    i = np.asarray(i)
    fin = np.isfinite(d) & (d < 1e38)
    # padding is (-1, inf/big) and distances ascend over the valid prefix
    assert ((i >= 0) == fin).all()
    dpad = np.where(fin, d, np.float32(3.0e38))
    assert (np.diff(dpad, axis=1) >= 0).all()
    for r in range(i.shape[0]):
        v = i[r][i[r] >= 0]
        assert len(set(v.tolist())) == len(v)       # unique ids
    if alive is not None:
        a = np.asarray(alive)
        assert a[i[i >= 0]].all()                   # only live ids


@pytest.mark.parametrize("nq,cfg", [
    # q not a multiple of the block
    (37, SearchConfig(beam=16, rounds=16, expand=4, q_block=16)),
    # E*k > beam: the select/merge must bound the candidate tile
    (8, SearchConfig(beam=8, rounds=12, expand=4, q_block=8)),
    # E > unexpanded pool entries; single round budget
    (5, SearchConfig(beam=4, rounds=2, expand=8, q_block=4)),
])
def test_fused_search_odd_shapes(built_graph, nq, cfg):
    x, _, idx = built_graph
    q = x[:nq] + 0.01
    d, i = graph_search(x, idx, q, k_out=4, key=jax.random.key(0), cfg=cfg)
    assert d.shape == (nq, 4) and i.shape == (nq, 4)
    _invariants(d, i)
    assert (np.asarray(i) >= 0).mean() == 1.0       # pool always fills


def test_fixed_block_matches_bucketed(built_graph):
    """fixed_block=True (the SLO-bench baseline that pads every batch to
    the full q_block) must be semantically identical to the bucketed
    ladder — only the padded block shape differs."""
    from repro.core.graph_search import q_block_bucket
    x, _, idx = built_graph
    q = x[:7] + 0.01
    outs = {}
    for fixed in (False, True):
        cfg = SearchConfig(beam=16, rounds=12, expand=3, q_block=64,
                           fixed_block=fixed)
        qb = q_block_bucket(7, cfg)
        assert qb == (64 if fixed else 8)
        d, i = graph_search(x, idx, q, k_out=5, key=jax.random.key(4),
                            cfg=cfg)
        outs[fixed] = (np.asarray(d), np.asarray(i))
    np.testing.assert_array_equal(outs[False][1], outs[True][1])
    np.testing.assert_allclose(outs[False][0], outs[True][0],
                               rtol=1e-5, atol=1e-5)


def test_fused_interpret_matches_jnp_dispatch(built_graph):
    """backend="interpret" (every Pallas kernel body under the
    interpreter) must agree with the default jnp-oracle dispatch
    end-to-end, bit-for-bit on indices."""
    x, _, idx = built_graph
    q = x[:16] + 0.01
    outs = {}
    for backend in ("auto", "interpret"):
        cfg = SearchConfig(beam=16, rounds=12, expand=3, q_block=8,
                           backend=backend)
        d, i = graph_search(x, idx, q, k_out=5, key=jax.random.key(2),
                            cfg=cfg)
        outs[backend] = (np.asarray(d), np.asarray(i))
    np.testing.assert_array_equal(outs["auto"][1], outs["interpret"][1])
    np.testing.assert_allclose(
        np.where(np.isfinite(outs["auto"][0]), outs["auto"][0], 0.0),
        np.where(np.isfinite(outs["interpret"][0]),
                 outs["interpret"][0], 0.0),
        rtol=1e-5, atol=1e-5)


def test_fused_empty_query_batch(built_graph):
    """An idle serving tick (zero queries) returns empty, like the ref
    path and the pre-fused implementation."""
    x, _, idx = built_graph
    d, i = graph_search(x, idx, x[:0], k_out=5, key=jax.random.key(0))
    assert d.shape == (0, 5) and i.shape == (0, 5)


def test_fused_matches_ref_recall(built_graph):
    """Same expansion budget -> the fused multi-expansion path must match
    the one-node-per-round oracle's recall within a hair."""
    x, _, idx = built_graph
    q = x[:128] + 0.01
    _, ti = brute_force_knn(x, q, K, exclude_self=False)
    rs = {}
    for backend in ("auto", "ref"):
        cfg = SearchConfig(beam=32, rounds=24, expand=4, backend=backend)
        _, gi = graph_search(x, idx, q, k_out=K, key=jax.random.key(3),
                             cfg=cfg)
        rs[backend] = recall_at_k(gi, ti)
    assert rs["auto"] >= rs["ref"] - 0.02, rs


def test_fused_search_seeded_recall_pin(built_graph):
    """Acceptance pin: the fused serving path holds >= 0.97 recall on the
    seeded 512-pt regression at the default serving budget."""
    x, _, idx = built_graph
    q = x[:256] + 0.01
    _, ti = brute_force_knn(x, q, K, exclude_self=False)
    d, i = graph_search(x, idx, q, k_out=K, key=jax.random.key(2),
                        cfg=SearchConfig(beam=32, rounds=24, expand=4))
    r = recall_at_k(i, ti)
    assert r >= 0.97, r
    # deterministic given the key
    d2, i2 = graph_search(x, idx, q, k_out=K, key=jax.random.key(2),
                          cfg=SearchConfig(beam=32, rounds=24, expand=4))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))


def test_batch_content_derived_entries(built_graph):
    """No silent shared-constant entry points: two different batches with
    no key draw different entries (content-derived), while the same batch
    stays deterministic."""
    x, _, idx = built_graph
    cfg = SearchConfig(beam=8, rounds=4, expand=2)
    qa = x[:16] + 0.01
    qb = x[16:32] + 0.01
    da1, ia1 = graph_search(x, idx, qa, k_out=4, cfg=cfg)
    da2, ia2 = graph_search(x, idx, qa, k_out=4, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(ia1), np.asarray(ia2))
    # different content -> different entry draw -> (with a tiny beam and
    # budget) almost surely different result sets for at least one query
    db, ib = graph_search(x, idx, qb, k_out=4, cfg=cfg)
    assert not np.array_equal(np.asarray(ia1), np.asarray(ib))


# ---------------------------------------------------------------------------
# tombstone / alive masking parity
# ---------------------------------------------------------------------------

def test_fused_tombstone_parity_with_ref(built_graph):
    """With a tombstone mask, the fused path and backend="ref" both never
    surface a dead id, keep every slot filled from live rows, and agree
    on recall against the alive-filtered truth."""
    x, dist, idx = built_graph
    n = x.shape[0]
    store = MutableKNNStore.from_graph(x, dist, idx, cfg=OnlineConfig())
    dead = jnp.arange(0, 64, dtype=jnp.int32)
    store, _ = knn_delete(store, dead)
    q = x[:96] + 0.01

    # alive-filtered brute-force truth
    d_all = ref.pairwise_sq_l2(q, x.astype(jnp.float32))
    d_all = jnp.where(store.alive[:n][None, :], d_all, jnp.inf)
    _, ti = jax.lax.top_k(-d_all, 5)

    recalls = {}
    for backend in ("auto", "ref"):
        d, i = store.search(
            q, k_out=5, key=jax.random.key(0),
            cfg=SearchConfig(beam=32, rounds=24, backend=backend),
        )
        got = np.asarray(i)
        assert not np.isin(got[got >= 0], np.asarray(dead)).any(), backend
        assert (got >= 0).mean() == 1.0, backend
        if backend == "auto":
            _invariants(d, i, alive=store.alive[:n])
        recalls[backend] = recall_at_k(i, ti)
    assert recalls["auto"] >= recalls["ref"] - 0.05, recalls


def test_fused_all_dead_returns_empty(built_graph):
    x, _, idx = built_graph
    alive = jnp.zeros((x.shape[0],), bool)
    d, i = graph_search(x, idx, x[:5], k_out=5, key=jax.random.key(0),
                        alive=alive, cfg=SearchConfig(beam=8, rounds=4))
    assert (np.asarray(i) == -1).all()
    assert np.isinf(np.asarray(d)).all()


def test_empty_corpus_returns_empty():
    """A store before its first insert: zero allocated rows answer every
    query with the empty result instead of a degenerate gather."""
    d, i = graph_search(jnp.zeros((0, 16)), jnp.zeros((0, K), jnp.int32),
                        jnp.ones((7, 16)), k_out=5, key=jax.random.key(0))
    assert d.shape == (7, 5) and i.shape == (7, 5)
    assert (np.asarray(i) == -1).all()
    assert np.isinf(np.asarray(d)).all()


def test_admission_sanitizes_poisoned_rows(built_graph):
    """Default (strict=False): NaN/Inf rows are sanitized — their
    results come back empty, the CLEAN rows' results are bit-identical
    to the unpoisoned batch (no NaN reaches the pool merge)."""
    x, _, idx = built_graph
    q = np.array(x[:16], np.float32)
    clean_d, clean_i = graph_search(x, idx, jnp.asarray(q), k_out=5,
                                    key=jax.random.key(3))
    bad = q.copy()
    bad[0, 0] = np.nan
    bad[3, :] = np.inf
    with pytest.warns(RuntimeWarning, match="sanitized 2"):
        d, i = graph_search(x, idx, jnp.asarray(bad), k_out=5,
                            key=jax.random.key(3))
    d, i = np.asarray(d), np.asarray(i)
    assert (i[0] == -1).all() and (i[3] == -1).all()
    assert np.isinf(d[0]).all() and np.isinf(d[3]).all()
    ok = [r for r in range(16) if r not in (0, 3)]
    assert np.isfinite(d[ok]).all()
    _invariants(d[ok], i[ok])


def test_admission_strict_rejects_poisoned_batch(built_graph):
    x, _, idx = built_graph
    bad = np.array(x[:8], np.float32)
    bad[2, 1] = np.inf
    with pytest.raises(ValueError, match="non-finite"):
        graph_search(x, idx, jnp.asarray(bad), k_out=5,
                     key=jax.random.key(3), cfg=SearchConfig(strict=True))


def test_admission_rejects_dim_mismatch(built_graph):
    """A wrong-dimensionality batch always rejects (both strict modes):
    there is no safe way to guess which features the caller meant."""
    x, _, idx = built_graph
    bad = jnp.ones((4, x.shape[1] + 1))
    for cfg in (SearchConfig(strict=False), SearchConfig(strict=True)):
        with pytest.raises(ValueError, match="feature dim"):
            graph_search(x, idx, bad, k_out=5, key=jax.random.key(0),
                         cfg=cfg)


def test_deadline_degrades_not_crashes(built_graph):
    """max_rounds_deadline: an already-expired time slice cuts the
    budget of every block after the first — results stay VALID (the
    invariants hold, every query answered), only recall may degrade."""
    x, _, idx = built_graph
    q = x[:64] + 0.01
    cfg = SearchConfig(beam=16, rounds=24, q_block=16,
                       max_rounds_deadline=1e-9)
    d, i = graph_search(x, idx, q, k_out=5, key=jax.random.key(2), cfg=cfg)
    assert i.shape == (64, 5)
    assert (np.asarray(i) >= 0).all()
    _invariants(d, i)
    # and a generous slice changes nothing vs. the undeadlined config
    lazy = SearchConfig(beam=16, rounds=24, q_block=16,
                        max_rounds_deadline=60.0)
    d0, i0 = graph_search(x, idx, q, k_out=5, key=jax.random.key(2),
                          cfg=SearchConfig(beam=16, rounds=24, q_block=16))
    d1, i1 = graph_search(x, idx, q, k_out=5, key=jax.random.key(2),
                          cfg=lazy)
    assert (np.asarray(i0) == np.asarray(i1)).all()
    assert (np.asarray(d0) == np.asarray(d1)).all()


def test_search_cfg_threads_through_knn_logits():
    """serve/knn_lm: cfg + key thread to the store search and the result
    distribution reacts to retrieval."""
    from repro.serve import MutableKNNDatastore, knn_logits
    vocab, dk = 16, 8
    keys0 = jax.random.normal(jax.random.key(0), (128, dk))
    vals0 = jnp.full((128,), 7, jnp.int32)
    ds = MutableKNNDatastore.build(keys0, vals0, k=8, key=jax.random.key(2),
                                   q_block=32)
    assert ds.store.cfg.q_block == 32
    lp = knn_logits(ds, keys0[:4] + 0.01, vocab, k=4,
                    key=jax.random.key(9),
                    cfg=SearchConfig(beam=16, rounds=8, expand=2))
    assert (jnp.argmax(lp, -1) == 7).all()


# ---------------------------------------------------------------------------
# entry-point seeding regressions
# ---------------------------------------------------------------------------

def test_batch_key_distinguishes_permuted_batches():
    """The content-derived entry key must not be permutation-invariant: a
    shuffled copy of a batch used to hash identically (plain jnp.sum) and
    reuse the same entry points; the position-weighted fold breaks that
    while identical batches stay deterministic."""
    from repro.core.graph_search import _batch_key, _draw_entries
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    perm = rng.permutation(8)
    qp = q[jnp.asarray(perm)]
    k1, k2 = _batch_key(q), _batch_key(qp)
    assert not jnp.array_equal(jax.random.key_data(k1),
                               jax.random.key_data(k2))
    e1 = _draw_entries(k1, 512, 16, None)
    e2 = _draw_entries(k2, 512, 16, None)
    assert not jnp.array_equal(e1, e2)
    # determinism: the same batch maps to the same key
    assert jnp.array_equal(jax.random.key_data(k1),
                           jax.random.key_data(_batch_key(q)))


def test_draw_entries_no_duplicates():
    """Both branches (alive=None and masked) must sample WITHOUT
    replacement — the retired randint draw produced duplicate ids whose
    pool-merge dedup silently wasted beam slots."""
    from repro.core.graph_search import _draw_entries
    key = jax.random.key(5)
    e = np.asarray(_draw_entries(key, 64, 32, None))
    assert e.shape == (32,)
    assert len(set(e.tolist())) == 32
    assert ((e >= 0) & (e < 64)).all()
    alive = jnp.arange(64) % 2 == 0
    ea = np.asarray(_draw_entries(key, 64, 32, alive))
    assert len(set(ea.tolist())) == 32
    assert (ea % 2 == 0).all()              # live rows only
    # width clamps to n when beam > n
    small = np.asarray(_draw_entries(key, 8, 32, None))
    assert small.shape == (8,) and len(set(small.tolist())) == 8


@pytest.mark.parametrize("precision", ["f32", "int8", "bf16"])
def test_search_block_phases_are_named(built_graph, precision):
    """Every phase of ``spans.SCOPES`` names ops of the compiled search
    block (their ``op_name`` metadata), at each scoring precision, with
    routed (per-query) entries."""
    import importlib
    import re

    from repro.core import quantize
    from repro.core.spans import SCOPES
    gs = importlib.import_module("repro.core.graph_search")
    x, _, idx = built_graph
    qb, e = 8, 16
    cfg = SearchConfig(beam=16, rounds=8, q_block=qb, precision=precision)
    qstore = None if precision == "f32" else \
        quantize.quantize_corpus(x, precision)
    q = x[:qb] + 0.01
    entry = jnp.tile(jnp.arange(e, dtype=jnp.int32), (qb, 1))
    text = gs._search_block.lower(
        x, jnp.sum(x * x, 1), idx, q, jnp.sum(q * q, 1), entry, None, None,
        qstore, k_out=4, cfg=cfg).compile().as_text()
    parts = {part for name in re.findall(r'op_name="([^"]+)"', text)
             for part in name.split("/")[:-1]}
    assert set(SCOPES) <= parts


@pytest.mark.parametrize("select_c", [0, 64])
@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
def test_search_block_insert_matches_ref(built_graph, precision, select_c):
    """The search block's round merge through the pool-insert kernel
    (interpret) returns what the ``ref`` oracles return, at a beam wider
    than the E*k tile: select_c 0 narrows the select to E*k, select_c =
    beam keeps it at the beam."""
    import importlib

    from repro.core import quantize
    gs = importlib.import_module("repro.core.graph_search")
    x, _, idx = built_graph
    qb, beam = 16, 64
    q = x[:qb] + 0.01
    entry = jnp.tile(jnp.arange(24, dtype=jnp.int32), (qb, 1))
    qstore = None if precision == "f32" else \
        quantize.quantize_corpus(x, precision)
    outs = [gs._search_block(
        x, jnp.sum(x * x, 1), idx, q, jnp.sum(q * q, 1), entry, None, None,
        qstore, k_out=K, cfg=SearchConfig(
            beam=beam, rounds=16, q_block=qb, precision=precision,
            select_c=select_c, backend=backend))
        for backend in ("ref", "interpret")]
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])


def test_search_stats_counts(built_graph):
    """``stats=True`` counts the rounds' pool inserts: what was kept was
    offered, and no round offers more than its E*k tile per query."""
    x, _, idx = built_graph
    q = x[:40] + 0.02
    cfg = SearchConfig(beam=32, rounds=16, q_block=16)
    d, i, st = graph_search(x, idx, q, k_out=K, cfg=cfg, stats=True)
    d0, i0 = graph_search(x, idx, q, k_out=K, cfg=cfg)
    np.testing.assert_array_equal(i, i0)
    np.testing.assert_array_equal(d, d0)
    assert st.blocks == 3 and 0 < st.rounds <= 3 * cfg.n_rounds
    assert 0 < st.inserted <= st.offered
    assert st.offered <= st.rounds * cfg.expand * K * cfg.q_block
    with pytest.raises(ValueError):
        graph_search(x, idx, q, k_out=K, cfg=SearchConfig(backend="ref"),
                     stats=True)
