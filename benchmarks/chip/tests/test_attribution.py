"""The attribution of a chip trace to the program's spans and scopes
(``attribution.py``): the ``tf_op`` reader on traces recorded on a v5e,
idle attribution and gap names on synthetic intervals, and a whole
attribution run at a CPU size. Not part of the tier-1 suite; run with

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import copy
import os

import pytest

from benchmarks.chip import attribution as A
from benchmarks.chip import harness, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DATA = os.path.join(ROOT, "benchmarks", "chip", "tests", "data")
# a one-block search (8 queries, 4 rounds) before the spans and scopes
OLD = os.path.join(DATA, "tiny_search.xplane.pb")
# two store searches (one 8-query block each, beam 32, 4 rounds) over a
# 1,024 x 784 routed store, with spans, scopes and the window mark
SPANS = os.path.join(DATA, "tiny_spans.xplane.pb")


def synthetic(ops, spans, host=(), window=(0, 50)):
    """A trace of one device with ``ops`` (start, end, tf_op) in one run
    of the search program, and the host events ``spans`` (start, end,
    span name) and ``host`` (start, end, event name)."""
    t = A.Trace.__new__(A.Trace)
    t.devices = [{"ops": [(s, e, "", tf_op) for s, e, tf_op in ops],
                  "modules": [(0, 10**6, A.PROGRAM)]}]
    t.host = [(s, e, A.PREFIX + n) for s, e, n in spans] + list(host)
    t.window = window
    return t


def test_names_are_the_programs():
    """The reduction reads the names the program writes."""
    from repro.core import spans
    assert A.PREFIX == spans.PREFIX
    assert A.SCOPES == tuple(spans.SCOPES)


def test_tf_op_reader_on_recorded_trace():
    t = A.Trace(OLD)
    tf_ops = {op[3].rstrip(":") for d in t.devices for op in d["ops"]}
    assert "jit(_search_block)/while/body/gather" in tf_ops
    assert len(t.devices) == 1 and t.window[1] > t.window[0] > -1


def test_idle_attribution_sums_to_idle():
    """Idle pieces go to the innermost span over them; the pieces under
    no span to OUTSIDE; together they are the idle share."""
    gather = "jit(_search_block)/while/body/gather/gather:"
    t = synthetic(
        ops=[(0, 10, gather), (15, 20, gather), (30, 40, gather)],
        spans=[(5, 44, "store.search"), (12, 27, "search.route")],
        host=[(41, 49, "ReadSyncFlag")])
    out = A.attribute(t)
    assert out["idle_s"] == pytest.approx(
        {"store.search": 9e-12, "search.route": 10e-12, A.OUTSIDE: 6e-12})
    assert out["under_s"] == pytest.approx(
        {"store.search": 19e-12, "search.route": 10e-12})
    assert out["idle_pct"] == pytest.approx(50.0)
    assert sum(out["idle_s"].values()) == pytest.approx(
        out["idle_pct"] / 100 * out["window_s"])
    assert out["metrics"]["idle_program.search"] == pytest.approx(38.0)
    assert out["metrics"]["idle_route.search"] == pytest.approx(20.0)
    assert out["metrics"]["gather_share.search"] == pytest.approx(100.0)
    # a gap under a span takes the innermost span's name; one under none
    # takes the host event's, as trace.py names it
    assert [g[0] for g in out["idle_gaps"]] == [
        "repro/search.route", "ReadSyncFlag", "repro/search.route"]


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(_search_block)/while/body/gather:", A.NO_SCOPE),   # the op
    ("jit(_search_block)/while/body/gather/jit(clip)/min:", "gather"),
    ("jit(_search_block)/seed/jit(knn_merge_blocked)/knn_merge:", "seed"),
    ("jit(_search_block)/while/body/merge/knn_merge:", "merge"),
    ("jit(_search_block)/while:", A.NO_SCOPE),
    ("jit(gather)/gather:", A.NO_SCOPE),
])
def test_scope_of(tf_op, scope):
    assert A.scope_of(tf_op) == scope


def test_scope_seconds_count_self_time():
    """A loop's own time is what its ops leave; ops of other programs
    are not counted."""
    body = "jit(_search_block)/while/body/"
    t = synthetic(ops=[(0, 100, "jit(_search_block)/while:"),
                       (0, 10, body + "gather/gather:"),
                       (10, 30, body + "merge/knn_merge:"),
                       (30, 95, body + "score/knn_search_dists:")],
                  spans=[], window=(0, 100))
    t.devices[0]["modules"] = [(0, 100, A.PROGRAM), (200, 300, "other")]
    t.devices[0]["ops"].append((200, 300, "", "jit(other)/add:"))
    scopes = A.scope_seconds(t)
    assert scopes == pytest.approx({"gather": 10e-12, "merge": 20e-12,
                                    "score": 65e-12, A.NO_SCOPE: 5e-12})


def test_readers_on_recorded_spans_trace():
    """A trace recorded on a v5e with the spans and scopes: every reading
    is there, the idle adds up, and the scopes cover the program."""
    from jax.profiler import ProfileData
    t = A.Trace(SPANS)
    out = A.attribute(t)
    assert set(out["metrics"]) == {
        "idle_program.search", "idle_route.search", "gather_share.search",
        "merge_share.search"}
    assert all(0 < v < 100 for v in out["metrics"].values()), out["metrics"]
    assert {n for _, _, n in t.spans()} == {
        "store.search", "search.admit", "search.route", "search.dispatch"}
    assert sum(out["idle_s"].values()) == pytest.approx(
        out["idle_pct"] / 100 * out["window_s"])
    assert set(out["scope_s"]) >= {"seed", "select", "gather", "score",
                                   "merge", "rerank"}
    assert out["scope_s"].get(A.NO_SCOPE, 0) < 0.05 * out["program_s"]
    assert any(g[0].startswith(A.PREFIX) for g in out["idle_gaps"])
    # the idle share agrees with trace.py's over the same window
    s = trace.reduce_profile(ProfileData.from_file(SPANS),
                             window_s=out["window_s"])
    assert 100 * (1 - s.busy_s / s.window_s) == pytest.approx(
        out["idle_pct"], abs=0.1)


def test_measure_at_cpu_size():
    """The whole attribution run of the search cell, cut to a CPU size:
    both windows run and the traced one is found by its mark."""
    import jax
    spec = copy.deepcopy(harness.load_spec(ROOT, "mnist784.search-batch"))
    c = spec.config
    c["data"]["rows"] = 640
    c["descent"]["backend"] = "interpret"
    c["search_split"] = {"base": 512, "queries": 128}
    c["search"].update(beam=32, rounds=32, q_block=32, seed_width=128,
                       backend="interpret")
    spec.mix.update(batch=32)
    assert jax.devices()[0].platform == "cpu"
    out = A.measure(spec, 2**31 + 11, 0.5)
    assert out["search_qps_off"] > 0 and out["search_qps_on"] > 0
    assert 0.5 <= out["window_s"] < 5
