"""Rehearsals of the chip benchmark on the CPU, at tiny sizes, with the
Pallas kernels in interpret mode. Not part of the tier-1 suite; run with

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

They drive whole runs of each kind of cell below the chip check, the
control and the planted faults (which must come out not correct), the
trace reduction on a small trace recorded on a v5e, and the addition of a
cell, configuration, mix and metric by new files alone.
"""
from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
HERE = os.path.join(ROOT, "benchmarks", "chip")
SEED = 2**31 + 11           # a seed past 32 signed bits

jax = pytest.importorskip("jax")

from benchmarks.chip import (check, control, faults, harness, reference,  # noqa: E402
                             trace, traffic, work)
from benchmarks.chip.run import run_cell  # noqa: E402

CELLS = ["mnist784.build", "audio192.build", "mnist784.search-batch"]


def load(cell: str):
    return harness.load_spec(ROOT, cell, bench=harness.with_left_out(ROOT))


def tiny(cell: str):
    """The cell's spec at a CPU size: every width kept, rows cut, the
    kernels in interpret mode."""
    spec = copy.deepcopy(load(cell))
    c, m = spec.config, spec.mix
    c["data"]["rows"] = 640
    c["descent"]["backend"] = "interpret"
    if m["kind"] == "build":
        m["sample_rows"] = 200
    else:
        c["search_split"] = {"base": 512, "queries": 128}
        c["search"].update(beam=32, rounds=32, q_block=32, seed_width=128,
                           backend="interpret")
        m.update(batch=min(m["batch"], 32))
    return spec


def faulty(cell: str):
    """A size at which the planted faults read past the cell's limits. A
    search over 512 rows is nearly exact from its routed seeds alone, so
    the search cells keep their search settings over 16,384 base rows
    (with the kernels' jnp oracles, for time)."""
    if cell.endswith("build"):
        return tiny(cell)
    spec = copy.deepcopy(load(cell))
    spec.config["data"]["rows"] = 16384 + 128
    spec.config["search_split"] = {"base": 16384, "queries": 128}
    spec.mix.update(batch=min(spec.mix["batch"], 128))
    return spec


V5E = work.peaks("TPU v5 lite")


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    """The CPU has no entry in the table of peaks; the readers get the
    v5e's."""
    monkeypatch.setattr(work, "peaks", lambda kind: V5E)


def run_tiny(cell, trace_on=0, seed=SEED):
    return run_cell(tiny(cell), seed, 0.5, trace_on, jax.devices())[:2]


@pytest.mark.parametrize("cell", CELLS[2:])
@pytest.mark.parametrize("trace_on", [0, 1])
def test_search_cell_runs_correct(cell, trace_on):
    result, checks = run_tiny(cell, trace_on)
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, checks
    assert result["failed"] == 0 and result["attempted"] > 0
    spec = load(cell)
    kind = "per_layer" if trace_on else "end_to_end"
    names = {m["name"] for m in spec.metrics(kind)}
    assert set(result["metrics"]) <= names
    if not trace_on:
        assert set(result["metrics"]) == names
        assert all(v["value"] > 0 for v in result["metrics"].values())
    else:
        assert "busy_s" in result["device"] and "breakdown" in result


@pytest.mark.parametrize("cell", CELLS[:2])
def test_build_cell_runs(cell):
    """A whole build run. At 640 rows the random initial lists repeat an
    id in some rows and a repeat that is a true near neighbour survives
    (PERF.md, Open questions); ``invalid`` must count those repeats and
    nothing else, and every other number must hold."""
    spec = tiny(cell)
    drv = traffic.runner(spec.mix, spec.config, SEED)
    drv.setup()
    drv.window(0.5)
    drv.release()
    numbers, recall = drv.check(reference.Exact())
    repeats = sum(idx.shape[1] - len(np.unique(row))
                  for _, _, idx in drv.answers for row in idx)
    assert numbers["invalid"] == repeats
    for name in ("dist_err", "join_dist_err", "dist_excess"):
        assert numbers[name] <= spec.limits[name], numbers
    assert recall > 0.9
    result, _, record = run_cell(spec, SEED, 0.5, 1, jax.devices())
    assert list(result)[-1] == "checks"
    assert record["window_compiles"] == []
    assert result["metrics"]["dist_evals_per_row.build"]["value"] > 0


@pytest.mark.parametrize("cell", [CELLS[0], CELLS[2]])
def test_control_is_not_correct(cell):
    """The program with its bf16 scoring switched on fails, by the
    distances its joins score before the fp32 re-rank."""
    spec = tiny(cell)
    out = control.readings(spec, SEED, 0.5, "control")
    assert out["correct"] is False
    assert out["numbers"]["join_dist_err"] > spec.limits["join_dist_err"]
    assert out["numbers"]["dist_err"] <= spec.limits["dist_err"]


@pytest.mark.parametrize("fault,number", [("unchanged", "dist_excess"),
                                          ("altered", "dist_err")])
@pytest.mark.parametrize("cell", [CELLS[0], CELLS[2]])
def test_planted_fault_is_not_correct(cell, fault, number):
    """A run with the timed path broken underneath comes out not correct,
    by the number meant to catch that fault."""
    spec = faulty(cell)
    out = control.readings(spec, SEED, 0.5, fault)
    assert out["correct"] is False
    caught = [k for k in (number, "invalid")
              if out["numbers"][k] > spec.limits[k]]
    assert caught, out


def test_trace_reduction_on_recorded_trace():
    """A one-block search (8 queries, 4 rounds) traced on a v5e."""
    from jax.profiler import ProfileData
    path = os.path.join(HERE, "tests", "data", "tiny_search.xplane.pb")
    s = trace.reduce_profile(ProfileData.from_file(path), window_s=1.0)
    assert 0 < s.busy_s < 1.0
    assert s.module_seconds("_search_block") > 0
    assert s.op_count("knn_search_dists") == sum(
        s.shapes["knn_search_dists"].values()) > 1
    assert s.op_seconds("knn_search_dists") > 0
    assert (8, 80) in s.shapes["knn_search_dists"]
    bd = s.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    # a while loop's own time excludes the ops inside it
    total = sum(v[0] for v in s.ops.values())
    assert total <= s.busy_s * 1.0001


def test_reorder_share_leaves_the_profiler_out():
    """The host-clock marks of a traced 70,000-row build on a v5e: the
    profiler's start falls in the second iteration's span, and its
    collection in the build's time."""
    ends = [0.61, 11.154, 11.756, 12.306, 12.854, 13.402, 13.951, 14.502,
            15.056, 15.611, 16.168, 16.725]
    row = {"seconds": 23.53, "iteration_ends": ends}
    read = harness.reader("reorder_share.build")
    spec = load("mnist784.build")
    untraced = read(harness.Run(spec, {"builds": [row]}, None, {}))
    traced = read(harness.Run(
        spec, {"builds": [dict(row, profiler_s=4.85)]}, None, {}))
    assert traced == pytest.approx(100 * (10.544 - 0.55) / 18.68, rel=1e-3)
    assert untraced < traced


def test_op_names():
    ev = "%knn_search_dists.6 = f32[256,80]{1,0:T(8,128)} custom-call(x)"
    assert trace.op_name(ev) == "knn_search_dists"
    assert trace.op_key(ev) == "knn_search_dists.6"
    assert trace.out_shape(ev) == (256, 80)
    assert trace.module_name("jit_greedy_reorder(1234)") == "greedy_reorder"


def test_check_counts_each_broken_guarantee():
    ref_d = np.array([[1.0, 2.0, 3.0]])
    ref_i = np.array([[5, 6, 7]])
    dist = np.array([[1.0, 2.0, 3.0]])
    good, rec = check.compare(dist, ref_i, ref_d, ref_d, ref_i, n=10,
                              self_ids=[0])
    assert good == {"dist_err": 0.0, "invalid": 0.0, "dist_excess": 0.0}
    assert rec == 1.0
    assert check.pair_err(dist * 1.01, [[5, -1, 7]], ref_d, n=10) == \
        pytest.approx(0.01)
    for idx in ([[5, 5, 7]], [[0, 6, 7]], [[5, 6, 10]], [[5, 6, -1]]):
        bad, _ = check.compare(dist, np.array(idx), ref_d, ref_d, ref_i,
                               n=10, self_ids=[0])
        assert bad["invalid"] >= 1, idx
    unsorted, _ = check.compare(dist[:, ::-1], ref_i, ref_d[:, ::-1], ref_d,
                                ref_i, n=10)
    assert unsorted["invalid"] >= 1


def test_every_name_has_its_file():
    bench = harness.with_left_out(ROOT)
    for cell in bench["workloads"]:
        spec = load(cell["name"])
        assert spec.mix["kind"] in traffic.KINDS
        assert set(spec.limits) >= {"dist_err", "join_dist_err", "invalid",
                                    "dist_excess"}
    for m in bench["per_layer"]:
        assert callable(harness.reader(m["name"]))
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]


def test_benchmark_names_only_what_it_holds():
    """Every configuration of ``BENCHMARK.json`` has a cell, and every
    per-layer metric moves one of its end-to-end metrics."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert {c["name"] for c in bench["configs"]} == {
        w["config"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in e2e for m in bench["per_layer"])


def test_a_cell_is_added_by_new_files_alone(tmp_path):
    """A new configuration, mix, cell, limits and per-layer metric, each a
    new file plus an entry, with no existing file edited."""
    root = tmp_path / "repo"
    shutil.copytree(HERE, root / "benchmarks" / "chip")
    here = root / "benchmarks" / "chip"
    bench = harness.with_left_out(ROOT)
    cfg = json.load(open(here / "configs" / "audio192.json"))
    cfg.update(name="dummy64", data=dict(cfg["data"], dim=64))
    (here / "configs" / "dummy64.json").write_text(json.dumps(cfg))
    (here / "traffic" / "build-twice.json").write_text(json.dumps(
        {"kind": "build", "sample_rows": 100, "trace_seconds": 1}))
    (here / "limits" / "dummy64.build-twice.json").write_text(json.dumps(
        {"dist_err": 1e-4, "join_dist_err": 1e-4, "invalid": 0,
         "dist_excess": 1.0}))
    (here / "metrics" / "builds.dummy.py").write_text(
        "def read(run):\n    return len(run.record['builds'])\n")
    bench["configs"].append({"name": "dummy64", "source": "x",
                             "file": "benchmarks/chip/configs/dummy64.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "dummy64.build-twice",
                               "config": "dummy64", "traffic": "build-twice",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "builds.dummy", "unit": "builds",
                               "better": "higher", "source": "host_clock",
                               "layer": "build", "moves": "build_rows_per_s",
                               "workloads": ["dummy64.build-twice"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.load_spec(str(root), "dummy64.build-twice", here=str(here))
    assert spec.config["data"]["dim"] == 64
    spec.config["data"]["rows"] = 256
    names = [m["name"] for m in spec.metrics("per_layer")]
    assert "builds.dummy" in names
    run = harness.Run(spec, {"builds": [{}, {}]}, None, {})
    assert harness.reader("builds.dummy", here=str(here))(run) == 2


def _run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mnist784.search-batch", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_chip_no_result():
    p = _run_py(ROOT, {})
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_bare_benchmark_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip")
    p = _run_py(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout == ""


def test_faults_restore_the_program():
    import repro
    graph_search, nn_descent = faults._modules()
    before = (repro.build_knn_graph, nn_descent.nn_descent_iteration,
              graph_search._search_block)
    for plant in faults.FAULTS.values():
        with plant():
            pass
    assert before == (repro.build_knn_graph, nn_descent.nn_descent_iteration,
                      graph_search._search_block)
