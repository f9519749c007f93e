"""The harness: finds a cell's configuration, traffic mix, limits and
per-layer readers by name, runs it, and assembles the result line.

Everything that belongs to one configuration, mix, cell or metric lives
in a file of its own, found by the name that ``BENCHMARK.json`` gives:

  configs/<config>.json    sizes, source, deployment and guarantees
  traffic/<mix>.json       the mix's runner ``kind`` and its parameters
  limits/<cell>.json       the limit of each number that decides ``correct``
  metrics/<metric>.py      ``read(run) -> float | None``: one per-layer metric

A later cell, configuration, mix or metric is a new file and a new entry.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Spec:
    """One cell of ``BENCHMARK.json`` with everything found by its names."""
    cell: dict
    config: dict
    mix: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    def metrics(self, kind: str) -> list[dict]:
        """The cell's metrics of one kind: those that list the cell, or
        that list no cells."""
        group = self.end_to_end if kind == "end_to_end" else self.per_layer
        name = self.cell["name"]
        return [m for m in group if name in m.get("workloads", [name])]


def with_left_out(root: str, here: str = HERE) -> dict:
    """``BENCHMARK.json`` with the entries of ``left_out.json`` added:
    cells kept out of the benchmark while a fault of the program stands,
    for the harness tests and ``control.py``."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    left = _load_json(os.path.join(here, "left_out.json"))
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] = bench[key] + left[key]
    return bench


def load_spec(root: str, workload: str, here: str = HERE,
              bench: dict | None = None) -> Spec:
    """The cell ``workload`` of ``bench`` (by default ``BENCHMARK.json``
    under ``root``) with every file its names lead to."""
    if bench is None:
        bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return Spec(
        cell=cell,
        config=_load_json(os.path.join(root, configs[cell["config"]]["file"])),
        mix=_load_json(os.path.join(here, "traffic", cell["traffic"] + ".json")),
        limits=_load_json(os.path.join(here, "limits", workload + ".json")),
        end_to_end=bench["end_to_end"],
        per_layer=bench["per_layer"],
    )


def reader(name: str, here: str = HERE):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = os.path.join(here, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.chip.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What a per-layer reader sees: the cell's files, the runner's host
    spans and program counters (``record``), the reduced device trace
    (``trace``, None when nothing was traced) and the chip's peaks."""
    spec: Spec
    record: dict
    trace: object | None
    peaks: dict


class Tracer:
    """The JAX profiler over one part of the window. ``window_s`` is the
    host-clock length of the traced part; ``overhead_s`` the time spent
    starting the profiler and collecting its trace."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.window_s = 0.0
        self.overhead_s = 0.0
        self._t0 = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        ts = time.perf_counter()
        jax.profiler.start_trace(self.log_dir)
        self._t0 = time.perf_counter()
        self.overhead_s += self._t0 - ts

    def stop(self) -> None:
        import jax
        if self._t0 is None:
            return
        ts = time.perf_counter()
        self.window_s = ts - self._t0
        self._t0 = None
        jax.profiler.stop_trace()
        self.overhead_s += time.perf_counter() - ts


class Compiles:
    """The programs that JAX compiles, or loads from its persistent cache,
    while ``on``: none should while the window runs."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on = False
        self.seen: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, secs, **kw):
        if self.on and event == self.EVENT:
            self.seen.append((str(kw.get("fun_name")), secs))


def per_layer(spec: Spec, record: dict, trace, peaks: dict) -> dict:
    """Every per-layer metric of the cell that its reader finds."""
    run = Run(spec, record, trace, peaks)
    out = {}
    for m in spec.metrics("per_layer"):
        value = reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
