"""Reduction of a JAX profiler trace (``.xplane.pb``) to device busy time,
per-op and per-program device time, and the longest idle gaps.

Device planes are named ``/device:TPU:<i>``. On each, the ``XLA Ops``
line holds one event per operation that ran: a Pallas kernel appears
under the ``name`` it was given (``knn_join_dists``, ``knn_search_dists``,
...), and the ``XLA Modules`` line holds one event per run of a jitted
program, named ``jit_<function>(<id>)``. Busy time is the union of the
op intervals, averaged over the device planes. Idle gaps are named by
the innermost host event (``/host:CPU`` plane) that spans the gap's
middle, i.e. what the host was doing while the device waited.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_MODULE_NAME = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
_OP_NAME = re.compile(r"^%?([^ =]+?)(?:\.\d+)?(?: =|$)")
_OP_KEY = re.compile(r"^%?([^ =]+)")
_OUT_SHAPE = re.compile(r"= \(?[a-z0-9]+\[([0-9,]*)\]")


def module_name(event_name: str) -> str:
    """``jit_greedy_reorder(12)`` -> ``greedy_reorder``."""
    return _MODULE_NAME.match(event_name).group(1)


def op_name(event_name: str) -> str:
    """``%knn_search_dists.6 = f32[256,80]{...} custom-call(...)`` ->
    ``knn_search_dists``: the op's name without its per-program number."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def op_key(event_name: str) -> str:
    """The op's name with its per-program number (``fusion.36``)."""
    m = _OP_KEY.match(event_name)
    return m.group(1) if m else event_name


def out_shape(event_name: str) -> tuple[int, ...]:
    """The (first) output shape of an op, from its HLO text."""
    m = _OUT_SHAPE.search(event_name)
    if not m or not m.group(1):
        return ()
    return tuple(int(v) for v in m.group(1).split(","))


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Summary:
    busy_s: float                   # mean over devices of the op union
    window_s: float                 # length of the traced window
    ops: dict[str, list]            # "program/op.N" -> [self s, count, name]
    shapes: dict[str, dict]         # op name -> {output shape: count}
    modules: dict[str, list]        # program name -> [seconds, count]
    gaps: list[list]                # [[host activity, seconds], ...]

    def op_seconds(self, name: str) -> float:
        """Device time of every op of this name, in any program."""
        return sum(v[0] for v in self.ops.values() if v[2] == name)

    def op_count(self, name: str, shape: tuple | None = None) -> int:
        if shape is not None:
            return self.shapes.get(name, {}).get(tuple(shape), 0)
        return sum(v[1] for v in self.ops.values() if v[2] == name)

    def module_seconds(self, name: str) -> float:
        return self.modules.get(name, [0.0, 0])[0]

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][0])[:top]
        return {"device_ops": [[k, v[0]] for k, v in ops],
                "idle_gaps": self.gaps[:top]}


def _host_events(planes) -> list[tuple[int, int, str]]:
    out = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            out.extend((e.start_ns, e.end_ns, e.name) for e in line.events)
    return out


def _name_gap(s: int, e: int, host) -> str:
    mid = (s + e) // 2
    spans = [(he - hs, name) for hs, he, name in host if hs <= mid <= he]
    return min(spans)[1] if spans else "(no host event)"


def _self_times(events):
    """(start, end, name, self ns) of nested events: a while loop's time
    less the ops inside it."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    stack: list[list] = []
    for s, e, name in events:
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()))
        row = [s, e, name, e - s]
        if stack:
            stack[-1][3] -= e - s
        stack.append(row)
    out.extend(tuple(r) for r in stack)
    return out


def reduce_profile(profile, window_s: float, top: int = 10) -> Summary:
    """Reduce a ``jax.profiler.ProfileData``."""
    planes = list(profile.planes)
    devices = [p for p in planes if p.name.startswith("/device:TPU:")
               and p.name[len("/device:TPU:"):].isdigit()]
    ops: dict[str, list] = {}
    shapes: dict[str, dict] = {}
    modules: dict[str, list] = {}
    busy_ns = 0
    gaps: list[tuple[int, int]] = []
    for p in devices:
        lines = {line.name: line for line in p.lines}
        mods = []
        if MODULES_LINE in lines:
            for e in lines[MODULES_LINE].events:
                name = module_name(e.name)
                a = modules.setdefault(name, [0.0, 0])
                a[0] += e.duration_ns / 1e9
                a[1] += 1
                mods.append((e.start_ns, e.end_ns, name))
        mods.sort()
        starts = [m[0] for m in mods]
        spans = []
        if OPS_LINE in lines:
            raw = [(e.start_ns, e.end_ns, e.name)
                   for e in lines[OPS_LINE].events]
            for s, e, name, self_ns in _self_times(raw):
                spans.append((s, e))
                j = bisect.bisect_right(starts, s) - 1
                prog = mods[j][2] if j >= 0 and mods[j][1] >= e else "?"
                base = op_name(name)
                a = ops.setdefault(f"{prog}/{op_key(name)}", [0.0, 0, base])
                a[0] += self_ns / 1e9
                a[1] += 1
                sh = shapes.setdefault(base, {})
                shape = out_shape(name)
                sh[shape] = sh.get(shape, 0) + 1
        merged = _union(spans)
        busy_ns += sum(e - s for s, e in merged)
        gaps.extend((a[1], b[0]) for a, b in zip(merged, merged[1:]))
    n_dev = max(len(devices), 1)
    gaps.sort(key=lambda g: g[0] - g[1])
    host = _host_events(planes) if gaps else []
    named = [[_name_gap(s, e, host), (e - s) / 1e9] for s, e in gaps[:top]]
    return Summary(busy_ns / 1e9 / n_dev, window_s, ops, shapes, modules,
                   named)


def reduce(log_dir: str, window_s: float) -> Summary:
    """Reduce the one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} traces under {log_dir}")
    return reduce_profile(ProfileData.from_file(files[0]), window_s)


def remove(log_dir: str) -> None:
    shutil.rmtree(log_dir, ignore_errors=True)


def idle_pct(run) -> float | None:
    """Per-layer reader: the device's idle share of the traced window."""
    t = run.trace
    if t is None or t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
