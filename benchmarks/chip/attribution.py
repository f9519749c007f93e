"""Attribution of a chip trace to the program's own names: the device's
idle time to the host spans over it, and the fused search's busy time to
the named scopes of its ops.

The program writes host spans ``repro/<name>`` (``repro.core.spans``)
and runs the phases of ``_search_block`` under ``jax.named_scope``
(``SCOPES``). The trace carries a scope in each device op's ``tf_op``
(``jit(_search_block)/while/body/gather/...``), a stat of the op's event
metadata, which ``jax.profiler.ProfileData`` does not expose; so the
``.xplane.pb`` is read here through a minimal schema of the profiler's
``XSpace`` protocol buffer (planes, lines, events, event metadata and
stats), with the ``google.protobuf`` runtime.

- Idle: the traced window less the union of the device's op intervals.
  Each idle piece goes to the innermost ``repro/`` span over it
  (``idle_s``), or to ``OUTSIDE`` where no span is; ``under_s`` is the
  idle under any span of a name, its children included.
- Busy: the self time of every op of a ``_search_block`` run, by the
  first scope on its ``tf_op`` path.
- ``idle_gaps``: the longest idle gaps, named by the innermost
  ``repro/`` span over the gap's middle, else by the host event there as
  ``trace.py`` names them.

The window is the host event ``WINDOW`` where the trace has one (the
runner below writes it around the traced part), else the profiler's own
start and stop.

    PYTHONPATH=src python3 -m benchmarks.chip.attribution \
        --workload <cell> --seed <n> --seconds <s>

runs the cell's set-up, one window with the profiler off and one traced
whole, on the chip, and prints one JSON object: ``search_qps`` of each,
the attribution of the traced window, and ``device_idle`` of the same
trace by ``trace.py``.
"""
from __future__ import annotations

import argparse
import bisect
import functools
import glob
import json
import os
import sys
import tempfile

from benchmarks.chip import harness
from benchmarks.chip.trace import (MODULES_LINE, OPS_LINE, _name_gap,
                                   _self_times, _union, module_name)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PREFIX = "repro/"
WINDOW = "bench/window"
OUTSIDE = "(outside the program)"
NO_SCOPE = "(no scope)"
PROGRAM = "_search_block"
SCOPES = ("seed", "select", "gather", "score", "merge", "rerank")


@functools.cache
def _space_class():
    """The message class of a minimal ``XSpace`` schema: the fields read
    here, under their numbers in ``tsl/profiler/protobuf/xplane.proto``."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64 = F.TYPE_INT64, F.TYPE_UINT64
    text, msg = F.TYPE_STRING, F.TYPE_MESSAGE
    schema = {
        "Stat": [("metadata_id", 1, i64), ("uint64_value", 3, u64),
                 ("int64_value", 4, i64), ("str_value", 5, text)],
        "Event": [("metadata_id", 1, i64), ("offset_ps", 2, i64),
                  ("duration_ps", 3, i64)],
        "Line": [("name", 2, text), ("timestamp_ns", 3, i64),
                 ("events", 4, "Event", many)],
        "EventMetadata": [("id", 1, i64), ("name", 2, text),
                          ("stats", 5, "Stat", many)],
        "StatMetadata": [("id", 1, i64), ("name", 2, text)],
        "EventEntry": [("key", 1, i64), ("value", 2, "EventMetadata")],
        "StatEntry": [("key", 1, i64), ("value", 2, "StatMetadata")],
        "Plane": [("name", 2, text), ("lines", 3, "Line", many),
                  ("event_metadata", 4, "EventEntry", many),
                  ("stat_metadata", 5, "StatEntry", many),
                  ("stats", 6, "Stat", many)],
        "Space": [("planes", 1, "Plane", many)],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name="xspace_min.proto", package="xspace_min", syntax="proto3")
    for name, fields in schema.items():
        m = fd.message_type.add(name=name)
        for fname, number, kind, *label in fields:
            f = m.field.add(name=fname, number=number,
                            label=label[0] if label else one)
            if isinstance(kind, str):
                f.type, f.type_name = msg, ".xspace_min." + kind
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("xspace_min.Space"))


class Trace:
    """One ``.xplane.pb``: per device plane the ops (start ps, end ps,
    HLO text, ``tf_op``) and program runs (start ps, end ps, name); the
    host events of every thread (start ps, end ps, name); and the
    window."""

    def __init__(self, path: str):
        with open(path, "rb") as f:
            space = _space_class().FromString(f.read())
        self.devices: list[dict] = []
        self.host: list[tuple[int, int, str]] = []
        bounds = {}
        for plane in space.planes:
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            if plane.name == "Task Environment":
                bounds = {stat_names.get(s.metadata_id): s.int64_value
                          or s.uint64_value for s in plane.stats}
            device = plane.name.startswith("/device:TPU:") \
                and plane.name[len("/device:TPU:"):].isdigit()
            if not device and not plane.name.startswith("/host:"):
                continue
            meta = {}
            for e in plane.event_metadata:
                tf_op = ""
                for s in e.value.stats:
                    if stat_names.get(s.metadata_id) == "tf_op":
                        tf_op = s.str_value
                meta[e.key] = (e.value.name, tf_op)
            lines = {}
            for line in plane.lines:
                t0 = line.timestamp_ns * 1000
                lines[line.name] = [
                    (t0 + ev.offset_ps, t0 + ev.offset_ps + ev.duration_ps)
                    + meta.get(ev.metadata_id, ("", ""))
                    for ev in line.events]
            if device:
                self.devices.append({
                    "ops": lines.get(OPS_LINE, []),
                    "modules": sorted((s, e, module_name(n)) for s, e, n, _
                                      in lines.get(MODULES_LINE, []))})
            else:
                self.host.extend((s, e, n) for evs in lines.values()
                                 for s, e, n, _ in evs)
        marks = [(s, e) for s, e, n in self.host if n == WINDOW]
        if marks:
            self.window = marks[0]
        else:
            span_ns = bounds.get("profile_stop_time", 0) \
                - bounds.get("profile_start_time", 0)
            self.window = (0, span_ns * 1000)

    def spans(self) -> list[tuple[int, int, str]]:
        """The program's host spans: (start, end, name without prefix)."""
        return [(s, e, n[len(PREFIX):]) for s, e, n in self.host
                if n.startswith(PREFIX)]


def idle_intervals(ops, window) -> list[tuple[int, int]]:
    """The window less the union of the op intervals."""
    w0, w1 = window
    out, t = [], w0
    for s, e in _union([(max(s, w0), min(e, w1)) for s, e, *_ in ops
                        if e > w0 and s < w1]):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < w1:
        out.append((t, w1))
    return out


def attribute_idle(idle, spans) -> tuple[dict, dict]:
    """(innermost, under): idle time per span name, by the innermost
    span over each piece (``OUTSIDE`` where none is), and by every span
    over it. ``idle`` is disjoint; ``spans`` are (start, end, name)."""
    cuts = sorted({t for s, e in idle for t in (s, e)}
                  | {t for s, e, _ in spans for t in (s, e)})
    opens = sorted(spans)
    innermost: dict[str, int] = {OUTSIDE: 0}
    under: dict[str, int] = {}
    active: list[tuple[int, int, str]] = []
    i = j = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(idle) and idle[i][1] <= a:
            i += 1
        if i == len(idle):
            break
        while j < len(opens) and opens[j][0] <= a:
            active.append(opens[j])
            j += 1
        active = [sp for sp in active if sp[1] > a]
        if idle[i][0] > a:
            continue                    # [a, b) is busy
        if not active:
            innermost[OUTSIDE] += b - a
            continue
        inner = min(active, key=lambda sp: sp[1] - sp[0])[2]
        innermost[inner] = innermost.get(inner, 0) + b - a
        for name in {sp[2] for sp in active}:
            under[name] = under.get(name, 0) + b - a
    return innermost, under


def scope_of(tf_op: str) -> str:
    """The first scope of ``SCOPES`` on the op's path (the last element
    of the path is the op itself), else ``NO_SCOPE``."""
    for part in tf_op.rstrip(":").split("/")[:-1]:
        if part in SCOPES:
            return part
    return NO_SCOPE


def scope_seconds(trace: Trace, program: str = PROGRAM) -> dict:
    """Device self seconds of the ops of each run of ``program``, by
    scope, averaged over the device planes."""
    out: dict[str, float] = {}
    for d in trace.devices:
        mods = d["modules"]
        starts = [m[0] for m in mods]
        ops = [(s, e, tf_op) for s, e, _, tf_op in d["ops"]]
        for s, e, tf_op, self_ps in _self_times(ops):
            j = bisect.bisect_right(starts, s) - 1
            if j < 0 or mods[j][1] < e or mods[j][2] != program:
                continue
            key = scope_of(tf_op)
            out[key] = out.get(key, 0.0) + self_ps / 1e12
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in out.items()}


def attribute(trace: Trace, top: int = 10) -> dict:
    """Idle and busy time of ``trace`` by the program's own names, and
    the per-layer readings taken from them (``metrics``, in %)."""
    w0, w1 = trace.window
    window_ps = max(w1 - w0, 1)
    n = max(len(trace.devices), 1)
    spans = trace.spans()
    idle_ps: dict[str, float] = {}
    under_ps: dict[str, float] = {}
    gaps: list[tuple[int, int]] = []
    for d in trace.devices:
        idle = idle_intervals(d["ops"], trace.window)
        gaps.extend(idle)
        inner, under = attribute_idle(idle, spans)
        for k, v in inner.items():
            idle_ps[k] = idle_ps.get(k, 0) + v / n
        for k, v in under.items():
            under_ps[k] = under_ps.get(k, 0) + v / n
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:top]:
        mid = (s + e) // 2
        over = [(he - hs, PREFIX + name) for hs, he, name in spans
                if hs <= mid <= he]
        named.append([min(over)[1] if over else _name_gap(s, e, trace.host),
                      (e - s) / 1e12])
    scopes = scope_seconds(trace)
    program_s = sum(scopes.values())
    pct = lambda ps: 100.0 * ps / window_ps
    metrics = {}
    if trace.devices:
        metrics["idle_program.search"] = pct(under_ps.get("store.search", 0))
        metrics["idle_route.search"] = pct(under_ps.get("search.route", 0))
    if program_s > 0:
        metrics["gather_share.search"] = \
            100.0 * scopes.get("gather", 0.0) / program_s
        metrics["merge_share.search"] = \
            100.0 * scopes.get("merge", 0.0) / program_s
    return {"window_s": window_ps / 1e12,
            "idle_pct": pct(sum(idle_ps.values())),
            "idle_s": {k: v / 1e12 for k, v in idle_ps.items()},
            "under_s": {k: v / 1e12 for k, v in under_ps.items()},
            "scope_s": scopes, "program_s": program_s,
            "metrics": metrics, "idle_gaps": named}


def find(log_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} traces under {log_dir}")
    return files[0]


def measure(spec, seed: int, seconds: float) -> dict:
    """Set-up of a batch cell, one window with the profiler off and one
    traced whole; ``search_qps`` of each, the attribution of the traced
    window, and ``device_idle``, ``trace.py``'s idle share of the same
    trace over the host-clock window."""
    from benchmarks.chip import traffic
    from benchmarks.chip import trace as trace_mod
    mix = dict(spec.mix, trace_seconds=float("inf"))    # trace it whole
    drv = traffic.runner(mix, spec.config, seed)
    drv.setup()
    drv.window(seconds)
    out = {"seed": seed, "search_qps_off": drv.end_to_end()["search_qps"]}
    log_dir = tempfile.mkdtemp(prefix="bench_attr_")
    try:
        tracer = Marked(log_dir)
        drv.window(seconds, tracer)
        out["search_qps_on"] = drv.end_to_end()["search_qps"]
        summary = trace_mod.reduce(log_dir, window_s=tracer.window_s)
        out["device_idle"] = trace_mod.idle_pct(
            harness.Run(spec, drv.record, summary, {}))
        out.update(attribute(Trace(find(log_dir))))
    finally:
        trace_mod.remove(log_dir)
    drv.release()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    if jax.devices()[0].platform != "tpu":
        print("attribution.py: JAX finds no TPU", file=sys.stderr)
        return 2
    spec = harness.load_spec(ROOT, args.workload)
    if spec.mix["kind"] != "batch":
        print("attribution.py: only the store's search has spans",
              file=sys.stderr)
        return 2
    print(json.dumps(measure(spec, args.seed, args.seconds)), flush=True)
    return 0


class Marked(harness.Tracer):
    """``harness.Tracer`` with the host event ``WINDOW`` written around
    the traced part, which places the host-clock window in the trace."""

    _mark = None

    def start(self) -> None:
        import jax
        super().start()
        self._mark = jax.profiler.TraceAnnotation(WINDOW)
        self._mark.__enter__()

    def stop(self) -> None:
        if self._mark is not None:
            self._mark.__exit__(None, None, None)
            self._mark = None
        super().stop()


if __name__ == "__main__":
    sys.exit(main())
