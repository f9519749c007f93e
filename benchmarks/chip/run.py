#!/usr/bin/env python3
"""Chip benchmark of the kNN-graph index: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with a TPU. The cell's
configuration, traffic mix, limits and per-layer readers are found by
name (``harness.py``). A run makes its inputs from ``--seed``, warms
every shape its window uses (set-up, ``setup_s``), measures for
``--seconds`` (with ``--trace 1``: under the profiler for the mix's
``trace_seconds``), then compares the window's answers with the plain
reference. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: each compared number
beside its limit, which also end standard error, after the runner's
record (host spans, counters, and the programs compiled in the window).

Exits 2 without a result where JAX finds no TPU, fewer chips than the
cell asks for, or no program (``src/repro``) beside the benchmark.
The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def fail(msg: str) -> int:
    print(f"run.py: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        return fail("--seed must be non-negative")

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail("no src/repro beside the benchmark: run it from a "
                    "checkout of the repository")
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.chip import harness
    spec = harness.load_spec(ROOT, args.workload)

    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < spec.cell["chips"]:
        return fail(f"the cell needs {spec.cell['chips']} chips, JAX finds "
                    f"{len(devs)}")
    result, checks, record = run_cell(spec, args.seed, args.seconds,
                                      args.trace, devs[:spec.cell["chips"]])
    print("record " + json.dumps(record), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(spec, seed: int, seconds: float, trace: int, devs):
    """One run of the cell on ``devs``: set-up, window, reference check.
    Returns (result line, checks, the runner's record)."""
    from benchmarks.chip import check, harness, reference, traffic, work
    from benchmarks.chip import trace as trace_mod

    drv = traffic.runner(spec.mix, spec.config, seed)
    compiles = harness.Compiles()
    drv.setup()
    setup_s = time.perf_counter() - T_START
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    tracer = harness.Tracer(log_dir) if trace else None
    compiles.on = True
    drv.window(seconds, tracer)
    compiles.on = False
    drv.record["window_compiles"] = compiles.seen
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    e2e = drv.end_to_end()
    attempted, failed = drv.attempted()
    drv.release()
    numbers, recall = drv.check(reference.Exact())
    correct = check.judge(numbers, spec.limits)
    e2e.update(setup_s=setup_s, recall=recall)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed}
    if trace:
        summary = trace_mod.reduce(log_dir, window_s=tracer.window_s)
        trace_mod.remove(log_dir)
        metrics = harness.per_layer(spec, drv.record, summary,
                                    work.peaks(devs[0].device_kind))
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
    else:
        units = {m["name"]: m["unit"] for m in spec.metrics("end_to_end")}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in e2e.items() if k in units}
    result.update(metrics=metrics, device=device)
    checks = {k: {"value": numbers[k], "limit": spec.limits[k]}
              for k in numbers}
    result["checks"] = checks
    return result, checks, drv.record


if __name__ == "__main__":
    sys.exit(main())
