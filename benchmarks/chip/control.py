"""The control and the readings that the limits of ``limits/<cell>.json``
are set from (PERF.md section 2).

The control is the program's own path in the precision below the
configuration's fp32: ``precision="bf16"`` in its build
(``DescentConfig``) and its search (``SearchConfig``, with the store's
bf16 mirror), each of which scores candidates in bfloat16 and re-ranks
what survives in fp32. It runs as a program reading does and goes
through the same comparison.

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 1,2,...,12 --control-seeds 101,102,103 --seconds 2

runs, in one process on the chip, the program's set-up, a short window
and the comparison for each of ``--seeds``, then the control for each
of ``--control-seeds``, then the program under each planted fault of
``faults.py`` for each of ``--fault-seeds``; one JSON line per reading.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def lowered(config: dict) -> dict:
    """The configuration with the program's bf16 scoring switched on."""
    config = copy.deepcopy(config)
    for part in ("descent", "search"):
        if part in config:
            config[part]["precision"] = "bf16"
    return config


def readings(spec, seed: int, seconds: float, kind: str) -> dict:
    """The compared numbers of one seed: ``kind`` "program", "control",
    or the name of a fault of ``faults.py`` planted under the program."""
    from benchmarks.chip import check, faults, reference, traffic
    config = lowered(spec.config) if kind == "control" else spec.config
    drv = traffic.runner(spec.mix, config, seed)
    plant = faults.FAULTS.get(kind, contextlib.nullcontext)
    with plant():
        # one process compiles once: a build cell's warm-up build is
        # left out, a serving cell's set-up builds its index
        drv.inputs() if spec.mix["kind"] == "build" else drv.setup()
        drv.window(seconds)
    drv.release()
    numbers, recall = drv.check(reference.Exact())
    return {"seed": seed, "kind": kind, "numbers": numbers,
            "recall": recall, "correct": check.judge(numbers, spec.limits)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="",
                    help="seeds for each fault of faults.py")
    ap.add_argument("--faults", default="unchanged,altered")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax

    from benchmarks.chip import harness
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    spec = harness.load_spec(ROOT, args.workload,
                             bench=harness.with_left_out(ROOT))
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    fseeds = [int(s) for s in args.fault_seeds.split(",") if s]
    runs = ([(s, "program") for s in seeds] + [(s, "control") for s in cseeds]
            + [(s, f) for f in args.faults.split(",") if f for s in fseeds])
    for seed, kind in runs:
        print(json.dumps(readings(spec, seed, args.seconds, kind)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
