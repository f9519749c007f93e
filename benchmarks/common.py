"""Shared benchmark utilities: timing, CSV rows, result sink."""
from __future__ import annotations

import json
import os
import time

RESULTS_DIR = os.environ.get("BENCH_RESULTS", "results/bench")


def timeit(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall time of fn(*args) with block_until_ready."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


class Sink:
    def __init__(self, name: str):
        self.name = name
        self.rows: list[dict] = []

    def row(self, **kw):
        kw["bench"] = self.name
        self.rows.append(kw)
        print(",".join(f"{k}={v}" for k, v in kw.items()), flush=True)

    def save(self):
        os.makedirs(RESULTS_DIR, exist_ok=True)
        with open(os.path.join(RESULTS_DIR, f"{self.name}.json"), "w") as f:
            json.dump(self.rows, f, indent=2, default=str)
        return self.rows


def flops_per_eval(d: int) -> int:
    """Paper §2 cost model: d subs + d mults + (d-1) adds per evaluation."""
    return 3 * d - 1
