"""Persistence benchmark: snapshot -> fresh-process restore parity and
zero-rebuild cold start (the CI receipt for core/persist.py).

Modes (``python benchmarks/bench_persist.py --mode ...``):

  * ``smoke`` (default) — the gated CI lane: a build child builds a small
    datastore with the full serving state attached (int8 mirror +
    router), runs a streamed insert + delete so the snapshot carries
    tombstones and post-build rows, snapshots it and answers a query
    batch; then a restore child restores IN A FRESH PROCESS (nothing
    cached, the honest cold start) and answers the same batch. The
    parent never touches JAX — on a TPU host only one process can hold
    the chip, so each child holds it in turn. Emits ``results/bench/persist.json``
    with ``ids_bitident`` / ``dists_bitident`` (restored search results
    compared to the live store's, float bits and all), ``rebuild_s``
    (what a restart pays without persistence: the full NN-Descent build
    including compile) vs ``restore_s`` (what it pays with: array load +
    device put), and ``cold_start_speedup``. Gated by check_gate.py
    --persist (bit-identical AND speedup >= the pinned floor). An
    informative ``smoke_persist_qfirst`` row measures the quantized-first
    cold start (serve from the int8 mirror while fp32 loads) — not gated.

  * ``build-child`` / ``restore-child`` — internal: the two halves of
    the smoke lane. The build child builds and snapshots into ``--dir``;
    the restore child restores from it. Each regenerates the
    (deterministic, seeded) query batch, searches, and prints one
    ``BUILD_RESULT`` / ``RESTORE_RESULT`` JSON line for the parent to
    compare bit-for-bit.

The snapshot directory lands under results/bench/persist_smoke/ so the
CI artifact picks up its manifest.json next to the bench JSON.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _queries(n: int, d: int, q_n: int):
    """Deterministic query batch derived from the (seeded) smoke corpus —
    regenerated identically on both sides of the process boundary."""
    import jax
    from repro.core import datasets
    x = datasets.clustered(jax.random.key(20), n, d, 16)
    q = x[:q_n] + 0.01 * jax.random.normal(jax.random.key(23), (q_n, d))
    return x, q


def _search(ds, q, k_out: int):
    import jax
    dist, idx = ds.store.search(q, k_out=k_out, key=jax.random.key(24))
    return (np.asarray(dist, np.float32).view(np.int32),
            np.asarray(idx, np.int32))


def _build_live(n: int, d: int, k: int):
    """Full build (the cost persistence avoids) + post-build mutations
    (so the snapshot carries tombstones, streamed rows, and the
    incrementally-maintained mirror/router — the real online state)."""
    import jax
    import jax.numpy as jnp
    from repro.core.nn_descent import DescentConfig
    from repro.core.router import RouterConfig
    from repro.serve.knn_lm import MutableKNNDatastore
    x, _ = _queries(n, d, 0)
    vals = jnp.arange(n, dtype=jnp.int32)
    t0 = time.perf_counter()
    ds = MutableKNNDatastore.build(
        x, vals, k=k, cfg=DescentConfig(k=k, rho=1.0, max_iters=8),
        precision="int8",
        router=RouterConfig(n_centroids=32, members=32),
        key=jax.random.key(21))
    jax.block_until_ready(ds.store.nl.idx)
    rebuild_s = time.perf_counter() - t0
    ds, _ = ds.delete(jnp.arange(16, dtype=jnp.int32))
    extra = x[:32] + 0.05 * jax.random.normal(jax.random.key(22), (32, d))
    ds, _ = ds.append(extra, jnp.arange(32, dtype=jnp.int32) + n,
                      key=jax.random.key(25))
    jax.block_until_ready(ds.store.nl.idx)
    return ds, rebuild_s


def _dir_mb(path: str) -> float:
    total = 0
    for root, _, files in os.walk(path):
        total += sum(os.path.getsize(os.path.join(root, f)) for f in files)
    return total / 1e6


def run_restore_child(snap_dir: str, n: int, d: int, q_n: int,
                      k_out: int, qfirst: bool) -> None:
    """Fresh-process restore: nothing from the builder process survives
    except the snapshot directory. Prints RESTORE_RESULT for the parent."""
    import jax
    from repro.serve.knn_lm import MutableKNNDatastore
    t0 = time.perf_counter()
    ds = MutableKNNDatastore.restore(snap_dir)
    jax.block_until_ready(ds.store.x)
    restore_s = time.perf_counter() - t0
    _, q = _queries(n, d, q_n)
    bits, ids = _search(ds, q, k_out)
    out = {
        "restore_s": restore_s,
        "ids": ids.ravel().tolist(),
        "dist_bits": bits.ravel().tolist(),
        "live": ds.build_stats.get("live"),
        "tombstones": ds.build_stats.get("tombstones"),
        "restored_step": ds.build_stats.get("restored_step"),
    }
    if qfirst:
        t0 = time.perf_counter()
        dq = MutableKNNDatastore.restore(snap_dir, quantized_first=True)
        jax.block_until_ready(dq.store.x)
        qfirst_s = time.perf_counter() - t0
        qbits, qids = _search(dq, q, k_out)
        dq = dq.finish_fp32()
        fbits, fids = _search(dq, q, k_out)
        out["qfirst"] = {
            "restore_s": qfirst_s,
            # quantized-accurate serving while fp32 streams in: overlap
            # with the exact answer is informative, not gated
            "ids_overlap": float((qids == ids).mean()),
            # after finish_fp32 the swap must be exact again
            "fp32_ids_bitident": bool((fids == ids).all()),
            "fp32_dists_bitident": bool((fbits == bits).all()),
        }
    print("RESTORE_RESULT " + json.dumps(out), flush=True)


def run_build_child(snap_root: str, n: int, d: int, q_n: int, k: int,
                    k_out: int) -> None:
    """Build + mutate + snapshot into ``snap_root``, answer the query
    batch on the live store. Prints BUILD_RESULT for the parent."""
    ds, rebuild_s = _build_live(n, d, k)
    step_dir = ds.snapshot(snap_root, keep=1)
    _, q = _queries(n, d, q_n)
    bits, ids = _search(ds, q, k_out)
    print("BUILD_RESULT " + json.dumps({
        "rebuild_s": rebuild_s,
        "snapshot_mb": _dir_mb(step_dir),
        "ids": ids.ravel().tolist(),
        "dist_bits": bits.ravel().tolist(),
    }), flush=True)


def _run_child(mode: str, snap_root: str, tag: str, args: list) -> dict:
    """Run one half of the smoke lane in its own process; returns the
    JSON it printed after ``tag``."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(_REPO, "src"), _REPO,
                    env.get("PYTHONPATH")) if p)
    cmd = [sys.executable, os.path.abspath(__file__), "--mode", mode,
           "--dir", snap_root] + args
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=_REPO, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(
            f"{mode} failed (rc={proc.returncode}):\n{proc.stderr}")
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith(tag + " ")]
    if not lines:
        raise RuntimeError(f"{mode} printed no {tag}:\n{proc.stdout}")
    return json.loads(lines[-1][len(tag) + 1:])


def run_smoke(n: int = 4096, d: int = 16, q_n: int = 256, k: int = 10,
              k_out: int = 10, qfirst: bool = True) -> list:
    from benchmarks.common import RESULTS_DIR, Sink
    sink = Sink("persist")
    snap_root = os.path.join(RESULTS_DIR, "persist_smoke")
    # a stale snapshot from an earlier (differently-sized) run would both
    # win the keep=1 retention race and be what the child restores — the
    # lane must only ever see the snapshot written by THIS run
    shutil.rmtree(snap_root, ignore_errors=True)

    sizes = ["--n", str(n), "--d", str(d), "--q", str(q_n),
             "--k-out", str(k_out)]
    built = _run_child("build-child", snap_root, "BUILD_RESULT",
                       sizes + ["--k", str(k)])
    res = _run_child("restore-child", snap_root, "RESTORE_RESULT",
                     sizes + (["--qfirst"] if qfirst else []))
    ids_live = np.asarray(built["ids"], np.int32).reshape(q_n, k_out)
    bits_live = np.asarray(built["dist_bits"], np.int32).reshape(q_n, k_out)
    rebuild_s = float(built["rebuild_s"])
    snapshot_mb = float(built["snapshot_mb"])

    ids_child = np.asarray(res["ids"], np.int32).reshape(ids_live.shape)
    bits_child = np.asarray(res["dist_bits"],
                            np.int32).reshape(bits_live.shape)
    restore_s = float(res["restore_s"])
    sink.row(op="smoke_persist", n=n, d=d, q=q_n, k=k, k_out=k_out,
             precision="int8", router_centroids=32,
             live=res["live"], tombstones=res["tombstones"],
             restored_step=res["restored_step"],
             ids_bitident=bool((ids_child == ids_live).all()),
             dists_bitident=bool((bits_child == bits_live).all()),
             rebuild_s=round(rebuild_s, 3),
             restore_s=round(restore_s, 3),
             cold_start_speedup=round(rebuild_s / max(restore_s, 1e-9), 2),
             snapshot_mb=round(snapshot_mb, 3))
    if "qfirst" in res:
        qf = res["qfirst"]
        sink.row(op="smoke_persist_qfirst",
                 restore_s=round(float(qf["restore_s"]), 3),
                 ids_overlap=round(qf["ids_overlap"], 4),
                 fp32_ids_bitident=qf["fp32_ids_bitident"],
                 fp32_dists_bitident=qf["fp32_dists_bitident"])
    return sink.save()


def main(argv: list | None = None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mode", choices=("smoke", "build-child",
                                      "restore-child"),
                   default="smoke")
    p.add_argument("--dir", default=None,
                   help="snapshot directory (child modes)")
    p.add_argument("--n", type=int, default=4096)
    p.add_argument("--d", type=int, default=16)
    p.add_argument("--q", type=int, default=256)
    p.add_argument("--k", type=int, default=10)
    p.add_argument("--k-out", type=int, default=10)
    p.add_argument("--qfirst", action="store_true", default=None,
                   help="also measure the quantized-first cold start "
                        "(informative row; on by default in smoke mode)")
    args = p.parse_args(argv)
    if args.mode != "smoke" and args.dir is None:
        p.error(f"--mode {args.mode} requires --dir")
    if args.mode == "build-child":
        return run_build_child(args.dir, args.n, args.d, args.q, args.k,
                               args.k_out)
    if args.mode == "restore-child":
        return run_restore_child(args.dir, args.n, args.d, args.q,
                                 args.k_out, bool(args.qfirst))
    return run_smoke(n=args.n, d=args.d, q_n=args.q, k=args.k,
                     k_out=args.k_out,
                     qfirst=True if args.qfirst is None else args.qfirst)


if __name__ == "__main__":
    main()
