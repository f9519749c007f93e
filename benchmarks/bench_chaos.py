"""Chaos benchmark: a scripted fault schedule against the live serving
paths (the CI receipt for core/faults.py and every graceful-degradation
site it scripts).

Modes (``python benchmarks/bench_chaos.py --mode ...``):

  * ``smoke`` (default) — the gated CI lane. Four phases, one scripted
    ``FaultPlan`` each, all against real serving objects (a live
    ContinuousBatcher, a restored datastore, a sharded dispatch):

      1. **flaky/slow writer** — a ContinuousBatcher streams decode
         captures into its datastore while the periodic background
         snapshot write fails transiently (``persist.write``, absorbed
         by the SnapshotWriter's backoff retries — the retry sleeps ARE
         the slowed writer). The decode stream must finish every
         request and the drain snapshot must land at the final
         high-water mark.
      2. **poisoned batch** — a NaN-poisoned query batch goes through
         ``knn_logits`` un-strict (sanitized: every row answered, all
         logits finite) and an Inf-poisoned batch goes through strict
         admission (rejected with ValueError, never a crash).
      3. **corrupted newest snapshot** — the newest committed step is
         torn post-commit (truncated array file); a cold start must
         quarantine it and fall back to the next-older committed step
         bit-identically (same ids and fp32 distance bits as restoring
         that step directly). ``recovery_s`` is the fallback restore
         wall time.
      4. **dead shard** — routed sharded dispatch on a forced 4-device
         CPU topology (forked subprocess, like bench_search's routed
         sidecar) with shard 1 marked dead via ``shard.dead``.
         ``degraded_recall`` is recall against the best *attainable*
         ground truth (brute force over surviving shards' rows): the
         survivors must still answer well, with 0 dropped queries.

    Emits one ``smoke_chaos`` row into results/bench/chaos.json, gated
    by check_gate.py --chaos: ``crashes == 0`` (any unhandled exception
    OR violated degradation contract counts), ``dropped_queries == 0``,
    ``degraded_recall >= --chaos-floor``, ``fallback_bitident``.

  * ``shard-child`` — internal: phase 4's forked half (jax device
    topology is fixed at first backend init, so the multi-device run
    needs a fresh process).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import time
import warnings

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# phase-4 child: cluster-aligned 4-shard corpus, routed dispatch with
# shard 1 dead. route_p=2 gives every query a second entry shard, so a
# query whose home shard died still lands somewhere near; route_cap has
# slack for the re-routed load (256*2/3 ~ 171 per surviving shard).
_SHARD_CHILD_SRC = """
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.core import DescentConfig, RouterConfig, SearchConfig
from repro.core.distributed import graph_search_sharded
from repro.core.faults import FaultPlan, FaultSpec
from repro.core.nn_descent import build_knn_graph
from repro.core.recall import brute_force_knn, recall_at_k
from repro.core.router import build_router

P, n, d, k_out, DEAD = 4, 1024, 16, 10, 1
n_local = n // P
cent = jax.random.normal(jax.random.key(0), (P, d)) * 8.0
noise = jax.random.normal(jax.random.key(1), (P, n_local, d)) * 0.5
x = (cent[:, None, :] + noise).reshape(n, d).astype(jnp.float32)
cfg = DescentConfig(k=10, rho=1.0, max_iters=10, reorder=False)
parts = []
for s in range(P):
    _, gi, _ = build_knn_graph(x[s*n_local:(s+1)*n_local], k=10, cfg=cfg,
                               key=jax.random.key(s))
    parts.append(gi)
gidx = jnp.concatenate(parts)
router = build_router(x, cfg=RouterConfig(n_centroids=16, sample=1024),
                      key=jax.random.key(7))
mesh = jax.make_mesh((P,), ("data",))
q = x[::8] + 0.01
scfg = SearchConfig(beam=16, rounds=24, expand=4)

def dispatch():
    return graph_search_sharded(mesh, x, gidx, q, k_out=k_out, cfg=scfg,
                                key=jax.random.key(2), router=router,
                                route_p=2, route_cap=256, with_stats=True)

_, ti_full = brute_force_knn(x, q, k_out, exclude_self=False)
_, gi_live, st_live = dispatch()
plan = FaultPlan(specs=(FaultSpec(site="shard.dead", arg=DEAD),))
with plan.active():
    _, gi_dead, st_dead = dispatch()

# attainable ground truth: brute force over the SURVIVING shards' rows
live_ids = np.concatenate([np.arange(s*n_local, (s+1)*n_local)
                           for s in range(P) if s != DEAD])
_, tl = brute_force_knn(x[live_ids], q, k_out, exclude_self=False)
ti_live = jnp.asarray(live_ids)[tl]
print("CHAOS_SHARD " + json.dumps({
    "baseline_recall": float(recall_at_k(gi_live, ti_full)),
    "degraded_recall": float(recall_at_k(gi_dead, ti_live)),
    "degraded_recall_full": float(recall_at_k(gi_dead, ti_full)),
    "baseline_dropped": int(st_live.get("dropped_queries", 0)),
    "dropped_queries": int(st_dead.get("dropped_queries", 0)),
    "degraded_shards": list(st_dead.get("degraded_shards", [])),
    "cover_frac": float(st_dead.get("cover_frac", 0.0)),
    "platform": jax.devices()[0].platform,
}))
"""


def _shard_phase(n_devices: int = 4, timeout: int = 600) -> dict:
    """Run the dead-shard phase in a forked process with a forced
    multi-device CPU topology; returns the CHAOS_SHARD dict."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"     # the forced CPU topology, always
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(_REPO, "src"), env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, "-c", _SHARD_CHILD_SRC],
                          capture_output=True, text=True, env=env,
                          cwd=_REPO, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"dead-shard chaos child failed "
            f"(rc={proc.returncode}):\n{proc.stderr}")
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("CHAOS_SHARD ")]
    if not lines:
        raise RuntimeError(
            f"dead-shard chaos child printed no CHAOS_SHARD:"
            f"\n{proc.stdout}")
    return json.loads(lines[-1][len("CHAOS_SHARD "):])


def _search_bits(ds, q, k_out: int, key):
    dist, idx = ds.store.search(q, k_out=k_out, key=key)
    return (np.asarray(dist, np.float32).view(np.int32),
            np.asarray(idx, np.int32))


def _tear_newest(snap_root: str, step: int) -> str:
    """Truncate one array file of an already-committed step directory —
    the torn-page corruption COMMIT ordering alone cannot catch."""
    step_dir = os.path.join(snap_root, f"step_{step:08d}")
    target = sorted(glob.glob(os.path.join(step_dir, "*.npy")))[0]
    size = os.path.getsize(target)
    with open(target, "r+b") as f:
        f.truncate(size // 2)
    return target


def run_smoke(n0: int = 256, dk: int = 16, vocab: int = 32,
              n_requests: int = 4, max_new: int = 25,
              k_out: int = 8) -> list:
    import jax
    import jax.numpy as jnp

    from benchmarks.common import RESULTS_DIR, Sink
    from repro.core import SearchConfig, faults, persist
    from repro.core.faults import FaultPlan, FaultSpec
    from repro.serve.knn_lm import MutableKNNDatastore, knn_logits
    from repro.serve.scheduler import ContinuousBatcher, Request

    sink = Sink("chaos")
    snap_root = os.path.join(RESULTS_DIR, "chaos_smoke")
    shutil.rmtree(snap_root, ignore_errors=True)

    crashes = 0
    dropped = 0
    notes = []

    # ---- phase 1: live batcher with a flaky (and thereby slow) writer
    keys0 = jax.random.normal(jax.random.key(0), (n0, dk))
    vals0 = jax.random.randint(jax.random.key(1), (n0,), 0, vocab)
    ds0 = MutableKNNDatastore.build(keys0, vals0, k=8,
                                    key=jax.random.key(2))
    proj = jax.random.normal(jax.random.key(5), (vocab, dk))

    def prefill_fn(toks):
        return jnp.ones((1, vocab)), None, toks.shape[1]

    def step_fn(cache, toks, lengths):
        lg = jax.nn.one_hot((toks[:, 0] * 3 + lengths) % vocab,
                            vocab) * 4.0
        return lg, cache

    b = ContinuousBatcher(
        2, step_fn, prefill_fn, lambda c, i, o, length: c,
        knn_store=ds0, knn_capture=lambda lg: lg @ proj, knn_chunk=16,
        knn_snapshot_dir=snap_root, knn_snapshot_every=48)
    reqs = [Request(rid=r, prompt=np.array([1, 2, 3], np.int32),
                    max_new=max_new) for r in range(n_requests)]
    for r in reqs:
        b.submit(r)
    # 2 transient write failures: absorbed by the writer's 2 retries
    # with backoff — the first periodic snapshot is slowed, never lost
    plan = FaultPlan(specs=(FaultSpec(site="persist.write", times=2),))
    t0 = time.perf_counter()
    try:
        with plan.active(), warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            b.run(None)
    except Exception as e:          # noqa: BLE001 — the gate counts these
        crashes += 1
        notes.append(f"batcher: {e!r}")
    run_s = time.perf_counter() - t0
    streamed = n_requests * (max_new - 1)
    # a request that did not finish its full token budget was dropped
    dropped += sum(1 for r in reqs
                   if not r.done or len(r.out) < r.max_new)
    writer_faults = plan.fired("persist.write")
    drain_committed = (persist.latest_snapshot(snap_root)
                       == b.knn_store.store.n)
    if not drain_committed:
        crashes += 1
        notes.append("batcher: drain snapshot missing at high-water mark")
    ds = b.knn_store

    # ---- phase 2: poisoned query batches at the retrieval boundary
    qc = jax.random.normal(jax.random.key(11), (32, dk), jnp.float32)
    skey = jax.random.key(12)
    poisoned_finite = False
    strict_rejected = False
    try:
        qp = jnp.asarray(faults.poison_batch(np.asarray(qc), "nan"))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            lg = knn_logits(ds, qp, vocab, k=k_out, key=skey)
        poisoned_finite = bool(jnp.isfinite(lg).all())
        try:
            qi = jnp.asarray(faults.poison_batch(np.asarray(qc), "inf"))
            knn_logits(ds, qi, vocab, k=k_out, key=skey,
                       cfg=SearchConfig(beam=32, rounds=24, strict=True))
        except ValueError:
            strict_rejected = True
    except Exception as e:          # noqa: BLE001
        crashes += 1
        notes.append(f"poison: {e!r}")
    if not poisoned_finite:
        crashes += 1
        notes.append("poison: sanitized batch produced non-finite logits")
    if not strict_rejected:
        crashes += 1
        notes.append("poison: strict admission did not reject Inf batch")

    # ---- phase 3: corrupted newest snapshot -> bit-identical fallback
    fallback_bitident = False
    fallback_step = None
    torn_step = None
    recovery_s = float("nan")
    try:
        committed = persist.list_snapshots(snap_root)
        older, torn_step = committed[-2], committed[-1]
        ref = MutableKNNDatastore.restore(snap_root, step=older)
        ref_bits, ref_ids = _search_bits(ref, qc, k_out, skey)
        _tear_newest(snap_root, torn_step)
        t0 = time.perf_counter()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            ds2 = MutableKNNDatastore.restore(snap_root)
        jax.block_until_ready(ds2.store.x)
        recovery_s = time.perf_counter() - t0
        fallback_step = ds2.build_stats.get("restored_step")
        bits2, ids2 = _search_bits(ds2, qc, k_out, skey)
        fallback_bitident = bool(fallback_step == older
                                 and (ids2 == ref_ids).all()
                                 and (bits2 == ref_bits).all())
    except Exception as e:          # noqa: BLE001
        crashes += 1
        notes.append(f"fallback: {e!r}")

    # ---- phase 4: dead shard 1-of-4 under routed dispatch (forked)
    shard = {}
    try:
        shard = _shard_phase()
        dropped += int(shard.get("dropped_queries", 0))
        dropped += int(shard.get("baseline_dropped", 0))
        if shard.get("degraded_shards") != [1]:
            crashes += 1
            notes.append(
                f"shard: degraded_shards={shard.get('degraded_shards')} "
                "(expected [1])")
    except Exception as e:          # noqa: BLE001
        crashes += 1
        notes.append(f"shard: {e!r}")

    sink.row(op="smoke_chaos", n0=n0, dk=dk, vocab=vocab,
             streamed=streamed, k_out=k_out,
             crashes=crashes, dropped_queries=dropped,
             degraded_recall=round(shard.get("degraded_recall", 0.0), 4),
             baseline_recall=round(shard.get("baseline_recall", 0.0), 4),
             degraded_recall_full=round(
                 shard.get("degraded_recall_full", 0.0), 4),
             cover_frac=round(shard.get("cover_frac", 0.0), 4),
             shard_phase_platform=shard.get("platform"),
             degraded_shards=shard.get("degraded_shards", []),
             fallback_bitident=fallback_bitident,
             fallback_step=fallback_step, torn_step=torn_step,
             recovery_s=round(recovery_s, 3),
             writer_faults=writer_faults,
             drain_committed=drain_committed,
             poisoned_finite=poisoned_finite,
             strict_rejected=strict_rejected,
             run_s=round(run_s, 3),
             notes="; ".join(notes))
    return sink.save()


def main(argv: list | None = None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mode", choices=("smoke", "shard-child"),
                   default="smoke")
    args = p.parse_args(argv)
    if args.mode == "shard-child":
        # exec the child inline (debug convenience; CI forks it itself)
        exec(compile(_SHARD_CHILD_SRC, "<shard-child>", "exec"), {})
        return None
    return run_smoke()


if __name__ == "__main__":
    main()
