"""The plain reference: exact k-NN and pair distances in fp32 difference
form, ||q - x||^2 summed over features, in chunks.

Copied from ``chip_smoke.py`` (``Exact``, ``_exact_fns``). It imports
nothing of the program. One change from the original: ``knn`` returns
the distances beside the ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("k", "m"))
def _block(qc, qid, corpus, valid, *, k, m):
    """Exact top-k of a query chunk over ``corpus`` in chunks of m rows:
    (b, d) -> (dist (b, k) ascending, idx (b, k))."""
    n, d = corpus.shape
    pad = (-n) % m
    xs = jnp.pad(corpus, ((0, pad), (0, 0))).reshape(-1, m, d)
    vs = jnp.pad(valid, (0, pad)).reshape(-1, m)
    ids = jnp.arange(n + pad, dtype=jnp.int32).reshape(-1, m)

    def step(carry, c):
        bd, bi = carry
        xc, vc, ic = c
        diff = qc[:, None, :] - xc[None, :, :]
        dd = jnp.sum(diff * diff, axis=-1)
        dd = jnp.where(vc[None, :] & (ic[None, :] != qid[:, None]),
                       dd, jnp.inf)
        ad = jnp.concatenate([bd, dd], axis=1)
        ai = jnp.concatenate(
            [bi, jnp.broadcast_to(ic[None, :], dd.shape)], axis=1)
        neg, pos = jax.lax.top_k(-ad, k)
        return (-neg, jnp.take_along_axis(ai, pos, axis=1)), None

    b = qc.shape[0]
    init = (jnp.full((b, k), jnp.inf, jnp.float32),
            jnp.full((b, k), -1, jnp.int32))
    (dist, idx), _ = jax.lax.scan(step, init, (xs, vs, ids))
    return dist, idx


@jax.jit
def _pair(q, corpus, ids):
    """Exact distances of each query to its listed rows."""
    rows = corpus[jnp.clip(ids, 0, corpus.shape[0] - 1)]
    diff = rows - q[:, None, :]
    return jnp.sum(diff * diff, axis=-1)


class Exact:
    """Exact k-NN and pair distances in difference form."""

    def knn(self, queries, corpus, k, *, valid=None, self_ids=None,
            chunk=128, m=2048):
        """Returns (dist (nq, k), idx (nq, k)) as numpy arrays."""
        nq = queries.shape[0]
        if valid is None:
            valid = jnp.ones((corpus.shape[0],), bool)
        if self_ids is None:
            self_ids = jnp.full((nq,), -1, jnp.int32)
        out_d, out_i = [], []
        with jax.default_matmul_precision("highest"):
            for s in range(0, nq, chunk):
                qc = queries[s:s + chunk]
                qi = self_ids[s:s + chunk]
                if qc.shape[0] < chunk:
                    padn = chunk - qc.shape[0]
                    qc = jnp.pad(qc, ((0, padn), (0, 0)))
                    qi = jnp.pad(qi, (0, padn), constant_values=-1)
                dist, idx = _block(qc, qi, corpus, valid, k=k, m=m)
                keep = min(chunk, nq - s)
                out_d.append(np.asarray(dist)[:keep])
                out_i.append(np.asarray(idx)[:keep])
        return np.concatenate(out_d), np.concatenate(out_i)

    def pair(self, queries, corpus, ids, chunk=1024):
        """fp32 distances of each query row to the ids listed for it."""
        out = []
        with jax.default_matmul_precision("highest"):
            for s in range(0, queries.shape[0], chunk):
                out.append(np.asarray(_pair(
                    queries[s:s + chunk], corpus,
                    jnp.asarray(ids[s:s + chunk]))))
        return np.concatenate(out)
