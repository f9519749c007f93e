"""MXU-blocked pairwise squared-l2 distance kernel (paper §3.3, TPU form).

The paper's 5x5 AVX2 register blocking maximizes reuse of loaded vectors:
25 distances share 10 loads. On TPU the same insight maps to the 128x128
systolic MXU via the norm expansion

    ||a - b||^2 = ||a||^2 + ||b||^2 - 2 a.b^T

so the cross term is a tile matmul streamed through VMEM: a (TM, TK) tile of
A and a (TN, TK) tile of B produce TM*TN partial distances from TM+TN rows
loaded — reuse factor TM*TN/(TM+TN) ~ 64 at the default 128x128 tiles
(the paper's 25/10, scaled to the MXU).

The feature axis is the innermost (reduction) grid axis; squared norms are
accumulated alongside the dot product in VMEM scratch and fused into the
epilogue on the final reduction step, with a clamp at zero guarding the
cancellation the expansion form can suffer for near-identical points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TM = 128
DEFAULT_TN = 128
DEFAULT_TK = 512


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _l2_kernel(a_ref, b_ref, out_ref, acc_ref, a2_ref, b2_ref):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        a2_ref[...] = jnp.zeros_like(a2_ref)
        b2_ref[...] = jnp.zeros_like(b2_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    # cross term on the MXU at full fp32 precision
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    a2_ref[...] += jnp.sum(a * a, axis=1, keepdims=True)
    b2_ref[...] += jnp.sum(b * b, axis=1, keepdims=True).T

    @pl.when(kk == pl.num_programs(2) - 1)
    def _epilogue():
        d2 = a2_ref[...] + b2_ref[...] - 2.0 * acc_ref[...]
        out_ref[...] = jnp.maximum(d2, 0.0)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "tk", "interpret"))
def pairwise_sq_l2_blocked(
    a: jax.Array,
    b: jax.Array,
    *,
    tm: int = DEFAULT_TM,
    tn: int = DEFAULT_TN,
    tk: int = DEFAULT_TK,
    interpret: bool = False,
) -> jax.Array:
    """Blocked pairwise squared l2: a (M, D), b (N, D) -> (M, N) f32.

    M, N, D are padded to tile multiples internally. Zero feature padding is
    exact (changes neither norms nor dot products); padded rows are sliced
    away from the output.
    """
    m, d = a.shape
    n, _ = b.shape
    tk = min(tk, _ceil_to(d, 128))
    mp, np_, dp = _ceil_to(m, tm), _ceil_to(n, tn), _ceil_to(d, tk)
    a = jnp.pad(a, ((0, mp - m), (0, dp - d)))
    b = jnp.pad(b, ((0, np_ - n), (0, dp - d)))

    out = pl.pallas_call(
        _l2_kernel,
        grid=(mp // tm, np_ // tn, dp // tk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((tn, tk), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tm, tn), jnp.float32),
            pltpu.VMEM((tm, 1), jnp.float32),
            pltpu.VMEM((1, tn), jnp.float32),
        ],
        interpret=interpret,
        name="pairwise_sq_l2",
    )(a, b)
    return out[:m, :n]


def vmem_bytes(tm: int, tn: int, tk: int, in_dtype=jnp.float32) -> int:
    """Static VMEM working-set estimate for a tile choice (for tuning)."""
    itemsize = jnp.dtype(in_dtype).itemsize
    tiles_in = (tm * tk + tn * tk) * itemsize
    scratch = (tm * tn + tm + tn) * 4
    out = tm * tn * 4
    # double-buffered inputs (pipeline) + scratch + output block
    return 2 * tiles_in + scratch + out
