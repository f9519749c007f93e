"""The operations and bytes that the algorithm needs, from the cell's
logical sizes: rows, candidates per row, unpadded d, fp32. Never from
padded block shapes or a kernel's grid, so the count stays the same
whatever kernel does the work. The XLA gather that feeds a kernel is
outside that kernel's time, and outside these counts.

Also the table of peaks (``peaks.json``), keyed by ``device_kind``.
"""
from __future__ import annotations

import json
import os

F32 = 4
I32 = 4


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is
    an error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    the peak rate and bytes over the peak bandwidth. fp32 products run on
    the MXU as bf16 passes, so the bf16 peak is an upper bound on the fp32
    rate and this stays a lower bound on the time."""
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])


def join_iteration(rows: int, dim: int, cand_new: int, cand_old: int):
    """One sampled local join (NN-Descent): every row scores its new x new
    (unordered) and new x old candidate pairs. Reads each candidate's
    features, norm and id once, writes one distance per pair.
    Returns (flops, bytes)."""
    pairs = cand_new * (cand_new - 1) // 2 + cand_new * cand_old
    c = cand_new + cand_old
    flops = rows * pairs * 2 * dim
    nbytes = rows * (c * (dim * F32 + F32 + I32) + pairs * F32)
    return flops, nbytes


def search_tile(queries: int, width: int, dim: int):
    """One candidate-scoring tile of the graph search: each query scores
    ``width`` gathered candidate rows. Reads the query, the candidates'
    features, norms and ids, writes one distance per candidate.
    Returns (flops, bytes)."""
    flops = queries * width * 2 * dim
    nbytes = queries * (dim * F32 + width * (dim * F32 + F32 + I32 + F32))
    return flops, nbytes
