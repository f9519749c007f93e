"""What decides ``correct``: the window's answers against the plain
reference (``reference.py``), as numbers each held to a limit of its
own (``limits/<cell>.json``; PERF.md section 2 gives the readings each
limit was set from).

  dist_err     the largest relative gap between a returned distance and
               the reference's fp32 distance of the same pair. The
               program returns exact fp32 distances (its final re-rank),
               so only rounding separates the two; an id altered after
               its distance was taken reads far above.
  join_dist_err  the same gap over the lists after a build's first
               sampled iteration (the window's builds; a serving cell's
               set-up build), before any re-rank: the distances as the
               local join scored them. Scoring in a lower precision,
               bf16 or a single-pass fp32 product, reads far above fp32
               at ``highest``, though the final re-rank hides it.
  invalid      entries that break the result's guarantees: an id out of
               range or -1, a row's own id, an id twice in one row,
               distances out of ascending order or not finite.
  dist_excess  the mean over rows of (sum of returned distances / sum of
               the exact k nearest distances) - 1: how far the
               approximate lists lie from the exact ones. A build or a
               search that stops improving its lists reads several times
               what sound runs read.
  missing      requests neither answered nor refused: a silent drop. A
               typed refusal (a shed request) is no wrong answer; it
               counts in ``failed``.

``recall`` (the share of the exact k nearest ids returned) is reported
beside them as an end-to-end metric, bounded against the parent.
"""
from __future__ import annotations

import numpy as np


def rel_gap(dist, pair):
    """|dist - pair| / pair, elementwise, in float64."""
    pair = np.asarray(pair, np.float64)
    return np.abs(np.asarray(dist, np.float64) - pair) / np.maximum(
        pair, 1e-30)


def pair_err(dist, idx, pair, *, n: int) -> float:
    """The largest ``rel_gap`` over the entries with an id in range and a
    finite distance."""
    idx = np.asarray(idx)
    ok = (idx >= 0) & (idx < n) & np.isfinite(dist)
    return float(rel_gap(dist, pair)[ok].max()) if ok.any() else 1.0


def compare(dist, idx, pair, ref_d, ref_i, *, n: int, self_ids=None,
            ref_rows=None):
    """Rows of returned (dist, idx) against the reference: ``pair`` holds
    the reference distance of every returned (row, id); ``ref_d`` and
    ``ref_i`` the exact k nearest of the rows ``ref_rows`` (all rows when
    None), which ``dist_excess`` and recall are taken over.
    Returns (numbers, recall)."""
    dist = np.asarray(dist, np.float64)
    idx = np.asarray(idx)
    bad = (idx < 0) | (idx >= n) | ~np.isfinite(dist)
    if self_ids is not None:
        bad |= idx == np.asarray(self_ids)[:, None]
    srt = np.sort(idx, axis=1)
    bad[:, 1:] |= srt[:, 1:] == srt[:, :-1]
    bad[:, 1:] |= dist[:, 1:] < dist[:, :-1]
    ok = ~bad
    gap = rel_gap(dist, pair)
    dist_err = float(gap[ok].max()) if ok.any() else 1.0
    rows = slice(None) if ref_rows is None else np.asarray(ref_rows)
    d_r, i_r, ok_r = dist[rows], idx[rows], ok[rows]
    excess = (np.where(ok_r, d_r, 0.0).sum(1)
              / np.asarray(ref_d, np.float64).sum(1) - 1.0)
    hits = ((i_r[:, :, None] == np.asarray(ref_i)[:, None, :]).any(2)
            & (i_r >= 0))
    recall = float(hits.mean())
    return {"dist_err": dist_err, "invalid": float(bad.sum()),
            "dist_excess": float(np.mean(excess))}, recall


def worst(numbers: list[dict]) -> dict:
    """Each number's worst (largest) reading over several answers."""
    return {k: max(d[k] for d in numbers) for k in numbers[0]}


def judge(numbers: dict, limits: dict) -> bool:
    """Correct when every number is at or under its limit. A number
    without a limit, or a limit without a number, is a fault."""
    if set(numbers) != set(limits):
        raise KeyError(f"numbers {sorted(numbers)} vs limits "
                       f"{sorted(limits)}")
    return all(numbers[k] <= limits[k] for k in numbers)
