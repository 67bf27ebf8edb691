"""Plain references that decide ``correct``.

Nothing here imports the program.  Each function takes what the timed
path produced, as NumPy arrays, and the benchmark's own inputs, and
returns the numbers that the cell's limits file compares.

Ingest (the sketch stage): the exact cell count of every chunk fed, and
the heavy-hitter check that ``chip_smoke.py`` runs, copied here.  With
``err`` the largest |estimate - exact count| over the served cells and
``sigma = sqrt(F2 / C)`` (F2 the second moment of the exact counts, C the
sketch width: the Count Sketch error scale), the served set is the exact
top-K up to ``allowed = max(min served exact count + 2 err,
hh_error_bound)``: no cell of the exact top-K left out has a larger count
(``missed_over_allowed`` <= 1).  That bound is not a theorem (a cell the
reservoir evicted and took back can be left out above it), so it is
reported beside the numbers compared.  Which cells the reservoir keeps
is compared by ``top_half_missed_share``, the share of the cells of the
exact top K/2 that are not served, with ``first_missed_rank`` beside it: the exact rank
(1 = heaviest) of the heaviest top-K cell not served, as a share of K
(above 1 when none is missed).
"""
from __future__ import annotations

import math

import numpy as np


def cell_keys(points: np.ndarray, bins: int) -> np.ndarray:
    """uint64 cell key of each point of [0, 1]^D: float32
    floor(p * M) clipped to [0, M), ceil(log2 M) bits per dimension,
    the first dimension most significant."""
    q = np.floor(points * np.float32(bins))
    q = np.clip(q, 0, bins - 1).astype(np.uint64)
    bits = np.uint64(max(1, math.ceil(math.log2(bins))))
    key = np.zeros(points.shape[0], np.uint64)
    for i in range(points.shape[1]):
        key = (key << bits) | q[:, i]
    return key


def exact_counts(chunks, times, bins: int):
    """(sorted distinct keys, counts) of the multiset in which chunk i
    occurs ``times[i]`` times."""
    keys, weights = [], []
    for c, t in zip(chunks, times):
        if t:
            k = cell_keys(c, bins)
            keys.append(k)
            weights.append(np.full(k.size, t, np.int64))
    keys = np.concatenate(keys)
    uniq, inv = np.unique(keys, return_inverse=True)
    return uniq, np.bincount(inv, weights=np.concatenate(weights)
                             ).astype(np.int64)


def heavy_hitter_numbers(served_keys: np.ndarray, served_est: np.ndarray,
                         error_bound: float, uniq: np.ndarray,
                         exact: np.ndarray, top_k: int,
                         log2_cols: int) -> dict:
    """The numbers of the heavy-hitter check (see the module docstring)."""
    want = min(top_k, uniq.size)
    pos = np.searchsorted(uniq, served_keys)
    found = (pos < uniq.size) & \
        (uniq[np.minimum(pos, uniq.size - 1)] == served_keys)
    f_served = np.where(found, exact[np.minimum(pos, uniq.size - 1)], 0)
    err = float(np.max(np.abs(served_est - f_served))) \
        if served_keys.size else math.inf
    sigma = math.sqrt(float(np.sum(exact.astype(np.float64) ** 2))
                      / (1 << log2_cols))
    order = np.argsort(-exact, kind="stable")[:want]
    missed = ~np.isin(uniq[order], served_keys)
    max_missed = float(exact[order][missed].max()) if missed.any() else 0.0
    first_missed = int(np.argmax(missed)) + 1 if missed.any() else want + 1
    min_served = float(f_served.min()) if served_keys.size else 0.0
    allowed = max(min_served + 2.0 * err, error_bound, 1.0)
    return {
        "served_gap": abs(int(np.unique(served_keys).size) - want),
        "foreign_cells": int((~found).sum()),
        "sketch_err_sigma": err / sigma,
        "missed_over_allowed": max_missed / allowed,
        "top_half_missed_share": float(missed[: want // 2].mean())
        if want >= 2 else 0.0,
        "first_missed_rank": first_missed / want,
    }
