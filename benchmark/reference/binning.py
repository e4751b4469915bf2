"""Plain reference of LightGBM's numerical feature binning.

Written from the algorithm LightGBM documents (``bin_construct_sample_cnt``
rows are sampled, each feature's sampled values are cut into at most
``max_bin`` bins of about equal count, zero keeps a bin of its own,
a boundary is the midpoint of two neighbouring distinct values) and from
its description in ``src/io/bin.cpp`` (``GreedyFindBin``,
``FindBinWithZeroAsOneBin``). It imports nothing of ``lightgbm_tpu``.

Departures, all of them outside what the benchmark's data has:
categorical features, missing values and values that fill a whole bin by
themselves (a count of at least the mean bin size) are refused with an
error instead of handled.
"""
from __future__ import annotations

import math

import numpy as np

ZERO_THRESHOLD = 1e-35


def sample_rows(n_rows: int, sample_cnt: int, data_random_seed: int):
    """The rows the bins are found from, as the configuration states it:
    ``RandomState(data_random_seed).choice`` without replacement, sorted;
    every row where the data has no more than ``sample_cnt``."""
    if sample_cnt >= n_rows:
        return None
    rng = np.random.RandomState(data_random_seed)
    return np.sort(rng.choice(n_rows, sample_cnt, replace=False))


def _next_up(x: float) -> float:
    return math.nextafter(x, math.inf)


def greedy_bounds(values: np.ndarray, counts: np.ndarray, max_bin: int,
                  total_cnt: int, min_data_in_bin: int) -> list:
    """Upper bounds of equal-count bins over sorted distinct ``values``."""
    nd = len(values)
    bounds: list = []
    if nd == 0:
        return [math.inf]
    if nd <= max_bin:
        cur = 0
        for i in range(nd - 1):
            cur += int(counts[i])
            if cur >= min_data_in_bin:
                val = _next_up((float(values[i]) + float(values[i + 1])) / 2.0)
                if not bounds or val > _next_up(bounds[-1]):
                    bounds.append(val)
                    cur = 0
        bounds.append(math.inf)
        return bounds
    if min_data_in_bin > 0:
        max_bin = max(1, min(max_bin, total_cnt // min_data_in_bin))
    mean = total_cnt / max_bin
    if (counts >= mean).any():
        raise NotImplementedError(
            "a value that fills a bin by itself is outside this reference")
    cum = np.cumsum(counts).astype(np.float64)  # no cast in each search
    rest_bins = max_bin
    uppers, lowers = [], [float(values[0])]
    done = 0            # rows in closed bins
    while True:
        # the first value at which the open bin holds `mean` rows or more
        i = int(np.searchsorted(cum, done + mean, side="left"))
        if i > nd - 2:
            break
        uppers.append(float(values[i]))
        lowers.append(float(values[i + 1]))
        if len(uppers) >= max_bin - 1:
            break
        done = int(cum[i])
        rest_bins -= 1
        mean = (total_cnt - done) / rest_bins
    for up, lo in zip(uppers, lowers[1:]):
        val = _next_up((up + lo) / 2.0)
        if not bounds or val > _next_up(bounds[-1]):
            bounds.append(val)
    bounds.append(math.inf)
    return bounds


def feature_bounds(sorted_sample: np.ndarray, total_sample_cnt: int,
                   max_bin: int, min_data_in_bin: int) -> np.ndarray:
    """Bin upper bounds of one numerical feature from its sorted sampled
    values (zeros included); zero sits in a bin of its own."""
    v = np.asarray(sorted_sample)
    if np.isnan(v[-1:]).any():          # NaN sorts last
        raise NotImplementedError("missing values are outside this reference")
    new = np.empty(len(v), dtype=bool)
    new[:1] = True
    np.not_equal(v[1:], v[:-1], out=new[1:])
    starts = np.flatnonzero(new)
    values = v[starts].astype(np.float64)
    counts = np.diff(np.append(starts, len(v)))
    neg = values <= -ZERO_THRESHOLD
    pos = values > ZERO_THRESHOLD
    left_cnt_data = int(counts[neg].sum())
    right_cnt_data = int(counts[pos].sum())
    cnt_zero = total_sample_cnt - left_cnt_data - right_cnt_data
    n_left = int(neg.sum())
    bounds: list = []
    if n_left > 0 and max_bin > 1:
        denom = max(total_sample_cnt - cnt_zero, 1)
        left_max_bin = max(1, int(left_cnt_data / denom * (max_bin - 1)))
        bounds = greedy_bounds(values[:n_left], counts[:n_left],
                               left_max_bin, left_cnt_data, min_data_in_bin)
        bounds[-1] = -ZERO_THRESHOLD
    right_max_bin = max_bin - 1 - len(bounds)
    if pos.any() and right_max_bin > 0:
        first = int(np.argmax(pos))
        bounds.append(ZERO_THRESHOLD)
        bounds.extend(greedy_bounds(values[first:], counts[first:],
                                    right_max_bin, right_cnt_data,
                                    min_data_in_bin))
    else:
        bounds.append(math.inf)
    return np.asarray(bounds, dtype=np.float64)


def find_bounds(X: np.ndarray, max_bin: int, min_data_in_bin: int,
                sample_cnt: int, data_random_seed: int) -> list:
    """Bin upper bounds of every column of ``X`` (float32 ``[R, F]``)."""
    idx = sample_rows(X.shape[0], sample_cnt, data_random_seed)
    sample = X if idx is None else X[idx]
    total = sample.shape[0]
    # a row per feature, so that each sort runs over contiguous memory
    columns = np.ascontiguousarray(sample.T)

    columns.sort(axis=1)
    return [feature_bounds(col, total, max_bin, min_data_in_bin)
            for col in columns]


def bounds_matrix(bounds: list, max_bin: int) -> np.ndarray:
    """``[F, max_bin - 1]`` float32 thresholds, padded with +inf, such that
    a float32 value's bin is the number of thresholds below it: each
    float64 bound is rounded down to float32, which keeps ``x <= bound``
    true for exactly the same float32 ``x``."""
    out = np.full((len(bounds), max_bin - 1), np.inf, dtype=np.float32)
    for f, b in enumerate(bounds):
        b = np.asarray(b[:-1], dtype=np.float64)
        b32 = b.astype(np.float32)
        over = b32.astype(np.float64) > b
        b32[over] = np.nextafter(b32[over], np.float32(-np.inf))
        out[f, :len(b32)] = b32
    return out
