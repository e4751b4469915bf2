"""Plain reference of leaf-wise gradient boosting for a binary objective.

LightGBM's algorithm as its paper and documentation state it, in
straightforward numpy and ``jax.numpy`` float32 at ``highest`` matmul
precision: bin the features (``binning.py``), start from the log-odds of
the label mean, and per boosting step take the logistic loss's gradient
and hessian, grow one tree best-first (histogram of the smaller child,
sibling by subtraction, best (feature, bin) by the gain
``GL^2/(HL+l2) + GR^2/(HR+l2) - G^2/(H+l2)`` under the minimum hessian
sum), and add ``-learning_rate * G/(H+l2)`` of its leaf to every row's
score. No kernel, no compaction ladder, no cache: the rows of a leaf are
a slice of one permutation kept on the host, their histogram is a
one-hot product on the device in blocks of rows. It imports nothing of
``lightgbm_tpu``.

Not covered (the benchmark's configurations use none of it): missing
values, categorical features, L1, depth limits, sampling, weights.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import binning

HIGHEST = jax.lax.Precision.HIGHEST
NUM_BINS = 256          # histogram width: max_bin <= 255 bins, uint8
BIN_ROWS = 16384        # rows binned per device call
HIST_ROWS = 1024        # rows per one-hot block


@dataclasses.dataclass
class Params:
    num_leaves: int
    max_bin: int
    learning_rate: float
    min_sum_hessian_in_leaf: float
    min_data_in_leaf: int = 1
    lambda_l2: float = 0.0
    min_data_in_bin: int = 3
    bin_construct_sample_cnt: int = 200000
    data_random_seed: int = 1

    @classmethod
    def from_dict(cls, d: dict) -> "Params":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


# ----------------------------------------------------------------- device
@jax.jit
def _bin_block(x, thresholds):
    """uint8 bins of float32 rows ``x [r, F]``: thresholds below the value."""
    return jnp.sum(x[:, :, None] > thresholds[None, :, :], axis=2,
                   dtype=jnp.int32).astype(jnp.uint8)


def _leaf_histogram(bins, gh, idx, n):
    """``[F, NUM_BINS, 2]`` sums of ``gh`` over the rows ``idx[:n]``."""
    iota = jnp.arange(NUM_BINS, dtype=jnp.int32)
    lane = jnp.arange(HIST_ROWS, dtype=jnp.int32)

    def body(i, acc):
        rows = jax.lax.dynamic_slice(idx, (i * HIST_ROWS,), (HIST_ROWS,))
        live = (i * HIST_ROWS + lane) < n
        w = jnp.where(live[:, None], gh[rows], 0.0)
        onehot = (bins[rows].astype(jnp.int32)[:, :, None]
                  == iota).astype(jnp.float32)
        return acc + jnp.einsum("rfb,rc->fbc", onehot, w, precision=HIGHEST)

    zero = jnp.zeros((bins.shape[1], NUM_BINS, 2), dtype=jnp.float32)
    return jax.lax.fori_loop(0, (n + HIST_ROWS - 1) // HIST_ROWS, body, zero)


def _best_split(hist, g_sum, h_sum, min_hess, l2):
    """Best (gain, feature, bin, GL, HL) of a leaf: rows whose bin is at
    most ``bin`` go left."""
    gl = jnp.cumsum(hist[..., 0], axis=1)
    hl = jnp.cumsum(hist[..., 1], axis=1)
    gr, hr = g_sum - gl, h_sum - hl
    ok = (hl >= min_hess) & (hr >= min_hess)
    gain = (gl * gl / (hl + l2) + gr * gr / (hr + l2)
            - g_sum * g_sum / (h_sum + l2))
    gain = jnp.where(ok, gain, -jnp.inf)
    k = jnp.argmax(gain)
    f, b = k // NUM_BINS, k % NUM_BINS
    return jnp.stack([gain[f, b], f.astype(jnp.float32),
                      b.astype(jnp.float32), gl[f, b], hl[f, b]])


@jax.jit
def _root_step(bins, gh, idx, n, sums, min_hess, l2):
    hist = _leaf_histogram(bins, gh, idx, n)
    return hist, _best_split(hist, sums[0], sums[1], min_hess, l2)


@jax.jit
def _split_step(bins, gh, idx, n, parent, small_sums, large_sums, min_hess,
                l2):
    small = _leaf_histogram(bins, gh, idx, n)
    large = parent - small
    return (small, large,
            _best_split(small, small_sums[0], small_sums[1], min_hess, l2),
            _best_split(large, large_sums[0], large_sums[1], min_hess, l2))


# ------------------------------------------------------------------- host
def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def init_score(y: np.ndarray) -> float:
    p = float(np.mean(y, dtype=np.float64))
    return float(np.log(p / (1.0 - p)))


def logloss(score: np.ndarray, y: np.ndarray) -> float:
    """Mean logistic loss of raw scores, in float64."""
    s = np.asarray(score, dtype=np.float64)
    return float(np.mean(np.logaddexp(0.0, s) - y * s))


loss = logloss      # the loss of this reference's objective (the interface)


@dataclasses.dataclass
class RefTree:
    """Splits in the order they were made: leaf ``leaf[i]`` sends its rows
    with ``bin > thr_bin[i]`` of feature ``feature[i]`` to new leaf ``i+1``."""
    leaf: list
    feature: list
    thr_bin: list
    value: np.ndarray       # per leaf, learning rate applied
    smaller_rows: list      # rows histogrammed at each split, root first

    def leaves(self, bins_of_rows) -> np.ndarray:
        """Leaf of each row, ``bins_of_rows(f)`` giving the rows' bins of
        feature ``f``."""
        out = None
        for i, (leaf, f, thr) in enumerate(zip(self.leaf, self.feature,
                                               self.thr_bin)):
            col = bins_of_rows(f)
            if out is None:
                out = np.zeros(len(col), dtype=np.int32)
            out[(out == leaf) & (col > thr)] = i + 1
        return out


class Reference:
    """Binned data on the device and the host, and ``step()`` by ``step()``
    the boosted scores of the training rows."""

    def __init__(self, X: np.ndarray, y: np.ndarray, params: Params,
                 gh_dtype=jnp.float32, drop_odd_rows: bool = False,
                 freeze_scores: bool = False):
        self.p = params
        self.y = np.asarray(y, dtype=np.float32)
        self.R, self.F = X.shape
        self.gh_dtype = gh_dtype
        self.drop_odd_rows = drop_odd_rows      # fault: half the batch
        self.freeze_scores = freeze_scores      # fault: state unchanged
        t0 = time.perf_counter()
        bounds = binning.find_bounds(
            X, params.max_bin, params.min_data_in_bin,
            params.bin_construct_sample_cnt, params.data_random_seed)
        self.thresholds = jnp.asarray(
            binning.bounds_matrix(bounds, NUM_BINS))
        t1 = time.perf_counter()
        self.bins = self.bin_rows(X)                        # device [R, F]
        self.bins_t = np.ascontiguousarray(
            np.asarray(self.bins).T)                        # host [F, R]
        self.seconds = {"find bins": t1 - t0,
                        "bin rows": time.perf_counter() - t1, "steps": []}
        self.init = init_score(self.y)
        self.score = np.full(self.R, self.init, dtype=np.float32)
        self.trees: list = []
        self._idx_len = -(-self.R // HIST_ROWS) * HIST_ROWS

    def bin_rows(self, X: np.ndarray):
        parts = [_bin_block(jnp.asarray(X[lo:lo + BIN_ROWS]), self.thresholds)
                 for lo in range(0, X.shape[0], BIN_ROWS)]
        return jnp.concatenate(parts, axis=0)

    def _idx(self, rows: np.ndarray):
        buf = np.zeros(self._idx_len, dtype=np.int32)
        buf[:len(rows)] = rows
        return jnp.asarray(buf), jnp.int32(len(rows))

    def step(self) -> np.ndarray:
        """One boosting step; returns the scores after it."""
        t0 = time.perf_counter()
        p = _sigmoid(self.score.astype(np.float32))
        g = (p - self.y).astype(np.float32)
        h = (p * (1.0 - p)).astype(np.float32)
        if self.drop_odd_rows:
            g[1::2] = 0.0
            h[1::2] = 0.0
        gh = jnp.asarray(np.stack([g, h], axis=1)).astype(self.gh_dtype)
        gh = gh.astype(jnp.float32)
        gh_host = np.asarray(gh, dtype=np.float64)
        tree, leaf_rows = self._grow(gh, gh_host)
        self.trees.append(tree)
        if not self.freeze_scores:
            for leaf, rows in leaf_rows.items():
                self.score[rows] += np.float32(tree.value[leaf])
        self.seconds["steps"].append(time.perf_counter() - t0)
        return self.score.copy()

    def _grow(self, gh, gh_host):
        prm = self.p
        min_hess = jnp.float32(prm.min_sum_hessian_in_leaf)
        l2 = jnp.float32(prm.lambda_l2)
        order = np.arange(self.R, dtype=np.int32)
        seg = {0: (0, self.R)}
        sums = {0: gh_host.sum(axis=0)}
        idx, n = self._idx(order)
        hist, best = _root_step(self.bins, gh, idx, n,
                                jnp.asarray(sums[0], dtype=jnp.float32),
                                min_hess, l2)
        hists = {0: hist}
        cand = {0: np.asarray(best, dtype=np.float64)}
        tree = RefTree([], [], [], None, [self.R])
        new_leaf = 1
        while new_leaf < prm.num_leaves:
            leaf = max(cand, key=lambda k: cand[k][0])
            gain, f, b, gl, hl = cand[leaf]
            if not gain > 0.0:
                break
            f, b = int(f), int(b)
            lo, hi = seg[leaf]
            rows = order[lo:hi]
            left = self.bins_t[f, rows] <= b
            rows_l, rows_r = rows[left], rows[~left]
            if len(rows_l) < prm.min_data_in_leaf or \
                    len(rows_r) < prm.min_data_in_leaf:
                cand[leaf][0] = -np.inf
                continue
            order[lo:hi] = np.concatenate([rows_l, rows_r])
            mid = lo + len(rows_l)
            seg[leaf], seg[new_leaf] = (lo, mid), (mid, hi)
            parent = sums[leaf]
            sums[leaf] = np.array([gl, hl])
            sums[new_leaf] = parent - sums[leaf]
            small, large = ((leaf, new_leaf) if len(rows_l) <= len(rows_r)
                            else (new_leaf, leaf))
            s_lo, s_hi = seg[small]
            idx, n = self._idx(order[s_lo:s_hi])
            h_small, h_large, b_small, b_large = _split_step(
                self.bins, gh, idx, n, hists[leaf],
                jnp.asarray(sums[small], dtype=jnp.float32),
                jnp.asarray(sums[large], dtype=jnp.float32), min_hess, l2)
            hists[small], hists[large] = h_small, h_large
            cand[small] = np.asarray(b_small, dtype=np.float64)
            cand[large] = np.asarray(b_large, dtype=np.float64)
            tree.leaf.append(leaf)
            tree.feature.append(f)
            tree.thr_bin.append(b)
            tree.smaller_rows.append(s_hi - s_lo)
            new_leaf += 1
        n_leaves = len(tree.leaf) + 1
        tree.value = np.array(
            [-prm.learning_rate * sums[k][0] / (sums[k][1] + prm.lambda_l2)
             for k in range(n_leaves)], dtype=np.float64)
        return tree, {k: order[seg[k][0]:seg[k][1]] for k in range(n_leaves)}

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        """Raw scores of unseen float32 rows through every tree grown."""
        bins_t = np.ascontiguousarray(np.asarray(self.bin_rows(X)).T)
        out = np.full(X.shape[0], self.init, dtype=np.float64)
        for tree in self.trees:
            out += tree.value[tree.leaves(lambda f: bins_t[f])]
        return out
