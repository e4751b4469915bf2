"""Plain reference of leaf-wise boosting on a one-hot table (scipy CSR rows
of a few dense numerical columns and many indicator columns), for a binary
objective, with the rows in conflict read as Exclusive Feature Bundling
(Ke et al., "LightGBM: A Highly Efficient Gradient Boosting Decision Tree",
NeurIPS 2017, Algorithms 3 and 4; ``enable_bundle``, ``max_conflict_rate``
of LightGBM's ``docs/Parameters.rst``) leaves them.

Boosting as ``gbdt.py`` states it: the same gradients, split rule, leaf
values and score update, float32 on the device at ``highest``. What differs
is how a column is stated:

- a column whose stored values are all 1 is an indicator: bin 1 where the
  row holds the level, bin 0 elsewhere; one absent from the bin sample has
  one bin and is never split on (every column's bins come from the sampled
  rows, ``binning.sample_rows``);
- every other column is numerical and binned by ``binning.py``;
- a leaf's indicator histograms are segment sums of g and h over the leaf's
  rows holding each level (bin 1), and bin 0 is the leaf's total less bin
  1; the numerical columns' histograms are ``gbdt.py``'s one-hot products;
- nothing is bundled, except that the rows in conflict read as the bundles
  leave them. This reference finds the bundles itself, over its own sample
  (Algorithm 3, as the configuration's ``assumed`` states the rules): the
  candidates are the used numerical columns (indicators among them) whose
  sampled rows away from bin 0 are at most 30% of the sample, taken
  densest first (ties: the lower column first), each into the first bundle
  whose bins (1 + the members' bins less one each) stay within
  ``max(max_num_bin, min(max_bin + 1, 256))`` and whose members' sampled
  rows it meets in no more than ``int(max_conflict_rate * sample)`` rows;
  in a training row where two indicators of one bundle are both held, the
  one of the higher column is kept and the others read bin 0 (a bundle
  column keeps one value a row).

``predict_raw`` reads held-out rows as they are (a model's thresholds apply
to raw values), with no conflict. It imports nothing of ``lightgbm_tpu``.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import binning, gbdt
from .gbdt import HIGHEST, HIST_ROWS, NUM_BINS, RefTree, logloss

loss = logloss          # the loss of this reference's objective
init_score = gbdt.init_score

SPARSE_SHARE = 0.3      # a candidate's sampled rows away from bin 0, at most


@dataclasses.dataclass
class Params(gbdt.Params):
    enable_bundle: bool = True
    max_conflict_rate: float = 0.0


# ----------------------------------------------------------------- device
def _indicator_histogram(slots, gh, idx, n, indicators: int):
    """``[indicators, 2]`` sums of ``gh`` over the rows ``idx[:n]`` holding
    each indicator (``slots [R, K]``: a row's indicators, -1 in the unused
    slots): bin 1 of every indicator column, a one-hot product in blocks of
    rows as ``gbdt.py`` takes its histograms."""
    iota = jnp.arange(indicators, dtype=jnp.int32)
    lane = jnp.arange(HIST_ROWS, dtype=jnp.int32)

    def body(i, acc):
        rows = jax.lax.dynamic_slice(idx, (i * HIST_ROWS,), (HIST_ROWS,))
        live = (i * HIST_ROWS + lane) < n
        w = jnp.where(live[:, None], gh[rows], 0.0)
        held = jnp.any(slots[rows][:, :, None] == iota, axis=1)
        return acc + jnp.einsum("ri,rc->ic", held.astype(jnp.float32), w,
                                precision=HIGHEST)

    zero = jnp.zeros((indicators, 2), dtype=jnp.float32)
    return jax.lax.fori_loop(0, (n + HIST_ROWS - 1) // HIST_ROWS, body, zero)


@functools.partial(jax.jit, static_argnames=("indicators",))
def _leaf_histograms(bins, slots, gh, idx, n, indicators: int):
    """The numerical columns' ``[D, 256, 2]`` histograms
    (``gbdt._leaf_histogram``) and the indicators' bin 1 ``[I, 2]`` over
    the rows ``idx[:n]``, in one program whatever ``n`` is."""
    return (gbdt._leaf_histogram(bins, gh, idx, n),
            _indicator_histogram(slots, gh, idx, n, indicators))


@jax.jit
def _scan(dense, bin1, g_sum, h_sum, min_hess, l2):
    """Best ``(gain, feature, bin, GL, HL)`` over the numerical columns'
    ``[D, 256, 2]`` histograms and the indicators' bin 1 ``[I, 2]`` (rows
    at or under ``bin`` go left: an indicator's left is its bin 0), in the
    order of the columns: the numerical ones first."""
    parent = g_sum * g_sum / (h_sum + l2)

    def gains(gl, hl):
        gr, hr = g_sum - gl, h_sum - hl
        ok = (hl >= min_hess) & (hr >= min_hess)
        return jnp.where(ok, gl * gl / (hl + l2) + gr * gr / (hr + l2)
                         - parent, -jnp.inf)

    gl_d = jnp.cumsum(dense[..., 0], axis=1)
    hl_d = jnp.cumsum(dense[..., 1], axis=1)
    gl_i, hl_i = g_sum - bin1[:, 0], h_sum - bin1[:, 1]
    gain = jnp.concatenate([gains(gl_d, hl_d).reshape(-1), gains(gl_i, hl_i)])
    gl = jnp.concatenate([gl_d.reshape(-1), gl_i])
    hl = jnp.concatenate([hl_d.reshape(-1), hl_i])
    k = jnp.argmax(gain)
    D = dense.shape[0]
    dense_k = k < D * NUM_BINS
    f = jnp.where(dense_k, k // NUM_BINS, D + k - D * NUM_BINS)
    b = jnp.where(dense_k, k % NUM_BINS, 0)
    return jnp.stack([gain[k], f.astype(jnp.float32), b.astype(jnp.float32),
                      gl[k], hl[k]])


# ------------------------------------------------------------------- host
def _indicator_columns(X) -> np.ndarray:
    """Columns of CSR ``X`` whose stored values are all 1."""
    not_one = np.zeros(X.shape[1], dtype=bool)
    not_one[np.unique(X.indices[X.data != 1.0])] = True
    return np.flatnonzero(~not_one)


def find_bundles(sample_rows: list, num_bins: np.ndarray, sample_cnt: int,
                 max_bundle_bins: int, max_conflict_rate: float) -> list:
    """Algorithm 3 over the candidates' sampled rows: ``sample_rows[j]``
    the sorted sampled rows in which candidate ``j`` is away from bin 0;
    returns lists of candidate numbers, one per bundle."""
    budget = int(max_conflict_rate * sample_cnt)
    order = sorted(range(len(sample_rows)),
                   key=lambda j: (-len(sample_rows[j]), j))
    bundles, held, conflicts, bins = [], [], [], []
    for j in order:
        rows, extra = sample_rows[j], int(num_bins[j]) - 1
        for b in range(len(bundles)):
            if bins[b] + extra > max_bundle_bins:
                continue
            meets = int(np.count_nonzero(held[b][rows]))
            if conflicts[b] + meets <= budget:
                bundles[b].append(j)
                held[b][rows] = True
                conflicts[b] += meets
                bins[b] += extra
                break
        else:
            mask = np.zeros(sample_cnt, dtype=bool)
            mask[rows] = True
            bundles.append([j])
            held.append(mask)
            conflicts.append(0)
            bins.append(1 + extra)
    return bundles


class Reference:
    """The table's bins on the device and the host, and ``step()`` by
    ``step()`` the boosted scores of the training rows."""

    def __init__(self, X, y: np.ndarray, params: Params,
                 gh_dtype=jnp.float32, drop_odd_rows: bool = False,
                 freeze_scores: bool = False):
        self.p = params
        self.y = np.asarray(y, dtype=np.float32)
        self.R = X.shape[0]
        self.gh_dtype = gh_dtype
        self.drop_odd_rows = drop_odd_rows      # fault: half the batch
        self.freeze_scores = freeze_scores      # fault: state unchanged
        t0 = time.perf_counter()
        X = X.tocsr()
        indicators = _indicator_columns(X)
        self.dense_cols = np.setdiff1d(np.arange(X.shape[1]), indicators)
        sample = binning.sample_rows(self.R, params.bin_construct_sample_cnt,
                                     params.data_random_seed)
        dense = X[:, self.dense_cols].toarray().astype(np.float32)
        bounds = binning.find_bounds(
            dense, params.max_bin, params.min_data_in_bin,
            params.bin_construct_sample_cnt, params.data_random_seed)
        self.thresholds = jnp.asarray(binning.bounds_matrix(bounds,
                                                            NUM_BINS))
        # an indicator is used where the sample holds its level
        Xs = X if sample is None else X[sample]
        sampled = np.bincount(Xs.indices, minlength=X.shape[1])
        if (sampled[self.dense_cols] <= SPARSE_SHARE * Xs.shape[0]).any():
            raise NotImplementedError(
                "a sparse numerical column would be a bundling candidate, "
                "which is outside this reference")
        self.ind_cols = indicators[sampled[indicators] > 0]
        self.D, self.I = len(self.dense_cols), len(self.ind_cols)
        t1 = time.perf_counter()
        self.bins = self._bin_dense(dense)                  # device [R, D]
        self.bins_t = np.ascontiguousarray(np.asarray(self.bins).T)
        dense_bins = np.asarray([len(b) for b in bounds])
        self.conflict_rows = 0
        slots = self._slots(X, sample, Xs, dense_bins)
        self.slots_host = slots                              # [R, K]
        self.slots = jnp.asarray(slots)
        self.seconds = {"find bins": t1 - t0,
                        "bin rows": time.perf_counter() - t1, "steps": []}
        self.init = gbdt.init_score(self.y)
        self.score = np.full(self.R, self.init, dtype=np.float32)
        self.trees: list = []
        self._idx_len = -(-self.R // HIST_ROWS) * HIST_ROWS

    def _bin_dense(self, dense: np.ndarray):
        parts = [gbdt._bin_block(jnp.asarray(dense[lo:lo + gbdt.BIN_ROWS]),
                                 self.thresholds)
                 for lo in range(0, dense.shape[0], gbdt.BIN_ROWS)]
        return jnp.concatenate(parts, axis=0)

    def _slots(self, X, sample, Xs, dense_bins) -> np.ndarray:
        """``[R, K]``: each training row's indicators (numbered from 0 in
        column order), -1 in the slots left over, with the members of a
        bundle in conflict reduced to the one of the higher column."""
        col_to_ind = np.full(X.shape[1], -1, dtype=np.int64)
        col_to_ind[self.ind_cols] = np.arange(self.I)
        rows = np.repeat(np.arange(self.R), np.diff(X.indptr))
        ind = col_to_ind[X.indices]
        rows, ind = rows[ind >= 0], ind[ind >= 0]
        bundle = np.full(self.I, -1, dtype=np.int64)
        if self.p.enable_bundle:
            bundle = self._bundle_of(Xs, col_to_ind, dense_bins)
        # in each (row, bundle) of more than one member keep the last in
        # column order; indicators in no bundle keep all their rows
        key = np.where(bundle[ind] >= 0, bundle[ind], -1 - ind)
        order = np.lexsort((ind, key, rows))
        rows, ind, key = rows[order], ind[order], key[order]
        last = np.ones(len(rows), dtype=bool)
        last[:-1] = (rows[1:] != rows[:-1]) | (key[1:] != key[:-1])
        self.conflict_rows = int(np.count_nonzero(
            ~last[:-1] & last[1:]) if len(last) else 0)
        rows, ind = rows[last], ind[last]
        counts = np.bincount(rows, minlength=self.R)
        K = max(int(counts.max()), 1)
        slots = np.full((self.R, K), -1, dtype=np.int32)
        start = np.concatenate([[0], np.cumsum(counts)[:-1]])
        slots[rows, np.arange(len(rows)) - start[rows]] = ind
        return slots

    def _bundle_of(self, Xs, col_to_ind, dense_bins) -> np.ndarray:
        """Per indicator its bundle among those of more than one member
        (-1 where alone), from this reference's own pass over the sample."""
        n = Xs.shape[0]
        csc = Xs.tocsc()
        cand, sample_rows = [], []
        for i, col in enumerate(self.ind_cols):
            rows = csc.indices[csc.indptr[col]:csc.indptr[col + 1]]
            if len(rows) <= SPARSE_SHARE * n:
                cand.append(i)
                sample_rows.append(np.sort(rows))
        # the numerical columns are dense (checked in __init__): none is a
        # candidate
        max_num_bin = max(int(dense_bins.max()) if len(dense_bins) else 2, 2)
        limit = max(max_num_bin, min(self.p.max_bin + 1, 256))
        bundles = find_bundles(sample_rows, np.full(len(cand), 2), n, limit,
                               self.p.max_conflict_rate)
        bundle = np.full(self.I, -1, dtype=np.int64)
        for b, members in enumerate(bundles):
            if len(members) > 1:
                bundle[[cand[j] for j in members]] = b
        return bundle

    def _idx(self, rows: np.ndarray):
        buf = np.zeros(self._idx_len, dtype=np.int32)
        buf[:len(rows)] = rows
        return jnp.asarray(buf), jnp.int32(len(rows))

    def _histograms(self, gh, rows: np.ndarray):
        idx, n = self._idx(rows)
        return _leaf_histograms(self.bins, self.slots, gh, idx, n, self.I)

    def holds(self, f: int, rows: np.ndarray) -> np.ndarray:
        """Whether each of ``rows`` holds indicator ``f`` (feature
        ``D + f``) after the conflicts."""
        return (self.slots_host[rows] == f).any(axis=1)

    def step(self) -> np.ndarray:
        """One boosting step; returns the scores after it."""
        t0 = time.perf_counter()
        p = gbdt._sigmoid(self.score.astype(np.float32))
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

    def _goes_left(self, f: int, b: int, rows: np.ndarray) -> np.ndarray:
        if f < self.D:
            return self.bins_t[f, rows] <= b
        return ~self.holds(f - self.D, rows)

    def _grow(self, gh, gh_host):
        prm = self.p
        min_hess = jnp.float32(prm.min_sum_hessian_in_leaf)
        l2 = jnp.float32(prm.lambda_l2)
        order = np.arange(self.R, dtype=np.int32)
        seg = {0: (0, self.R)}
        sums = {0: gh_host.sum(axis=0)}
        dense, bin1 = self._histograms(gh, order)
        hists = {0: (dense, bin1)}
        cand = {0: np.asarray(_scan(dense, bin1, *jnp.asarray(
            sums[0], jnp.float32), min_hess, l2), dtype=np.float64)}
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
            left = self._goes_left(f, b, rows)
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
            d_small, i_small = self._histograms(gh, order[s_lo:s_hi])
            d_parent, i_parent = hists[leaf]
            hists[small] = (d_small, i_small)
            hists[large] = (d_parent - d_small, i_parent - i_small)
            for k in (small, large):
                cand[k] = np.asarray(_scan(*hists[k], *jnp.asarray(
                    sums[k], jnp.float32), min_hess, l2), dtype=np.float64)
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

    def predict_raw(self, X) -> np.ndarray:
        """Raw scores of held-out CSR rows through every tree grown, each
        column read as it is (no conflict)."""
        X = X.tocsc()
        dense = X[:, self.dense_cols].toarray().astype(np.float32)
        bins_t = np.ascontiguousarray(np.asarray(self._bin_dense(dense)).T)

        def column(f):
            if f < self.D:
                return bins_t[f]
            col = self.ind_cols[f - self.D]
            out = np.zeros(X.shape[0], dtype=np.int32)
            out[X.indices[X.indptr[col]:X.indptr[col + 1]]] = 1
            return out

        out = np.full(X.shape[0], self.init, dtype=np.float64)
        for tree in self.trees:
            out += tree.value[tree.leaves(column)]
        return out
