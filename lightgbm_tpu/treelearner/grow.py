"""The leaf-wise grower's device code, shared by every learner.

One tree is grown by one loop (``_grow_tree``): while a leaf has a
positive-gain candidate, the device argmaxes the next leaf, writes the
winning ``SplitRecord`` into the read-back buffer and runs one split
step (``_split_step``): partition the leaf's rows, histogram the
smaller child, take its sibling by subtraction from the per-leaf store,
scan both children for their own best splits. The serial learner
(treelearner/serial.py), the mesh learners (parallel/) and the
out-of-core learner (treelearner/sharded.py) differ in how a histogram
is built and where its rows live; what a split *is* lives here, once.

XLA needs static shapes, so the two data-dependent quantities are
handled as:

- **row->leaf partition**: a full-length ``leaf_of_row`` vector updated
  by a vectorized compare on the split feature's bin column (the
  analogue of the reference's DataPartition::Split,
  src/treelearner/data_partition.hpp:21 / cuda_data_partition.cu:288).
- **rows ordered by leaf** (``_partition_order``; learners that compact,
  which is every learner on one device): ``order`` holds the row
  numbers with each leaf's rows contiguous and ascending, ``seg_begin``
  / ``seg_count`` each leaf's segment (the reference's ``indices_``,
  ``leaf_begin_``, ``leaf_count_``). A split reorders its parent's
  segment only: the smallest window of a static ladder
  (``_window_sizes``) that holds the parent is sliced out, stably
  partitioned into left rows then right rows, and written back.
- **per-leaf row gather** (``_compact_child_hist``): the smaller
  child's rows, a slice of ``order`` and so in ascending order, are
  histogrammed a tile at a time by a loop whose trip count is the
  child's row count over the tile's rows (``ops/histogram.py``
  ``histogram_tiles``); the last tile's tail carries gh 0 and vanishes
  from every sum.

max_depth gating follows BeforeFindBestSplit (serial_tree_learner.cpp:287):
a leaf at depth d is splittable iff max_depth <= 0 or d < max_depth —
enforced on device by zeroing candidate gains at record-creation time,
using a device-resident per-leaf depth vector.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..io.binning import MissingType
from ..io.dataset import BinnedDataset
from ..io.efb import member_bin
from ..models.tree import Tree
from ..ops.histogram import subtract_histogram
from ..ops.split import SplitInfo, find_best_split, make_rand_bins

_NEG_INF = -jnp.inf


class GrowState(NamedTuple):
    """Device-resident per-tree state (the analogue of the CUDA learner's
    CUDALeafSplits + histogram + partition buffers)."""
    leaf_of_row: jnp.ndarray      # [R] i32 (R = N+1; last row is a dummy, -1)
    gh: jnp.ndarray               # [R, 4] f32 (grad, hess, in-bag, total=1)
    hists: jnp.ndarray            # [L, F, B, 4] f32
    leaf_depth: jnp.ndarray       # [L] i32 — device-side max_depth gating
    # Per-leaf best-split candidates (SplitInfo fields, array-of-struct):
    gain: jnp.ndarray             # [L] f32, -inf when invalid
    feature: jnp.ndarray          # [L] i32
    threshold_bin: jnp.ndarray    # [L] i32
    default_left: jnp.ndarray    # [L] bool
    is_categorical: jnp.ndarray   # [L] bool
    cat_mask: jnp.ndarray         # [L, B] bool — bins going left (cat)
    # monotone bounds each candidate's children would inherit
    cand_left_min: jnp.ndarray    # [L] f32
    cand_left_max: jnp.ndarray
    cand_right_min: jnp.ndarray
    cand_right_max: jnp.ndarray
    left_sum_grad: jnp.ndarray    # [L] f32
    left_sum_hess: jnp.ndarray
    left_count: jnp.ndarray
    left_total_count: jnp.ndarray
    left_output: jnp.ndarray
    right_sum_grad: jnp.ndarray
    right_sum_hess: jnp.ndarray
    right_count: jnp.ndarray
    right_total_count: jnp.ndarray
    right_output: jnp.ndarray
    # Rows ordered by leaf, where the learner compacts (None where it
    # histograms the masked row space: no leaf of the pytree, so such a
    # learner's programs carry nothing for them):
    order: Optional[jnp.ndarray] = None      # [R + W] i32, make_root_state
    seg_begin: Optional[jnp.ndarray] = None  # [L] i32 — leaf's first entry
    seg_count: Optional[jnp.ndarray] = None  # [L] i32 — its physical rows


class SplitRecord(NamedTuple):
    """One winning split, read back to the host (per step or per tree)."""
    leaf: jnp.ndarray
    gain: jnp.ndarray
    feature: jnp.ndarray
    threshold_bin: jnp.ndarray
    default_left: jnp.ndarray
    is_categorical: jnp.ndarray
    cat_mask: jnp.ndarray
    left_sum_grad: jnp.ndarray
    left_sum_hess: jnp.ndarray
    left_count: jnp.ndarray
    left_total_count: jnp.ndarray
    left_output: jnp.ndarray
    right_sum_grad: jnp.ndarray
    right_sum_hess: jnp.ndarray
    right_count: jnp.ndarray
    right_total_count: jnp.ndarray
    right_output: jnp.ndarray


def _record_at(state: GrowState, leaf) -> SplitRecord:
    return SplitRecord(
        leaf=leaf, gain=state.gain[leaf], feature=state.feature[leaf],
        threshold_bin=state.threshold_bin[leaf],
        default_left=state.default_left[leaf],
        is_categorical=state.is_categorical[leaf],
        cat_mask=state.cat_mask[leaf],
        left_sum_grad=state.left_sum_grad[leaf],
        left_sum_hess=state.left_sum_hess[leaf],
        left_count=state.left_count[leaf],
        left_total_count=state.left_total_count[leaf],
        left_output=state.left_output[leaf],
        right_sum_grad=state.right_sum_grad[leaf],
        right_sum_hess=state.right_sum_hess[leaf],
        right_count=state.right_count[leaf],
        right_total_count=state.right_total_count[leaf],
        right_output=state.right_output[leaf])


def _empty_records(k: int, B: int) -> SplitRecord:
    """[k]-shaped record buffers; feature = -1 marks never-written slots."""
    zi = jnp.zeros(k, dtype=jnp.int32)
    zf = jnp.zeros(k, dtype=jnp.float32)
    zb = jnp.zeros(k, dtype=bool)
    return SplitRecord(
        leaf=zi, gain=jnp.full(k, _NEG_INF, dtype=jnp.float32),
        feature=jnp.full(k, -1, dtype=jnp.int32), threshold_bin=zi,
        default_left=zb, is_categorical=zb,
        cat_mask=jnp.zeros((k, B), dtype=bool),
        left_sum_grad=zf, left_sum_hess=zf, left_count=zf,
        left_total_count=zf, left_output=zf,
        right_sum_grad=zf, right_sum_hess=zf, right_count=zf,
        right_total_count=zf, right_output=zf)


@jax.named_scope("obs_split_scan")
def _store_info(state: GrowState, leaf, info: SplitInfo, allowed,
                valid=True) -> GrowState:
    """Write a leaf's candidate split; ``allowed`` zeroes the gain
    (max_depth gating), ``valid`` guards the whole write (loop steps
    after the no-more-splits point must leave state untouched)."""
    def put(arr, new):
        return arr.at[leaf].set(jnp.where(valid, new, arr[leaf]))
    return state._replace(
        gain=put(state.gain, jnp.where(allowed, info.gain, _NEG_INF)),
        feature=put(state.feature, info.feature),
        threshold_bin=put(state.threshold_bin, info.threshold_bin),
        default_left=put(state.default_left, info.default_left),
        is_categorical=put(state.is_categorical, info.is_categorical),
        cat_mask=state.cat_mask.at[leaf].set(
            jnp.where(valid, info.cat_mask, state.cat_mask[leaf])),
        cand_left_min=put(state.cand_left_min, info.left_min_output),
        cand_left_max=put(state.cand_left_max, info.left_max_output),
        cand_right_min=put(state.cand_right_min, info.right_min_output),
        cand_right_max=put(state.cand_right_max, info.right_max_output),
        left_sum_grad=put(state.left_sum_grad, info.left_sum_grad),
        left_sum_hess=put(state.left_sum_hess, info.left_sum_hess),
        left_count=put(state.left_count, info.left_count),
        left_total_count=put(state.left_total_count, info.left_total_count),
        left_output=put(state.left_output, info.left_output),
        right_sum_grad=put(state.right_sum_grad, info.right_sum_grad),
        right_sum_hess=put(state.right_sum_hess, info.right_sum_hess),
        right_count=put(state.right_count, info.right_count),
        right_total_count=put(state.right_total_count,
                              info.right_total_count),
        right_output=put(state.right_output, info.right_output))


def make_root_state(gh, hist, leaf_of_row, info, L: int, F: int, B: int,
                    children_allowed, hist_slots: int = 0,
                    ordered: bool = False) -> GrowState:
    """Initial GrowState after the root histogram+scan (shared by the
    serial and mesh-parallel learners). ``hist_slots`` shrinks the
    per-leaf histogram store for learners that never re-read it (the
    voting learner re-votes per leaf instead of subtracting).

    ``ordered`` (the learner compacts) adds the rows ordered by leaf:
    ``order[:R]`` is leaf 0's rows, ascending, then the pad rows (leaf
    -1); ``order[R:]`` is W entries of row 0 that no segment owns, W
    the largest window of ``_window_sizes``, there so that a window or
    a tile sliced from inside any segment never passes the end
    (``dynamic_slice`` would shift it left, and the rows would reach
    the histogram at other positions)."""
    hist_slots = hist_slots or L
    by_leaf = {}
    if ordered:
        R = leaf_of_row.shape[0]
        on_root = leaf_of_row == 0
        dest, n_root = _lefts_first(on_root, jnp.ones(R, dtype=bool))
        zl = jnp.zeros(L, dtype=jnp.int32)
        by_leaf = dict(
            order=jnp.zeros(R + _window_sizes(R)[0], dtype=jnp.int32)
            .at[dest].set(jnp.arange(R, dtype=jnp.int32),
                          unique_indices=True, mode="promise_in_bounds"),
            seg_begin=zl, seg_count=zl.at[0].set(n_root))
    zf = lambda: jnp.zeros(L, dtype=jnp.float32)
    state = GrowState(
        leaf_of_row=leaf_of_row, gh=gh,
        hists=jnp.zeros((hist_slots, F, B, 4),
                        dtype=hist.dtype).at[0].set(hist),
        leaf_depth=jnp.zeros(L, dtype=jnp.int32),
        gain=jnp.full(L, _NEG_INF, dtype=jnp.float32),
        feature=jnp.full(L, -1, dtype=jnp.int32),
        threshold_bin=jnp.zeros(L, dtype=jnp.int32),
        default_left=jnp.zeros(L, dtype=bool),
        is_categorical=jnp.zeros(L, dtype=bool),
        cat_mask=jnp.zeros((L, B), dtype=bool),
        cand_left_min=jnp.full(L, -jnp.inf, dtype=jnp.float32),
        cand_left_max=jnp.full(L, jnp.inf, dtype=jnp.float32),
        cand_right_min=jnp.full(L, -jnp.inf, dtype=jnp.float32),
        cand_right_max=jnp.full(L, jnp.inf, dtype=jnp.float32),
        left_sum_grad=zf(), left_sum_hess=zf(), left_count=zf(),
        left_total_count=zf(), left_output=zf(), right_sum_grad=zf(),
        right_sum_hess=zf(), right_count=zf(), right_total_count=zf(),
        right_output=zf(), **by_leaf)
    return _store_info(state, 0, info, children_allowed)


def record_is_valid(rec) -> bool:
    """Host-side check of a read-back split record."""
    return (int(rec.feature) >= 0 and np.isfinite(float(rec.gain))
            and float(rec.gain) > 0.0)


def rec_valid(rec: SplitRecord):
    """Device-side twin of record_is_valid — the two predicates MUST stay
    in lockstep (the device suppresses state writes for invalid records,
    the host stops applying them; divergence would desync the tree from
    the partition)."""
    return ((rec.feature >= 0) & jnp.isfinite(rec.gain)
            & (rec.gain > 0.0))


def apply_split_record(tree: Tree, dataset: BinnedDataset, rec) -> None:
    """Replay one device split record into the host Tree (reference:
    the Tree::Split call inside SerialTreeLearner::Split,
    serial_tree_learner.cpp:593)."""
    leaf = int(rec.leaf)
    f = int(rec.feature)
    tbin = int(rec.threshold_bin)
    mapper = dataset.bin_mappers[f]
    common = dict(
        leaf=leaf, feature=dataset.real_feature_index(f),
        feature_inner=f,
        left_value=float(rec.left_output),
        right_value=float(rec.right_output),
        left_count=int(round(float(rec.left_count))),
        right_count=int(round(float(rec.right_count))),
        left_weight=float(rec.left_sum_hess),
        right_weight=float(rec.right_sum_hess),
        gain=float(rec.gain))
    if bool(rec.is_categorical):
        bin_mask = np.asarray(rec.cat_mask)
        cats = [mapper.bin_2_categorical[b]
                for b in np.nonzero(bin_mask)[0]
                if b < len(mapper.bin_2_categorical)]
        tree.split_categorical(cat_values=cats, bin_mask=bin_mask, **common)
    else:
        tree.split(
            threshold_bin=tbin,
            threshold_real=dataset.real_threshold(f, tbin),
            missing_type=mapper.missing_type,
            default_left=bool(rec.default_left), **common)


@jax.named_scope("obs_partition")
def _go_left_by_bin(col: jnp.ndarray, tbin, default_left,
                    missing_type, nan_bin, zero_bin,
                    is_categorical=None, cat_mask=None) -> jnp.ndarray:
    """Training-time split direction over bin values (reference:
    DenseBin::Split templated missing handling, src/io/dense_bin.hpp;
    categorical bitset routing ≙ DenseBin::SplitCategorical).

    ``is_categorical``/``cat_mask`` are given only by a program that
    can meet a categorical split (``_partition_rec`` is the rule).
    ``cat_mask[col]`` is a gather from the [B] table over every row of
    the data, whatever the leaf's size, and XLA keeps it under a
    ``where(False, ...)``: 7.3 ns a row a split on the v5e, 1,865 ms of
    a 6,366 ms iteration at 1M rows x 254 splits (chip traces, PR
    27-31). Data with no categorical feature must not pay it."""
    gl = col <= tbin
    gl = jnp.where((missing_type == MissingType.NAN) & (col == nan_bin),
                   default_left, gl)
    gl = jnp.where((missing_type == MissingType.ZERO) & (col == zero_bin),
                   default_left, gl)
    if is_categorical is not None:
        gl = jnp.where(is_categorical, cat_mask[col], gl)
    return gl


def _partition_rec(rec: SplitRecord, has_cat: bool) -> SplitRecord:
    """``rec`` as the partition reads it: without its categorical fields
    where the data has no categorical feature (``has_cat`` is the
    learners' static ``_has_cat``), so that ``_go_left_by_bin`` lowers
    no table lookup there. The one place that decides it, for every
    learner; the sharded learner strips the record on the host, before
    its jitted shard steps see it."""
    if has_cat:
        return rec
    return rec._replace(is_categorical=None, cat_mask=None)


def _rows_go_left(bins, rec: SplitRecord, meta, btab, bundled: bool,
                  has_cat: bool) -> jnp.ndarray:
    """[R] bool: the rows that ``rec`` sends left, over all of ``bins``
    (the caller masks by leaf)."""
    rec = _partition_rec(rec, has_cat)
    f = jnp.maximum(rec.feature, 0)
    col = _partition_col(bins, f, meta, btab, bundled)
    return _go_left_by_bin(col, rec.threshold_bin, rec.default_left,
                           meta.missing_type[f], meta.num_bin[f] - 1,
                           meta.zero_bin[f], rec.is_categorical,
                           rec.cat_mask)


def _maybe_rand_bins(extra_trees: bool, rand_seed, node_id, meta, params):
    """Per-node extra_trees random thresholds, or None."""
    if not extra_trees:
        return None
    key = jax.random.fold_in(jax.random.PRNGKey(rand_seed), node_id)
    return make_rand_bins(key, meta, params)


class BundleTables(NamedTuple):
    """Device-resident EFB tables (io/efb.py BundleLayout, per feature,
    read by io/efb.py ``member_bin``). ``num_bins`` is 0 for padding
    features."""
    group_of: jnp.ndarray       # [Fp] i32 bundle column
    first_bin: jnp.ndarray      # [Fp] i32 (0 where alone)
    num_bins: jnp.ndarray       # [Fp] i32
    zero_fix: jnp.ndarray       # [Fp] bool


def build_bundle_tables(dataset: BinnedDataset, Fp: int) -> BundleTables:
    """Device EFB tables from the dataset's BundleLayout, padded to
    ``Fp`` features (shared by the serial and mesh-parallel learners)."""
    lay = dataset.bundle
    F = dataset.num_features
    group_of = np.zeros(Fp, dtype=np.int32)
    group_of[:F] = lay.group_of
    zero_fix = np.zeros(Fp, dtype=bool)
    zero_fix[:F] = lay.needs_zero_fix
    num_bins = np.zeros(Fp, dtype=np.int32)
    num_bins[:F] = lay.num_bins
    first_bin = np.zeros(Fp, dtype=np.int32)
    first_bin[:F] = lay.first_bin
    return BundleTables(
        group_of=jnp.asarray(group_of), first_bin=jnp.asarray(first_bin),
        num_bins=jnp.asarray(num_bins), zero_fix=jnp.asarray(zero_fix))


@jax.named_scope("obs_partition")
def _partition_col(bins, f, meta, btab, bundled: bool):
    """The split feature's ORIGINAL bin value per row (unbundling a
    member's slots of its bundle column when bundled, by comparisons
    alone; identity otherwise)."""
    if not bundled:
        return jnp.take(bins, f, axis=1).astype(jnp.int32)
    raw = jnp.take(bins, btab.group_of[f], axis=1).astype(jnp.int32)
    return member_bin(raw, btab.first_bin[f], btab.num_bins[f],
                      meta.zero_bin[f], btab.zero_fix[f], where=jnp.where)


def _split_hist_store(hists, leaf, new_leaf, hist_small, smaller_is_left,
                      valid):
    """Subtract the sibling from the parent's stored histogram and store
    both children: ``(hists, hist_left, hist_right)``. The one place
    where a split step touches the per-leaf store ``[L, F, B, 4]``
    (serial and mesh learners). An invalid step writes the old slices
    back, so the store stays bit for bit what it was.

    Both old slices are read once, before the first write, and held
    behind an ``optimization_barrier`` so that XLA cannot re-derive
    them from the store inside the update fusions: a read of the old
    store ordered after a write keeps the carried buffer live across
    that write, and on the v5e the whole store was then copied twice
    per split (two ``copy`` of ``f32[L,F,B,4]`` in the ``while`` body,
    a fifth to a quarter of an iteration; ISSUE 28,
    tests/test_hist_store_inplace.py). Read nothing of ``hists`` after
    the first ``.at[].set`` here."""
    old_leaf, old_new = jax.lax.optimization_barrier(
        (hists[leaf], hists[new_leaf]))
    hist_large = subtract_histogram(old_leaf, hist_small)
    with jax.named_scope("obs_hist_subtract"):
        hist_left = jnp.where(smaller_is_left, hist_small, hist_large)
        hist_right = jnp.where(smaller_is_left, hist_large, hist_small)
    with jax.named_scope("obs_hist_store"):
        hists = hists \
            .at[leaf].set(jnp.where(valid, hist_left, old_leaf)) \
            .at[new_leaf].set(jnp.where(valid, hist_right, old_new))
    return hists, hist_left, hist_right


def _finish_split(state: GrowState, rec: SplitRecord, leaf, new_leaf,
                  valid, hist_left, hist_right, mask_left, mask_right,
                  meta, params, *, max_depth: int, extra_trees: bool,
                  has_cat: bool, rand_seed=0, pen_left=None,
                  pen_right=None, children_allowed=None,
                  qscale=None) -> GrowState:
    """Depth gating + both children's best-split scans + candidate
    stores — the tail of ``_split_step``, and of the out-of-core
    learner's finish programs (treelearner/sharded.py), which build
    their child histograms shard by shard. ``children_allowed`` None
    means: derive from the
    device-side leaf_depth against the static max_depth."""
    with jax.named_scope("obs_split_scan"):
        child_depth = state.leaf_depth[leaf] + 1
        leaf_depth = state.leaf_depth \
            .at[leaf].set(jnp.where(valid, child_depth,
                                    state.leaf_depth[leaf])) \
            .at[new_leaf].set(jnp.where(valid, child_depth,
                                        state.leaf_depth[new_leaf]))
        if children_allowed is None:
            children_allowed = ((max_depth <= 0)
                                | (child_depth < max_depth))

    left_info = find_best_split(
        hist_left, rec.left_sum_grad, rec.left_sum_hess,
        rec.left_count, rec.left_total_count, meta, params,
        mask_left, state.cand_left_min[leaf],
        state.cand_left_max[leaf],
        parent_output=rec.left_output,
        rand_bins=_maybe_rand_bins(extra_trees, rand_seed, 2 * new_leaf,
                                   meta, params),
        gain_penalty=pen_left, leaf_depth=child_depth,
        has_categorical=has_cat, hist_scale=qscale)
    right_info = find_best_split(
        hist_right, rec.right_sum_grad, rec.right_sum_hess,
        rec.right_count, rec.right_total_count, meta, params,
        mask_right, state.cand_right_min[leaf],
        state.cand_right_max[leaf],
        parent_output=rec.right_output,
        rand_bins=_maybe_rand_bins(extra_trees, rand_seed,
                                   2 * new_leaf + 1, meta, params),
        gain_penalty=pen_right, leaf_depth=child_depth,
        has_categorical=has_cat, hist_scale=qscale)

    state = state._replace(leaf_depth=leaf_depth)
    state = _store_info(state, leaf, left_info, children_allowed, valid)
    state = _store_info(state, new_leaf, right_info, children_allowed,
                        valid)
    return state


_ORDER_CHUNK = 16384


def _window_sizes(R: int) -> list:
    """The window ladder's sizes for ``R`` rows, largest first: a chunk
    of ``_ORDER_CHUNK`` entries doubled until it holds every row, so
    each is a whole number of chunks. A window's cost is its size, and
    the branches hold no histogram, so a ladder this fine costs next to
    nothing to compile."""
    sizes = [_ORDER_CHUNK]
    while sizes[0] < R:
        sizes.insert(0, 2 * sizes[0])
    return sizes


def _ladder_branch(sizes, count):
    """Index of the smallest of ``sizes`` (largest first) that holds
    ``count`` rows."""
    return jnp.clip(
        jnp.sum(jnp.asarray(sizes, dtype=jnp.int32) >= count) - 1,
        0, len(sizes) - 1)


def _prefix_counts(flags):
    """Inclusive prefix counts of ``flags`` ([n] bool, or f32 holding
    whole numbers), as f32: exact, every partial sum being a whole
    number under 2**24. Rows of 128 are summed by a product with a
    triangle of ones, and the rows' totals the same way one level up.
    ``jnp.cumsum`` gives the same numbers, but the TPU's compiler takes
    18 s over one of a million entries and so much again for every
    other size (host-side v5e compile, PR 34), which seven window
    branches cannot afford; this takes 1 s."""
    x = flags.astype(jnp.float32)
    n = x.shape[0]
    if n <= 128:
        return jnp.cumsum(x)
    ones = jnp.triu(jnp.ones((128, 128), dtype=jnp.float32))
    rows = jnp.matmul(jnp.pad(x, (0, -n % 128)).reshape(-1, 128), ones,
                      precision=jax.lax.Precision.HIGHEST)
    before = _prefix_counts(rows[:, -1]) - rows[:, -1]
    return (rows + before[:, None]).reshape(-1)[:n]


def _lefts_first(left, inside):
    """``(dest, n_left)`` of a stable partition of the entries under
    ``inside`` (a prefix of the positions): an entry under ``left``
    goes behind the lefts before it, another behind all lefts and the
    others before it (its position less the lefts so far); what lies
    behind ``inside`` keeps its place."""
    j = jnp.arange(left.shape[0], dtype=jnp.int32)
    lefts = _prefix_counts(left).astype(jnp.int32)
    n_left = lefts[-1]
    return jnp.where(left, lefts - 1,
                     jnp.where(inside, n_left + j - lefts, j)), n_left


@jax.named_scope("obs_compact")
def _partition_order(order, seg_begin, seg_count, gl, leaf, new_leaf,
                     valid):
    """Reorder ``leaf``'s segment of ``order`` for its split: the rows
    that ``gl`` ([R] bool, by row number) sends left first, the others
    behind them, both still ascending; ``leaf`` keeps the left part and
    ``new_leaf`` gets the right — the reference's DataPartition::Split
    (data_partition.hpp:107) on ``indices_``, ``leaf_begin_`` and
    ``leaf_count_``. Returns ``(order, seg_begin, seg_count)``; an
    invalid step returns all three bit for bit as they were.

    Only the parent's rows are touched: the smallest window of
    ``_window_sizes`` that holds them is sliced out at the segment's
    begin (``order``'s spare tail keeps the slice from ever being
    shifted), its first ``cnt`` entries are placed by one prefix count
    and one scatter of the window's size, and the chunks of the window
    that hold them are written back. A scatter of every row number,
    whatever the leaf's size, was a third of an iteration at 1M rows
    (5.9 ns a row a split on the v5e, chip traces of PR 28-33)."""
    begin, cnt = seg_begin[leaf], seg_count[leaf]
    sizes = _window_sizes(gl.shape[0])

    def make_branch(W):
        @jax.named_scope("obs_window_%d" % W)
        def branch(_):
            win = jax.lax.dynamic_slice(order, (begin,), (W,))
            inside = jnp.arange(W, dtype=jnp.int32) < cnt
            dest, n_left = _lefts_first(
                inside & gl.at[win].get(mode="promise_in_bounds"), inside)
            moved = jnp.zeros_like(win).at[dest].set(
                win, unique_indices=True, mode="promise_in_bounds")
            return jnp.pad(moved, (0, sizes[0] - W)), n_left
        return branch

    moved, n_left = jax.lax.switch(
        _ladder_branch(sizes, cnt), [make_branch(W) for W in sizes], 0)

    # the window goes back chunk by chunk, in a loop and not in the
    # switch: a branch that returned the updated ``order`` made XLA copy
    # the whole of it (the branches of a conditional share one operand)
    def write_chunk(i, order):
        at = i * _ORDER_CHUNK
        return jax.lax.dynamic_update_slice(
            order, jax.lax.dynamic_slice(moved, (at,), (_ORDER_CHUNK,)),
            (begin + at,))
    order = jax.lax.fori_loop(
        0, jnp.where(valid, -(-cnt // _ORDER_CHUNK), 0), write_chunk,
        order)

    def put(arr, at, new):
        return arr.at[at].set(jnp.where(valid, new, arr[at]))
    return (order, put(seg_begin, new_leaf, begin + n_left),
            put(put(seg_count, leaf, n_left), new_leaf, cnt - n_left))


@jax.named_scope("obs_compact")
def _compact_child_hist(bins, state: GrowState, leaf, tiles):
    """Histogram the rows of ``leaf``'s segment of ``state.order`` (the
    smaller child's) and no others, ``tiles.rows`` of them at a time
    (``tiles``: ``ops/histogram.py`` ``histogram_tiles`` of the
    learner's data): a loop whose trip count is the segment's row count
    over the tile's rows takes each tile's row numbers as a slice of
    ``order`` (whose spare tail keeps the last slice from being
    shifted), gathers ``gh[idx]`` and ``bins[idx]`` for the tile alone
    and adds its histogram to the accumulator it carries. A leaf-wise
    tree's total smaller-child row count is ~N·log2(L)/2, so this cuts
    per-tree histogram work by ~50x at 255 leaves vs masked full-row
    scans — the single-chip analogue of the reference's per-leaf row
    iterators (data_partition.hpp:119 GetIndexOnLeaf). The passes visit
    the child's rows and less than a tile besides; the histogram's time
    follows the rows visited. The rows keep their ascending order and
    the last tile's tail is row 0 with ``gh`` zeroed, so the tile's
    size changes the compiled program, never values."""
    gh, order = state.gh, state.order
    begin, count = state.seg_begin[leaf], state.seg_count[leaf]
    T = tiles.rows
    if T > order.shape[0] - gh.shape[0]:
        raise ValueError("a tile of %d rows passes the spare tail of "
                         "the rows ordered by leaf" % T)

    def add_tile(i, acc):
        live = i * T + jnp.arange(T, dtype=jnp.int32) < count
        idx = jnp.where(
            live, jax.lax.dynamic_slice(order, (begin + i * T,), (T,)), 0)
        gh_keep = jnp.where(live[:, None], gh[idx],
                            jnp.zeros((), dtype=gh.dtype))
        return tiles.add(acc, bins[idx], gh_keep)

    return tiles.result(jax.lax.fori_loop(
        0, -(-count // T), add_tile, tiles.zeros()))


def _subtract_child_hists(state: GrowState, rec: SplitRecord, leaf,
                          new_leaf, leaf_of_row, smaller_is_left, valid,
                          small_hist):
    """Histogram the smaller child only, ``small_hist(leaf id, mask,
    totals)`` over its rows (its segment of ``state.order`` where the
    learner compacts, the masked row space where not) and record sums,
    take the sibling by subtraction from the parent's stored histogram
    — BIT-EXACT in quantized-integer mode — and store both: ``(hists,
    hist_left, hist_right)``."""
    small_id = jnp.where(smaller_is_left, leaf, new_leaf)
    small_sel = leaf_of_row == small_id
    small_totals = jnp.stack([
        jnp.where(smaller_is_left, rec.left_sum_grad,
                  rec.right_sum_grad),
        jnp.where(smaller_is_left, rec.left_sum_hess,
                  rec.right_sum_hess),
        jnp.where(smaller_is_left, rec.left_count, rec.right_count),
        jnp.where(smaller_is_left, rec.left_total_count,
                  rec.right_total_count)])
    hist_small = small_hist(small_id, small_sel, small_totals)
    return _split_hist_store(state.hists, leaf, new_leaf, hist_small,
                             smaller_is_left, valid)


def _split_step(bins, state: GrowState, rec: SplitRecord, leaf, new_leaf,
                valid, mask_left, mask_right, meta, params, btab,
                child_hists, *, bundled: bool, has_cat: bool,
                max_depth: int, extra_trees: bool, row_sharding=None,
                children_allowed=None, rand_seed=0, pen_left=None,
                pen_right=None, qscale=None) -> GrowState:
    """Apply one split (already chosen: ``rec`` at ``leaf``) and scan
    both children: the one split step of every whole-tree loop and
    host-stepped driver. ``valid`` guards every state write (loop steps
    after the no-more-splits point must leave state untouched).

    ``child_hists(bins, state, rec, leaf, new_leaf, leaf_of_row,
    smaller_is_left, valid, mask_left, mask_right, qscale)`` is the
    learner's part: it returns the updated per-leaf store, both
    children's histograms and their scan masks (``_subtract_child_hists``
    for the learners that keep a store); the ``state`` it gets has the
    rows ordered by the new leaves already. ``row_sharding`` pins the new
    partition to the mesh learners' row layout. ``children_allowed``
    None means: derive from the device-side leaf_depth."""
    gl = _rows_go_left(bins, rec, meta, btab, bundled, has_cat)
    with jax.named_scope("obs_partition"):
        on_leaf = state.leaf_of_row == leaf
        leaf_of_row = jnp.where(valid & on_leaf & ~gl, new_leaf,
                                state.leaf_of_row)
        if row_sharding is not None:
            leaf_of_row = jax.lax.with_sharding_constraint(
                leaf_of_row, row_sharding)

    if state.order is not None:
        order, seg_begin, seg_count = _partition_order(
            state.order, state.seg_begin, state.seg_count, gl, leaf,
            new_leaf, valid)
        state = state._replace(order=order, seg_begin=seg_begin,
                               seg_count=seg_count)

    smaller_is_left = rec.left_total_count <= rec.right_total_count
    (hists, hist_left, hist_right, mask_left,
     mask_right) = child_hists(
        bins, state, rec, leaf, new_leaf, leaf_of_row, smaller_is_left,
        valid, mask_left, mask_right, qscale)
    state = state._replace(leaf_of_row=leaf_of_row, hists=hists)
    return _finish_split(state, rec, leaf, new_leaf, valid, hist_left,
                         hist_right, mask_left, mask_right, meta, params,
                         max_depth=max_depth, extra_trees=extra_trees,
                         has_cat=has_cat, rand_seed=rand_seed,
                         pen_left=pen_left, pen_right=pen_right,
                         children_allowed=children_allowed,
                         qscale=qscale)


def _grow_tree(state: GrowState, split_step, L: int, B: int,
               start_leaf=None, max_splits=None):
    """Grow the whole tree in one dispatch: while splits remain, the
    device argmaxes the next leaf (the argmax the reference does on the
    host, serial_tree_learner.cpp:194, and reaches across ranks via
    SyncUpGlobalBestSplit), runs ``split_step(state, rec, leaf,
    new_leaf, valid)`` and appends the record to the ``[L-1]`` buffer
    the host reads back once per tree. Exits as soon as no positive-gain
    candidate is left, so a short tree costs no wasted iterations.

    ``start_leaf``/``max_splits`` (traced) continue a tree whose first
    ``start_leaf - 1`` splits were forced on the host; without them the
    loop numbers its leaves from 1 and is bounded by ``L`` alone."""
    kb = L - 1

    def cond(carry):
        i, _, _, cont = carry
        return cont & (i < kb)

    def body(carry):
        i, state, recs, _ = carry
        with jax.named_scope("obs_pick_leaf"):
            best = jnp.argmax(state.gain).astype(jnp.int32)
            rec = _record_at(state, best)
            valid = rec_valid(rec)
            if max_splits is not None:
                valid = valid & (i < max_splits)
            recs = jax.tree_util.tree_map(
                lambda buf, v: buf.at[i].set(v), recs, rec)
        first = 1 if start_leaf is None else start_leaf
        new_leaf = (i + first).astype(jnp.int32)
        state = split_step(state, rec, best, new_leaf, valid)
        return i + 1, state, recs, valid

    carry = (jnp.int32(0), state, _empty_records(kb, B),
             jnp.asarray(True))
    _, state, recs, _ = jax.lax.while_loop(cond, body, carry)
    return state, recs
