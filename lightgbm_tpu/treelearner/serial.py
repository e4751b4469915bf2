"""Single-chip leaf-wise tree learner.

TPU-native counterpart of the reference's SerialTreeLearner
(src/treelearner/serial_tree_learner.cpp:159 ``Train``) and, closer in
spirit, its CUDA whole-loop learner
(src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp:128): all heavy state
— binned rows, gradients, per-leaf histograms, the row→leaf partition — is
device-resident; the host only orchestrates batches of split steps and
records the chosen splits into the host ``Tree``.

The binned matrix is a **traced argument** of every jitted function, never a
closed-over constant: closing over it would embed the whole dataset into the
HLO as a literal, making the compiled program scale with the data (at Higgs
scale ~300 MB of program).

XLA needs static shapes, so the two data-dependent quantities are handled as:

- **row→leaf partition**: a full-length ``leaf_of_row`` vector updated by a
  vectorized compare on the split feature's bin column (no index lists; the
  analogue of the reference's DataPartition::Split,
  src/treelearner/data_partition.hpp:21 / cuda_data_partition.cu:288).
- **per-leaf row gather**: rows of the leaf to histogram are compacted with
  ``jnp.nonzero(..., size=S)`` where the static size S is a power of two
  ≥ half the largest current leaf. Padding rows point at a dummy row whose
  (grad, hess, count) are zero so they vanish from sums.

Unlike the reference's CUDA learner (one host sync per split), split steps
run in **batches**: a ``lax.fori_loop`` executes k split steps per device
dispatch — the device itself argmaxes the next leaf to split, applies the
split, histograms the smaller child, scans both children — and a buffer of
k split records is read back per batch. S stays valid for a whole batch
because the maximum leaf size never grows as splits proceed; k is derived
from S (many steps per dispatch once gathers are small) so both the number
of host round-trips per tree (~log₂ num_leaves + num_leaves/32) and the
number of compiled variants (~log₂ N, keyed on S alone) stay small.

max_depth gating follows BeforeFindBestSplit (serial_tree_learner.cpp:287):
a leaf at depth d is splittable iff max_depth <= 0 or d < max_depth —
enforced on device by zeroing candidate gains at record-creation time,
using a device-resident per-leaf depth vector.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.binning import MissingType
from ..io.dataset import BinnedDataset
from ..models.tree import Tree
from ..obs import compile as obs_compile
from ..obs.registry import registry as obs
from ..ops.histogram import (build_histogram, subtract_histogram,
                             unpack_bundle_histogram)
from ..ops.quantize import dequantize_sums, sum_gh
from ..ops.split import (FeatureMeta, SplitInfo, SplitParams,
                         calculate_leaf_output, find_best_split,
                         make_rand_bins)
from ..utils import log, next_pow2 as _next_pow2
from ..utils.scalars import dev_bool, dev_i32
from .capabilities import (CapabilityMixin, train_cegb, train_monotone,
                           train_stepwise)

_NEG_INF = -jnp.inf
_MIN_BUCKET = 256
# Splits per device dispatch cap. Each batch costs one host round-trip
# (its cost on the chip: not measured), so larger batches trade a
# little wasted compute (stale gather size S) for fewer syncs: ~12
# dispatches/tree at 255 leaves.
_MAX_BATCH = 64


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


class SplitRecord(NamedTuple):
    """One winning split, read back to the host (per step or per batch)."""
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
    (max_depth gating), ``valid`` guards the whole write (batched steps
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
                    children_allowed, hist_slots: int = 0) -> GrowState:
    """Initial GrowState after the root histogram+scan (shared by the
    serial and mesh-parallel learners). ``hist_slots`` shrinks the
    per-leaf histogram store for learners that never re-read it (the
    voting learner re-votes per leaf instead of subtracting)."""
    hist_slots = hist_slots or L
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
        right_output=zf())
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


# ----------------------------------------------------------------------
# Jitted step functions. Module-level + lru_cache so the compiled
# executables are shared across learner instances (every test / Booster
# builds a new learner; per-instance closures would recompile the same
# graphs). All data — bins, meta, params — is traced arguments; only
# shapes and structural flags are static.
# ----------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _stage_gh_fn_cached(R: int):
    """One fused dispatch staging (grad, hess, ind) → padded [R, 4] gh.
    The former eager jnp.ones/stack/concatenate chain launched ~5 tiny
    dispatches per tree and performed implicit scalar transfers (each
    fill constant became a device buffer per call) — the transfer-guard
    sanitizer test pins this staging transfer-free."""
    def stage(grad, hess, ind):
        n = grad.shape[0]
        gh = jnp.stack([grad * ind, hess * ind, ind,
                        jnp.ones_like(ind)], axis=1)
        return jnp.concatenate(
            [gh, jnp.zeros((R - n, 4), dtype=gh.dtype)], axis=0)

    return obs_compile.instrument_jit("serial.stage_gh", stage)


@functools.lru_cache(maxsize=None)
def _rows_out_fn_cached(N: int):
    """[R] → [N] unpadded row view, jitted: an eager ``[:N]`` slice
    turns its bounds into device scalars per call (implicit
    transfers)."""
    def rows_out(leaf_of_row):
        return leaf_of_row[:N]

    return obs_compile.instrument_jit("serial.rows_out", rows_out)


@functools.lru_cache(maxsize=None)
def _pad_rows_fn_cached(R: int):
    """Pad quantized [N, 4] gh rows to the learner's padded row count
    (zero rows vanish from every histogram sum)."""
    def pad(gh):
        n = gh.shape[0]
        return jnp.concatenate(
            [gh, jnp.zeros((R - n, gh.shape[1]), dtype=gh.dtype)],
            axis=0)

    return obs_compile.instrument_jit("serial.pad_gh", pad)


def _maybe_rand_bins(extra_trees: bool, rand_seed, node_id, meta, params):
    """Per-node extra_trees random thresholds, or None."""
    if not extra_trees:
        return None
    key = jax.random.fold_in(jax.random.PRNGKey(rand_seed), node_id)
    return make_rand_bins(key, meta, params)


class BundleTables(NamedTuple):
    """Device-resident EFB tables (io/efb.py BundleLayout mirror).
    ``member[g, b]``/``unmap[g, b]`` route a bundle bin back to its
    owning feature and original bin; ``gidx_*`` gather the bundle
    histogram into per-feature histograms; zero rows are reconstructed
    for ``zero_fix`` features."""
    group_of: jnp.ndarray       # [Fp] i32
    member: jnp.ndarray         # [Gp, Bg] i32
    unmap: jnp.ndarray          # [Gp, Bg] i32
    gidx_g: jnp.ndarray         # [Fp, B] i32 (-1 = empty)
    gidx_b: jnp.ndarray         # [Fp, B] i32
    zero_fix: jnp.ndarray       # [Fp] bool


def _leaf_histogram(bins, gh, meta, btab, *, B: int, Bg: int,
                    bundled: bool, totals=None,
                    hist_impl: tuple = ("auto", False)):
    """Histogram of (a subset of) rows → per-feature [Fp, B, 4].
    Bundled mode histograms the [*, G] bundle matrix at Bg bins then
    unpacks (totals = the leaf's channel sums for zero-bin rows; must
    match the histogram dtype — quantized integer gh recomputes the
    exact int sums here when the caller only holds dequantized f32)."""
    if not bundled:
        return build_histogram(bins, gh, B, hist_impl=hist_impl)
    bhist = build_histogram(bins, gh, Bg, hist_impl=hist_impl)
    if totals is None or jnp.issubdtype(gh.dtype, jnp.integer):
        totals = sum_gh(gh)
    return unpack_bundle_histogram(bhist, btab.gidx_g, btab.gidx_b,
                                   btab.zero_fix, meta.zero_bin, totals)


def build_bundle_tables(dataset: BinnedDataset, Fp: int, Gp: int,
                        B: int, Bg: int) -> BundleTables:
    """Device EFB tables from the dataset's BundleLayout, padded to
    ``Fp`` features / ``Gp`` bundle columns (shared by the serial and
    mesh-parallel learners)."""
    lay = dataset.bundle
    F = dataset.num_features
    G = lay.num_groups
    member = np.full((Gp, Bg), -1, dtype=np.int32)
    member[:G, :lay.member.shape[1]] = lay.member
    unmap = np.zeros((Gp, Bg), dtype=np.int32)
    unmap[:G, :lay.unmap.shape[1]] = lay.unmap
    group_of = np.zeros(Fp, dtype=np.int32)
    group_of[:F] = lay.group_of
    gidx_g = np.full((Fp, B), -1, dtype=np.int32)
    gidx_b = np.zeros((Fp, B), dtype=np.int32)
    gidx_g[:F, :lay.gidx_g.shape[1]] = lay.gidx_g
    gidx_b[:F, :lay.gidx_b.shape[1]] = lay.gidx_b
    zero_fix = np.zeros(Fp, dtype=bool)
    zero_fix[:F] = lay.needs_zero_fix
    return BundleTables(
        group_of=jnp.asarray(group_of), member=jnp.asarray(member),
        unmap=jnp.asarray(unmap), gidx_g=jnp.asarray(gidx_g),
        gidx_b=jnp.asarray(gidx_b), zero_fix=jnp.asarray(zero_fix))


@jax.named_scope("obs_partition")
def _partition_col(bins, f, meta, btab, bundled: bool):
    """The split feature's ORIGINAL bin value per row (unbundling via the
    member/unmap LUTs when bundled; identity otherwise)."""
    if not bundled:
        return jnp.take(bins, f, axis=1).astype(jnp.int32)
    g = btab.group_of[f]
    raw = jnp.take(bins, g, axis=1).astype(jnp.int32)
    owner = btab.member[g][raw]
    return jnp.where(owner == f, btab.unmap[g][raw], meta.zero_bin[f])


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
    stores — the split-step tail shared verbatim by the serial and
    mesh-parallel learners (only the child-histogram computation
    differs). ``children_allowed`` None means: derive from the
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


def _split_body(bins, state: GrowState, rec: SplitRecord, leaf, new_leaf,
                valid, mask_left, mask_right, meta, params, btab, *,
                S, B: int, Bg: int, bundled: bool, max_depth: int,
                extra_trees: bool, has_cat: bool = True,
                hist_impl: tuple = ("auto", False), children_allowed=None,
                rand_seed=0, pen_left=None, pen_right=None,
                qscale=None) -> GrowState:
    """Apply one split (already chosen: ``rec`` at ``leaf``) and scan both
    children. Shared by the per-split, batched and fused paths.
    ``children_allowed`` None means: derive from device leaf_depth.

    ``S`` is the smaller-child gather size: a static int on the
    host-stepped paths (the host buckets it per batch), or a static
    tuple of bucket sizes on the fused whole-tree path — the device
    then picks the branch of a ``lax.switch`` ladder from the record's
    own child count. Fill rows hit the gh-zero dummy row, so the
    gather size selects compiled programs, never values."""
    R = bins.shape[0]
    gl = _rows_go_left(bins, rec, meta, btab, bundled, has_cat)
    with jax.named_scope("obs_partition"):
        on_leaf = state.leaf_of_row == leaf
        leaf_of_row = jnp.where(valid & on_leaf & ~gl, new_leaf,
                                state.leaf_of_row)

    smaller_is_left = rec.left_total_count <= rec.right_total_count
    small_id = jnp.where(smaller_is_left, leaf, new_leaf)
    small_totals = jnp.stack([
        jnp.where(smaller_is_left, rec.left_sum_grad, rec.right_sum_grad),
        jnp.where(smaller_is_left, rec.left_sum_hess, rec.right_sum_hess),
        jnp.where(smaller_is_left, rec.left_count, rec.right_count),
        jnp.where(smaller_is_left, rec.left_total_count,
                  rec.right_total_count)])

    # quantized mode: the record's totals are dequantized f32, but the
    # bundled zero-bin fix needs exact int sums — _leaf_histogram
    # recomputes them from the gathered integer rows
    def hist_at(size: int):
        with jax.named_scope("obs_compact"), \
                jax.named_scope("obs_bucket_%d" % size):
            (idx,) = jnp.nonzero(leaf_of_row == small_id, size=size,
                                 fill_value=R - 1)
            return _leaf_histogram(bins[idx], state.gh[idx], meta, btab,
                                   B=B, Bg=Bg, bundled=bundled,
                                   totals=small_totals,
                                   hist_impl=hist_impl)

    ladder = S if isinstance(S, tuple) else (S,)
    if len(ladder) == 1:
        hist_small = hist_at(ladder[0])
    else:
        # device-side bucket choice (the host `_bucket` policy, on
        # device): smallest ladder size ≥ child count + the f32-count
        # rounding margin; the ladder tops out at next_pow2(N), which
        # covers any child
        small_cnt = small_totals[3]
        k = jnp.clip(
            jnp.sum(jnp.asarray(ladder, dtype=jnp.float32)
                    < small_cnt + 16.0),
            0, len(ladder) - 1).astype(jnp.int32)
        hist_small = jax.lax.switch(
            k, [lambda _, s=s: hist_at(s) for s in ladder], 0)
    hists, hist_left, hist_right = _split_hist_store(
        state.hists, leaf, new_leaf, hist_small, smaller_is_left, valid)

    state = state._replace(leaf_of_row=leaf_of_row, hists=hists)
    return _finish_split(state, rec, leaf, new_leaf, valid, hist_left,
                         hist_right, mask_left, mask_right, meta, params,
                         max_depth=max_depth, extra_trees=extra_trees,
                         has_cat=has_cat, rand_seed=rand_seed,
                         pen_left=pen_left, pen_right=pen_right,
                         children_allowed=children_allowed,
                         qscale=qscale)


@functools.lru_cache(maxsize=None)
def _root_fn_cached(L: int, B: int, Bg: int, bundled: bool,
                    extra_trees: bool, has_cat: bool = True,
                    hist_impl: tuple = ("auto", False)):
    def root(bins, gh, leaf_of_row0, feature_mask, children_allowed,
             rand_seed, qscale, meta, params, btab):
        F = meta.num_bin.shape[0]
        sums_raw = sum_gh(gh)          # exact ints in quantized mode
        hist = _leaf_histogram(bins, gh, meta, btab, B=B, Bg=Bg,
                               bundled=bundled, totals=sums_raw,
                               hist_impl=hist_impl)
        sums = dequantize_sums(sums_raw, qscale)
        # root "parent" output: its own unsmoothed output (reference:
        # SerialTreeLearner::GetParentOutput, serial_tree_learner.cpp:786)
        parent_out = calculate_leaf_output(sums[0], sums[1], params)
        info = find_best_split(
            hist, sums[0], sums[1], sums[2], sums[3], meta, params,
            feature_mask, parent_output=parent_out,
            rand_bins=_maybe_rand_bins(extra_trees, rand_seed, 0, meta,
                                       params),
            leaf_depth=jnp.int32(0), has_categorical=has_cat,
            hist_scale=qscale)
        state = make_root_state(gh, hist, leaf_of_row0, info, L, F, B,
                                children_allowed)
        return state, _record_at(state, 0)

    return obs_compile.instrument_jit("serial.root", root)


@functools.lru_cache(maxsize=None)
def _step_fn_cached(S: int, B: int, Bg: int, bundled: bool,
                    extra_trees: bool, has_cat: bool = True,
                    hist_impl: tuple = ("auto", False)):
    """Per-split step (host chooses the leaf): used when per-node feature
    masks (interaction constraints / bynode sampling) force a host
    round-trip per split."""
    def step(bins, state: GrowState, leaf, new_leaf, children_allowed,
             mask_left, mask_right, rand_seed, qscale, meta, params,
             btab):
        rec = _record_at(state, leaf)
        state = _split_body(bins, state, rec, leaf, new_leaf,
                            jnp.asarray(True), mask_left, mask_right,
                            meta, params, btab, S=S, B=B, Bg=Bg,
                            bundled=bundled, max_depth=0,
                            extra_trees=extra_trees, has_cat=has_cat,
                            hist_impl=hist_impl,
                            children_allowed=children_allowed,
                            rand_seed=rand_seed, qscale=qscale)
        best = jnp.argmax(state.gain).astype(jnp.int32)
        return state, _record_at(state, best)

    return obs_compile.instrument_jit("serial.step", step,
                                      donate_argnums=(1,))


def _cegb_penalty(params, count, used, coupled, unfetched, lazy):
    """Per-feature CEGB gain penalty for scanning one leaf (reference:
    CostEfficientGradientBoosting::DeltaGain,
    cost_effective_gradient_boosting.hpp:80-99): split penalty scaled by
    leaf size + coupled penalty for model-new features + lazy per-row
    fetch cost for rows that have not used the feature yet."""
    pen = params.cegb_penalty_split * count + coupled * (~used)
    if lazy is not None:
        pen = pen + lazy * unfetched
    return params.cegb_tradeoff * pen


@functools.lru_cache(maxsize=None)
def _cegb_root_fn_cached(L: int, B: int, Bg: int, bundled: bool,
                         has_lazy: bool, has_cat: bool = True,
                         hist_impl: tuple = ("auto", False)):
    def root(bins, gh, leaf_of_row0, feature_mask, children_allowed,
             used, fetched, coupled, lazy, qscale, meta, params, btab):
        F = meta.num_bin.shape[0]
        sums_raw = sum_gh(gh)
        hist = _leaf_histogram(bins, gh, meta, btab, B=B, Bg=Bg,
                               bundled=bundled, totals=sums_raw,
                               hist_impl=hist_impl)
        sums = dequantize_sums(sums_raw, qscale)
        parent_out = calculate_leaf_output(sums[0], sums[1], params)
        if has_lazy:
            in_rows = (leaf_of_row0 >= 0).astype(jnp.float32)
            unfetched = jnp.einsum("r,rf->f", in_rows, 1.0 - fetched)
        else:
            unfetched, lazy = None, None
        pen = _cegb_penalty(params, sums[3], used, coupled, unfetched,
                            lazy)
        info = find_best_split(
            hist, sums[0], sums[1], sums[2], sums[3], meta, params,
            feature_mask, parent_output=parent_out, gain_penalty=pen,
            has_categorical=has_cat, hist_scale=qscale)
        state = make_root_state(gh, hist, leaf_of_row0, info, L, F, B,
                                children_allowed)
        return state, _record_at(state, 0)

    return obs_compile.instrument_jit("serial.cegb_root", root)


@functools.lru_cache(maxsize=None)
def _cegb_step_fn_cached(S: int, B: int, Bg: int, bundled: bool,
                         has_lazy: bool, has_cat: bool = True,
                         hist_impl: tuple = ("auto", False)):
    """Per-split CEGB step: applies the pending split, updates the
    used-features vector and (lazy mode) the per-(row, feature) fetched
    matrix, and scans both children with penalized gains (reference:
    SerialTreeLearner::Split + CEGB UpdateLeafBestSplits,
    cost_effective_gradient_boosting.hpp:101). Divergence from the
    reference: candidates stored for *other* leaves are not retroactively
    refunded when a coupled feature first becomes used — they keep the
    penalty until re-scanned as children (pessimistic ordering only)."""
    def step(bins, state: GrowState, leaf, new_leaf, children_allowed,
             feature_mask, used, fetched, coupled, lazy, qscale, meta,
             params, btab):
        rec = _record_at(state, leaf)
        f = jnp.maximum(rec.feature, 0)
        used2 = used.at[f].set(True)
        on_leaf = state.leaf_of_row == leaf
        if has_lazy:
            # every row that flowed through the new split node has now
            # "fetched" feature f (both children)
            fetched2 = jnp.maximum(
                fetched,
                on_leaf.astype(fetched.dtype)[:, None]
                * jax.nn.one_hot(f, fetched.shape[1],
                                 dtype=fetched.dtype))
            gl = _rows_go_left(bins, rec, meta, btab, bundled, has_cat)
            unf = 1.0 - fetched2
            unf_left = jnp.einsum(
                "r,rf->f", (on_leaf & gl).astype(jnp.float32), unf)
            unf_right = jnp.einsum(
                "r,rf->f", (on_leaf & ~gl).astype(jnp.float32), unf)
        else:
            fetched2 = fetched
            unf_left = unf_right = None
            lazy = None
        pen_l = _cegb_penalty(params, rec.left_total_count, used2,
                              coupled, unf_left, lazy)
        pen_r = _cegb_penalty(params, rec.right_total_count, used2,
                              coupled, unf_right, lazy)
        state = _split_body(bins, state, rec, leaf, new_leaf,
                            jnp.asarray(True), feature_mask, feature_mask,
                            meta, params, btab, S=S, B=B, Bg=Bg,
                            bundled=bundled, max_depth=0,
                            extra_trees=False, has_cat=has_cat,
                            hist_impl=hist_impl,
                            children_allowed=children_allowed,
                            pen_left=pen_l, pen_right=pen_r,
                            qscale=qscale)
        best = jnp.argmax(state.gain).astype(jnp.int32)
        return state, _record_at(state, best), used2, fetched2

    return obs_compile.instrument_jit("serial.cegb_step", step,
                                      donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _mono_step_fn_cached(S: int, B: int, Bg: int, bundled: bool,
                         has_cat: bool = True,
                         hist_impl: tuple = ("auto", False)):
    """Per-split step for monotone_constraints_method=intermediate: the
    children's output bounds come from the host tracker (sibling-output
    based, monotone_constraints.hpp:543) instead of the mid-point rule
    baked into the stored candidate."""
    def step(bins, state: GrowState, leaf, new_leaf, children_allowed,
             feature_mask, lmin, lmax, rmin, rmax, qscale, meta, params,
             btab):
        state = state._replace(
            cand_left_min=state.cand_left_min.at[leaf].set(lmin),
            cand_left_max=state.cand_left_max.at[leaf].set(lmax),
            cand_right_min=state.cand_right_min.at[leaf].set(rmin),
            cand_right_max=state.cand_right_max.at[leaf].set(rmax))
        rec = _record_at(state, leaf)
        state = _split_body(bins, state, rec, leaf, new_leaf,
                            jnp.asarray(True), feature_mask, feature_mask,
                            meta, params, btab, S=S, B=B, Bg=Bg,
                            bundled=bundled, max_depth=0,
                            extra_trees=False, has_cat=has_cat,
                            hist_impl=hist_impl,
                            children_allowed=children_allowed,
                            qscale=qscale)
        best = jnp.argmax(state.gain).astype(jnp.int32)
        return state, _record_at(state, best), state.gain

    return obs_compile.instrument_jit("serial.mono_step", step,
                                      donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _rescan_fn_cached(B: int, has_cat: bool = True):
    """Recompute one leaf's best-split candidate from its stored
    histogram under tightened output bounds (reference:
    SerialTreeLearner::RecomputeBestSplitForLeaf,
    serial_tree_learner.cpp:800)."""
    def rescan(state: GrowState, leaf, sg, sh, c, tc, vmin, vmax, depth,
               allowed, feature_mask, qscale, meta, params, btab):
        hist = state.hists[leaf]
        own = calculate_leaf_output(sg, sh, params)
        parent_out = jnp.where(params.path_smooth > 1e-10, own, 0.0)
        info = find_best_split(hist, sg, sh, c, tc, meta, params,
                               feature_mask, vmin, vmax,
                               parent_output=parent_out,
                               leaf_depth=depth,
                               has_categorical=has_cat,
                               hist_scale=qscale)
        state = _store_info(state, leaf, info, allowed)
        best = jnp.argmax(state.gain).astype(jnp.int32)
        return state, _record_at(state, best), state.gain

    return obs_compile.instrument_jit("serial.rescan", rescan,
                                      donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _adv_rescan_fn_cached(B: int, has_cat: bool = True):
    """monotone_constraints_method=advanced candidate scan: the leaf's
    per-(feature, bin) constraint arrays replace the leaf-wide bound
    pair (reference: AdvancedLeafConstraints feeding FindBestThreshold
    through CumulativeFeatureConstraint,
    monotone_constraints.hpp:856-1184 + feature_histogram.hpp:874-951)."""
    def rescan(state: GrowState, leaf, sg, sh, c, tc, min_c, max_c,
               depth, allowed, feature_mask, qscale, meta, params, btab):
        hist = state.hists[leaf]
        own = calculate_leaf_output(sg, sh, params)
        parent_out = jnp.where(params.path_smooth > 1e-10, own, 0.0)
        info = find_best_split(hist, sg, sh, c, tc, meta, params,
                               feature_mask,
                               parent_output=parent_out,
                               leaf_depth=depth,
                               has_categorical=has_cat,
                               bound_arrays=(min_c, max_c),
                               hist_scale=qscale)
        state = _store_info(state, leaf, info, allowed)
        best = jnp.argmax(state.gain).astype(jnp.int32)
        return state, _record_at(state, best), state.gain

    return obs_compile.instrument_jit("serial.adv_rescan", rescan,
                                      donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _forced_fn_cached(S: int, B: int, Bg: int, bundled: bool,
                      extra_trees: bool, has_cat: bool = True,
                      hist_impl: tuple = ("auto", False)):
    """Forced split of a given (feature, threshold-bin) on a leaf
    (reference: SerialTreeLearner::ForceSplits,
    serial_tree_learner.cpp:451): the split record is built from the
    leaf's stored histogram instead of a best-gain scan, then applied
    through the normal split body so the children get their candidate
    scans."""
    def forced(bins, state: GrowState, leaf, new_leaf, f, tbin,
               children_allowed, feature_mask, rand_seed, qscale, meta,
               params, btab):
        row = state.hists[leaf][f]                   # [B, 4]
        cum = jnp.cumsum(row, axis=0)                # exact when integer
        tot = cum[-1]
        left = dequantize_sums(cum[tbin], qscale)
        right = dequantize_sums(tot, qscale) - left
        out_l = calculate_leaf_output(left[0], left[1], params)
        out_r = calculate_leaf_output(right[0], right[1], params)
        # default_left must match where the cumsum put the missing rows:
        # ZERO rows sit in the zero bin (left iff zero_bin <= tbin), NaN
        # rows in the last bin (left iff tbin reaches it) — same
        # convention as find_best_split's natural placement
        dl = jnp.where(meta.missing_type[f] == MissingType.NAN,
                       tbin >= meta.num_bin[f] - 1,
                       meta.zero_bin[f] <= tbin)
        rec = SplitRecord(
            leaf=leaf, gain=jnp.float32(0.0), feature=f,
            threshold_bin=tbin, default_left=dl,
            is_categorical=jnp.asarray(False),
            cat_mask=jnp.zeros(B, dtype=bool),
            left_sum_grad=left[0], left_sum_hess=left[1],
            left_count=left[2], left_total_count=left[3],
            left_output=out_l,
            right_sum_grad=right[0], right_sum_hess=right[1],
            right_count=right[2], right_total_count=right[3],
            right_output=out_r)
        ok = (left[3] > 0.5) & (right[3] > 0.5)
        state = _split_body(bins, state, rec, leaf, new_leaf, ok,
                            feature_mask, feature_mask, meta, params,
                            btab, S=S, B=B, Bg=Bg, bundled=bundled,
                            max_depth=0, extra_trees=extra_trees,
                            has_cat=has_cat, hist_impl=hist_impl,
                            children_allowed=children_allowed,
                            rand_seed=rand_seed, qscale=qscale)
        return state, rec, ok

    return obs_compile.instrument_jit("serial.forced", forced,
                                      donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _batch_fn_cached(S: int, kb: int, B: int, Bg: int, bundled: bool,
                     max_depth: int, extra_trees: bool,
                     has_cat: bool = True,
                     hist_impl: tuple = ("auto", False)):
    """Batched split steps: one dispatch runs kb splits, the device
    picking the best leaf each step (the argmax the reference does on host
    at serial_tree_learner.cpp:194). Records of the applied splits are
    written to [kb] buffers and read back once."""
    def batch(bins, state: GrowState, start_leaf, max_splits,
              feature_mask, rand_seed, qscale, meta, params, btab):
        def body(i, carry):
            state, recs = carry
            with jax.named_scope("obs_pick_leaf"):
                best = jnp.argmax(state.gain).astype(jnp.int32)
                rec = _record_at(state, best)
                valid = rec_valid(rec) & (i < max_splits)
                recs = jax.tree_util.tree_map(
                    lambda buf, v: buf.at[i].set(v), recs, rec)
            new_leaf = (start_leaf + i).astype(jnp.int32)
            state = _split_body(bins, state, rec, best, new_leaf, valid,
                                feature_mask, feature_mask, meta, params,
                                btab, S=S, B=B, Bg=Bg, bundled=bundled,
                                max_depth=max_depth,
                                extra_trees=extra_trees, has_cat=has_cat,
                                hist_impl=hist_impl,
                                rand_seed=rand_seed, qscale=qscale)
            return state, recs

        state, recs = jax.lax.fori_loop(
            0, kb, body, (state, _empty_records(kb, B)))
        return state, recs

    return obs_compile.instrument_jit("serial.batch", batch,
                                      donate_argnums=(1,))


def _bucket_ladder(bucket_fn, max_bucket: int) -> tuple:
    """Every gather size ``bucket_fn`` can return, ascending — the
    static branch ladder of the fused whole-tree grower. Each branch
    compiles one child-histogram gather size; the padded fill rows
    carry gh 0, so which branch runs changes compiled programs, never
    values."""
    sizes = {bucket_fn(0.0)}
    c = 1
    while c <= max_bucket:
        sizes.add(bucket_fn(float(c)))
        c <<= 1
    return tuple(sorted(sizes))


@functools.lru_cache(maxsize=None)
def _fused_fn_cached(L: int, B: int, Bg: int, bundled: bool,
                     max_depth: int, extra_trees: bool,
                     has_cat: bool = True,
                     hist_impl: tuple = ("auto", False),
                     ladder: tuple = ()):
    """Fused whole-tree growth: ONE dispatch runs the entire grow loop
    — the device argmaxes the next frontier leaf, applies the split
    (partition update + smaller-child histogram through the gather
    ladder + sibling subtraction), scans both children, and appends the
    record — until no positive-gain candidate remains. The host reads
    back only the [L-1] record buffer (the Booster-paper /
    XGBoost-GPU "whole pipeline on the accelerator" move; the serial
    analogue of the mesh learner's `_tree_impl`). Bit-identical to the
    stepped `serial.batch` loop: same body, same per-step argmax, same
    gather semantics."""
    kb = L - 1

    def fused(bins, state: GrowState, start_leaf, max_splits,
              feature_mask, rand_seed, qscale, meta, params, btab):
        def cond(carry):
            i, _, _, cont = carry
            return cont & (i < kb)

        def body(carry):
            i, state, recs, _ = carry
            with jax.named_scope("obs_pick_leaf"):
                best = jnp.argmax(state.gain).astype(jnp.int32)
                rec = _record_at(state, best)
                valid = rec_valid(rec) & (i < max_splits)
                recs = jax.tree_util.tree_map(
                    lambda buf, v: buf.at[i].set(v), recs, rec)
            new_leaf = (start_leaf + i).astype(jnp.int32)
            state = _split_body(bins, state, rec, best, new_leaf, valid,
                                feature_mask, feature_mask, meta, params,
                                btab, S=ladder, B=B, Bg=Bg,
                                bundled=bundled, max_depth=max_depth,
                                extra_trees=extra_trees, has_cat=has_cat,
                                hist_impl=hist_impl,
                                rand_seed=rand_seed, qscale=qscale)
            return i + 1, state, recs, valid

        carry = (jnp.int32(0), state, _empty_records(kb, B),
                 jnp.asarray(True))
        _, state, recs, _ = jax.lax.while_loop(cond, body, carry)
        return state, recs

    return obs_compile.instrument_jit("serial.fused_tree", fused,
                                      donate_argnums=(1,))


class SerialTreeLearner(CapabilityMixin):
    """Leaf-wise grower over a device-resident binned dataset."""

    def __init__(self, config, dataset: BinnedDataset):
        self.config = config
        self.dataset = dataset
        N = dataset.num_data
        F = dataset.num_features  # logical features (≠ bundle columns)
        if F == 0:
            log.fatal("Cannot train without features")
        self.N, self.F = N, F
        # pad the histogram width to a power of two: the actual max bin
        # count is data-dependent (e.g. 251 vs 247), and a canonical B
        # lets datasets with similar binning share compiled step variants
        self.B = _next_pow2(max(int(dataset.max_num_bin), 2))
        self.L = int(config.num_leaves)
        self.max_depth = int(config.max_depth)
        # Pad rows to a 4096 multiple (at least one dummy row) and
        # feature/bundle columns to an 8 multiple: pad rows carry gh 0 /
        # leaf -1 so they vanish from every sum, pad features are trivial
        # (num_bin 1), and the canonical shapes share compiled step
        # variants across datasets. The dummy rows double as the
        # nonzero-gather fill target.
        self.R = -(-(N + 1) // 4096) * 4096
        self.Fp = -(-F // 8) * 8
        from ..ops.histogram import resolve_hist_impl
        qbits = (int(getattr(config, "quant_grad_bits", 8))
                 if getattr(config, "use_quantized_grad", False) else 0)
        self._hist_impl = resolve_hist_impl(
            getattr(config, "hist_backend", "auto"),
            bool(getattr(config, "tpu_use_f64_hist", False)), qbits)
        self._init_quantization(self._hist_impl[2], config, N)
        self._bundled = dataset.bundle is not None
        ncols = (dataset.bundle.num_groups if self._bundled else F)
        self.Gp = -(-ncols // 8) * 8
        bins_host = np.zeros((self.R, self.Gp if self._bundled
                              else self.Fp), dtype=dataset.bins.dtype)
        bins_host[:N, :ncols if self._bundled else F] = dataset.bins
        with obs.scope("io::stage_bins_device"):
            self.bins = jnp.asarray(bins_host)
        self._leaf_of_row0 = jnp.concatenate([
            jnp.zeros(N, dtype=jnp.int32),
            jnp.full((self.R - N,), -1, dtype=jnp.int32)])
        # all-rows in-bag indicator, staged once (per-tree creation
        # would be an implicit scalar transfer per tree)
        self._ones_ind = jnp.ones(N, dtype=jnp.float32)
        from ..ops.split import pad_feature_meta
        self.meta = pad_feature_meta(
            FeatureMeta.from_dataset(dataset,
                                     int(config.max_cat_to_onehot)),
            self.Fp - F)
        self._build_bundle_tables(dataset)
        self.params = SplitParams.from_config(config)
        self._ff_rng = np.random.RandomState(config.feature_fraction_seed)
        self._resolve_constraints()
        self._max_bucket = _next_pow2(N)
        # fused whole-tree growth (default): the entire grow loop runs
        # as one dispatch; the stepped per-batch host loop stays behind
        # the flag (and under the host-stepped capability drivers)
        self._fused_growth = bool(getattr(config, "tpu_fused_tree", True))
        self._ladder = _bucket_ladder(self._bucket, self._max_bucket)
        # extra_trees (config.h:368): random single-threshold candidates,
        # seeded per tree (host counter) and per node (device fold-in)
        self._extra_trees = bool(config.extra_trees)
        self._extra_seed = int(config.extra_seed)
        self._tree_idx = 0
        # STATIC: all-numerical datasets compile out the categorical
        # scans entirely (two argsorts + a sequential 256-step lax.scan
        # per leaf scan)
        self._has_cat = bool(np.asarray(self.meta.is_categorical).any())
        self._root_fn = _root_fn_cached(self.L, self.B, self.Bg,
                                        self._bundled, self._extra_trees,
                                        self._has_cat, self._hist_impl)
        self._forced = self._load_forced_splits(config)
        self._init_cegb(config)
        self._init_monotone(config)

    # _sample_features lives on CapabilityMixin (shared with the
    # sharded out-of-core learner, treelearner/sharded.py)

    # ------------------------------------------------------------------
    def _build_bundle_tables(self, dataset: BinnedDataset) -> None:
        """Device EFB tables (or a dummy scalar when unbundled)."""
        if not self._bundled:
            self.Bg = 0
            self._btab = jnp.int32(0)
            return
        self.Bg = _next_pow2(max(dataset.bundle.num_bundled_bins, 2))
        self._btab = build_bundle_tables(dataset, self.Fp, self.Gp,
                                         self.B, self.Bg)

    def _step_fn(self, S: int):
        return _step_fn_cached(S, self.B, self.Bg, self._bundled,
                               self._extra_trees, self._has_cat,
                               self._hist_impl)

    def _batch_fn(self, S: int):
        kb = self._batch_k(S)
        return (_batch_fn_cached(S, kb, self.B, self.Bg, self._bundled,
                                 self.max_depth, self._extra_trees,
                                 self._has_cat, self._hist_impl), kb)

    def _fused_fn(self):
        return _fused_fn_cached(self.L, self.B, self.Bg, self._bundled,
                                self.max_depth, self._extra_trees,
                                self._has_cat, self._hist_impl,
                                self._ladder)

    def _batch_k(self, S: int) -> int:
        """Steps per dispatch: aim for ~4R gathered rows per batch so early
        (large-S) batches stay short while deep-tree batches amortize the
        host round-trip over many cheap steps. Derived from the padded row
        count R (not N) so the (S, kb) pair — and thus the compiled batch
        variant — is shared across datasets of similar size."""
        return int(np.clip((4 * self.R) // max(S, 1), 1, _MAX_BATCH))

    def _bucket(self, count: float) -> int:
        # Small data (one pad block): a single canonical gather size —
        # every small dataset then shares one compiled batch variant, and
        # the extra gathered rows are noise at this scale.
        if self.R <= 4096:
            return self.R // 2
        # +16 margin: counts travel as f32 sums and may round for very
        # large leaves. The floor caps compiled variants at ~log2(N) - 8.
        S = min(max(_next_pow2(int(count) + 16), _MIN_BUCKET),
                self._max_bucket)
        if self._max_bucket >= (1 << 20):
            # large datasets: even power-of-two exponents only — halves
            # the number of compiled batch variants for ≤2x gather slack
            e = S.bit_length() - 1
            if (e & 1) and S < self._max_bucket:
                S <<= 1
        return min(S, self._max_bucket)

    # ------------------------------------------------------------------
    def _load_forced_splits(self, config):
        """Parse forcedsplits_filename JSON (reference: forced splits
        config.h:518, format {"feature": i, "threshold": v,
        "left": {...}, "right": {...}})."""
        if not config.forcedsplits_filename:
            return None
        import json
        try:
            with open(config.forcedsplits_filename) as fh:
                return json.load(fh)
        except (OSError, ValueError) as e:
            log.warning("Cannot load forced splits from %s: %s"
                        % (config.forcedsplits_filename, e))
            return None

    def _apply_forced_splits(self, tree: Tree, state: GrowState,
                             feature_mask, rand_seed, leaf_total):
        """Apply the forced-split tree breadth-first before best-gain
        growth (reference: SerialTreeLearner::ForceSplits,
        serial_tree_learner.cpp:451). Returns (state, next_leaf)."""
        next_leaf = 1
        queue = [(0, self._forced)]
        while queue and next_leaf < self.L:
            leaf, spec = queue.pop(0)
            if not isinstance(spec, dict) or "feature" not in spec:
                continue
            inner = self.dataset.inner_feature_index(int(spec["feature"]))
            if inner < 0:
                continue
            mapper = self.dataset.bin_mappers[inner]
            tbin = int(mapper.value_to_bin(
                np.asarray([float(spec.get("threshold", 0.0))]))[0])
            M = max(leaf_total.values())
            S = self._bucket(M / 2)
            fn = _forced_fn_cached(S, self.B, self.Bg, self._bundled,
                                   self._extra_trees, self._has_cat,
                                   self._hist_impl)
            allowed = self._splittable(int(tree.leaf_depth[leaf]) + 1)
            state, rec, ok = fn(self.bins, state, jnp.int32(leaf),
                                jnp.int32(next_leaf), jnp.int32(inner),
                                jnp.int32(tbin), jnp.asarray(allowed),
                                feature_mask, rand_seed, self._qscale,
                                self.meta, self.params, self._btab)
            # jaxlint: disable=JLT001 -- forced splits are a host-
            # driven preamble (the host must validate each user-forced
            # split before recording it); runs once per tree root area
            if not bool(jax.device_get(ok)):
                log.warning("Forced split on feature %d leaves an empty "
                            "side; skipped" % int(spec["feature"]))
                continue
            # jaxlint: disable=JLT001 -- forced-split record read-back
            # (host Tree replay), same preamble as above
            r = jax.device_get(rec)
            apply_split_record(tree, self.dataset, r)
            leaf_total[leaf] = float(r.left_total_count)
            leaf_total[next_leaf] = float(r.right_total_count)
            if "left" in spec:
                queue.append((leaf, spec["left"]))
            if "right" in spec:
                queue.append((next_leaf, spec["right"]))
            next_leaf += 1
        return state, next_leaf

    def _splittable(self, depth: int) -> bool:
        return self.max_depth <= 0 or depth < self.max_depth

    def train(self, grad: jnp.ndarray, hess: jnp.ndarray,
              bag: Optional[jnp.ndarray] = None
              ) -> Tuple[Tree, jnp.ndarray]:
        """Grow one tree. ``grad``/``hess`` are f32[N] device arrays;
        ``bag`` an optional f32[N] in-bag indicator (0/1). Returns the host
        Tree and the final [N] row→leaf assignment (device) for score
        updates (reference: GBDT::UpdateScore uses the learner's partition,
        src/boosting/gbdt.cpp:475)."""
        with obs.scope("tree::stage_gh"):
            ind = self._ones_ind if bag is None else bag
            if self._quantized:
                gh, self._qscale = self._quantize_stage(
                    grad, hess, ind, self._tree_idx + 1)
                gh = _pad_rows_fn_cached(self.R)(gh)
            else:
                self._qscale = self._qs_ones
                # one fused dispatch for stack+pad: the former eager
                # jnp.ones/stack/concatenate chain performed implicit
                # scalar transfers each tree (transfer-guard sanitizer)
                gh = _stage_gh_fn_cached(self.R)(grad, hess, ind)
            # fencing mode blocks here so the staging cost lands in THIS
            # stage; sample/trace mode hands the output to the async
            # readiness drainer instead (no hot-path fence)
            obs.watch_ready("tree::stage_gh", gh)
            feature_mask = self._sample_features()

        tree = Tree(self.L)
        # per-tree extra_trees seed (traced, so no retrace per tree);
        # explicit device transfer — see utils/scalars.py
        self._tree_idx += 1
        rand_seed = dev_i32(
            (self._extra_seed + 7919 * self._tree_idx) & 0x7FFFFFFF)
        if self._cegb_enabled:
            state = train_cegb(self, tree, gh, feature_mask)
            return tree, _rows_out_fn_cached(self.N)(state.leaf_of_row)
        if self._mono_tracker is not None:
            state = train_monotone(self, tree, gh, feature_mask,
                                   rand_seed)
            return tree, _rows_out_fn_cached(self.N)(state.leaf_of_row)
        with obs.scope("tree::root_histogram"):
            state, rec = self._root_fn(self.bins, gh, self._leaf_of_row0,
                                       feature_mask,
                                       dev_bool(self._splittable(0)),
                                       rand_seed, self._qscale, self.meta,
                                       self.params, self._btab)
            obs.watch_ready("tree::root_histogram", rec)
        leaf_total = {0: float(self.N)}
        next_leaf = 1
        if self._forced is not None:
            state, next_leaf = self._apply_forced_splits(
                tree, state, feature_mask, rand_seed, leaf_total)
        per_node = self._needs_per_node_masks()
        if per_node and self._forced is not None:
            log.warning("forced splits combined with per-node feature "
                        "masks run without the per-node masks")
        if per_node and self._forced is None:
            state = train_stepwise(self, tree, state, rec, feature_mask,
                                   rand_seed)
        elif self._fused_growth:
            state = self._train_fused(tree, state, feature_mask,
                                      rand_seed, next_leaf)
        else:
            state = self._train_batched(tree, state, feature_mask,
                                        rand_seed, leaf_total, next_leaf)
        return tree, _rows_out_fn_cached(self.N)(state.leaf_of_row)

    # ------------------------------------------------------------------
    def _train_fused(self, tree: Tree, state: GrowState, feature_mask,
                     rand_seed, next_leaf: int = 1) -> GrowState:
        """Whole-tree device growth: one `serial.fused_tree` dispatch,
        one record read-back (vs one per ~kb-split batch on the stepped
        path). `next_leaf` > 1 continues after a forced-split
        preamble."""
        max_splits = self.L - next_leaf
        if max_splits <= 0:
            return state
        fn = self._fused_fn()
        with obs.scope("tree::split_batches"):
            state, recs = fn(self.bins, state, dev_i32(next_leaf),
                             dev_i32(max_splits), feature_mask,
                             rand_seed, self._qscale, self.meta,
                             self.params, self._btab)
            # jaxlint: disable=JLT001 -- THE per-tree host sync of the
            # fused path: the whole tree's split records read back in
            # one deliberate hop (the grow loop itself never syncs)
            recs_h = jax.device_get(recs)
        with obs.scope("tree::apply_records"):
            for i in range(max_splits):
                r = jax.tree_util.tree_map(lambda a: a[i], recs_h)
                if not record_is_valid(r):
                    break
                apply_split_record(tree, self.dataset, r)
        return state

    # ------------------------------------------------------------------
    def _train_batched(self, tree: Tree, state: GrowState,
                       feature_mask, rand_seed, leaf_total=None,
                       next_leaf: int = 1) -> GrowState:
        if leaf_total is None:
            leaf_total = {0: float(self.N)}
        while next_leaf < self.L:
            M = max(leaf_total.values())
            S = self._bucket(M / 2)
            fn, kb = self._batch_fn(S)
            max_splits = min(kb, self.L - next_leaf)
            # split_batches = per-leaf child histogram + best-split scan
            # steps fused into one dispatch; the device_get is the
            # per-batch sync, so the scope covers the real device time
            with obs.scope("tree::split_batches"):
                state, recs = fn(self.bins, state, dev_i32(next_leaf),
                                 dev_i32(max_splits), feature_mask,
                                 rand_seed, self._qscale, self.meta,
                                 self.params, self._btab)
                # jaxlint: disable=JLT001 -- the LEGACY stepped path's
                # per-batch host sync (tpu_fused_tree=false; also the
                # fused path's bit-parity reference): the split records
                # must reach the host Tree once per ~log2(L) batch
                recs_h = jax.device_get(recs)
            stop = False
            with obs.scope("tree::apply_records"):
                for i in range(max_splits):
                    r = jax.tree_util.tree_map(lambda a: a[i], recs_h)
                    if not record_is_valid(r):
                        stop = True
                        break
                    apply_split_record(tree, self.dataset, r)
                    leaf_total[int(r.leaf)] = float(r.left_total_count)
                    leaf_total[next_leaf] = float(r.right_total_count)
                    next_leaf += 1
            if stop:
                break
        return state

    # --- adapter methods for the shared capability drivers
    # (treelearner/capabilities.py): each wraps this learner's cached
    # jitted step functions with its bucketed gather size ---------------

    def _cegb_root(self, gh, feature_mask):
        root = _cegb_root_fn_cached(self.L, self.B, self.Bg,
                                    self._bundled, self._cegb_has_lazy,
                                    self._has_cat, self._hist_impl)
        return root(self.bins, gh, self._leaf_of_row0, feature_mask,
                    self._splittable(0), self._cegb_used,
                    self._cegb_fetched, self._cegb_coupled,
                    self._cegb_lazy, self._qscale, self.meta,
                    self.params, self._btab)

    def _cegb_step(self, state, leaf, k, allowed, feature_mask, smaller):
        S = self._bucket(smaller)
        fn = _cegb_step_fn_cached(S, self.B, self.Bg, self._bundled,
                                  self._cegb_has_lazy,
                                  self._has_cat, self._hist_impl)
        state, rec, self._cegb_used, self._cegb_fetched = fn(
            self.bins, state, jnp.int32(leaf), jnp.int32(k),
            jnp.asarray(allowed), feature_mask,
            self._cegb_used, self._cegb_fetched, self._cegb_coupled,
            self._cegb_lazy, self._qscale, self.meta, self.params,
            self._btab)
        return state, rec

    def _mono_root(self, gh, feature_mask, rand_seed):
        # extra_trees is ignored on this path — the root scan must be
        # greedy too, not just the step scans
        root_fn = _root_fn_cached(self.L, self.B, self.Bg, self._bundled,
                                  False, self._has_cat, self._hist_impl)
        return root_fn(self.bins, gh, self._leaf_of_row0, feature_mask,
                       self._splittable(0), rand_seed, self._qscale,
                       self.meta, self.params, self._btab)

    def _mono_step(self, state, leaf, k, allowed, feature_mask, bounds,
                   smaller):
        S = self._bucket(smaller)
        fn = _mono_step_fn_cached(S, self.B, self.Bg, self._bundled,
                                  self._has_cat, self._hist_impl)
        return fn(self.bins, state, jnp.int32(leaf), jnp.int32(k),
                  jnp.asarray(allowed), feature_mask,
                  jnp.float32(bounds[0]), jnp.float32(bounds[1]),
                  jnp.float32(bounds[2]), jnp.float32(bounds[3]),
                  self._qscale, self.meta, self.params, self._btab)

    def _mono_rescan(self, state, leaf, sums, entry, depth, allowed,
                     feature_mask):
        rescan = _rescan_fn_cached(self.B, self._has_cat)
        sg, sh, c, tc = sums
        return rescan(state, jnp.int32(leaf), jnp.float32(sg),
                      jnp.float32(sh), jnp.float32(c), jnp.float32(tc),
                      jnp.float32(entry[0]), jnp.float32(entry[1]),
                      jnp.int32(depth), jnp.asarray(allowed),
                      feature_mask, self._qscale, self.meta, self.params,
                      self._btab)

    def _adv_scan(self, state, leaf, sums, bound_arrays, depth, allowed,
                  feature_mask):
        fn = _adv_rescan_fn_cached(self.B, self._has_cat)
        sg, sh, c, tc = sums
        min_c, max_c = bound_arrays
        return fn(state, jnp.int32(leaf), jnp.float32(sg),
                  jnp.float32(sh), jnp.float32(c), jnp.float32(tc),
                  jnp.asarray(min_c), jnp.asarray(max_c),
                  jnp.int32(depth), jnp.asarray(allowed), feature_mask,
                  self._qscale, self.meta, self.params, self._btab)

    def _node_step(self, state, leaf, k, allowed, mask_left, mask_right,
                   rand_seed, smaller):
        S = self._bucket(smaller)
        return self._step_fn(S)(
            self.bins, state, jnp.int32(leaf), jnp.int32(k),
            jnp.asarray(allowed), mask_left, mask_right, rand_seed,
            self._qscale, self.meta, self.params, self._btab)
