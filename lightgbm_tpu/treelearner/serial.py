"""Single-chip leaf-wise tree learner.

TPU-native counterpart of the reference's SerialTreeLearner
(src/treelearner/serial_tree_learner.cpp:159 ``Train``) and, closer in
spirit, its CUDA whole-loop learner
(src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp:128): all heavy state
— binned rows, gradients, per-leaf histograms, the row→leaf partition — is
device-resident, and the whole tree grows in ONE dispatch
(``serial.fused_tree``): the host reads back the ``[L-1]`` split records
and replays them into the host ``Tree``.

The grower itself — the whole-tree loop, the split step, the partition,
the smaller-child compaction, the per-leaf histogram store — is
treelearner/grow.py, shared with the mesh learners. This module holds
what is the serial learner's own: its histogram (``_leaf_histogram``,
one device, EFB bundles unpacked in place), its jitted programs, padded
to canonical shapes so that datasets of similar size share compiled
variants, and forced splits.

The binned matrix is a **traced argument** of every jitted function, never a
closed-over constant: closing over it would embed the whole dataset into the
HLO as a literal, making the compiled program scale with the data (at Higgs
scale ~300 MB of program).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.binning import MissingType
from ..io.dataset import BinnedDataset
from ..models.tree import Tree
from ..obs import compile as obs_compile
from ..obs.registry import registry as obs
from ..ops.histogram import (build_histogram, histogram_tiles,
                             unpack_bundle_histogram)
from ..ops.quantize import dequantize_sums, sum_gh
from ..ops.split import (FeatureMeta, SplitParams, calculate_leaf_output,
                         find_best_split)
from ..utils import log, next_pow2 as _next_pow2
from ..utils.scalars import dev_bool, dev_i32
from .capabilities import (CapabilityMixin, _cegb_penalty, train_cegb,
                           train_monotone, train_stepwise)
from .grow import (GrowState, SplitRecord, _compact_child_hist,
                   _grow_tree, _maybe_rand_bins, _record_at,
                   _rows_go_left, _split_step, _store_info,
                   _subtract_child_hists, apply_split_record,
                   build_bundle_tables, make_root_state, record_is_valid)


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
    return _unbundled(bhist, meta, btab, totals, B)


def _unbundled(bhist, meta, btab, totals, B: int):
    """Per-feature [Fp, B, 4] from the bundle histogram (``totals``
    None: the histogram's own, ``unpack_bundle_histogram``)."""
    with jax.named_scope("obs_unpack"):
        return unpack_bundle_histogram(
            bhist, btab.group_of, btab.first_bin, btab.num_bins,
            btab.zero_fix, meta.zero_bin, totals, B)


def _split_body(bins, state: GrowState, rec: SplitRecord, leaf, new_leaf,
                valid, mask_left, mask_right, meta, params, btab, *,
                B: int, Bg: int, bundled: bool,
                hist_impl: tuple = ("auto", False), **step) -> GrowState:
    """grow.py's split step over this learner's histogram: the smaller
    child's rows and no others (``_compact_child_hist``), a tile at a
    time, on the path ``_leaf_histogram`` takes over all the rows.
    ``step`` is ``_split_step``'s keywords."""
    def child_hists(bins, state, rec, leaf, new_leaf, leaf_of_row,
                    smaller_is_left, valid, mask_left, mask_right, qscale):
        def small_hist(small, _mask, totals):
            hist = _compact_child_hist(
                bins, state, small,
                histogram_tiles(bins, state.gh, Bg if bundled else B,
                                hist_impl=hist_impl))
            if not bundled:
                return hist
            # quantized mode: the record's totals are dequantized f32,
            # but the bundled zero-bin fix needs exact int sums, which
            # the bundle histogram itself holds
            if jnp.issubdtype(state.gh.dtype, jnp.integer):
                totals = None
            return _unbundled(hist, meta, btab, totals, B)

        return _subtract_child_hists(
            state, rec, leaf, new_leaf, leaf_of_row, smaller_is_left,
            valid, small_hist) + (mask_left, mask_right)

    return _split_step(bins, state, rec, leaf, new_leaf, valid, mask_left,
                       mask_right, meta, params, btab, child_hists,
                       bundled=bundled, **step)


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
                                children_allowed, ordered=True)
        return state, _record_at(state, 0)

    return obs_compile.instrument_jit("serial.root", root)


@functools.lru_cache(maxsize=None)
def _step_fn_cached(B: int, Bg: int, bundled: bool,
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
                            meta, params, btab, B=B, Bg=Bg,
                            bundled=bundled, max_depth=0,
                            extra_trees=extra_trees, has_cat=has_cat,
                            hist_impl=hist_impl,
                            children_allowed=children_allowed,
                            rand_seed=rand_seed, qscale=qscale)
        best = jnp.argmax(state.gain).astype(jnp.int32)
        return state, _record_at(state, best)

    return obs_compile.instrument_jit("serial.step", step,
                                      donate_argnums=(1,))


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
                                children_allowed, ordered=True)
        return state, _record_at(state, 0)

    return obs_compile.instrument_jit("serial.cegb_root", root)


@functools.lru_cache(maxsize=None)
def _cegb_step_fn_cached(B: int, Bg: int, bundled: bool,
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
                            meta, params, btab, B=B, Bg=Bg,
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
def _mono_step_fn_cached(B: int, Bg: int, bundled: bool,
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
                            meta, params, btab, B=B, Bg=Bg,
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
def _forced_fn_cached(B: int, Bg: int, bundled: bool,
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
                            btab, B=B, Bg=Bg, bundled=bundled,
                            max_depth=0, extra_trees=extra_trees,
                            has_cat=has_cat, hist_impl=hist_impl,
                            children_allowed=children_allowed,
                            rand_seed=rand_seed, qscale=qscale)
        return state, rec, ok

    return obs_compile.instrument_jit("serial.forced", forced,
                                      donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _fused_fn_cached(L: int, B: int, Bg: int, bundled: bool,
                     max_depth: int, extra_trees: bool,
                     has_cat: bool = True,
                     hist_impl: tuple = ("auto", False)):
    """Whole-tree growth: ONE dispatch runs grow.py's loop over this
    learner's split step until no positive-gain candidate remains, and
    the host reads back only the [L-1] record buffer (the Booster-paper
    / XGBoost-GPU "whole pipeline on the accelerator" move; the serial
    twin of the mesh learner's ``_tree_impl``). ``start_leaf`` /
    ``max_splits`` continue after a forced-split preamble."""
    def fused(bins, state: GrowState, start_leaf, max_splits,
              feature_mask, rand_seed, qscale, meta, params, btab):
        def step(state, rec, leaf, new_leaf, valid):
            return _split_body(bins, state, rec, leaf, new_leaf, valid,
                               feature_mask, feature_mask, meta, params,
                               btab, B=B, Bg=Bg, bundled=bundled,
                               max_depth=max_depth,
                               extra_trees=extra_trees, has_cat=has_cat,
                               hist_impl=hist_impl, rand_seed=rand_seed,
                               qscale=qscale)

        return _grow_tree(state, step, L, B, start_leaf, max_splits)

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
        # variants across datasets.
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

    # _sample_features and _tree_feature_mask live on CapabilityMixin
    # (one draw for every learner)

    # ------------------------------------------------------------------
    def _build_bundle_tables(self, dataset: BinnedDataset) -> None:
        """Device EFB tables (or a dummy scalar when unbundled)."""
        if not self._bundled:
            self.Bg = 0
            self._btab = jnp.int32(0)
            return
        self.Bg = _next_pow2(max(dataset.bundle.num_bundled_bins, 2))
        self._btab = build_bundle_tables(dataset, self.Fp)

    def _step_fn(self):
        return _step_fn_cached(self.B, self.Bg, self._bundled,
                               self._extra_trees, self._has_cat,
                               self._hist_impl)

    def _fused_fn(self):
        return _fused_fn_cached(self.L, self.B, self.Bg, self._bundled,
                                self.max_depth, self._extra_trees,
                                self._has_cat, self._hist_impl)

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
                             feature_mask, rand_seed):
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
            fn = _forced_fn_cached(self.B, self.Bg, self._bundled,
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
        feature_mask = self._tree_feature_mask()

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
        next_leaf = 1
        if self._forced is not None:
            state, next_leaf = self._apply_forced_splits(
                tree, state, feature_mask, rand_seed)
        per_node = self._needs_per_node_masks()
        if per_node and self._forced is not None:
            log.warning("forced splits combined with per-node feature "
                        "masks run without the per-node masks")
        if per_node and self._forced is None:
            state = train_stepwise(self, tree, state, rec, feature_mask,
                                   rand_seed)
        else:
            state = self._train_fused(tree, state, feature_mask,
                                      rand_seed, next_leaf)
        return tree, _rows_out_fn_cached(self.N)(state.leaf_of_row)

    # ------------------------------------------------------------------
    def _train_fused(self, tree: Tree, state: GrowState, feature_mask,
                     rand_seed, next_leaf: int = 1) -> GrowState:
        """Whole-tree device growth: one `serial.fused_tree` dispatch,
        one record read-back. `next_leaf` > 1 continues after a
        forced-split preamble."""
        max_splits = self.L - next_leaf
        if max_splits <= 0:
            return state
        fn = self._fused_fn()
        with obs.scope("tree::split_batches"):
            state, recs = fn(self.bins, state, dev_i32(next_leaf),
                             dev_i32(max_splits), feature_mask,
                             rand_seed, self._qscale, self.meta,
                             self.params, self._btab)
            # jaxlint: disable=JLT001 -- THE per-tree host sync: the
            # whole tree's split records read back in one deliberate
            # hop (the grow loop itself never syncs)
            recs_h = jax.device_get(recs)
        with obs.scope("tree::apply_records"):
            for i in range(max_splits):
                r = jax.tree_util.tree_map(lambda a: a[i], recs_h)
                if not record_is_valid(r):
                    break
                apply_split_record(tree, self.dataset, r)
        return state

    # --- adapter methods for the shared capability drivers
    # (treelearner/capabilities.py): each wraps this learner's cached
    # jitted step functions ---------------------------------------------

    def _cegb_root(self, gh, feature_mask):
        root = _cegb_root_fn_cached(self.L, self.B, self.Bg,
                                    self._bundled, self._cegb_has_lazy,
                                    self._has_cat, self._hist_impl)
        return root(self.bins, gh, self._leaf_of_row0, feature_mask,
                    self._splittable(0), self._cegb_used,
                    self._cegb_fetched, self._cegb_coupled,
                    self._cegb_lazy, self._qscale, self.meta,
                    self.params, self._btab)

    def _cegb_step(self, state, leaf, k, allowed, feature_mask):
        fn = _cegb_step_fn_cached(self.B, self.Bg, self._bundled,
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

    def _mono_step(self, state, leaf, k, allowed, feature_mask, bounds):
        fn = _mono_step_fn_cached(self.B, self.Bg, self._bundled,
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
                   rand_seed):
        return self._step_fn()(
            self.bins, state, jnp.int32(leaf), jnp.int32(k),
            jnp.asarray(allowed), mask_left, mask_right, rand_seed,
            self._qscale, self.meta, self.params, self._btab)
