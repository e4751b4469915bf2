"""Data-parallel tree learner: rows sharded over the mesh 'data' axis.

TPU-native equivalent of the reference's ``DataParallelTreeLearner``
(reference: src/treelearner/data_parallel_tree_learner.cpp): there, each
rank histograms its row shard, ``Network::ReduceScatter`` sums histograms
across ranks (:185), each rank scans its feature block, and the best split
is agreed via an Allreduce with a max-gain reducer
(SyncUpGlobalBestSplit, parallel_tree_learner.h:190). Here the same
dataflow is expressed as GSPMD: the bin matrix and per-row (grad, hess)
carry a ``P('data', None)`` sharding, the histogram one-hot contraction
reduces over the sharded row axis — XLA inserts the cross-device psum
(the ReduceScatter analogue) — and the split scan runs replicated, which
*is* the "everyone knows the best split" state the reference reaches via
its two collectives. The row partition update is a purely local sharded
elementwise op, like the reference's per-rank ``DataPartition::Split``.

The grower — the whole-tree loop (one dispatch and one [L-1] record
read-back per tree, where the reference syncs rank↔rank per split), the
split step, the per-leaf histogram store — is treelearner/grow.py, the
code the single-chip learner (treelearner/serial.py) runs too. This
learner's own part is the histogram (``_mesh_hist``) and the sharding:

- on a one-device mesh the smaller child's rows are compacted before
  they are histogrammed, as in the serial learner; on a sharded mesh the
  compaction is replaced by a masked full-length histogram pass —
  compaction is a global reshuffle that would force cross-device
  gathers, while a mask rides the existing sharding. The
  histogram-subtraction trick still halves the work: only the smaller
  child is histogrammed, the sibling comes from parent − smaller.
- features whose per-split host state steers the scan (CEGB penalties,
  intermediate monotone bounds, per-node feature masks) fall back to a
  stepwise host loop, exactly like the serial learner — via the shared
  drivers in treelearner/capabilities.py.

EFB stays *bundled* across the mesh (reference: bundles are built before
ReduceScatter, src/io/dataset.cpp:107 + data_parallel_tree_learner.cpp:185):
the sharded [N, G] bundle matrix is histogrammed locally, the [G, Bg, 4]
bundle histogram crosses devices (comm O(G·Bg), not O(F·B)), and
``unpack_bundle_histogram`` runs on the replicated side.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..io.dataset import BinnedDataset
from ..models.tree import Tree
from ..obs import compile as obs_compile
from ..obs.registry import registry as obs
from ..ops.histogram import (build_histogram, histogram_tiles, mask_gh,
                             unpack_bundle_histogram)
from ..ops.quantize import dequantize_sums, sum_gh
from ..ops.split import (FeatureMeta, SplitParams, calculate_leaf_output,
                         find_best_split)
from ..treelearner.capabilities import (CapabilityMixin, _cegb_penalty,
                                        train_cegb, train_monotone,
                                        train_stepwise)
from ..treelearner.grow import (GrowState, SplitRecord,
                                _compact_child_hist, _grow_tree, _maybe_rand_bins, _record_at,
                                _rows_go_left, _split_step, _store_info,
                                _subtract_child_hists, _window_sizes,
                                apply_split_record,
                                build_bundle_tables, make_root_state,
                                rec_valid, record_is_valid,
                                stage_bins_by_feature)
from ..utils import log


def _padded_to_ladder(counts: np.ndarray, sizes: list) -> int:
    """``counts`` summed, each padded to the smallest of ``sizes`` (a
    window ladder of grow.py, largest first) that holds it: the host's
    twin of ``grow._ladder_branch``."""
    sizes = np.asarray(sizes[::-1])
    return sizes[np.minimum(np.searchsorted(sizes, counts),
                            len(sizes) - 1)].sum()


def make_mesh(n_devices: Optional[int] = None, axis: str = "data") -> Mesh:
    """1-D device mesh over the data axis (reference analogue: the
    machine list of src/network/linkers_socket.cpp:81)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


class DataParallelTreeLearner(CapabilityMixin):
    """Leaf-wise grower over row-sharded binned data.

    One device dispatch grows the whole tree:
      while splits remain: argmax over leaf gains -> partition update
      (local) -> masked histogram of the smaller child (local partials +
      XLA-inserted psum) -> sibling by subtraction -> replicated
      best-split scan -> record written to the read-back buffer.
    """

    # feature-/voting-parallel subclasses unbundle instead (their comm
    # patterns don't reduce over the full [F, B] histogram)
    _supports_bundles = True

    def __init__(self, config, dataset: BinnedDataset, mesh: Mesh,
                 axis: str = "data"):
        cols_host = self._init_mesh_common(config, dataset, mesh, axis)
        N, C = cols_host.shape
        if self.F == 0:
            log.fatal("Cannot train without features")
        self.N = N
        n_dev = mesh.devices.size
        # pad rows to a devices multiple; pad rows carry leaf -1 / gh 0.
        # Shards are materialized one at a time through
        # make_array_from_callback — a host-side concatenate of the full
        # padded matrix would double peak host memory at Higgs scale
        self.R = -(-N // n_dev) * n_dev
        sharding = NamedSharding(mesh, P(self.axis, None))

        def _shard(index):
            rs = index[0]
            start = rs.start or 0
            stop = rs.stop if rs.stop is not None else self.R
            avail = max(0, min(N, stop) - start)
            if avail == stop - start:
                return cols_host[start:stop]
            shard = np.zeros((stop - start, C), dtype=cols_host.dtype)
            if avail > 0:
                shard[:avail] = cols_host[start:start + avail]
            return shard

        with obs.scope("io::stage_bins_device"):
            self.bins = jax.make_array_from_callback(
                (self.R, C), sharding, _shard)
        self.bins_fm = stage_bins_by_feature(self.bins)
        self._init_cegb(config)
        self._init_monotone(config)

    def _init_mesh_common(self, config, dataset: BinnedDataset,
                          mesh: Mesh, axis: str):
        """Shared mesh-learner setup (also used by the multi-process
        DistributedDataParallelLearner); returns the host bin-column
        matrix — the EFB bundle matrix when bundled, per-feature
        otherwise."""
        self.config = config
        self.dataset = dataset
        self.mesh = mesh
        self.axis = axis
        self.F = dataset.num_features
        self.Fp = self.F  # masks/penalty vectors carry no padding here
        self._bundled = (dataset.bundle is not None
                         and self._supports_bundles)
        if dataset.bundle is not None and not self._bundled:
            cols_host = dataset.feature_bins()
        else:
            cols_host = dataset.bins
        # power-of-two histogram width (see SerialTreeLearner: canonical
        # shapes share compiled variants across datasets)
        from ..utils import next_pow2
        self.B = next_pow2(max(int(dataset.max_num_bin), 2))
        if self._bundled:
            self.Bg = next_pow2(max(dataset.bundle.num_bundled_bins, 2))
            self._btab = build_bundle_tables(dataset, self.F)
        else:
            self.Bg = 0
            self._btab = jnp.int32(0)
        self.L = int(config.num_leaves)
        self.max_depth = int(config.max_depth)
        self._hist_slots = self.L
        self.row_sharding = NamedSharding(mesh, P(axis))
        self.rep_sharding = NamedSharding(mesh, P())
        # histograms: replicated after the cross-row psum (the
        # feature-parallel subclass keeps them feature-sharded instead)
        self.hist_sharding = self.rep_sharding
        self.gh_sharding = NamedSharding(mesh, P(axis, None))
        self.meta = jax.device_put(
            FeatureMeta.from_dataset(dataset,
                                     int(config.max_cat_to_onehot)),
            self.rep_sharding)
        self.params = jax.device_put(SplitParams.from_config(config),
                                     self.rep_sharding)
        self._ff_rng = np.random.RandomState(config.feature_fraction_seed)
        from ..ops.histogram import resolve_hist_impl
        qbits = (int(getattr(config, "quant_grad_bits", 8))
                 if getattr(config, "use_quantized_grad", False) else 0)
        self._hist_impl = resolve_hist_impl(
            getattr(config, "hist_backend", "auto"),
            bool(getattr(config, "tpu_use_f64_hist", False)), qbits)
        self._init_quantization(self._hist_impl[2], config,
                                cols_host.shape[0])
        self._has_cat = bool(
            np.asarray(self.meta.is_categorical).any())
        self._extra_trees = bool(config.extra_trees)
        self._extra_seed = int(config.extra_seed)
        self._tree_idx = 0
        self._resolve_constraints()
        self._forced = None
        if config.forcedsplits_filename:
            log.warning("forcedsplits_filename is only implemented in "
                        "the serial (single-chip) learner; IGNORED by "
                        "mesh-parallel learners")
        self._root_fn = None
        self._tree_fn = None
        self._step_fn = None
        self._cegb_root_fn = None
        self._mono_step_fn = None
        self._mono_root_fn = None
        self._adv_rescan_fn = None
        self._many_fn = None
        self._many_multi_fn = None
        self._many_grad_fn = None
        self._many_sample = None
        return cols_host

    def _make_cegb_fetched(self, rows: int) -> jnp.ndarray:
        """Row-sharded lazy-fetched matrix (global-view creation works
        across processes for the multi-process subclass too)."""
        sh = (NamedSharding(self.mesh, P(self.axis, None)) if rows > 1
              else self.rep_sharding)
        # jaxlint: disable=JLT003 -- one-shot sharded-zeros allocation
        # at CEGB setup (out_shardings is the point); a jit_trace entry
        # per row-shape would be noise, and no dispatch ever repeats
        return jax.jit(lambda: jnp.zeros((rows, self.Fp),
                                         dtype=jnp.float32),
                       out_shardings=sh)()

    # ------------------------------------------------------------------
    def _place_feature_mask(self, mask: np.ndarray) -> jnp.ndarray:
        return jax.device_put(jnp.asarray(mask), self.rep_sharding)

    # ------------------------------------------------------------------
    def _initial_partition(self, gh):
        """Root row→leaf vector: rows 0, pad rows -1. Subclasses with a
        different pad layout (per-process interleaved pads in the
        multi-process learner) override this."""
        leaf_of_row = jnp.concatenate([
            jnp.zeros(self.N, dtype=jnp.int32),
            jnp.full((self.R - self.N,), -1, dtype=jnp.int32)])
        return jax.lax.with_sharding_constraint(leaf_of_row,
                                                self.row_sharding)

    def _mesh_hist(self, bins, gh, totals):
        """Globally-summed per-feature [F, B, 4] histogram. Bundled:
        only the [G, Bg, 4] bundle histogram crosses devices, then the
        per-feature unpack runs replicated (``totals`` reconstructs the
        zero-bin rows of bundled features, io/efb.py). Quantized mode:
        the local partials are int32 — the XLA-inserted cross-device
        psum then moves HALF the bytes of the f32 histogram (and a
        quarter on int8 gh rows vs f32 through the local pass)."""
        if jnp.issubdtype(gh.dtype, jnp.integer):
            # callers hold dequantized f32 record totals; the bundled
            # zero-bin fix needs the exact int sums of THESE (already
            # masked) rows
            totals = sum_gh(gh)
        return self._summed_hist(
            build_histogram(bins, gh, self._hist_bins(),
                            pallas_ok=self._pallas_ok(),
                            hist_impl=self._hist_impl), totals)

    def _pallas_ok(self) -> bool:
        """Only on a 1-device mesh: pallas_call has no SPMD partitioning
        rule, so with real sharding GSPMD would all-gather the bins;
        unsharded, the kernel is safe (and is the fast path for
        single-chip tree_learner=data runs)."""
        return self.mesh.devices.size == 1

    def _hist_bins(self) -> int:
        """Bins of the histogram the rows are summed into: the bundle
        histogram's where the columns are bundles."""
        return self.Bg if self._bundled else self.B

    def _hist_tiles(self, bins, gh):
        """``_mesh_hist``'s pass over all of ``bins`` and ``gh``, a tile
        at a time (both for shape and dtype alone)."""
        return histogram_tiles(bins, gh, self._hist_bins(),
                               pallas_ok=self._pallas_ok(),
                               hist_impl=self._hist_impl)

    def _summed_hist(self, h, totals):
        """The local histogram ``h`` summed over the mesh and, where the
        columns are bundles, unpacked per feature (``totals`` None:
        taken from ``h`` itself)."""
        if not self._bundled:
            # named so the XLA-inserted cross-device reduce is
            # attributable in device traces; the feature-parallel
            # subclass keeps histograms sharded (no psum crosses here),
            # so its boundary gets a distinct name
            name = ("obs_psum_histogram"
                    if self.hist_sharding == self.rep_sharding
                    else "obs_hist_feature_sharded")
            with jax.named_scope(name):
                return jax.lax.with_sharding_constraint(
                    h, self.hist_sharding)
        with jax.named_scope("obs_psum_bundle_histogram"):
            bh = jax.lax.with_sharding_constraint(h, self.rep_sharding)
        with jax.named_scope("obs_unpack"):
            return unpack_bundle_histogram(
                bh, self._btab.group_of, self._btab.first_bin,
                self._btab.num_bins, self._btab.zero_fix,
                self.meta.zero_bin, totals, self.B)

    def _root_impl_opts(self, bins, gh, feature_mask, rand_seed,
                        extra_trees: bool, qscale):
        sums_raw = sum_gh(gh)
        hist = self._mesh_hist(bins, gh, sums_raw)
        sums = dequantize_sums(sums_raw, qscale)
        parent_out = calculate_leaf_output(sums[0], sums[1], self.params)
        info = find_best_split(
            hist, sums[0], sums[1], sums[2], sums[3], self.meta,
            self.params, feature_mask, parent_output=parent_out,
            rand_bins=_maybe_rand_bins(extra_trees, rand_seed, 0,
                                       self.meta, self.params),
            leaf_depth=jnp.int32(0), has_categorical=self._has_cat,
            hist_scale=qscale)
        leaf_of_row = self._initial_partition(gh)
        state = make_root_state(gh, hist, leaf_of_row, info, self.L,
                                self.F, self.B, self._splittable(0),
                                hist_slots=self._hist_slots,
                                ordered=self._compacts())
        return state, _record_at(state, 0)

    def _root_impl(self, bins, gh, feature_mask, rand_seed, qscale):
        return self._root_impl_opts(bins, gh, feature_mask, rand_seed,
                                    self._extra_trees, qscale)

    def _compacts(self) -> bool:
        """Whether ``_children_histograms`` compacts the smaller child's
        rows, and the grow state so keeps the rows ordered by leaf: on
        one device. Across a mesh it would be a global reshuffle."""
        return self.mesh.devices.size == 1

    def _ordered_bag(self, bag):
        """What the staged ``gh`` carries as its last channel, and so
        the rows the grower orders by leaf (``grow.make_root_state``):
        the tree's bag where the learner compacts; None, for 1 on every
        row, where it histograms the masked row space or the tree has
        no bag."""
        return bag if self._compacts() else None

    def _mesh_split_body(self, bins, bins_fm, state: GrowState,
                         rec: SplitRecord, leaf, new_leaf, valid, mask_left,
                         mask_right, rand_seed=0, extra_trees=None,
                         pen_left=None, pen_right=None, qscale=None):
        """grow.py's split step over this learner's data, sharding and
        child histograms (``_children_histograms``)."""
        return _split_step(
            bins, bins_fm, state, rec, leaf, new_leaf, valid, mask_left,
            mask_right, self.meta, self.params, self._btab,
            self._children_histograms, bundled=self._bundled,
            has_cat=self._has_cat, max_depth=self.max_depth,
            extra_trees=(self._extra_trees if extra_trees is None
                         else extra_trees),
            row_sharding=self.row_sharding, rand_seed=rand_seed,
            pen_left=pen_left, pen_right=pen_right, qscale=qscale)

    def _children_histograms(self, bins, state, rec, leaf, new_leaf,
                             leaf_of_row, smaller_is_left, valid,
                             mask_left, mask_right, qscale=None):
        """The updated per-leaf store, the cross-device-summed child
        histograms and the per-child scan masks. Base learner: the
        smaller child's histogram, its sibling by subtraction
        (``_subtract_child_hists``). On one device the pass visits the
        child's rows alone (its segment of ``state.order``, which the
        split step has just reordered, a tile at a time), so histogram
        cost tracks the child's size (the reference's DataPartition +
        per-leaf iterators,
        data_partition.hpp:21); a sharded mesh keeps the masked
        histogram over the full row space (the analogue of the
        reference ranks histogramming their local leaf rows then
        ReduceScatter-summing, data_parallel_tree_learner.cpp:185):
        compaction across shards would need an all-to-all, and each
        shard already scans only its local rows. Voting-parallel
        overrides this with the reduced-comm vote and skips the
        store."""
        def small_hist(small, mask, totals):
            if self._compacts():
                # quantized rows: the exact int sums of the child's
                # rows, which the record does not hold, come from the
                # histogram itself
                return self._summed_hist(
                    _compact_child_hist(bins, state, small,
                                        self._hist_tiles(bins, state.gh)),
                    None if jnp.issubdtype(state.gh.dtype, jnp.integer)
                    else totals)
            # dtype-preserving mask (an f32 multiply would de-quantize
            # integer gh rows)
            return self._mesh_hist(bins, mask_gh(state.gh, mask), totals)

        return _subtract_child_hists(
            state, rec, leaf, new_leaf, leaf_of_row, smaller_is_left,
            valid, small_hist) + (mask_left, mask_right)

    # ------------------------------------------------------------------
    def _tree_impl(self, bins, bins_fm, state: GrowState, feature_mask,
                   rand_seed, qscale):
        """Grow the whole tree in one dispatch (``_grow_tree``)."""
        def step(state, rec, leaf, new_leaf, valid):
            return self._mesh_split_body(bins, bins_fm, state, rec, leaf,
                                         new_leaf, valid, feature_mask,
                                         feature_mask,
                                         rand_seed=rand_seed,
                                         qscale=qscale)

        return _grow_tree(state, step, self.L, self.B)

    def _step_impl(self, bins, bins_fm, state: GrowState, leaf, new_leaf,
                   mask_left, mask_right, rand_seed, qscale):
        """Single split step with a host-chosen leaf — the stepwise path
        used when per-split host state steers the scan (per-node feature
        masks; CEGB and intermediate monotone have their own variants)."""
        rec = _record_at(state, leaf)
        valid = rec_valid(rec)
        state = self._mesh_split_body(bins, bins_fm, state, rec, leaf,
                                      new_leaf, valid, mask_left,
                                      mask_right, rand_seed=rand_seed,
                                      qscale=qscale)
        best = jnp.argmax(state.gain).astype(jnp.int32)
        return state, _record_at(state, best)

    # --- CEGB (reference: cost_effective_gradient_boosting.hpp) -------
    def _cegb_root_impl(self, bins, gh, feature_mask, used, fetched,
                        qscale):
        sums_raw = sum_gh(gh)
        hist = self._mesh_hist(bins, gh, sums_raw)
        sums = dequantize_sums(sums_raw, qscale)
        parent_out = calculate_leaf_output(sums[0], sums[1], self.params)
        leaf_of_row = self._initial_partition(gh)
        if self._cegb_has_lazy:
            in_rows = (leaf_of_row >= 0).astype(jnp.float32)
            unfetched = jnp.einsum("r,rf->f", in_rows, 1.0 - fetched)
            lazy = self._cegb_lazy
        else:
            unfetched, lazy = None, None
        pen = _cegb_penalty(self.params, sums[3], used,
                            self._cegb_coupled, unfetched, lazy)
        info = find_best_split(
            hist, sums[0], sums[1], sums[2], sums[3], self.meta,
            self.params, feature_mask, parent_output=parent_out,
            gain_penalty=pen, has_categorical=self._has_cat,
            hist_scale=qscale)
        state = make_root_state(gh, hist, leaf_of_row, info, self.L,
                                self.F, self.B, self._splittable(0),
                                hist_slots=self._hist_slots,
                                ordered=self._compacts())
        return state, _record_at(state, 0)

    def _cegb_step_impl(self, bins, bins_fm, state, leaf, new_leaf,
                        feature_mask, used, fetched, qscale):
        """Mesh CEGB step (mirrors serial.py _cegb_step_fn_cached; the
        unfetched row sums reduce over the sharded row axis — XLA
        inserts the psum)."""
        rec = _record_at(state, leaf)
        f = jnp.maximum(rec.feature, 0)
        used2 = used.at[f].set(True)
        on_leaf = state.leaf_of_row == leaf
        if self._cegb_has_lazy:
            fetched2 = jnp.maximum(
                fetched,
                on_leaf.astype(fetched.dtype)[:, None]
                * jax.nn.one_hot(f, fetched.shape[1],
                                 dtype=fetched.dtype))
            gl = _rows_go_left(bins_fm, on_leaf.shape[0], rec, self.meta,
                               self._btab, self._bundled, self._has_cat)
            unf = 1.0 - fetched2
            unf_left = jnp.einsum(
                "r,rf->f", (on_leaf & gl).astype(jnp.float32), unf)
            unf_right = jnp.einsum(
                "r,rf->f", (on_leaf & ~gl).astype(jnp.float32), unf)
            lazy = self._cegb_lazy
        else:
            fetched2 = fetched
            unf_left = unf_right = lazy = None
        pen_l = _cegb_penalty(self.params, rec.left_total_count, used2,
                              self._cegb_coupled, unf_left, lazy)
        pen_r = _cegb_penalty(self.params, rec.right_total_count, used2,
                              self._cegb_coupled, unf_right, lazy)
        valid = rec_valid(rec)
        state = self._mesh_split_body(bins, bins_fm, state, rec, leaf,
                                      new_leaf, valid, feature_mask,
                                      feature_mask, extra_trees=False,
                                      pen_left=pen_l, pen_right=pen_r,
                                      qscale=qscale)
        best = jnp.argmax(state.gain).astype(jnp.int32)
        return state, _record_at(state, best), used2, fetched2

    # --- intermediate monotone (reference: monotone_constraints.hpp) --
    def _mono_step_impl(self, bins, bins_fm, state, leaf, new_leaf,
                        feature_mask, lmin, lmax, rmin, rmax, qscale):
        """The children's output bounds come from the host tracker
        (sibling-output based, monotone_constraints.hpp:543) instead of
        the mid-point rule baked into the stored candidate."""
        state = state._replace(
            cand_left_min=state.cand_left_min.at[leaf].set(lmin),
            cand_left_max=state.cand_left_max.at[leaf].set(lmax),
            cand_right_min=state.cand_right_min.at[leaf].set(rmin),
            cand_right_max=state.cand_right_max.at[leaf].set(rmax))
        rec = _record_at(state, leaf)
        valid = rec_valid(rec)
        state = self._mesh_split_body(bins, bins_fm, state, rec, leaf,
                                      new_leaf, valid, feature_mask,
                                      feature_mask, extra_trees=False,
                                      qscale=qscale)
        best = jnp.argmax(state.gain).astype(jnp.int32)
        return state, _record_at(state, best), state.gain

    def _rescan_impl(self, state, leaf, sg, sh, c, tc, vmin, vmax, depth,
                     allowed, feature_mask, qscale):
        """Recompute one leaf's candidate from its stored (replicated)
        histogram under tightened bounds (reference:
        SerialTreeLearner::RecomputeBestSplitForLeaf,
        serial_tree_learner.cpp:800)."""
        hist = state.hists[leaf]
        own = calculate_leaf_output(sg, sh, self.params)
        parent_out = jnp.where(self.params.path_smooth > 1e-10, own, 0.0)
        info = find_best_split(hist, sg, sh, c, tc, self.meta,
                               self.params, feature_mask, vmin, vmax,
                               parent_output=parent_out,
                               leaf_depth=depth,
                               has_categorical=self._has_cat,
                               hist_scale=qscale)
        state = _store_info(state, leaf, info, allowed)
        best = jnp.argmax(state.gain).astype(jnp.int32)
        return state, _record_at(state, best), state.gain

    def _adv_rescan_impl(self, state, leaf, sg, sh, c, tc, min_c, max_c,
                         depth, allowed, feature_mask, qscale):
        """monotone_constraints_method=advanced candidate scan — the
        per-(feature, bin) constraint arrays (replicated inputs) replace
        the leaf-wide pair (reference: AdvancedLeafConstraints,
        monotone_constraints.hpp:856; serial analogue
        _adv_rescan_fn_cached in treelearner/serial.py)."""
        hist = state.hists[leaf]
        own = calculate_leaf_output(sg, sh, self.params)
        parent_out = jnp.where(self.params.path_smooth > 1e-10, own, 0.0)
        info = find_best_split(hist, sg, sh, c, tc, self.meta,
                               self.params, feature_mask,
                               parent_output=parent_out,
                               leaf_depth=depth,
                               has_categorical=self._has_cat,
                               bound_arrays=(min_c, max_c),
                               hist_scale=qscale)
        state = _store_info(state, leaf, info, allowed)
        best = jnp.argmax(state.gain).astype(jnp.int32)
        return state, _record_at(state, best), state.gain

    def _adv_scan(self, state, leaf, sums, bound_arrays, depth, allowed,
                  feature_mask):
        if self._adv_rescan_fn is None:
            self._adv_rescan_fn = obs_compile.instrument_jit(
                "mesh.adv_rescan", self._adv_rescan_impl,
                donate_argnums=(0,))
        sg, sh, c, tc = sums
        min_c, max_c = bound_arrays
        return self._adv_rescan_fn(
            state, jnp.int32(leaf), jnp.float32(sg), jnp.float32(sh),
            jnp.float32(c), jnp.float32(tc), jnp.asarray(min_c),
            jnp.asarray(max_c), jnp.int32(depth), jnp.asarray(allowed),
            feature_mask, self._qscale)

    # --- adapter methods for the shared capability drivers ------------
    def _cegb_root(self, gh, feature_mask):
        if self._cegb_root_fn is None:
            self._cegb_root_fn = obs_compile.instrument_jit(
                "mesh.cegb_root", self._cegb_root_impl)
            self._cegb_step_fn = obs_compile.instrument_jit(
                "mesh.cegb_step", self._cegb_step_impl,
                donate_argnums=(2,))
        return self._cegb_root_fn(self.bins, gh, feature_mask,
                                  self._cegb_used, self._cegb_fetched,
                                  self._qscale)

    def _cegb_step(self, state, leaf, k, allowed, feature_mask):
        state, rec, self._cegb_used, self._cegb_fetched = \
            self._cegb_step_fn(self.bins, self.bins_fm, state,
                               jnp.int32(leaf), jnp.int32(k), feature_mask,
                               self._cegb_used, self._cegb_fetched,
                               self._qscale)
        return state, rec

    def _mono_root(self, gh, feature_mask, rand_seed):
        # the root scan must be greedy too, not just the step scans
        # (extra_trees is ignored under intermediate monotone — serial
        # learner contract, _mono_root in treelearner/serial.py)
        if self._mono_root_fn is None:
            def mesh_mono_root(b, g, f, r, q):
                return self._root_impl_opts(b, g, f, r, False, q)
            self._mono_root_fn = obs_compile.instrument_jit(
                "mesh.mono_root", mesh_mono_root)
        return self._mono_root_fn(self.bins, gh, feature_mask,
                                  jnp.int32(rand_seed), self._qscale)

    def _mono_step(self, state, leaf, k, allowed, feature_mask, bounds):
        if self._mono_step_fn is None:
            self._mono_step_fn = obs_compile.instrument_jit(
                "mesh.mono_step", self._mono_step_impl,
                donate_argnums=(2,))
            self._rescan_fn = obs_compile.instrument_jit(
                "mesh.rescan", self._rescan_impl,
                donate_argnums=(0,))
        return self._mono_step_fn(
            self.bins, self.bins_fm, state, jnp.int32(leaf), jnp.int32(k),
            feature_mask, jnp.float32(bounds[0]), jnp.float32(bounds[1]),
            jnp.float32(bounds[2]), jnp.float32(bounds[3]),
            self._qscale)

    def _mono_rescan(self, state, leaf, sums, entry, depth, allowed,
                     feature_mask):
        sg, sh, c, tc = sums
        return self._rescan_fn(
            state, jnp.int32(leaf), jnp.float32(sg), jnp.float32(sh),
            jnp.float32(c), jnp.float32(tc), jnp.float32(entry[0]),
            jnp.float32(entry[1]), jnp.int32(depth), jnp.asarray(allowed),
            feature_mask, self._qscale)

    def _node_step(self, state, leaf, k, allowed, mask_left, mask_right,
                   rand_seed):
        if self._step_fn is None:
            self._step_fn = obs_compile.instrument_jit(
                "mesh.step", self._step_impl,
                donate_argnums=(2,))
        return self._step_fn(self.bins, self.bins_fm, state,
                             jnp.int32(leaf), jnp.int32(k), mask_left,
                             mask_right, jnp.int32(rand_seed),
                             self._qscale)

    # ------------------------------------------------------------------
    def _ensure_compiled(self):
        if self._root_fn is None:
            self._root_fn = obs_compile.instrument_jit(
                "mesh.root", self._root_impl)
            self._tree_fn = obs_compile.instrument_jit(
                "mesh.tree", self._tree_impl,
                donate_argnums=(2,))

    def _splittable(self, depth: int) -> bool:
        return self.max_depth <= 0 or depth < self.max_depth

    def _make_gh(self, grad, hess, bag) -> jnp.ndarray:
        """[N] grad/hess (+bag) → padded sharded [R, 4] gh matrix, its
        last channel ``_ordered_bag``'s rows."""
        pad_n = self.R - self.N
        ones = jnp.ones(self.N, dtype=jnp.float32)
        ind = ones if bag is None else bag
        ordered = self._ordered_bag(bag)
        gh = jnp.stack([grad * ind, hess * ind, ind,
                        ones if ordered is None else ordered], axis=1)
        if pad_n:
            gh = jnp.concatenate(
                [gh, jnp.zeros((pad_n, 4), dtype=jnp.float32)], axis=0)
        return jax.device_put(gh, self.gh_sharding)

    def _make_gh_quantized(self, grad, hess, bag):
        """Quantized staging: discretize the UNPADDED [N] rows (the
        padding-invariant draw shared with the serial learner,
        capabilities.py _quantize_stage), then pad and shard the int
        rows. Returns (gh int[R, 4] sharded, qscale f32[2] replicated)."""
        ind = jnp.ones(self.N, dtype=jnp.float32) if bag is None else bag
        gh, qscale = self._quantize_stage(grad, hess, ind,
                                          self._tree_idx + 1,
                                          self._ordered_bag(bag))
        pad_n = self.R - self.N
        if pad_n:
            gh = jnp.concatenate(
                [gh, jnp.zeros((pad_n, 4), dtype=gh.dtype)], axis=0)
        return (jax.device_put(gh, self.gh_sharding),
                jax.device_put(qscale, self.rep_sharding))

    def _finalize_partition(self, leaf_of_row):
        return leaf_of_row[:self.N]

    def train(self, grad: jnp.ndarray, hess: jnp.ndarray,
              bag: Optional[jnp.ndarray] = None) -> Tuple[Tree, jnp.ndarray]:
        """Grow one tree over the sharded dataset. Same contract as
        SerialTreeLearner.train (treelearner/serial.py). On the default
        path there is exactly one host read-back per tree: the [L-1]
        record buffer."""
        self._ensure_compiled()
        with obs.scope("tree::stage_gh"):
            if self._quantized:
                gh, self._qscale = self._make_gh_quantized(grad, hess,
                                                           bag)
            else:
                gh = self._make_gh(grad, hess, bag)
                self._qscale = self._qs_ones
            obs.watch_ready("tree::stage_gh", gh)
        feature_mask = self._tree_feature_mask()

        tree = Tree(self.L)
        self._tree_idx += 1
        rand_seed = jnp.int32(
            (self._extra_seed + 7919 * self._tree_idx) & 0x7FFFFFFF)
        if self._cegb_enabled:
            state = train_cegb(self, tree, gh, feature_mask)
            return tree, self._finalize_partition(state.leaf_of_row)
        if self._mono_tracker is not None:
            state = train_monotone(self, tree, gh, feature_mask,
                                   rand_seed)
            return tree, self._finalize_partition(state.leaf_of_row)
        with obs.scope("tree::root_histogram"):
            state, rec = self._root_fn(self.bins, gh, feature_mask,
                                       rand_seed, self._qscale)
            obs.watch_ready("tree::root_histogram", rec)
        if self._needs_per_node_masks():
            state = train_stepwise(self, tree, state, rec, feature_mask,
                                   rand_seed)
            return tree, self._finalize_partition(state.leaf_of_row)
        # whole-tree dispatch (child histograms + split scans fused);
        # the device_get is the per-tree sync, so the scope covers the
        # real device time
        with obs.scope("tree::split_batches"):
            state, recs = self._tree_fn(self.bins, self.bins_fm, state,
                                        feature_mask, rand_seed,
                                        self._qscale)
            # jaxlint: disable=JLT001 -- THE per-tree sync: the whole
            # tree's split records read back in one hop (scope comment)
            recs_h = jax.device_get(recs)
        with obs.scope("tree::apply_records"):
            applied = 0
            for i in range(self.L - 1):
                r = jax.tree_util.tree_map(lambda a: a[i], recs_h)
                if not record_is_valid(r):
                    break
                apply_split_record(tree, self.dataset, r)
                applied += 1
            if obs.enabled:
                self._count_hist_rows(recs_h, applied)
                self._count_partition_splits(recs_h, applied)
                self._count_partition_rows(recs_h, applied)
                self._count_unpacks(applied)
                if bag is not None and applied:
                    obs.inc("sample/rows_in_bag",
                            int(recs_h.left_count[0]
                                + recs_h.right_count[0]))
        return tree, self._finalize_partition(state.leaf_of_row)

    def _count_hist_rows(self, recs_h, applied: int) -> None:
        """``grow/hist_rows_needed``: rows of the smaller child of each
        applied split, which a histogram has to visit (its in-bag rows
        where the learner orders the bag's alone, ``_ordered_bag``);
        ``grow/hist_rows_bucketed``: rows the learner's passes visited
        for them; ``grow/hist_rows_kernel``: the rows of those passes
        and of the root's that went through the Pallas kernel (all or
        none: the static gate of ``ops/histogram.py`` is one for both);
        ``grow/hist_rows_kernel_pieces``: those of them the kernel took
        as float32 rows, in three bf16 pieces (all where the rows are
        not quantized); ``grow/hist_rows_in_bag``: those of the smaller
        child's rows that are in the bag (all of them where nothing is
        sampled or where the learner orders the bag's rows alone), the
        only ones that carry weight."""
        left_total = recs_h.left_total_count[:applied]
        right_total = recs_h.right_total_count[:applied]
        small = np.minimum(left_total, right_total)
        bucketed = int(self._hist_rows_bucketed(small))
        obs.inc("grow/hist_rows_needed", int(small.sum()))
        obs.inc("grow/hist_rows_bucketed", bucketed)
        if self._compacts() and self._data_tiles().kernel:
            obs.inc("grow/hist_rows_kernel", self.R + bucketed)
            if not self._quantized:
                obs.inc("grow/hist_rows_kernel_pieces", self.R + bucketed)
        # the child _split_step takes as the smaller one (ties: left)
        obs.inc("grow/hist_rows_in_bag", int(np.where(
            left_total <= right_total, recs_h.left_count[:applied],
            recs_h.right_count[:applied]).sum()))

    def _count_unpacks(self, applied: int) -> None:
        """Where the columns are EFB bundles: ``efb/unpacks``, the bundle
        histograms unpacked per feature (the root's and one a split);
        ``efb/unpacked_entries``, the (feature, bin) pairs of the
        features' own bins they made, and ``efb/bundle_entries``, the
        (bundle, bin) pairs of the bundles' own bins they were made from:
        what an unpack has to write and read, whatever width the store
        pads a feature to."""
        if not self._bundled:
            return
        calls = 1 + applied
        obs.inc("efb/unpacks", calls)
        obs.inc("efb/unpacked_entries",
                calls * int(self.dataset.num_bin_per_feature.sum()))
        obs.inc("efb/bundle_entries",
                calls * int(self.dataset.bundle.group_bins.sum()))

    @staticmethod
    def _count_partition_splits(recs_h, applied: int) -> None:
        """``grow/partition_splits``: applied splits, each one partition
        pass over every row; ``grow/partition_cols_feature_major``:
        those whose split column the pass read from the feature-major
        copy of the bins (``grow.bins_by_feature``), which this learner
        holds, so all of them; ``grow/partition_cat_splits``: those whose
        record is categorical, the only ones that need the table lookup
        of ``_go_left_by_bin``."""
        obs.inc("grow/partition_splits", applied)
        obs.inc("grow/partition_cols_feature_major", applied)
        obs.inc("grow/partition_cat_splits",
                int(recs_h.is_categorical[:applied].sum()))

    def _hist_rows_bucketed(self, small: np.ndarray) -> int:
        """Rows ``_children_histograms`` passes over for splits whose
        smaller children hold ``small`` rows: the whole tiles
        ``_compact_child_hist`` takes on one device, the whole masked
        row space on a sharded mesh."""
        if not self._compacts():
            return self.R * len(small)
        T = self._data_tiles().rows
        return int((-(-small.astype(np.int64) // T) * T).sum())

    def _data_tiles(self):
        """``_hist_tiles`` of the learner's own rows, for the counters."""
        return self._hist_tiles(self.bins, jax.ShapeDtypeStruct(
            (self.R, 4),
            self._qdtype if self._quantized else jnp.float32))

    def _count_partition_rows(self, recs_h, applied: int) -> None:
        """Where the learner keeps the rows ordered by leaf:
        ``grow/rows_ordered``, the rows the root's ``order`` holds (the
        in-bag rows where the tree has a bag, ``_ordered_bag``), of
        trees that split; ``grow/partition_parent_rows``, the rows of
        each applied split's parent, which ``_partition_order`` has to
        reorder; ``grow/partition_window_rows``: the windows it
        reordered for them."""
        if not self._compacts():
            return
        parents = (recs_h.left_total_count[:applied]
                   + recs_h.right_total_count[:applied])
        obs.inc("grow/rows_ordered", int(parents[:1].sum()))
        obs.inc("grow/partition_parent_rows", int(parents.sum()))
        obs.inc("grow/partition_window_rows",
                int(_padded_to_ladder(parents, _window_sizes(self.R))))

    # --- device-resident multi-iteration batching ---------------------
    # Every dispatch and every host sync costs the device idle time
    # (how much on the chip: not measured). When nothing in the scan
    # needs per-tree host state, T boosting iterations
    # (gradients -> tree growth -> score update)
    # run as ONE lax.scan dispatch with a single [T, L-1] record
    # read-back. The reference's CUDA learner amortizes the same way —
    # whole-loop on device (cuda_single_gpu_tree_learner.cpp:128) — but
    # per tree; the scan extends it across trees.

    def supports_train_many(self) -> bool:
        """True when the split scan needs no per-split or per-tree host
        state (CEGB penalties, monotone trackers, per-node feature
        masks) and no host RNG (feature_fraction redraws a host mask
        per tree). Quantized-gradient mode batches too: the per-tree
        stochastic-rounding key folds in from a scan-carried device
        counter, and the scan's ``alive`` flag freezes the score after
        a stump step — a later redraw can no longer grow a tree the
        host never applies. extra_trees batches under the same alive
        treatment: its per-node rand_bins key on the scanned per-tree
        seed, the exact sequence the looped path derives from
        ``_tree_idx``."""
        return (not self._cegb_enabled
                and self._mono_tracker is None
                and not self._needs_per_node_masks()
                and not (0.0 < float(self.config.feature_fraction) < 1.0))

    def _make_gh_traced(self, grad, hess, ind=None):
        """_make_gh without the device_put (inside jit the sharding is a
        constraint, not a transfer). ``ind`` is the in-bag indicator,
        None for all-rows — the same masked staging the looped
        ``_make_gh`` performs."""
        ones = jnp.ones(self.N, dtype=jnp.float32)
        if ind is None:
            gh = jnp.stack([grad, hess, ones, ones], axis=1)
        else:
            ordered = self._ordered_bag(ind)
            gh = jnp.stack([grad * ind, hess * ind, ind,
                            ones if ordered is None else ordered], axis=1)
        if self.R - self.N:
            gh = jnp.concatenate(
                [gh, jnp.zeros((self.R - self.N, 4), dtype=jnp.float32)],
                axis=0)
        return jax.lax.with_sharding_constraint(gh, self.gh_sharding)

    def _make_gh_quantized_traced(self, grad, hess, ind, key):
        """_make_gh_quantized inside the batched scan: the stochastic
        draw runs on the UNPADDED [N] rows with the scan-carried
        fold-in key (bit-identical to the looped path's per-tree
        quantize_gh dispatch), then pads and shards the int rows. The
        barrier pins the quantize output at what is a dispatch
        boundary in the looped path — without it XLA may fuse the
        rounding into the histogram kernels and drift the drawn
        integers."""
        from ..ops.quantize import _quantize_gh
        barrier = jax.lax.optimization_barrier
        if ind is None:
            ind = jnp.ones(self.N, dtype=jnp.float32)
        gh, qscale = barrier(_quantize_gh(grad, hess, ind, key,
                                          self._qmax, self._qdtype,
                                          self._ordered_bag(ind)))
        if self.R - self.N:
            gh = jnp.concatenate(
                [gh, jnp.zeros((self.R - self.N, 4), dtype=gh.dtype)],
                axis=0)
        return (barrier(jax.lax.with_sharding_constraint(
            gh, self.gh_sharding)), qscale)

    def _leaf_outputs_from_records(self, recs) -> jnp.ndarray:
        """[L] final leaf outputs replayed from the record buffer: step i
        re-homes the split leaf's rows under the same index (left child)
        and creates leaf i+1 (right child), so an in-order scatter of
        (left_output -> rec.leaf, right_output -> i+1) leaves each
        surviving leaf holding the value the host Tree will store."""
        L = self.L

        def body(i, out):
            rec = jax.tree_util.tree_map(lambda a: a[i], recs)
            v = rec_valid(rec)
            out = out.at[jnp.where(v, rec.leaf, L)].set(rec.left_output)
            out = out.at[jnp.where(v, i + 1, L)].set(rec.right_output)
            return out

        out = jnp.zeros(L + 1, dtype=jnp.float32)
        return jax.lax.fori_loop(0, L - 1, body, out)[:L]

    def _grow_one(self, bins, bins_fm, gh, feature_mask, seed, lr, qscale):
        """One tree inside the scan: root + whole-tree loop + leaf-output
        replay. Returns (records, per-row output deltas [N])."""
        barrier = jax.lax.optimization_barrier
        state, _ = self._root_impl(bins, gh, feature_mask, seed, qscale)
        state = barrier(state)
        state, recs = self._tree_impl(bins, bins_fm, state, feature_mask,
                                      seed, qscale)
        state, recs = barrier((state, recs))
        outs = self._leaf_outputs_from_records(recs) * lr
        return recs, outs[state.leaf_of_row[:self.N]]

    def _step_gh(self, grad, hess, ind, qkey, ctr):
        """Per-tree gh staging inside the scan: exact f32 rows, or —
        quantized — advance the scan-carried tree counter and draw
        with its fold-in key (the looped path's ops/quantize.tree_key
        sequence, bit-exact). ``ind`` is the iteration's in-bag
        indicator (None for all rows). Returns (gh, qscale, ctr)."""
        barrier = jax.lax.optimization_barrier
        if qkey is None:
            return (barrier(self._make_gh_traced(grad, hess, ind)),
                    self._qs_ones, ctr)
        ctr = ctr + jnp.uint32(1)
        gh, qscale = self._make_gh_quantized_traced(
            grad, hess, ind, jax.random.fold_in(qkey, ctr))
        return gh, qscale, ctr

    def _apply_sampling(self, iter_idx, grad, hess):
        """The sample strategy's draw inside the scan
        (``apply_traced``): bagging indicators / GOSS rescales keyed on
        the traced iteration index — the fold_in sequence the looped
        path's ``bagging`` dispatches one iteration at a time. The
        barrier pins the outputs at what is a dispatch boundary on the
        looped path."""
        strat = self._many_sample
        if strat is None:
            return grad, hess, None
        g, h, ind = strat.apply_traced(iter_idx, grad, hess)
        if ind is None:
            return g, h, None
        return jax.lax.optimization_barrier((g, h, ind))

    def _many_impl(self, bins, bins_fm, score0, seeds, iters, feature_mask,
                   lr, qkey=None, qctr0=None):
        # optimization_barrier at every boundary that is a separate
        # dispatch in the per-iteration path: without them XLA fuses the
        # gradient math into the histogram kernels, changing rounding,
        # and the batched trees drift bit-wise from the looped ones
        barrier = jax.lax.optimization_barrier

        def step(carry, xs):
            seed, it = xs
            # score [N] (single-model objectives)
            score, ctr, alive = carry
            grad, hess = barrier(self._many_grad_fn(score))
            grad, hess, ind = self._apply_sampling(it, grad, hess)
            gh, qscale, ctr = self._step_gh(grad, hess, ind, qkey, ctr)
            recs, delta = self._grow_one(bins, bins_fm, gh, feature_mask,
                                         seed, lr, qscale)
            grew = rec_valid(jax.tree_util.tree_map(
                lambda a: a[0], recs))
            # after a stump step the score FREEZES: a quantized redraw
            # (new fold-in per step) may otherwise grow a tree the
            # host — which stops applying at the first stump — never
            # sees; dead steps also surface invalid records
            score = barrier(jnp.where(alive, score + delta, score))
            recs = recs._replace(
                gain=jnp.where(alive, recs.gain, -jnp.inf))
            return (score, ctr, alive & grew), recs

        ctr0 = jnp.uint32(0) if qctr0 is None else qctr0
        carry = (score0, ctr0, jnp.asarray(True))
        (score, ctr, _), recs = jax.lax.scan(step, carry, (seeds, iters))
        return (score, ctr), recs

    def _many_impl_multi(self, bins, bins_fm, score0, seeds, iters,
                         feature_mask, lr, qkey=None, qctr0=None):
        # K trees per iteration (multiclass): one gradient pass per step
        # over the [N, K] scores, then a statically unrolled per-class
        # tree (reference: the k-loop of GBDT::TrainOneIter)
        barrier = jax.lax.optimization_barrier
        K = int(seeds.shape[1])

        def step(carry, xs):
            seeds_k, it = xs
            score, ctr, alive = carry
            grad, hess = barrier(self._many_grad_fn(score))
            # one sampling draw per ITERATION over the [N, K] columns —
            # the looped path draws before its per-class loop too
            grad, hess, ind = self._apply_sampling(it, grad, hess)
            all_recs = []
            grew = jnp.asarray(False)
            for k in range(K):
                gh, qscale, ctr = self._step_gh(grad[:, k], hess[:, k],
                                                ind, qkey, ctr)
                recs, delta = self._grow_one(bins, bins_fm, gh,
                                             feature_mask, seeds_k[k], lr,
                                             qscale)
                grew = grew | rec_valid(jax.tree_util.tree_map(
                    lambda a: a[0], recs))
                score = score.at[:, k].add(
                    jnp.where(alive, delta, jnp.float32(0.0)))
                all_recs.append(recs)
            recs = jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), *all_recs)
            recs = recs._replace(
                gain=jnp.where(alive, recs.gain, -jnp.inf))
            return (barrier(score), ctr, alive & grew), recs

        ctr0 = jnp.uint32(0) if qctr0 is None else qctr0
        carry = (score0, ctr0, jnp.asarray(True))
        (score, ctr, _), recs = jax.lax.scan(step, carry, (seeds, iters))
        return (score, ctr), recs

    def train_many(self, grad_fn, sample_strategy, score0: jnp.ndarray,
                   seeds, iters, shrinkage: float):
        """Run T boosting iterations in one dispatch. ``seeds`` is [T]
        (single-model objectives; ``score0`` is the [N] score column)
        or [T, K] (K trees per iteration; ``score0`` is [N, K]);
        ``iters`` is the [T] vector of absolute iteration numbers (the
        sample strategy's draw index). Returns (final scores, stacked
        SplitRecords [T, (K,) L-1]) — the record read-back is the
        batch's single host sync. ``grad_fn`` must be traceable (the
        objective's jitted gradient fn); ``sample_strategy`` provides
        the traceable ``apply_traced`` draw (None for no sampling).
        Quantized mode threads the learner's device-side tree counter
        through the scan and stores its advanced value back, so a
        later looped tree draws the key the looped path would have
        drawn."""
        self._ensure_compiled()
        # explicit staging of the batch's control vectors (the
        # transfer-guard sanitizer pins the warmed batch clean)
        seeds = jax.device_put(np.asarray(seeds, dtype=np.int32))
        iters = jax.device_put(np.asarray(iters, dtype=np.int32))
        # bound methods are rebuilt per attribute access: compare by
        # equality (__self__/__func__), not identity, or every batch
        # would re-jit the scan; strategies compare by value the same
        # way (sample_strategy.py _jit_key)
        if self._many_fn is None or self._many_grad_fn != grad_fn \
                or self._many_sample != sample_strategy:
            self._many_grad_fn = grad_fn
            self._many_sample = sample_strategy
            self._many_fn = obs_compile.instrument_jit(
                "mesh.train_many", self._many_impl)
            self._many_multi_fn = obs_compile.instrument_jit(
                "mesh.train_many_multi", self._many_impl_multi)
        feature_mask = self._sample_features()
        self._tree_idx += int(seeds.size)
        from ..utils.scalars import dev_f32
        lr = dev_f32(float(shrinkage))
        fn = self._many_multi_fn if seeds.ndim == 2 else self._many_fn
        if self._quantized:
            out, recs = fn(self.bins, self.bins_fm, score0, seeds, iters,
                           feature_mask, lr, self._quant_base_key,
                           self._quant_ctr)
            score_t, self._quant_ctr = out
            # the scan advanced the device counter once per tree slot;
            # keep the host mirror (the _quantize_stage assert) in step
            self._quant_ctr_host += int(seeds.size)
            self._count_discretized(int(seeds.size), self.N)
        else:
            out, recs = fn(self.bins, self.bins_fm, score0, seeds, iters,
                           feature_mask, lr)
            score_t = out[0]
        return score_t, recs
