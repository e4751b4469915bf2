"""Out-of-core sharded tree learner.

Grows exactly the serial learner's trees over a
:class:`~..io.shards.ShardedBinnedDataset` whose binned rows never sit
in device memory all at once: every histogram pass is an ordered sweep
over memory-mapped shards, each staged into HBM by the double-buffered
:class:`~..io.shards.ShardPrefetcher` while the previous shard
computes.

Bit-parity contract (pinned by tests/test_shards.py): trees are
BIT-IDENTICAL to :class:`~.serial.SerialTreeLearner` on the same rows
because

- gh staging, feature sampling, split scans (``find_best_split``),
  candidate bookkeeping (``_finish_split``/``_store_info``) and the
  split-record replay are the serial learner's own functions, reused;
- per-leaf histograms accumulate shard-by-shard through an ORDERED
  scatter-add (``acc.at[flat].add``) whose update order is the global
  ascending row order — on scatter backends (CPU auto-selects the
  segment-sum scatter path) this is the very same sequence of f32 adds
  the serial learner's single-pass ``segment_sum`` performs, and under
  quantized integer gradients the accumulation is exact int32/int64
  arithmetic, order-invariant on every backend;
- the per-tree quantization scale is ``max|g|`` over the full
  device-resident gradient vector — identical to the serial staging —
  so quantized rows are drawn bit-identically.

Per-row O(1)-width state (the [R, 4] gh rows, per-shard row→leaf
segments) stays device-resident: O(N) words next to the O(N·F)-byte
bins payload the shards stream. The device argmax that picks the next
leaf is read back once per split (the documented JLT001 sync, where the
serial learner reads back once per tree) — so a tree costs
``num_leaves`` shard sweeps. Batching K splits per sweep is the
standing follow-up (ROADMAP).

Unsupported here (loud ``log.fatal`` at setup): CEGB, the
intermediate/advanced monotone methods (``basic`` works — it lives
inside the split scan), forced splits, interaction constraints /
per-node column sampling, linear trees, EFB (the sharded dataset never
bundles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..io.shards import ShardedBinnedDataset, ShardPrefetcher
from ..models.tree import Tree
from ..obs import compile as obs_compile
from ..obs.registry import registry as obs
from ..ops.histogram import mask_gh, resolve_hist_impl, subtract_histogram
from ..ops.quantize import acc_dtype, dequantize_sums, sum_gh
from ..ops.split import (FeatureMeta, SplitParams, calculate_leaf_output,
                         find_best_split, pad_feature_meta,
                         select_frontier)
from ..utils import log, next_pow2 as _next_pow2
from ..utils.scalars import dev_bool, dev_i32
from .capabilities import CapabilityMixin
from .grow import (_finish_split, _go_left_by_bin, _maybe_rand_bins,
                   _partition_rec, _record_at, apply_split_record,
                   make_root_state, rec_valid, record_is_valid)
from .serial import _pad_rows_fn_cached, _stage_gh_fn_cached


def _accum_hist(hist: jnp.ndarray, bins: jnp.ndarray,
                gh: jnp.ndarray) -> jnp.ndarray:
    """Ordered scatter-add of one shard's rows into the running
    [F, B, C] accumulator. The flat-index + broadcast layout matches
    ops/histogram._segment_histogram exactly, and seeding the scatter
    with the RUNNING accumulator (instead of summing per-shard partials)
    is what keeps the f32 result bit-identical to the serial learner's
    single segment-sum pass: the adds land in the same global row
    order. Rows with gh == 0 (shard pad, rows outside the leaf) vanish
    from every sum."""
    S, F = bins.shape
    B = hist.shape[1]
    C = gh.shape[1]
    flat = (jnp.arange(F, dtype=jnp.int32)[None, :] * B
            + bins.astype(jnp.int32)).reshape(-1)
    vals = jnp.broadcast_to(
        gh.astype(hist.dtype)[:, None, :], (S, F, C)).reshape(-1, C)
    return hist.reshape(F * B, C).at[flat].add(vals).reshape(F, B, C)


@functools.lru_cache(maxsize=None)
def _zero_hist_fn_cached(Fp: int, B: int, dtype_name: str):
    """Fresh [Fp, B, 4] accumulator per sweep, produced on device by a
    jitted constant (an eager ``jnp.zeros`` would be an implicit
    host→device transfer per tree — the sanitizer pins this)."""
    dtype = jnp.dtype(dtype_name)

    def zero():
        return jnp.zeros((Fp, B, 4), dtype=dtype)

    return obs_compile.instrument_jit("sharded.zero_hist", zero)


_sum_gh_fn = obs_compile.instrument_jit("sharded.sum_gh", sum_gh)


@functools.lru_cache(maxsize=None)
def _gh_seg_fn_cached(n_k: int, n_pad: int):
    """Slice one shard's [n_pad, 4] gh segment (trailing zero pad rows)
    out of the full padded gh matrix; the pad row is the shard gather's
    fill target."""
    def seg(gh_full, offset):
        part = jax.lax.dynamic_slice(
            gh_full, (offset, jnp.int32(0)), (n_k, gh_full.shape[1]))
        return jnp.concatenate(
            [part, jnp.zeros((n_pad - n_k, gh_full.shape[1]),
                             dtype=part.dtype)], axis=0)

    return obs_compile.instrument_jit("sharded.gh_seg", seg)


@functools.lru_cache(maxsize=None)
def _root_fn_cached(L: int, B: int, extra_trees: bool, has_cat: bool):
    """Root split scan over the swept histogram — the tail of the
    serial learner's ``_root_fn`` with the histogram (and the channel
    sums) computed outside."""
    def root(hist, sums_raw, gh0, leaf0, feature_mask, children_allowed,
             rand_seed, qscale, meta, params):
        F = meta.num_bin.shape[0]
        sums = dequantize_sums(sums_raw, qscale)
        parent_out = calculate_leaf_output(sums[0], sums[1], params)
        info = find_best_split(
            hist, sums[0], sums[1], sums[2], sums[3], meta, params,
            feature_mask, parent_output=parent_out,
            rand_bins=_maybe_rand_bins(extra_trees, rand_seed, 0, meta,
                                       params),
            leaf_depth=jnp.int32(0), has_categorical=has_cat,
            hist_scale=qscale)
        state = make_root_state(gh0, hist, leaf0, info, L, F, B,
                                children_allowed)
        return state, _record_at(state, 0)

    return obs_compile.instrument_jit("sharded.root", root)


def _shard_step(shard_bins, leaf_seg, gh_seg, hist, rec, new_leaf, meta,
                S: int):
    """One shard's slice of a split step: route the shard's rows of the
    split leaf left/right (the partition update of grow.py's
    ``_split_step``, applied to this contiguous row segment), then gather the rows now
    sitting on the SMALLER child and scatter them into the running
    child histogram. Shard segments are disjoint contiguous row ranges,
    so sweeping them in order performs the identical per-row updates —
    and the identical ordered histogram adds — as the serial learner's
    full-array pass.

    ``S`` is the STATIC gather width: a power-of-two bucket of the
    smaller child's global row count (an upper bound on any shard's
    share of it), chosen on the host, which steps this learner split
    by split, to keep deep-tree steps from scanning all rows. Fill
    rows hit the
    shard's zero pad row (gh 0), so the bucket size changes compiled
    variants, never values. ``rec`` comes through ``_partition_rec``:
    on data with no categorical feature its categorical fields are
    None and no table lookup is lowered."""
    n_pad = shard_bins.shape[0]
    leaf = rec.leaf
    f = jnp.maximum(rec.feature, 0)
    col = jnp.take(shard_bins, f, axis=1).astype(jnp.int32)
    gl = _go_left_by_bin(col, rec.threshold_bin, rec.default_left,
                         meta.missing_type[f], meta.num_bin[f] - 1,
                         meta.zero_bin[f], rec.is_categorical,
                         rec.cat_mask)
    on_leaf = leaf_seg == leaf
    leaf_seg = jnp.where(on_leaf & ~gl, new_leaf, leaf_seg)
    smaller_is_left = rec.left_total_count <= rec.right_total_count
    small_id = jnp.where(smaller_is_left, leaf, new_leaf)
    (idx,) = jnp.nonzero(leaf_seg == small_id, size=S,
                         fill_value=n_pad - 1)
    hist = _accum_hist(hist, shard_bins[idx], gh_seg[idx])
    return leaf_seg, hist


_shard_step_fn = obs_compile.instrument_jit(
    "sharded.shard_step", _shard_step, static_argnums=(7,))

# gather-bucket floor: caps compiled shard-step variants
_MIN_BUCKET = 256


@functools.lru_cache(maxsize=None)
def _finish_fn_cached(B: int, max_depth: int, extra_trees: bool,
                      has_cat: bool):
    """Split-step tail after the shard sweep: sibling subtraction from
    the parent's stored histogram, per-leaf store updates and both
    children's best-split scans (``_finish_split``, shared verbatim
    with the serial learner), then the device argmax that names the
    next split."""
    def finish(state, rec, new_leaf, hist_small, feature_mask,
               rand_seed, qscale, meta, params):
        leaf = rec.leaf
        smaller_is_left = rec.left_total_count <= rec.right_total_count
        hist_large = subtract_histogram(state.hists[leaf], hist_small)
        hist_left = jnp.where(smaller_is_left, hist_small, hist_large)
        hist_right = jnp.where(smaller_is_left, hist_large, hist_small)
        hists = state.hists.at[leaf].set(hist_left) \
            .at[new_leaf].set(hist_right)
        state = state._replace(hists=hists)
        state = _finish_split(state, rec, leaf, new_leaf,
                              jnp.asarray(True), hist_left, hist_right,
                              feature_mask, feature_mask, meta, params,
                              max_depth=max_depth,
                              extra_trees=extra_trees, has_cat=has_cat,
                              rand_seed=rand_seed, qscale=qscale)
        best = jnp.argmax(state.gain).astype(jnp.int32)
        return state, _record_at(state, best)

    return obs_compile.instrument_jit("sharded.finish", finish,
                                      donate_argnums=(0,))


# ----------------------------------------------------------------------
# K-splits-per-sweep frontier batching. One shard staging serves up to
# K pending splits: the round SPECULATES the top-K best-split
# candidates of the current store (slot 0 pinned to the argmax —
# ops/split.py select_frontier), applies all K partition routings and
# histograms all K smaller children in a single sweep, then a
# device-side finish VALIDATES the leaf-wise order split by split —
# a speculated slot is accepted only while the store argmax still
# names it, exactly reproducing the sequential grower's choices (a
# freshly-scanned child that out-gains the next pending candidate
# rejects the tail). Rejected slots' partition routings are reverted
# at the next staging (their new-leaf ids are about to be reused), and
# their histograms are discarded — wasted compute, but the staging
# traffic (the out-of-core bottleneck) is paid ONCE per round instead
# of once per split. Trees stay BIT-identical to serial growth:
# accepted splits perform the identical ordered scatter-adds and scans
# the one-split-per-sweep path performs.
# ----------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _zero_khist_fn_cached(K: int, Fp: int, B: int, dtype_name: str):
    """Fresh [K, Fp, B, 4] per-slot accumulator block per sweep round
    (jitted constant, like _zero_hist_fn_cached)."""
    dtype = jnp.dtype(dtype_name)

    def zero():
        return jnp.zeros((K, Fp, B, 4), dtype=dtype)

    return obs_compile.instrument_jit("sharded.zero_khist", zero)


def _slot(recs, i: int):
    """Record ``i`` of a [K]-stacked SplitRecord."""
    return jax.tree_util.tree_map(lambda a: a[i], recs)


def _spec_records(state, K: int):
    """Stacked top-K speculation records. The record gain carries the
    SELECTION value from select_frontier — -inf on dead slots even
    when their index aliases a live leaf — so host
    ``record_is_valid`` and device ``rec_valid`` both reject exactly
    the slots the selection did not really pick."""
    leaves, vals = select_frontier(state.gain, K)
    return _record_at(state, leaves)._replace(gain=vals)


def _shard_kstep(shard_bins, leaf_seg, gh_seg, hists, recs,
                 new_leaf_base, spec_valid, revert_from, revert_to,
                 meta, K: int, S: int):
    """One shard's slice of a K-split sweep round.

    1. revert the previous round's REJECTED routings (their new-leaf
       ids are reused by this round's slots, so this must precede the
       new updates); ``revert_from`` is -1 on non-rejected slots, and
       the explicit ``>= 0`` guard keeps the -1 sentinel from
       matching the pad rows' leaf -1;
    2. apply the K speculated partition updates — the speculated
       leaves are distinct (one pending candidate per leaf), so the
       updates commute and match the sequential per-split routing;
    3. gather + scatter each slot's smaller child into its running
       histogram. Child ``i``'s membership is unaffected by the other
       slots' routings (distinct source and target leaf ids), so the
       gathered rows — and the ordered adds — are exactly the
       sequential sweep's.

    ``S`` is one static gather bucket for all K slots (the max of the
    slots' smaller-child buckets, host-chosen); fill rows hit the
    shard's zero pad row."""
    n_pad = shard_bins.shape[0]
    leaf_seg = _apply_reverts(leaf_seg, revert_from, revert_to, K)
    for i in range(K):
        rec = _slot(recs, i)
        f = jnp.maximum(rec.feature, 0)
        col = jnp.take(shard_bins, f, axis=1).astype(jnp.int32)
        gl = _go_left_by_bin(col, rec.threshold_bin, rec.default_left,
                             meta.missing_type[f], meta.num_bin[f] - 1,
                             meta.zero_bin[f], rec.is_categorical,
                             rec.cat_mask)
        on_leaf = leaf_seg == rec.leaf
        leaf_seg = jnp.where(spec_valid[i] & on_leaf & ~gl,
                             new_leaf_base + i, leaf_seg)
    for i in range(K):
        rec = _slot(recs, i)
        smaller_is_left = rec.left_total_count <= rec.right_total_count
        small_id = jnp.where(smaller_is_left, rec.leaf,
                             new_leaf_base + i)
        (idx,) = jnp.nonzero(leaf_seg == small_id, size=S,
                             fill_value=n_pad - 1)
        # invalid slots still gather (static shapes) but their rows are
        # zeroed so the slot histogram stays null
        gh_rows = mask_gh(gh_seg[idx], spec_valid[i])
        hists = hists.at[i].set(
            _accum_hist(hists[i], shard_bins[idx], gh_rows))
    return leaf_seg, hists


_shard_kstep_fn = obs_compile.instrument_jit(
    "sharded.shard_kstep", _shard_kstep, static_argnums=(10, 11))


def _apply_reverts(leaf_seg, revert_from, revert_to, K: int):
    """Undo the previous round's rejected routings on one shard
    segment. ``revert_from`` is -1 on non-rejected slots; the explicit
    ``>= 0`` guard keeps the sentinel from matching the pad rows' leaf
    -1. Shared by the in-sweep revert (``_shard_kstep``) and the
    post-loop cleanup (``_revert_fn_cached``) — the two MUST apply
    identical rules or the partition handed to the score update
    desyncs from what the next sweep assumed."""
    for j in range(K):
        hit = (revert_from[j] >= 0) & (leaf_seg == revert_from[j])
        leaf_seg = jnp.where(hit, revert_to[j], leaf_seg)
    return leaf_seg


@functools.lru_cache(maxsize=None)
def _revert_fn_cached(K: int):
    """Standalone revert of rejected routings — applied to every shard
    segment after the grow loop ends with rejections still pending
    (no further sweep will fold the revert in)."""
    def revert(leaf_seg, revert_from, revert_to):
        return _apply_reverts(leaf_seg, revert_from, revert_to, K)

    return obs_compile.instrument_jit("sharded.revert", revert)


@functools.lru_cache(maxsize=None)
def _kfinish_fn_cached(B: int, K: int, max_depth: int, extra_trees: bool,
                       has_cat: bool):
    """Validated finish of one K-split sweep round: slot by slot —
    check the store argmax still names the speculated leaf (the
    sequential grower's choice), then masked sibling subtraction +
    per-leaf store updates + both children's scans (the shared
    ``_finish_split`` tail). The first rejected slot kills the rest of
    the round (`alive` chain): their state writes are suppressed and
    the host reverts their routings next staging. Returns the
    accepted mask; the NEXT round's speculation comes from the
    separate gather-only ``_spec_fn`` dispatch (an in-jit epilogue
    was measured to shift the scans' f32 sums an ulp off the
    one-split compile — see ``_spec_fn_cached``)."""
    def kfinish(state, recs, hists, new_leaf_base, spec_valid,
                feature_mask, rand_seed, qscale, meta, params):
        accepted = jnp.zeros(K, dtype=bool)
        alive = jnp.asarray(True)
        for i in range(K):
            # barrier between slots: each slot's subtraction + child
            # scans must compile like the one-split finish dispatch —
            # cross-slot fusion is free to contract a dequantize
            # multiply into an FMA and drift the stored gains by an
            # ulp off the stepped path (the train_many precedent)
            state = jax.lax.optimization_barrier(state)
            rec = _slot(recs, i)
            is_next = (jnp.argmax(state.gain).astype(jnp.int32)
                       == rec.leaf)
            ok = alive & spec_valid[i] & is_next & rec_valid(rec)
            new_leaf = (new_leaf_base + i).astype(jnp.int32)
            leaf = rec.leaf
            smaller_is_left = (rec.left_total_count
                               <= rec.right_total_count)
            hist_small = hists[i]
            hist_large = subtract_histogram(state.hists[leaf],
                                            hist_small)
            hist_left = jnp.where(smaller_is_left, hist_small,
                                  hist_large)
            hist_right = jnp.where(smaller_is_left, hist_large,
                                   hist_small)
            hs = state.hists \
                .at[leaf].set(jnp.where(ok, hist_left,
                                        state.hists[leaf])) \
                .at[new_leaf].set(jnp.where(ok, hist_right,
                                            state.hists[new_leaf]))
            state = state._replace(hists=hs)
            state = _finish_split(state, rec, leaf, new_leaf, ok,
                                  hist_left, hist_right, feature_mask,
                                  feature_mask, meta, params,
                                  max_depth=max_depth,
                                  extra_trees=extra_trees,
                                  has_cat=has_cat, rand_seed=rand_seed,
                                  qscale=qscale)
            accepted = accepted.at[i].set(ok)
            alive = ok
        return state, accepted

    return obs_compile.instrument_jit("sharded.kfinish", kfinish,
                                      donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _spec_fn_cached(K: int):
    """Top-K speculation records off an existing GrowState — pure
    gathers (select_frontier + _record_at), no split math. Runs as its
    OWN dispatch after the shared ``_root_fn``: compiling a combined
    root+spec program was measured to shift the root scan's f32
    cumsum sums by an ulp against the one-split path (XLA refuses the
    same contraction choices under a different epilogue), breaking
    bit parity; a gather-only follow-up dispatch cannot."""
    def spec_of(state):
        return _spec_records(state, K)

    return obs_compile.instrument_jit("sharded.spec", spec_of)


@functools.lru_cache(maxsize=None)
def _rows_out_fn_cached(sizes: tuple):
    """Per-shard leaf segments → the full [N] row→leaf vector the
    boosting layer's score update gathers over."""
    def rows_out(*segs):
        return jnp.concatenate([s[:n] for s, n in zip(segs, sizes)])

    return obs_compile.instrument_jit("sharded.rows_out", rows_out)


class ShardedTreeLearner(CapabilityMixin):
    """Leaf-wise grower over memory-mapped binned shards."""

    def __init__(self, config, dataset: ShardedBinnedDataset):
        self.config = config
        self.dataset = dataset
        N = dataset.num_data
        F = dataset.num_features
        if F == 0:
            log.fatal("Cannot train without features")
        self.N, self.F = N, F
        # identical canonical geometry to the serial learner — part of
        # the bit-parity contract (gh padding enters the channel sums)
        self.B = _next_pow2(max(int(dataset.max_num_bin), 2))
        self.L = int(config.num_leaves)
        self.max_depth = int(config.max_depth)
        self.R = -(-(N + 1) // 4096) * 4096
        self.Fp = -(-F // 8) * 8
        self._check_unsupported(config)
        qbits = (int(getattr(config, "quant_grad_bits", 8))
                 if getattr(config, "use_quantized_grad", False) else 0)
        hist_impl = resolve_hist_impl(
            getattr(config, "hist_backend", "auto"),
            bool(getattr(config, "tpu_use_f64_hist", False)), qbits)
        if hist_impl[1]:
            log.warning("tpu_use_f64_hist is ignored on the sharded "
                        "path (f32 ordered-scatter accumulation)")
        self._init_quantization(hist_impl[2], config, N)
        if not self._quantized and jax.default_backend() != "cpu":
            log.warning("sharded exact-f32 training off a scatter "
                        "backend: histogram accumulation order may "
                        "differ from the in-memory learner "
                        "(use_quantized_grad is order-invariant "
                        "everywhere)")
        self.meta = pad_feature_meta(
            FeatureMeta.from_dataset(dataset,
                                     int(config.max_cat_to_onehot)),
            self.Fp - F)
        self.params = SplitParams.from_config(config)
        self._ff_rng = np.random.RandomState(config.feature_fraction_seed)
        self._resolve_constraints()
        self._extra_trees = bool(config.extra_trees)
        self._extra_seed = int(config.extra_seed)
        self._tree_idx = 0
        self._has_cat = bool(np.asarray(self.meta.is_categorical).any())
        self._hist_dtype = (np.dtype(acc_dtype(self._qdtype)).name
                            if self._quantized else "float32")
        self._ones_ind = jnp.ones(N, dtype=jnp.float32)
        # per-shard geometry + the device-resident per-shard row→leaf
        # segments' initial value (pad row = -1, never a real leaf)
        self.prefetcher = ShardPrefetcher(dataset, self.Fp)
        self._offsets = [int(o) for o in dataset.shard_offsets]
        self._sizes = [int(s) for s in dataset.shard_sizes]
        self._pads = [n + 1 for n in self._sizes]
        self._leaf_seg0 = [
            jnp.concatenate([jnp.zeros(n, dtype=jnp.int32),
                             jnp.full((p - n,), -1, dtype=jnp.int32)])
            for n, p in zip(self._sizes, self._pads)]
        self._gh0 = jnp.zeros((1, 4), dtype=jnp.float32)
        self._leaf0 = jnp.zeros(1, dtype=jnp.int32)
        self._root_fn = _root_fn_cached(self.L, self.B,
                                        self._extra_trees, self._has_cat)
        # K pending splits per shard sweep (frontier batching): each
        # staging pass serves up to K splits; 0/1 keeps the legacy
        # one-split-per-sweep loop (also the K-batch's bit-parity
        # reference)
        self._K = max(1, min(
            int(getattr(config, "tpu_frontier_splits", 8)), self.L - 1))
        # cross-ITERATION prefetch scheduling (pipelined boosting): a
        # sweep started but unconsumed when tree t's grow loop ends —
        # or started deliberately at the end of train() — is stashed
        # here, so shard 0 of tree t+1's ROOT sweep stages while the
        # boosting layer runs t's score update and t+1's gradients /
        # gh staging. The stash is always a FRESH (never-iterated)
        # sweep: prestarted sweeps are consumed from the top or not at
        # all, so the ordered-accumulation bit-parity contract is
        # untouched.
        self._next_sweep = None
        self._rebind_compiled()

    def _rebind_compiled(self) -> None:
        """(Re)resolve the lru-cached step programs from the current
        static config (max_depth bakes into finish/kfinish) — called
        at setup and again by ops_refresh.refresh_learner_params after
        a reset_parameter."""
        self._finish_fn = _finish_fn_cached(self.B, self.max_depth,
                                            self._extra_trees,
                                            self._has_cat)
        if self._K > 1:
            self._spec_fn = _spec_fn_cached(self._K)
            self._kfinish_fn = _kfinish_fn_cached(
                self.B, self._K, self.max_depth, self._extra_trees,
                self._has_cat)

    # ------------------------------------------------------------------
    def _check_unsupported(self, config) -> None:
        if self.dataset.bundle is not None:
            log.fatal("sharded datasets never carry EFB bundles")
        if config.linear_tree:
            log.fatal("linear_tree needs raw rows resident; not "
                      "supported with sharded datasets")
        if config.forcedsplits_filename:
            log.fatal("forced splits are not supported with sharded "
                      "datasets")
        if (config.cegb_tradeoff < 1.0 or config.cegb_penalty_split > 0.0
                or config.cegb_penalty_feature_coupled
                or config.cegb_penalty_feature_lazy):
            log.fatal("CEGB is not supported with sharded datasets")
        if config.interaction_constraints \
                or 0.0 < float(config.feature_fraction_bynode) < 1.0:
            log.fatal("per-node feature masks (interaction_constraints "
                      "/ feature_fraction_bynode) are not supported "
                      "with sharded datasets")
        if config.monotone_constraints and any(
                int(v) != 0 for v in config.monotone_constraints) \
                and config.monotone_constraints_method != "basic":
            log.fatal("monotone_constraints_method=%s needs resident "
                      "histogrammed rescans; only 'basic' is supported "
                      "with sharded datasets"
                      % config.monotone_constraints_method)

    def _splittable(self, depth: int) -> bool:
        return self.max_depth <= 0 or depth < self.max_depth

    def _zero_hist(self):
        return _zero_hist_fn_cached(self.Fp, self.B, self._hist_dtype)()

    def _zero_khist(self):
        return _zero_khist_fn_cached(self._K, self.Fp, self.B,
                                     self._hist_dtype)()

    # ------------------------------------------------------------------
    def train(self, grad, hess, bag=None):
        """Grow one tree over the shard sweep; returns the host Tree and
        the device [N] row→leaf vector for the score update — the same
        contract as SerialTreeLearner.train."""
        with obs.scope("tree::stage_gh"):
            ind = self._ones_ind if bag is None else bag
            if self._quantized:
                gh, self._qscale = self._quantize_stage(
                    grad, hess, ind, self._tree_idx + 1)
                gh = _pad_rows_fn_cached(self.R)(gh)
            else:
                self._qscale = self._qs_ones
                gh = _stage_gh_fn_cached(self.R)(grad, hess, ind)
            obs.watch_ready("tree::stage_gh", gh)
        feature_mask = self._tree_feature_mask()
        tree = Tree(self.L)
        self._tree_idx += 1
        rand_seed = dev_i32(
            (self._extra_seed + 7919 * self._tree_idx) & 0x7FFFFFFF)
        gh_segs = [
            _gh_seg_fn_cached(n, p)(gh, dev_i32(o))
            for n, p, o in zip(self._sizes, self._pads, self._offsets)]
        leaf_segs = list(self._leaf_seg0)

        if self._K > 1:
            leaf_segs = self._grow_kbatch(tree, gh, gh_segs, leaf_segs,
                                          feature_mask, rand_seed)
        else:
            leaf_segs = self._grow_stepped(tree, gh, gh_segs, leaf_segs,
                                           feature_mask, rand_seed)
        if self._next_sweep is None:
            # schedule the NEXT iteration's root sweep across the
            # boosting boundary: shard 0 stages while the caller runs
            # this tree's score update and the next tree's gradients +
            # gh staging (the last training iteration wastes one
            # worker-side staging — the same accepted cost as the
            # grow loops' early-stop prestarts)
            self._next_sweep = self.prefetcher.sweep()
        rows_out = _rows_out_fn_cached(tuple(self._sizes))
        return tree, rows_out(*leaf_segs)

    # ------------------------------------------------------------------
    def release_prefetch(self) -> None:
        """Drop the cross-iteration sweep stash. Called by the boosting
        layer when a training run ends: the parked sweep pins one
        staged shard buffer in device memory, which is paid-for
        overlap DURING training but dead weight once no further tree
        will consume it. Correctness is unaffected — the next
        ``_root_round`` (continued training) simply starts a fresh
        sweep."""
        self._next_sweep = None

    # ------------------------------------------------------------------
    def _root_round(self, gh, gh_segs, feature_mask, rand_seed):
        """Root round shared by BOTH growth strategies — the lockstep
        matters: the K-batch's bit-parity contract rests on the SAME
        `sharded.root` compile and the same staging/prestart
        discipline as the stepped path. Accumulates the root histogram
        over one staging sweep, scans it, prestarts the first split
        round's sweep through the read-back window, and reads back the
        chosen record (stepped) or the top-K speculation (K-batch).
        Returns (state, recs_dev, recs_host, pending_sweep)."""
        hist = self._zero_hist()
        # the previous iteration stashed this tree's root sweep at its
        # own end (cross-iteration prefetch scheduling; train() above)
        root_sweep, self._next_sweep = (
            self._next_sweep or self.prefetcher.sweep(), None)
        for k, bins_dev in root_sweep:
            hist = _accum_hist_fn(hist, bins_dev, gh_segs[k])
        sums_raw = _sum_gh_fn(gh)
        state, rec = self._root_fn(
            hist, sums_raw, self._gh0, self._leaf0, feature_mask,
            dev_bool(self._splittable(0)), rand_seed, self._qscale,
            self.meta, self.params)
        out = rec if self._K <= 1 else self._spec_fn(state)
        # prestart the first split's sweep: shard 0 stages through
        # the root read-back window instead of after it
        pending = self.prefetcher.sweep() if self.L > 1 else None
        # jaxlint: disable=JLT001 -- the root record(s) must reach the
        # host Tree replay (one deliberate sync per tree root)
        out_h = jax.device_get(out)
        obs.watch_ready("tree::root_histogram", out)
        return state, out, out_h, pending

    # ------------------------------------------------------------------
    def _grow_stepped(self, tree, gh, gh_segs, leaf_segs, feature_mask,
                      rand_seed):
        """Legacy one-split-per-sweep growth (tpu_frontier_splits<=1;
        also the K-batch's bit-parity reference)."""
        with obs.scope("tree::root_histogram"):
            state, rec, rec_h, pending = self._root_round(
                gh, gh_segs, feature_mask, rand_seed)

        next_leaf = 1
        while next_leaf < self.L:
            if not record_is_valid(rec_h):
                break
            small_count = min(float(rec_h.left_total_count),
                              float(rec_h.right_total_count))
            with obs.scope("tree::shard_sweep"):
                hist_small = self._zero_hist()
                new_leaf = dev_i32(next_leaf)
                part_rec = _partition_rec(rec, self._has_cat)
                for k, bins_dev in pending:
                    S = min(max(_next_pow2(int(small_count) + 16),
                                _MIN_BUCKET), self._pads[k])
                    leaf_segs[k], hist_small = _shard_step_fn(
                        bins_dev, leaf_segs[k], gh_segs[k], hist_small,
                        part_rec, new_leaf, self.meta, S)
            # prestart the NEXT sweep before this split's read-back —
            # the worker overlaps staging with the finish dispatch +
            # sync below (one speculative staging is wasted per tree
            # that stops early; every other split saves a stall)
            pending = (self.prefetcher.sweep()
                       if next_leaf + 1 < self.L else None)
            with obs.scope("tree::split_scan"):
                state, next_rec = self._finish_fn(
                    state, rec, new_leaf, hist_small, feature_mask,
                    rand_seed, self._qscale, self.meta, self.params)
                # jaxlint: disable=JLT001 -- THE per-split host sync:
                # the applied split's record plus the next argmax
                # choice read back together
                next_rec_h = jax.device_get(next_rec)
            apply_split_record(tree, self.dataset, rec_h)
            next_leaf += 1
            rec, rec_h = next_rec, next_rec_h
        # a prestarted-but-unconsumed sweep (early stop) is a fresh full
        # sweep — exactly the next iteration's root sweep; stash it
        self._next_sweep = pending
        return leaf_segs

    # ------------------------------------------------------------------
    def _grow_kbatch(self, tree, gh, gh_segs, leaf_segs, feature_mask,
                     rand_seed):
        """K-splits-per-sweep growth (module docstring above the
        k-batch device functions): each round speculates the top-K
        pending candidates, serves all K from ONE staging pass, and
        the validated finish accepts the leaf-wise-order-preserving
        prefix. One host sync per ROUND instead of per split."""
        K = self._K
        with obs.scope("tree::root_histogram"):
            state, spec, spec_h, pending = self._root_round(
                gh, gh_segs, feature_mask, rand_seed)

        next_leaf = 1
        rev_from = np.full(K, -1, dtype=np.int32)
        rev_to = np.zeros(K, dtype=np.int32)
        while next_leaf < self.L:
            slots = [_slot(spec_h, i) for i in range(K)]
            n_slots = min(K, self.L - next_leaf)
            # speculation validity is a prefix: slots come gain-sorted
            n_valid = 0
            while n_valid < n_slots and record_is_valid(slots[n_valid]):
                n_valid += 1
            if n_valid == 0:
                break
            small_max = max(
                min(float(slots[i].left_total_count),
                    float(slots[i].right_total_count))
                for i in range(n_valid))
            # explicit device staging of the round's control vectors
            # (transfer-guard discipline: one deliberate device_put
            # per round, never an implicit transfer)
            sv_dev = jax.device_put(
                np.arange(K, dtype=np.int32) < n_valid)
            rf_dev = jax.device_put(rev_from)
            rt_dev = jax.device_put(rev_to)
            nlb = dev_i32(next_leaf)
            if pending is None:
                # the previous round's rejections forced an extra
                # round the prestart heuristic did not cover
                pending = self.prefetcher.sweep()
            with obs.scope("tree::shard_sweep"):
                hists = self._zero_khist()
                part_spec = _partition_rec(spec, self._has_cat)
                for k, bins_dev in pending:
                    S = min(max(_next_pow2(int(small_max) + 16),
                                _MIN_BUCKET), self._pads[k])
                    leaf_segs[k], hists = _shard_kstep_fn(
                        bins_dev, leaf_segs[k], gh_segs[k], hists,
                        part_spec, nlb, sv_dev, rf_dev, rt_dev,
                        self.meta, K, S)
            # prestart the next round's staging only when even a fully
            # accepted round leaves splits to grow (a rejected tail
            # instead pays one stall at the loop top)
            pending = (self.prefetcher.sweep()
                       if next_leaf + n_valid < self.L else None)
            with obs.scope("tree::split_scan"):
                state, accepted = self._kfinish_fn(
                    state, spec, hists, nlb, sv_dev, feature_mask,
                    rand_seed, self._qscale, self.meta, self.params)
                spec = self._spec_fn(state)
                # jaxlint: disable=JLT001 -- THE per-round host sync:
                # the accepted mask plus the next round's speculation
                # read back in one hop (the K-batch analogue of the
                # stepped path's per-split read-back)
                accepted_h, spec_h = jax.device_get((accepted, spec))
            n_acc = 0
            while n_acc < K and bool(accepted_h[n_acc]):
                n_acc += 1
            for i in range(n_acc):
                apply_split_record(tree, self.dataset, slots[i])
            rev_from = np.full(K, -1, dtype=np.int32)
            rev_to = np.zeros(K, dtype=np.int32)
            for i in range(n_acc, n_valid):
                rev_from[i] = next_leaf + i
                rev_to[i] = int(slots[i].leaf)
            next_leaf += n_acc
            if n_acc == 0:
                break  # defensive: slot 0 is argmax-pinned

        if (rev_from >= 0).any():
            # the loop ended with rejected routings still applied:
            # revert them before the partition feeds the score update
            # (no further sweep folds the revert in)
            rf_dev = jax.device_put(rev_from)
            rt_dev = jax.device_put(rev_to)
            rev = _revert_fn_cached(K)
            for k in range(len(leaf_segs)):
                leaf_segs[k] = rev(leaf_segs[k], rf_dev, rt_dev)
        # stash a prestarted-but-unconsumed sweep for the next
        # iteration's root (same as the stepped path)
        self._next_sweep = pending
        return leaf_segs


_accum_hist_fn = obs_compile.instrument_jit("sharded.accum_hist",
                                            _accum_hist)
