"""Device-side tree traversal over binned rows.

TPU-native replacement for the host Python node-walk the round-2 review
flagged (models/tree.py predict_by_bin): validation scoring runs per tree
per valid set per iteration, so it must be a device op, not a host loop.

Reference analogue: the CUDA build keeps valid scores on device and walks
trees with a kernel (src/boosting/cuda/cuda_score_updater.*,
src/io/cuda/cuda_tree.cu AddPredictionToScoreKernel). Here one tree is
scored in one of two forms, chosen by what ``build_device_tree`` sees:

- **all nodes at once** (numerical splits over unbundled bins of at most
  256 values, up to ``ALL_NODES_MAX_LEAVES`` leaves): every row's bin in
  every node's column by one MXU product with a one-hot of the nodes'
  features, every node's decision by the walk's formula, and the leaf by
  one product with the tree's leaf-path matrix (the "GEMM" strategy of
  tree compilers such as Hummingbird). Both products are exact: 0/1 and
  +-1 operands in bf16, integer sums of at most 255 terms in float32;
- **a lockstep walk** (categorical nodes, EFB bundles, wider bins, larger
  trees): every row advances one level per iteration of a
  ``lax.fori_loop`` whose trip count is the tree depth (padded to a power
  of two so compiled variants are shared across trees of similar depth);
  nodes are flat arrays (gathers), leaves encoded as ``~leaf`` negatives
  exactly like the host Tree / reference tree.h:25.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.binning import MissingType
from ..models.tree import Tree, kCategoricalMask, kDefaultLeftMask
from ..obs import compile as obs_compile
from ..utils import next_pow2 as _next_pow2


# Trees of at most this many leaves are scored all nodes at once; larger
# ones walk. The leaf-path product costs NI x NL a row, so it grows with
# the square of the leaves where the walk grows with the depth.
ALL_NODES_MAX_LEAVES = 4096
# Rows the all-nodes form takes at a time: its [rows, NI] and [rows, NL]
# temporaries, where the compiler keeps them, stay under 64 MB each
# whatever the row count. On a TPU v5e the form takes 1.08 ms for 183,747
# x 968 bins and a tree of 255 leaves in blocks of 65,536 rows (1.09-1.13
# in blocks of 8,192-32,768; 0.79 unblocked), where the 16-hop walk took
# 277 ms; 4,095 leaves 61 ms (the walk 522).
ALL_NODES_ROW_BLOCK = 65536


class DeviceTree(NamedTuple):
    """Flat node arrays of one tree, padded to a power-of-two node count
    (padding keeps the jitted traversal shared across trees). A tree
    scored all nodes at once carries ``path``/``turns`` and no child or
    categorical arrays; a walked tree the reverse."""
    feat: jnp.ndarray          # [NI] i32 inner feature index
    tbin: jnp.ndarray          # [NI] i32 threshold bin
    default_left: jnp.ndarray  # [NI] bool
    nan_bin: jnp.ndarray       # [NI] i32 (-1 when feature has no NaN bin)
    zero_bin: jnp.ndarray      # [NI] i32 (-1 unless MissingType.ZERO)
    left: Optional[jnp.ndarray]      # [NI] i32 (>=0 node, <0 ~leaf)
    right: Optional[jnp.ndarray]     # [NI] i32
    is_cat: Optional[jnp.ndarray]    # [NI] bool
    cat_mask: Optional[jnp.ndarray]  # [NI, B] bool (all-false for non-cat)
    leaf_value: jnp.ndarray    # [NL] f32
    depth: int                 # host int: max hops needed
    path: Optional[jnp.ndarray] = None   # [NI, NL] i8 leaf_path_matrix
    turns: Optional[jnp.ndarray] = None  # [NL] i32 left turns a leaf


def leaf_path_matrix(tree: Tree, NI: int, NL: int):
    """``(path, turns)`` of a tree of numerical nodes: ``path`` [NI, NL]
    int8 is +1 where leaf ``l`` lies under node ``i``'s left child, -1
    under its right child, 0 elsewhere; ``turns`` [NL] int32 the left
    turns on each leaf's path. A row whose decisions are ``D`` (1 = left)
    falls into the one leaf where ``(D @ path)[l] == turns[l]``: the sum
    reaches the left turns only where every left turn is taken and no
    right turn is. Padding leaves have a zero column and ``turns`` -1,
    which no row reaches."""
    ni, nl = tree.num_internal, tree.num_leaves
    node_parent = np.full(ni, -1, dtype=np.int64)
    node_side = np.zeros(ni, dtype=np.int8)
    leaf_parent = np.zeros(nl, dtype=np.int64)
    leaf_side = np.zeros(nl, dtype=np.int8)
    owner = np.arange(ni)
    for side, child in ((1, tree.left_child[:ni]), (-1, tree.right_child[:ni])):
        inner = child >= 0
        node_parent[child[inner]] = owner[inner]
        node_side[child[inner]] = side
        leaf_parent[~child[~inner]] = owner[~inner]
        leaf_side[~child[~inner]] = side
    path = np.zeros((NI, NL), dtype=np.int8)
    leaf, node, side = np.arange(nl), leaf_parent, leaf_side
    while leaf.size:           # one level up per pass, every leaf at once
        path[node, leaf] = side
        up = node_parent[node] >= 0
        leaf, side, node = leaf[up], node_side[node[up]], \
            node_parent[node[up]]
    turns = np.full(NL, -1, dtype=np.int32)
    turns[:nl] = (path[:, :nl] == 1).sum(axis=0)
    return path, turns


def build_device_tree(tree: Tree, bin_meta, B: int,
                      bundle=None) -> Optional[DeviceTree]:
    """Pack a host Tree into device arrays for binned traversal.
    ``bin_meta`` is the GBDT's (nan_bins, zero_bins, missing_types) per
    inner feature. Returns None for stump trees (constant output).

    A tree of numerical nodes over ``B <= 256`` bins (bin values exact in
    bf16) and at most ``ALL_NODES_MAX_LEAVES`` leaves gets its leaf-path
    matrix and is scored all nodes at once; any other tree walks.

    ``bundle`` (io/efb.py BundleLayout): when the binned rows are EFB
    bundles, every node's decision becomes a boolean LUT over its bundle
    column's bins (computed host-side by io/efb.py ``member_bin`` — the
    same mechanism as categorical masks), and ``feat`` points at the
    bundle column."""
    ni = tree.num_internal
    if ni == 0:
        return None
    if bundle is not None:
        return _build_bundled_device_tree(tree, bin_meta, B, bundle)
    nan_bins, zero_bins, missing_types = bin_meta
    NI = _next_pow2(ni)
    NL = _next_pow2(tree.num_leaves)
    feat = np.zeros(NI, dtype=np.int32)
    feat[:ni] = tree.split_feature_inner[:ni]
    tbin = np.zeros(NI, dtype=np.int32)
    tbin[:ni] = tree.threshold_in_bin[:ni]
    dt = tree.decision_type[:ni]
    dl = np.zeros(NI, dtype=bool)
    dl[:ni] = (dt & kDefaultLeftMask) != 0
    f = tree.split_feature_inner[:ni]
    nb = np.full(NI, -1, dtype=np.int32)
    zb = np.full(NI, -1, dtype=np.int32)
    nb[:ni] = np.where(missing_types[f] == MissingType.NAN, nan_bins[f], -1)
    zb[:ni] = np.where(missing_types[f] == MissingType.ZERO,
                       zero_bins[f], -1)
    is_cat = np.zeros(NI, dtype=bool)
    is_cat[:ni] = (dt & kCategoricalMask) != 0
    for node in tree.cat_bin_masks:
        if node < ni:
            is_cat[node] = True
    lv = np.zeros(NL, dtype=np.float32)
    lv[:tree.num_leaves] = tree.leaf_value[:tree.num_leaves]
    depth = int(tree.leaf_depth[:tree.num_leaves].max())
    nodes = dict(feat=jnp.asarray(feat), tbin=jnp.asarray(tbin),
                 default_left=jnp.asarray(dl), nan_bin=jnp.asarray(nb),
                 zero_bin=jnp.asarray(zb), leaf_value=jnp.asarray(lv),
                 depth=depth)
    if not is_cat.any() and B <= 256 \
            and tree.num_leaves <= ALL_NODES_MAX_LEAVES:
        path, turns = leaf_path_matrix(tree, NI, NL)
        return DeviceTree(left=None, right=None, is_cat=None, cat_mask=None,
                          path=jnp.asarray(path), turns=jnp.asarray(turns),
                          **nodes)
    left = np.zeros(NI, dtype=np.int32)
    right = np.zeros(NI, dtype=np.int32)
    left[:ni] = tree.left_child[:ni]
    right[:ni] = tree.right_child[:ni]
    cat_mask = np.zeros((NI, B), dtype=bool)
    for node, mask in tree.cat_bin_masks.items():
        if node < ni:
            m = np.asarray(mask, dtype=bool)[:B]
            cat_mask[node, :len(m)] = m
    return DeviceTree(left=jnp.asarray(left), right=jnp.asarray(right),
                      is_cat=jnp.asarray(is_cat),
                      cat_mask=jnp.asarray(cat_mask), **nodes)


def _build_bundled_device_tree(tree: Tree, bin_meta, B: int,
                               bundle) -> DeviceTree:
    """LUT-mode DeviceTree over EFB-bundled bins: per node, a bool[B]
    left/right table over the node's bundle column, all nodes at once."""
    from ..io.binning import MissingType as MT
    from ..io.efb import member_bin
    nan_bins, zero_bins, missing_types = bin_meta
    ni = tree.num_internal
    NI = _next_pow2(ni)
    NL = _next_pow2(tree.num_leaves)
    f = tree.split_feature_inner[:ni]
    feat = np.zeros(NI, dtype=np.int32)
    feat[:ni] = bundle.group_of[f]
    # every node's original bin at each bin of its bundle column: [ni, W]
    orig = member_bin(np.arange(min(B, bundle.num_bundled_bins))[None, :],
                      bundle.first_bin[f, None], bundle.num_bins[f, None],
                      zero_bins[f, None], bundle.needs_zero_fix[f, None])
    dt = tree.decision_type[:ni]
    dl = ((dt & kDefaultLeftMask) != 0)[:, None]
    gl = orig <= tree.threshold_in_bin[:ni, None]
    mt = missing_types[f, None]
    gl = np.where((mt == MT.NAN) & (orig == nan_bins[f, None]), dl, gl)
    gl = np.where((mt == MT.ZERO) & (orig == zero_bins[f, None]), dl, gl)
    for node in np.flatnonzero(dt & kCategoricalMask):
        mask = np.asarray(tree.cat_bin_masks[node], dtype=bool)
        gl[node] = mask[np.minimum(orig[node], len(mask) - 1)]
    lut = np.zeros((NI, B), dtype=bool)
    lut[:ni, :gl.shape[1]] = gl
    left = np.zeros(NI, dtype=np.int32)
    right = np.zeros(NI, dtype=np.int32)
    left[:ni] = tree.left_child[:ni]
    right[:ni] = tree.right_child[:ni]
    lv = np.zeros(NL, dtype=np.float32)
    lv[:tree.num_leaves] = tree.leaf_value[:tree.num_leaves]
    depth = int(tree.leaf_depth[:tree.num_leaves].max())
    neg1 = np.full(NI, -1, dtype=np.int32)
    return DeviceTree(
        feat=jnp.asarray(feat), tbin=jnp.asarray(neg1),
        default_left=jnp.zeros(NI, dtype=bool),
        nan_bin=jnp.asarray(neg1), zero_bin=jnp.asarray(neg1),
        left=jnp.asarray(left), right=jnp.asarray(right),
        is_cat=jnp.ones(NI, dtype=bool), cat_mask=jnp.asarray(lut),
        leaf_value=jnp.asarray(lv), depth=depth)


def _node_decisions(rows, dt: DeviceTree) -> jnp.ndarray:
    """[r, F] bins → [r, NI] bool: every node's decision (True = left) for
    every row, by the walk's formula. Each row's bin in each node's column
    comes from one product with the nodes' one-hot ``[F, NI]``: exact, as
    a bin of at most 255 is exact in bf16 and one term of each sum is not
    0."""
    onehot = jnp.arange(rows.shape[1], dtype=jnp.int32)[:, None] \
        == dt.feat[None, :]
    b = jnp.dot(rows.astype(jnp.bfloat16), onehot.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32).astype(jnp.int32)
    gl = b <= dt.tbin
    gl = jnp.where(b == dt.nan_bin, dt.default_left, gl)
    return jnp.where(b == dt.zero_bin, dt.default_left, gl)


def _leaves_of_block(rows, dt: DeviceTree) -> jnp.ndarray:
    """[r, F] bins → [r] i32 leaf ids from every node's decision and one
    product with the leaf-path matrix (``leaf_path_matrix``): 0/1 times
    +-1 in bf16, integer sums in float32, so the match is exact."""
    score = jnp.dot(_node_decisions(rows, dt).astype(jnp.bfloat16),
                    dt.path.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
    match = score == dt.turns.astype(jnp.float32)
    ids = jnp.arange(match.shape[1], dtype=jnp.int32)
    return jnp.max(jnp.where(match, ids, 0), axis=1)


def _all_nodes_leaves(bins, dt: DeviceTree) -> jnp.ndarray:
    """[n, F] bins → [n] i32 leaf ids, ``ALL_NODES_ROW_BLOCK`` rows at a
    time; the last block starts at ``n - block`` and rewrites rows the one
    before it wrote with the same ids."""
    n = bins.shape[0]
    block = min(ALL_NODES_ROW_BLOCK, n)
    if block == n:
        return _leaves_of_block(bins, dt)

    def body(i, out):
        start = jnp.minimum(i * block, n - block)
        rows = jax.lax.dynamic_slice_in_dim(bins, start, block)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _leaves_of_block(rows, dt), start, axis=0)

    return jax.lax.fori_loop(0, -(-n // block), body,
                             jnp.zeros(n, dtype=jnp.int32))


def _traverse_body(bins, dt: DeviceTree,
                   trips: Optional[int]) -> jnp.ndarray:
    """Binned traversal: [n, F] uint bins → [n] i32 leaf ids. A tree with
    a leaf-path matrix is scored all nodes at once (``trips`` None: no
    hops); any other walks in lockstep, every row running all ``trips``
    hops, whatever the depth of its leaf (``valid/walk_hops_run`` against
    ``valid/walk_hops_needed``, boosting/gbdt.py)."""
    if dt.path is not None:
        return _all_nodes_leaves(bins, dt)
    n = bins.shape[0]

    def body(_, node):
        nd = jnp.maximum(node, 0)
        f = dt.feat[nd]
        b = jnp.take_along_axis(bins, f[:, None], axis=1)[:, 0] \
            .astype(jnp.int32)
        gl = b <= dt.tbin[nd]
        gl = jnp.where(b == dt.nan_bin[nd], dt.default_left[nd], gl)
        gl = jnp.where(b == dt.zero_bin[nd], dt.default_left[nd], gl)
        gl = jnp.where(dt.is_cat[nd], dt.cat_mask[nd, b], gl)
        nxt = jnp.where(gl, dt.left[nd], dt.right[nd])
        return jnp.where(node >= 0, nxt, node)

    node = jax.lax.fori_loop(0, trips, body,
                             jnp.zeros(n, dtype=jnp.int32))
    # rows still on an internal node after `trips` hops cannot happen when
    # trips >= tree depth; ~node maps leaf encodings back to indices
    return jnp.where(node < 0, ~node, 0).astype(jnp.int32)


_traverse = obs_compile.instrument_jit(
    "predict.traverse", _traverse_body, static_argnames=("trips",))


def predict_leaf_on_device(bins_dev: jnp.ndarray, dtree: DeviceTree):
    """``(leaf, trips)``: [n] leaf index of every binned row (device)
    and the hops the walk ran for each of them, a power of two so that
    trees of many depths share few compiled programs; ``trips`` None for
    a tree scored all nodes at once, one program for every depth."""
    trips = None if dtree.path is not None else _next_pow2(dtree.depth)
    return _traverse(bins_dev, dtree, trips), trips


# ---------------------------------------------------------------------------
# Stacked-forest kernels (serving): the whole forest in one dispatch.
#
# Where the training-side DeviceTree walks ONE tree over dataset-binned
# rows, serving packs ALL T trees' flat node arrays into single [T, NI]
# arrays and vmaps the same lockstep walk over the tree axis — the
# XLA-shaped analogue of batching the forest, not the tree (the lever
# XGBoost-GPU and the reference's CUDA scorer pull; see docs/SERVING.md).
# Rows arrive as RAW float features and are quantized on device against
# the model's own threshold set (serve/forest.py builds the tables), so
# the uint gather matrix never leaves HBM between quantize and walk.
# ---------------------------------------------------------------------------

# sentinel bin ids assigned by the quantizer; they can never collide with
# a real bin (>= 0) or a node threshold index (>= -1)
kNanBin = -2    # NaN value on a MissingType.NAN feature
kZeroBin = -4   # |v| <= kZeroThreshold on a MissingType.ZERO feature


class StackedNodes(NamedTuple):
    """All T trees' node arrays, padded to common [T, NI] / [T, NL]
    shapes (serving analogue of DeviceTree; serve/forest.py packs it).

    Two encodings share this layout (serve/forest.py builds both):

    - **compare nodes**: numeric decisions are ``bin <= tbin`` integer
      compares, categorical ones LUT rows (``is_cat``/``cat_slot``);
    - **LUT nodes**: EVERY node is a boolean LUT row over its feature's
      bin space (``is_cat`` all-True, ``tbin`` all ``-1``) — one gather
      decides the node, which cuts the walk's inner-loop op count on
      wide sparse / EFB-bundled models (the "LUT node" encoding from
      the sparse-oblique-forest direction; docs/SERVING.md)."""
    feat: jnp.ndarray          # [T, NI] i32 COMPACT (used-feature) index
    tbin: jnp.ndarray          # [T, NI] i32 threshold rank (-1: none left)
    default_left: jnp.ndarray  # [T, NI] bool
    left: jnp.ndarray          # [T, NI] i32 (>=0 node, <0 ~leaf)
    right: jnp.ndarray         # [T, NI] i32
    is_cat: jnp.ndarray        # [T, NI] bool
    cat_slot: jnp.ndarray      # [T, NI] i32 row of the shared LUT
    leaf_value: jnp.ndarray    # [T, NL] f32


class QuantizerTables(NamedTuple):
    """Per-USED-feature raw-value→bin tables derived from the model's
    own split thresholds (serve/forest.py builds them; exact in f32).
    ``used`` maps the compacted table rows back to raw row columns, so
    the bins matrix the walk gathers from is [n, U] with U = #features
    the forest actually splits on — the gather-width cut for wide
    sparse models."""
    used: jnp.ndarray          # [U] i32 raw column of each table row
    thresholds: jnp.ndarray    # [U, M] f32 round-down thresholds, +inf pad
    is_cat: jnp.ndarray        # [U] bool
    nan_feat: jnp.ndarray      # [U] bool (MissingType.NAN features)
    zero_feat: jnp.ndarray     # [U] bool (MissingType.ZERO features)
    vmax: jnp.ndarray          # [] i32 max categorical value in the LUT
    zero_eps: jnp.ndarray      # [] f32 round-down f32 of kZeroThreshold


class QuantizerTablesDD(NamedTuple):
    """Double-double quantizer tables for f64 request rows: each f64
    threshold t is the exact pair (round-down-f32(t), integer residual
    rank) — see ``serve/forest.py encode_dd`` for the row-side encoding
    and the exactness argument."""
    used: jnp.ndarray          # [U] i32 raw column of each table row
    thr_hi: jnp.ndarray        # [U, M64] f32 round-down f32(t), +inf pad
    thr_lo: jnp.ndarray        # [U, M64] i32 exact residual rank, 0 pad
    is_cat: jnp.ndarray        # [U] bool
    nan_feat: jnp.ndarray      # [U] bool
    zero_feat: jnp.ndarray     # [U] bool
    vmax: jnp.ndarray          # [] i32


class LinearLeaves(NamedTuple):
    """Linear-leaf (``linear_tree``) models packed into stacked arrays:
    per leaf a constant + up-to-C coefficients over RAW feature columns
    (the leaf's root-path features). ``valid`` masks the padding lanes
    so a NaN in an unused pad column can never poison the NaN-fallback
    check (host semantics: any NaN among the leaf's fitted features →
    constant ``leaf_value`` fallback, models/linear.py)."""
    const: jnp.ndarray         # [T, NL] f32
    coeff: jnp.ndarray         # [T, NL, C] f32 (0 pad)
    feat: jnp.ndarray          # [T, NL, C] i32 RAW feature column (0 pad)
    valid: jnp.ndarray         # [T, NL, C] bool
    has: jnp.ndarray           # [T, NL] bool (a linear fit exists)


def _quantize_rows_impl(X: jnp.ndarray, qt: QuantizerTables) -> jnp.ndarray:
    """[n, F] raw f32 rows → [n, U] i32 model-space bins over the used
    feature columns.

    Numeric bin = #{thresholds on f < v} — so ``bin <= rank(t)`` decides
    exactly like the host's ``v <= t`` (thresholds are stored as the
    largest f32 <= t, which preserves every comparison against
    f32-representable values). NaN/zero missing semantics are resolved
    here once per row, into sentinel bins the walk maps to default_left.
    """
    X = jnp.take(X, qt.used, axis=1)
    isnan = jnp.isnan(X)
    # NaN behaves as 0.0 except on MissingType.NAN features (tree.py
    # _decide: v = where(isnan & missing != NAN, 0, fval))
    Xn = jnp.where(isnan & ~qt.nan_feat[None, :], jnp.float32(0.0), X)
    b = jax.vmap(lambda t, col: jnp.searchsorted(t, col, side="left"),
                 in_axes=(0, 1), out_axes=1)(qt.thresholds, Xn)
    b = b.astype(jnp.int32)
    b = jnp.where(qt.nan_feat[None, :] & isnan, jnp.int32(kNanBin), b)
    b = jnp.where(qt.zero_feat[None, :] & (jnp.abs(Xn) <= qt.zero_eps),
                  jnp.int32(kZeroBin), b)
    # categorical: the "bin" is the category value itself, clamped into
    # the shared LUT's row (out-of-range / negative / NaN → vmax+1, an
    # always-False column == the host's FindInBitset miss → go right)
    vmax = qt.vmax.astype(jnp.float32)
    iv = jnp.clip(jnp.where(isnan, jnp.float32(-1.0), X),
                  -1.0, vmax + 1.0).astype(jnp.int32)
    cb = jnp.where((iv >= 0) & (iv <= qt.vmax), iv, qt.vmax + 1)
    return jnp.where(qt.is_cat[None, :], cb, b)


def _quantize_rows_dd_impl(Xhi: jnp.ndarray, Xlo: jnp.ndarray,
                           qt: QuantizerTablesDD) -> jnp.ndarray:
    """[n, F] double-double rows → [n, U] i32 bins in the model's f64
    threshold grid. The host encoder (serve/forest.py ``encode_dd``)
    already resolved NaN-as-zero and zero-as-missing semantics, so here
    a bin is a lexicographic pair count:

        bin = #{j : (thr_hi_j, thr_lo_j) < (hi, lo)}

    which is EXACTLY #{t_j < v} because the pair encoding is monotone
    and exact for every f64 whose f32 round-down is a normal float.
    The encoder preserves NaN in ``hi`` everywhere (so linear-leaf
    NaN-fallback masks still see it); NaN-as-zero on non-NaN-missing
    numeric features substitutes the exact (0, 0) pair here."""
    Xhi = jnp.take(Xhi, qt.used, axis=1)
    Xlo = jnp.take(Xlo, qt.used, axis=1)
    isnan = jnp.isnan(Xhi)
    as_zero = isnan & ~qt.nan_feat[None, :]
    hi = jnp.where(as_zero, jnp.float32(0.0), Xhi)[:, :, None]
    lo = jnp.where(as_zero, jnp.int32(0), Xlo)[:, :, None]
    thi = qt.thr_hi[None, :, :]
    tlo = qt.thr_lo[None, :, :]
    less = (thi < hi) | ((thi == hi) & (tlo < lo))
    b = jnp.sum(less, axis=2).astype(jnp.int32)
    b = jnp.where(qt.nan_feat[None, :] & isnan, jnp.int32(kNanBin), b)
    # zero-as-missing rides the encoder's lo == -1 sentinel (the f64
    # |v| <= kZeroThreshold test is exact on host, not re-derivable
    # from the pair)
    b = jnp.where(qt.zero_feat[None, :] & (Xlo == -1),
                  jnp.int32(kZeroBin), b)
    vmax = qt.vmax.astype(jnp.float32)
    iv = jnp.clip(jnp.where(isnan, jnp.float32(-1.0), Xhi),
                  -1.0, vmax + 1.0).astype(jnp.int32)
    cb = jnp.where((iv >= 0) & (iv <= qt.vmax), iv, qt.vmax + 1)
    return jnp.where(qt.is_cat[None, :], cb, b)


def _walk_stacked(bins: jnp.ndarray, nodes: StackedNodes,
                  cat_lut: jnp.ndarray, trips: int) -> jnp.ndarray:
    """[n, U] bins → [T, n] leaf ids: the DeviceTree lockstep walk,
    vmapped over the stacked tree axis. The LUT always reserves its two
    last columns for the NaN/zero sentinel bins, so LUT-encoded nodes
    resolve default_left with the same single gather that decides the
    split (compare-encoded categorical nodes never receive sentinels —
    the pad columns are dead for them)."""
    n = bins.shape[0]
    lut_w = cat_lut.shape[1]

    def walk_one(feat, tbin, dl, left, right, is_cat, cat_slot):
        def body(_, node):
            nd = jnp.maximum(node, 0)
            f = feat[nd]
            b = jnp.take_along_axis(bins, f[:, None], axis=1)[:, 0]
            gl = b <= tbin[nd]
            gl = jnp.where(b == kNanBin, dl[nd], gl)
            gl = jnp.where(b == kZeroBin, dl[nd], gl)
            bi = jnp.where(b == kNanBin, lut_w - 2,
                           jnp.where(b == kZeroBin, lut_w - 1,
                                     jnp.maximum(b, 0)))
            lu = cat_lut[cat_slot[nd], bi]
            gl = jnp.where(is_cat[nd], lu, gl)
            nxt = jnp.where(gl, left[nd], right[nd])
            return jnp.where(node >= 0, nxt, node)

        node = jax.lax.fori_loop(0, trips, body,
                                 jnp.zeros(n, dtype=jnp.int32))
        return jnp.where(node < 0, ~node, 0).astype(jnp.int32)

    return jax.vmap(walk_one)(nodes.feat, nodes.tbin, nodes.default_left,
                              nodes.left, nodes.right, nodes.is_cat,
                              nodes.cat_slot)


def leaf_path_values(X, leaf, feat):
    """[n, C]: each row's raw values of the C columns ``feat[leaf]`` of
    its leaf (``feat`` [NL, C]). Each value is picked from its row by a
    compare and select over the row's F columns and a sum in which every
    other term is 0, exact (a NaN stays NaN): on a TPU v5e 14.6 ms for
    [1M, 968] -> [1M, 16], where the element gather of
    ``take_along_axis`` takes 251 ms."""
    cols = jnp.arange(X.shape[1], dtype=jnp.int32)
    return jnp.sum(jnp.where(cols[None, None, :] == feat[leaf][:, :, None],
                             X[:, None, :], jnp.zeros((), X.dtype)),
                   axis=-1)


def linear_leaf_output(xv, leaf, val, const, coeff, valid, has):
    """[n]: each row's linear value ``const + coeff . xv`` over its
    leaf's ``valid`` columns, or ``val`` (the leaf's constant value)
    where its leaf has no fit or one of those values is NaN. ``xv`` is
    ``leaf_path_values`` of the rows; ``const``/``has`` [NL],
    ``coeff``/``valid`` [NL, C]."""
    v = valid[leaf]
    bad = jnp.any(jnp.isnan(xv) & v, axis=1)
    s = const[leaf] + jnp.sum(
        jnp.where(v, coeff[leaf] * xv, jnp.float32(0.0)), axis=1)
    return jnp.where(has[leaf] & ~bad, s, val)


def _linear_leaf_values(X, leaves, vals, lin: LinearLeaves):
    """Override stacked leaf values with each leaf's linear model where
    one exists and none of its fitted features is NaN (f32 device math —
    the throughput path; the bit-exact host path accumulates linear
    values in f64 from the same device leaf ids). Training computes the
    same values for its own rows (``ops/linear.py``)."""
    def lin_one(leaf_t, val_t, const_t, coeff_t, feat_t, valid_t, has_t):
        xv = leaf_path_values(X, leaf_t, feat_t)             # [n, C]
        return linear_leaf_output(xv, leaf_t, val_t, const_t, coeff_t,
                                  valid_t, has_t)

    return jax.vmap(lin_one)(leaves, vals, lin.const, lin.coeff,
                             lin.feat, lin.valid, lin.has)


def _raw_from_leaves(X, leaves, nodes, K, lin):
    vals = jnp.take_along_axis(nodes.leaf_value, leaves, axis=1)  # [T, n]
    if lin is not None:
        vals = _linear_leaf_values(X, leaves, vals, lin)
    # models are iteration-major: tree i contributes to class i % K.
    # Per-class Kahan-compensated f32 sum over the iteration axis: the
    # compensation term recovers the low-order bits a plain f32 sum
    # drops, tightening deep forests from ~1e-5 rel error at 500 trees
    # to ~1 ulp of the correctly rounded result (ROADMAP open item).
    # XLA preserves FP semantics (no reassociation), so (t - s) - y is
    # not folded away.
    per_iter = vals.reshape(-1, K, vals.shape[1])                 # [I, K, n]

    def kahan_step(carry, v):
        s, c = carry
        y = v - c
        t = s + y
        c = (t - s) - y
        return (t, c), None

    zero = jnp.zeros(per_iter.shape[1:], dtype=vals.dtype)
    (total, _), _ = jax.lax.scan(kahan_step, (zero, zero), per_iter)
    return total.T                                                # [n, K]


def _stacked_leaves_body(X, qt, nodes, cat_lut, trips):
    return _walk_stacked(_quantize_rows_impl(X, qt), nodes, cat_lut, trips)


def _stacked_raw_body(X, qt, nodes, cat_lut, trips, K, lin=None):
    leaves = _stacked_leaves_body(X, qt, nodes, cat_lut, trips)
    return _raw_from_leaves(X, leaves, nodes, K, lin)


def _stacked_leaves_dd_body(Xhi, Xlo, qt, nodes, cat_lut, trips):
    return _walk_stacked(_quantize_rows_dd_impl(Xhi, Xlo, qt), nodes,
                         cat_lut, trips)


def _stacked_raw_dd_body(Xhi, Xlo, qt, nodes, cat_lut, trips, K,
                         lin=None):
    leaves = _stacked_leaves_dd_body(Xhi, Xlo, qt, nodes, cat_lut, trips)
    return _raw_from_leaves(Xhi, leaves, nodes, K, lin)


def _make_stacked_jits():
    """Jitted quantize+walk entry points, trace-tracked through
    obs/compile.py (one compile per (row-bucket, forest-shape); the
    serve cache pads rows so a second dispatch at the same bucket hits
    the jit cache with zero retraces — and replicas placing the SAME
    forest shapes on N devices share these traces too, so a fleet
    traces once per shape bucket, not once per device)."""
    leaves = obs_compile.instrument_jit(
        "serve.stacked_leaves", _stacked_leaves_body,
        static_argnames=("trips",))
    raw = obs_compile.instrument_jit(
        "serve.stacked_raw", _stacked_raw_body,
        static_argnames=("trips", "K"))
    leaves_dd = obs_compile.instrument_jit(
        "serve.stacked_leaves_dd", _stacked_leaves_dd_body,
        static_argnames=("trips",))
    raw_dd = obs_compile.instrument_jit(
        "serve.stacked_raw_dd", _stacked_raw_dd_body,
        static_argnames=("trips", "K"))
    return leaves, raw, leaves_dd, raw_dd


(stacked_forest_leaves, stacked_forest_raw,
 stacked_forest_leaves_dd, stacked_forest_raw_dd) = _make_stacked_jits()


def _gather_leaf_values_body(leaf_value, leaf):
    return leaf_value[leaf]


_gather_leaf_values = obs_compile.instrument_jit(
    "predict.gather_leaf", _gather_leaf_values_body)


def tree_output_on_device(bins_dev: jnp.ndarray, dtree: DeviceTree):
    """``(output, trips)``: [n] f32 per-row output of one tree over
    binned rows (device) and the hops its walk ran."""
    leaf, trips = predict_leaf_on_device(bins_dev, dtree)
    return _gather_leaf_values(dtree.leaf_value, leaf), trips
