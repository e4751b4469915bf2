"""Histogram construction — the hot op of GBDT training.

TPU-native replacement for the reference's histogram kernels
(CPU: src/io/dense_bin.hpp:99 ``ConstructHistogramInner``; GPU:
src/treelearner/ocl/histogram256.cl; CUDA:
src/treelearner/cuda/cuda_histogram_constructor.cu:18). Those are
scatter-add loops — per row, `hist[bin] += (grad, hess)` — which TPUs
execute poorly (XLA serializes scatters). Instead we reformulate the
accumulation as a one-hot contraction that runs on the MXU:

    onehot[t, f, b] = (bins[t, f] == b)           # exact in any dtype
    hist[f, b, c]   = sum_t onehot[t, f, b] * gh[t, c]

i.e. for each feature a [B, T] @ [T, C] matmul. A `lax.scan` over row
tiles bounds the materialized one-hot to a few MB so XLA keeps it in
VMEM; accumulation is f32. The one-hot factor is exact in bf16, so only
``gh`` needs more than one bf16 MXU pass: the einsum asks for
``precision=HIGHEST`` (six bf16 passes, both operands split in three),
the Pallas kernel splits ``gh`` itself into three bf16 pieces whose f32
sum is ``gh`` exactly (``_bf16_pieces``) and runs one bf16 pass a piece.
Either way the error is only the f32 accumulation order, same class as
the reference's GPU path (single-precision hists, gpu_use_dp=0,
docs/GPU-Performance.rst precedent).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows per one-hot tile. VMEM footprint of the one-hot is
# ROW_TILE * F * B * 4 bytes per scan step; XLA additionally tiles the
# contraction, so this just bounds the scan carry granularity.
DEFAULT_ROW_TILE = 512

# Rows per Pallas grid step for f32 gh rows.
PALLAS_ROW_TILE = 2048

# int8 gh rows: 1-byte blocks and one-hot factors let more rows ride
# each grid step. Read off the v5e at F = 968 (PR 38; ns a row a
# feature, a tile at a time as the tile loop calls it / a whole pass of
# 1M rows): 0.107 / 0.103 at 2,048 rows, 0.083 / 0.080 at 4,096, 0.072 /
# 0.070 at 8,192, where the int8 einsum takes 0.585 and the float32
# kernel 0.310. The tile loop visits whole tiles and most smaller
# children hold less than one, so the rate is not the whole cost:
# bosch-train-quant reads 1.151 s an iteration at 4,096 and 1.183 at
# 8,192.
PALLAS_ROW_TILE_INT = 2 * PALLAS_ROW_TILE

# The scoped-VMEM limit pallas_call hands the compiler
# (CompilerParams.vmem_limit_bytes) and _pallas_fits budgets against.
# 32 MiB is above Mosaic's 16 MiB default scope and a quarter of a v5e
# core's 128 MiB.
PALLAS_VMEM_LIMIT = 32 * 1024 * 1024

# Where the kernel's grid splits the features into blocks, a block's
# size is a multiple of this (the sublane tile of 4-byte rows).
FEATURE_BLOCK_ALIGN = 8


def resolve_hist_impl(backend: str = "auto",
                      f64: bool = False,
                      quant_bits: int = 0) -> tuple:
    """Validate Config.hist_backend / Config.tpu_use_f64_hist /
    Config.use_quantized_grad into a static (backend, f64, quant_bits)
    triple the learners thread through their compiled-step cache keys
    (f64 is the analogue of the reference's gpu_use_dp,
    docs/GPU-Performance.rst; quant_bits > 0 selects the integer
    accumulation paths of ops/quantize.py). f64 accumulation requires
    jax_enable_x64 and disables the Pallas kernel (f32-only); it is
    moot under quantization (integer accumulation is already exact), so
    the two together resolve to the quantized mode."""
    backend = (backend or "auto").lower()
    if backend not in ("auto", "onehot", "pallas", "scatter"):
        from ..utils import log
        log.warning("unknown hist_backend=%s; using auto" % backend)
        backend = "auto"
    quant_bits = int(quant_bits or 0)
    if quant_bits not in (0, 8, 16):
        from ..utils import log
        log.warning("quant_grad_bits must be 8 or 16; got %d — using 8"
                    % quant_bits)
        quant_bits = 8
    if f64 and quant_bits:
        _warn_once("tpu_use_f64_hist is ignored under use_quantized_grad "
                   "(integer histogram accumulation is already exact)")
        f64 = False
    if f64 and not jax.config.jax_enable_x64:
        from ..utils import log
        log.warning("tpu_use_f64_hist needs jax_enable_x64; histograms "
                    "stay f32")
        f64 = False
    return backend, bool(f64), quant_bits


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _sublanes(itemsize: int) -> int:
    """Rows of one (sublane, 128-lane) tile: 8 of f32, 32 of int8."""
    return 8 * (4 // itemsize)


def _hi_rows(num_bins: int, itemsize: int) -> int:
    """Rows of the hi-nibble one-hot (``bin >> 4``), padded to the
    sublane tile of the gh dtype so the matmul's M dimension is
    tile-aligned; the pad rows match no bin."""
    return _ceil_to(-(-num_bins // 16), _sublanes(itemsize))


def _pallas_row_tile(gh_dtype) -> int:
    return (PALLAS_ROW_TILE_INT
            if jnp.issubdtype(jnp.dtype(gh_dtype), jnp.integer)
            else PALLAS_ROW_TILE)


def _pallas_vmem_bytes(FB: int, num_bins: int, C: int, T: int,
                       gh_itemsize: int, bins_itemsize: int = 1,
                       blocked: bool = False) -> int:
    """Upper bound on the VMEM the compiled kernel holds at once for a
    block of ``FB`` features, counted the way Mosaic lays arrays out: a
    [rows, T] array pads its rows to the dtype's sublane tile, a minor
    dimension pads to 128 lanes, and every BlockSpec'd operand is
    double-buffered: the accumulator's output block always, and where
    the features are split into several blocks (``blocked``) the
    accumulator's input block too. Transients are counted as if each
    were materialized whole (an AOT bisection of ``vmem_limit_bytes``
    at the Higgs shape, F = 28, found 5.4 MiB for float32 rows and 5.4
    MiB for int8 against this bound's 5.8 / 6.1 MiB)."""
    H = _hi_rows(num_bins, gh_itemsize)
    # float32 rows reach the MXU as three bf16 pieces, int8 rows as one
    pieces, mxu_itemsize = (3, 2) if gh_itemsize == 4 else (1, gh_itemsize)

    def rows_by_T(rows: int, itemsize: int) -> int:
        return _ceil_to(rows, _sublanes(itemsize)) * T * itemsize

    blocks = 2 * (rows_by_T(FB, bins_itemsize) + rows_by_T(C, gh_itemsize))
    acc = (4 if blocked else 2) * FB * H * _ceil_to(16 * C, 128) * 4
    scratch = rows_by_T(FB, 4)                   # int32 copy of the bins
    # once a grid step, where there are three: the bf16 pieces of gh and
    # their float32 forms
    split = (pieces * (rows_by_T(C, 2) + rows_by_T(C, 4))
             if pieces > 1 else 0)
    # per feature: the bin row and its nibbles, both int32 iotas and
    # compare masks, the hi one-hot; per piece W's C selected pieces and
    # their concatenation at 4 bytes, and the matmul's W operand
    trans = (3 * rows_by_T(1, 4)
             + 2 * (rows_by_T(H, 4) + rows_by_T(16, 4))
             + rows_by_T(H, mxu_itemsize)
             + pieces * (2 * rows_by_T(16 * C, 4)
                         + rows_by_T(16 * C, mxu_itemsize)))
    return blocks + acc + scratch + split + trans


def _pallas_feature_block(F: int, num_bins: int, C: int, T: int,
                          gh_itemsize: int, bins_itemsize: int = 1) -> int:
    """Features one block of the kernel's grid holds, a pure function
    of the static shape and dtypes: all ``F`` where the VMEM bound
    admits them (one block: the accumulator stays in HBM and is copied
    in once), else the size of the fewest balanced blocks, each a
    multiple of ``FEATURE_BLOCK_ALIGN`` (Mosaic refuses a block that is
    neither that nor all of F), whose bound, the blocked accumulator
    operand counted, stays under the limit; 0 where not even the
    smallest block does (a huge ``num_bins`` or ``C``)."""
    def fits(fb: int, blocked: bool) -> bool:
        return _pallas_vmem_bytes(fb, num_bins, C, T, gh_itemsize,
                                  bins_itemsize, blocked) \
            <= PALLAS_VMEM_LIMIT

    if fits(F, False):
        return F
    for n_blocks in range(2, -(-F // FEATURE_BLOCK_ALIGN) + 1):
        fb = _ceil_to(-(-F // n_blocks), FEATURE_BLOCK_ALIGN)
        if fits(fb, True):
            return fb
    return 0


def _pallas_fits(F: int, num_bins: int, C: int,
                 T: int = PALLAS_ROW_TILE, itemsize: int = 4,
                 bins_itemsize: int = 1) -> bool:
    """Static gate: some block of features keeps the kernel's VMEM
    bound under the limit pallas_call passes the compiler."""
    return _pallas_feature_block(F, num_bins, C, T, itemsize,
                                 bins_itemsize) > 0


def _warn_once(msg: str, component: str = "ops.histogram") -> None:
    """One warning per distinct message — but only count it as warned
    when the current verbosity actually emits it, so a training run at
    verbosity=-1 does not permanently swallow the downgrade notice.
    Every distinct message ALSO emits one ``perf_warning`` event
    (regardless of verbosity — the events sink is how tests assert that
    no silent backend fallback happened). ``component`` names the
    module the condition originates in for event-log consumers."""
    from ..utils import log
    if msg not in _warn_once._emitted:
        _warn_once._emitted.add(msg)
        from ..obs import events as obs_events
        obs_events.emit("perf_warning", component=component,
                        message=msg)
    if log._level < log.LogLevel.WARNING:
        return
    if msg in _warn_once._seen:
        return
    _warn_once._seen.add(msg)
    log.warning(msg)


_warn_once._seen = set()
_warn_once._emitted = set()


def _reset_warn_once() -> None:
    """Clear the one-per-message dedup on registry reset (the
    obs/compile._WARNED pattern): a new run — or a test that resets the
    registry — must get its own warning AND its own assertable
    perf_warning event, not a silence inherited from the previous
    run."""
    _warn_once._seen.clear()
    _warn_once._emitted.clear()


from ..obs import compile as obs_compile  # noqa: E402
from ..obs.registry import add_reset_hook  # noqa: E402

add_reset_hook(_reset_warn_once)


def _acc_dtype_of(gh_dtype):
    """Accumulator dtype per gh row dtype: integer rows accumulate in
    int32/int64 (ops/quantize.py overflow discipline), f64 stays f64,
    anything else f32."""
    if jnp.issubdtype(jnp.dtype(gh_dtype), jnp.integer):
        from .quantize import acc_dtype
        return acc_dtype(gh_dtype)
    return jnp.float64 if gh_dtype == jnp.float64 else jnp.float32


@jax.named_scope("obs_hist_scatter")
def _segment_histogram(bins: jnp.ndarray, gh: jnp.ndarray,
                       num_bins: int, acc=None) -> jnp.ndarray:
    """Scatter-add formulation via a flat segment-sum — the direct
    analogue of the reference's CPU hot loop (dense_bin.hpp:99
    ``ConstructHistogramInner``: per row, hist[bin] += (g, h)). On CPU
    this is ~20x less work than the one-hot contraction (O(S·F·C)
    updates vs O(S·F·B·C) FLOPs); on TPU the MXU prefers the matmul
    forms, so this path is selected only for CPU backends. Integer gh
    accumulates int32/int64 — exact and order-invariant — and the int8
    value stream is 4x fewer bytes than f32 through the bandwidth-bound
    broadcast+scatter.

    ``acc`` ([F, B, C] in the accumulator's dtype) continues a histogram
    (``histogram_tiles``): the rows are scatter-added into it, each bin
    taking its additions in the order one pass over all the rows would
    give them, and it comes back uncast."""
    S, F = bins.shape
    C = gh.shape[1]
    acc_dtype = _acc_dtype_of(gh.dtype)
    flat = (jnp.arange(F, dtype=jnp.int32)[None, :] * num_bins
            + bins.astype(jnp.int32)).reshape(-1)            # [S*F]
    vals = jnp.broadcast_to(
        gh.astype(acc_dtype)[:, None, :], (S, F, C)).reshape(-1, C)
    if acc is not None:
        return acc.reshape(F * num_bins, C).at[flat].add(vals) \
            .reshape(F, num_bins, C)
    out = jax.ops.segment_sum(vals, flat, num_segments=F * num_bins)
    out = out.reshape(F, num_bins, C)
    return out if jnp.issubdtype(acc_dtype, jnp.integer) \
        else out.astype(jnp.float32)


def _tile_histogram(bins_tile: jnp.ndarray, gh_tile: jnp.ndarray,
                    num_bins: int) -> jnp.ndarray:
    """[T, F] uint bins x [T, C] stats -> [F, B, C] partial histogram.
    Accumulates in gh's dtype family (f64 under tpu_use_f64_hist, else
    f32; int32/int64 for quantized integer gh — the int8 x int8 one-hot
    contraction is the MXU's native low-precision matmul shape)."""
    acc_dtype = _acc_dtype_of(gh_tile.dtype)
    onehot = (bins_tile.astype(jnp.int32)[:, :, None]
              == jnp.arange(num_bins, dtype=jnp.int32)[None, None, :])
    if jnp.issubdtype(acc_dtype, jnp.integer):
        # exact in any precision; the one-hot factor rides the row dtype
        # so the contraction stays int8/int16 into an int32/int64 sum
        return jnp.einsum(
            "tfb,tc->fbc", onehot.astype(gh_tile.dtype), gh_tile,
            preferred_element_type=acc_dtype)
    return jnp.einsum(
        "tfb,tc->fbc", onehot.astype(acc_dtype), gh_tile,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=acc_dtype)


def _bf16_pieces(g: jnp.ndarray) -> tuple:
    """float32 ``g`` as three bfloat16 pieces, each the rounding of what
    the ones before it leave: ``f32(hi) + f32(mid) + f32(lo) == g`` bit
    for bit wherever the pieces stay in bf16's normal range (every
    gradient, hessian and indicator the objectives give), the 24-bit
    significand ``Precision.HIGHEST`` keeps. A remainder under bf16's
    smallest normal may lose bits (or be flushed to zero), so the sum
    is then within 2^-126 of ``g``."""
    hi = g.astype(jnp.bfloat16)
    rest = g - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _hist_kernel_body(FB: int, H: int, C: int, acc_in_hbm: bool,
                      bins_ref, gh_ref, acc_ref, out_ref, bins32_ref):
    """Pallas TPU kernel: one grid step accumulates a feature-major
    [FB, T] row tile of one block of features into the block's
    [FB, H, 16*C] VMEM-resident accumulator. The grid's last axis runs
    over the row tiles; where the features take several blocks an axis
    over the blocks is outside it, so a block sees all its row tiles
    before the next one starts and its accumulator stays resident
    meanwhile. The accumulator starts as ``acc_ref``, the histogram so
    far, and ``acc_ref`` and the output are one buffer
    (``input_output_aliases``). With one block (``acc_in_hbm``)
    ``acc_ref`` is left in HBM and copied in by the first step: a block
    of it would be a second accumulator in VMEM, which F = 968 in
    float32 has no room for. With several it is a blocked operand with
    the output's index map (Mosaic cannot slice an HBM ref whose minor
    dimension is 16*C = 64 lanes), which the block size leaves room
    for.

    The bin index factorizes as ``bin = hi*16 + lo``; per feature the
    contribution is ``A_f @ W_f^T`` where ``A_f[hi, t]`` is the
    hi-nibble one-hot and ``W_f[c*16+lo, t] = (lo_f[t] == lo) *
    gh[c, t]``. Rows ride the lanes of both operands, so the MXU
    contracts over lanes ([H, T] x [16*C, T]^T, the q.k^T form) with
    N = 16*C output lanes instead of the naive one-hot's N = C, and
    the one-hot factors never leave VMEM (the einsum path materializes
    S*F*B floats through HBM). Equivalent of the reference's
    shared-memory histogram kernels (cuda_histogram_constructor.cu:18,
    ocl/histogram256.cl).

    Feature ``f`` is a LEADING-axis index into refs (``bins32_ref[f]``
    row, ``out_ref[f]`` slab), so the loop stays a ``fori_loop`` whose
    code size does not grow with the block. A feature's histogram takes
    the same additions in the same order whatever the block size. The
    rows of a ragged last block beyond F hold whatever the padded bin
    block held and are never written back. Mosaic cannot take a dynamic
    sublane offset into a packed (1- or 2-byte) ref, hence the int32
    copy of the bin tile in scratch.

    The body branches on the dtype of gh. Quantized int8 rows contract
    as one int8 x int8 MXU matmul a feature into an int32 accumulator.
    Float32 rows are split once a grid step into three bf16 pieces
    (``_bf16_pieces``), and each feature contracts the exact bf16
    one-hot against each piece's W in one bf16 pass into f32: three
    passes where ``Precision.HIGHEST`` runs six and splits both
    operands again for every feature (0.18 against 0.31 ns a row a
    feature on the v5e at F = 968). ``W`` is selected in 32 bits and
    narrowed after (exact: the values are already int8 or bf16) —
    Mosaic cannot move an int32 compare mask onto int8's (32, 128) or
    bf16's (16, 128) tiling."""
    @pl.when(pl.program_id(0 if acc_in_hbm else 1) == 0)
    def _init():
        if acc_in_hbm:
            pltpu.sync_copy(acc_ref, out_ref)
        else:
            out_ref[...] = acc_ref[...]

    T = bins_ref.shape[1]
    bins32_ref[...] = bins_ref[...].astype(jnp.int32)
    g = gh_ref[...]                                      # [C, T]
    quantized = jnp.issubdtype(g.dtype, jnp.integer)
    if quantized:
        mxu_dtype, pieces = g.dtype, (g.astype(jnp.int32),)
    else:
        mxu_dtype = jnp.bfloat16
        pieces = tuple(p.astype(jnp.float32) for p in _bf16_pieces(g))
    zero = jnp.zeros((), dtype=pieces[0].dtype)
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (H, T), 0)
    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (16, T), 0)

    def body(f, carry):
        row = bins32_ref[pl.ds(f, 1), :]                 # [1, T]
        A = ((row >> 4) == iota_hi).astype(mxu_dtype)    # [H, T]
        lo_hit = (row & 15) == iota_lo                   # [16, T]
        Ws = [jnp.concatenate(
            [jnp.where(lo_hit, p[c:c + 1, :], zero)
             for c in range(C)], axis=0).astype(mxu_dtype)  # [16C, T]
            for p in pieces]

        def product(W):                                  # [H, 16C]
            return jax.lax.dot_general(
                A, W, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=out_ref.dtype)

        if quantized:
            out_ref[f] += product(Ws[0])
        else:
            hi, mid, lo = Ws
            out_ref[f] += product(hi) + (product(mid) + product(lo))
        return carry

    jax.lax.fori_loop(0, FB, body, 0)


def _pallas_accumulate(acc: jnp.ndarray, bins: jnp.ndarray,
                       gh: jnp.ndarray, row_tile: int,
                       interpret: bool = False,
                       feature_block: int = 0) -> jnp.ndarray:
    """``acc`` ([F, H, 16*C], the kernel's own layout) plus the
    histogram of [S, F] bins x [S, C] gh, S a whole number of row tiles:
    the kernel adds one tile after another into ``acc``'s buffer, so a
    histogram built over several calls takes the additions one call
    over all the rows would give it. The features go through the kernel
    in blocks of ``_pallas_feature_block`` (``feature_block`` is the
    tests', to split shapes small enough to interpret)."""
    S, F = bins.shape
    C = gh.shape[1]
    H = acc.shape[1]
    T = row_tile
    # 16 * H bins have H hi-nibble rows: all the bound reads of them
    FB = min(F, feature_block or _pallas_feature_block(
        F, 16 * H, C, T, gh.dtype.itemsize, bins.dtype.itemsize))
    one_block = FB == F
    if one_block:
        grid = (S // T,)
        bins_at = gh_at = lambda i: (0, i)
        out_at = lambda i: (0, 0, 0)
        acc_spec = pl.BlockSpec(memory_space=pl.ANY)
    else:
        grid = (pl.cdiv(F, FB), S // T)
        bins_at, gh_at = (lambda j, i: (j, i)), (lambda j, i: (0, i))
        out_at = lambda j, i: (j, 0, 0)
        acc_spec = pl.BlockSpec((FB, H, 16 * C), out_at)
    return pl.pallas_call(
        functools.partial(_hist_kernel_body, FB, H, C, one_block),
        name="hist_kernel",
        grid=grid,
        in_specs=[
            pl.BlockSpec((FB, T), bins_at),
            pl.BlockSpec((C, T), gh_at),
            acc_spec,
        ],
        out_specs=pl.BlockSpec((FB, H, 16 * C), out_at),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        scratch_shapes=[pltpu.VMEM((FB, T), jnp.int32)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=PALLAS_VMEM_LIMIT),
        interpret=interpret,
    )(bins.T, gh.T, acc)


def _kernel_zeros(F: int, num_bins: int, C: int, gh_dtype) -> jnp.ndarray:
    """An empty histogram in the kernel's layout."""
    gh_dtype = jnp.dtype(gh_dtype)
    return jnp.zeros(
        (F, _hi_rows(num_bins, gh_dtype.itemsize), 16 * C),
        dtype=(jnp.int32 if jnp.issubdtype(gh_dtype, jnp.integer)
               else jnp.float32))


def _from_kernel_layout(acc: jnp.ndarray, num_bins: int) -> jnp.ndarray:
    """[F, H, C*16] -> [F, H*16, C] -> [F, B, C]"""
    F, H, C16 = acc.shape
    hist = acc.reshape(F, H, C16 // 16, 16).transpose(0, 1, 3, 2)
    return hist.reshape(F, H * 16, C16 // 16)[:, :num_bins, :]


def _pallas_histogram_body(bins: jnp.ndarray, gh: jnp.ndarray,
                           num_bins: int, row_tile: int,
                           interpret: bool = False) -> jnp.ndarray:
    """[S, F] bins x [S, C] gh -> [F, B, C] through the Pallas kernel.
    ``interpret`` exists for the CPU parity test only: the jitted
    product entry ``_pallas_histogram`` does not expose it."""
    S, F = bins.shape
    C = gh.shape[1]
    pad = (-S) % row_tile
    if pad:
        bins = jnp.concatenate(
            [bins, jnp.zeros((pad, F), dtype=bins.dtype)])
        gh = jnp.concatenate([gh, jnp.zeros((pad, C), dtype=gh.dtype)])
    return _from_kernel_layout(
        _pallas_accumulate(_kernel_zeros(F, num_bins, C, gh.dtype), bins,
                           gh, row_tile, interpret), num_bins)


_pallas_histogram = obs_compile.instrument_jit(
    "ops.pallas_histogram", _pallas_histogram_body,
    static_argnums=(2, 3))


def _pallas_excluded(S: int, F: int, num_bins: int, C: int, gh_dtype,
                     bins_itemsize: int, pallas_ok: bool, f64: bool,
                     backend: str):
    """Why the static gate keeps this call off the Pallas kernel, or
    None when it admits it — a pure function of the caller's mesh
    (``pallas_ok``), the configured backend, dtypes and shapes. The
    int16 quantized mode's int64 accumulator has no kernel variant."""
    gh_dtype = jnp.dtype(gh_dtype)
    T = _pallas_row_tile(gh_dtype)
    if backend in ("onehot", "scatter"):
        return "hist_backend=%s" % backend
    if not pallas_ok:
        return "sharded-mesh caller"
    if f64:
        return "f64 histograms"
    if jnp.issubdtype(gh_dtype, jnp.integer) and gh_dtype != jnp.int8:
        return "int16 quantized rows (int64 accumulation)"
    if S < T:
        return "S=%d < %d row tile" % (S, T)
    if C > 8:
        return "C=%d > 8 stat columns" % C
    if not _pallas_fits(F, num_bins, C, T, gh_dtype.itemsize,
                        bins_itemsize):
        return ("VMEM bound (B=%d C=%d: no block of features fits)"
                % (num_bins, C))
    return None


def _choose_backend(S: int, F: int, num_bins: int, C: int, gh_dtype,
                    bins_itemsize: int, pallas_ok: bool,
                    hist_impl: tuple) -> tuple:
    """``(path, f64)`` for a histogram of these static shapes: the
    Pallas kernel (``"pallas"``) where this is a TPU and the static gate
    admits it, else the scatter (``"scatter"``) on the CPU or on
    request, else the one-hot einsum (``"einsum"``); ``f64`` is whether
    the latter two accumulate in float64."""
    backend, f64 = hist_impl[0], hist_impl[1]
    if jnp.issubdtype(jnp.dtype(gh_dtype), jnp.integer):
        f64 = False
    excluded = _pallas_excluded(S, F, num_bins, C, gh_dtype,
                                bins_itemsize, pallas_ok, f64, backend)
    on_tpu = jax.default_backend() == "tpu"
    if backend == "pallas" and (excluded or not on_tpu):
        # Explicit request could not be honored — say why (a silent
        # downgrade skews kernel benchmarks).
        _warn_once("hist_backend=pallas requested but unavailable here "
                   "(%s); using the einsum path"
                   % (excluded or "no TPU backend"))
    if on_tpu and excluded is None:
        # chosen by shape: a kernel that does not compile is an error
        # that reaches the user, never a silent einsum run
        return "pallas", False
    if backend == "scatter" or (backend == "auto"
                                and jax.default_backend() == "cpu"):
        return "scatter", f64
    return "einsum", f64


def build_histogram(bins: jnp.ndarray, gh: jnp.ndarray, num_bins: int,
                    row_tile: int = DEFAULT_ROW_TILE,
                    pallas_ok: bool = True,
                    hist_impl: tuple = ("auto", False)) -> jnp.ndarray:
    """Accumulate (grad, hess, count) per (feature, bin).

    Parameters
    ----------
    bins : uint8/uint16/int32 [S, F] — quantized rows (padding rows must
        carry gh == 0; their bin values are irrelevant)
    gh : f32 [S, C] — per-row stats; C is typically 3 = (grad, hess, in-bag)
    num_bins : static histogram width B
    pallas_ok : callers whose rows are SHARDED across a device mesh must
        pass False — pallas_call has no SPMD partitioning rule, so GSPMD
        would all-gather the full bins array per device; the einsum path
        partitions cleanly and lets XLA insert the psum.
    hist_impl : STATIC (backend, f64[, quant_bits]) from
        resolve_hist_impl — callers thread it through their compiled-fn
        cache keys so a setting is never baked stale into a cached
        trace.

    Returns f32 [F, B, C] — or int32/int64 [F, B, C] when ``gh`` holds
    quantized integer rows (ops/quantize.py): integer accumulation is
    exact and order-invariant, and the caller dequantizes once per
    split scan (ops/split.py).
    """
    S, F = bins.shape
    path, f64 = _choose_backend(S, F, num_bins, gh.shape[1], gh.dtype,
                                bins.dtype.itemsize, pallas_ok, hist_impl)
    if path == "pallas":
        with jax.named_scope("obs_hist_pallas"):
            return _pallas_histogram(bins, gh, num_bins,
                                     _pallas_row_tile(gh.dtype))
    if f64:
        gh = gh.astype(jnp.float64)
    if path == "scatter":
        return _segment_histogram(bins, gh, num_bins)
    return _einsum_histogram(
        bins, gh, num_bins, row_tile,
        jnp.issubdtype(jnp.dtype(gh.dtype), jnp.integer))


class HistogramTiles(NamedTuple):
    """A histogram built ``rows`` rows at a time, for a caller whose row
    count is traced (grow.py's ``_compact_child_hist``): ``zeros()`` is
    the empty accumulator, in the path's own layout and accumulation
    dtype; ``add(acc, bins [rows, F], gh [rows, C])`` continues it;
    ``result(acc)`` is what ``build_histogram`` returns for the same
    rows in the same order, addition for addition. The layout change
    and the cast are ``result``'s, once a histogram and not once a
    tile. ``kernel``: the path is the Pallas kernel's."""
    rows: int
    zeros: Callable
    add: Callable
    result: Callable
    kernel: bool = False


def histogram_tiles(bins, gh, num_bins: int, pallas_ok: bool = True,
                    hist_impl: tuple = ("auto", False)) -> HistogramTiles:
    """The tile-by-tile form of ``build_histogram(bins, gh, ...)``, on
    the path that call takes: ``bins`` and ``gh`` are the whole data (or
    their ``ShapeDtypeStruct``), read for shape and dtype alone. A tile
    is what one step of that path's own row loop takes, so its
    arithmetic is the whole pass's."""
    (S, F), C = bins.shape, gh.shape[1]
    gh_dtype = jnp.dtype(gh.dtype)
    path, f64 = _choose_backend(S, F, num_bins, C, gh_dtype,
                                jnp.dtype(bins.dtype).itemsize, pallas_ok,
                                hist_impl)
    if path == "pallas":
        T = _pallas_row_tile(gh_dtype)

        def add(acc, bins_tile, gh_tile):
            with jax.named_scope("obs_hist_pallas"):
                return _pallas_accumulate(acc, bins_tile, gh_tile, T)

        def result(acc):
            with jax.named_scope("obs_hist_pallas"):
                return _from_kernel_layout(acc, num_bins)

        return HistogramTiles(
            T, lambda: _kernel_zeros(F, num_bins, C, gh_dtype), add,
            result, kernel=True)
    quantized = jnp.issubdtype(gh_dtype, jnp.integer)
    row_dtype = jnp.dtype(jnp.float64) if f64 else gh_dtype
    acc_dtype = _acc_dtype_of(row_dtype)

    def add(acc, bins_tile, gh_tile):
        gh_tile = gh_tile.astype(row_dtype)
        if path == "scatter":
            return _segment_histogram(bins_tile, gh_tile, num_bins, acc)
        return _einsum_histogram(bins_tile, gh_tile, num_bins,
                                 DEFAULT_ROW_TILE, quantized, acc)

    # Read off the v5e (PR 36; a tile's gathers, layout copies and loop
    # step are 20-80 us, against half a tile of zero rows a histogram):
    # the int8 einsum runs 588 ns a row in tiles of two scan steps for
    # 628 in tiles of one (F = 968), the float32 einsum 1,609 for 1,548
    # (F = 2,000), and the kernel gains nothing from a second grid step.
    steps = 2 if path == "einsum" and quantized else 1
    return HistogramTiles(
        steps * DEFAULT_ROW_TILE,
        lambda: jnp.zeros((F, num_bins, C), dtype=acc_dtype), add,
        lambda acc: acc.astype(acc_dtype if quantized else jnp.float32))


@jax.named_scope("obs_hist_einsum")
def _einsum_histogram(bins: jnp.ndarray, gh: jnp.ndarray, num_bins: int,
                      row_tile: int, quantized: bool,
                      acc=None) -> jnp.ndarray:
    """One-hot contraction over ``row_tile``-row tiles, scanned.
    ``acc`` ([F, B, C] in the accumulator's dtype) continues a histogram
    (``histogram_tiles``): it is the scan's first carry, and comes back
    uncast."""
    S, F = bins.shape
    C = gh.shape[1]
    acc_dtype = _acc_dtype_of(gh.dtype)
    out_dtype = acc_dtype if quantized else jnp.float32
    if acc is None and S <= row_tile:
        return _tile_histogram(bins, gh, num_bins).astype(out_dtype)
    # Pad S to a tile multiple; padded rows use gh = 0 so they vanish.
    pad = (-S) % row_tile
    if pad:
        bins = jnp.concatenate(
            [bins, jnp.zeros((pad, F), dtype=bins.dtype)])
        gh = jnp.concatenate([gh, jnp.zeros((pad, C), dtype=gh.dtype)])
    n_tiles = bins.shape[0] // row_tile
    bins_t = bins.reshape(n_tiles, row_tile, F)
    gh_t = gh.reshape(n_tiles, row_tile, C)

    def step(acc, xs):
        b, g = xs
        return acc + _tile_histogram(b, g, num_bins).astype(acc.dtype), \
            None

    if acc is not None:
        if n_tiles == 1:
            return step(acc, (bins, gh))[0]
        return jax.lax.scan(step, acc, (bins_t, gh_t))[0]
    init = jnp.zeros((F, num_bins, C), dtype=acc_dtype)
    hist, _ = jax.lax.scan(step, init, (bins_t, gh_t))
    return hist.astype(out_dtype)


@jax.named_scope("obs_hist_subtract")
def subtract_histogram(parent: jnp.ndarray, child: jnp.ndarray) -> jnp.ndarray:
    """Sibling histogram via subtraction (reference:
    serial_tree_learner.cpp:421-424 ``larger.Subtract(smaller)``)."""
    return parent - child


def mask_gh(gh: jnp.ndarray, keep) -> jnp.ndarray:
    """Dtype-preserving row mask: zero the gh rows where ``keep`` is
    False (``keep`` is [S] per-row or a scalar). A float multiply
    would silently de-quantize integer gh rows; ``where`` against a
    same-dtype zero keeps the int8/int16 stream intact."""
    keep = jnp.asarray(keep)
    if keep.ndim == 1:
        keep = keep[:, None]
    return jnp.where(keep, gh, jnp.zeros((), dtype=gh.dtype))


def unpack_bundle_histogram(bhist: jnp.ndarray, group_of: jnp.ndarray,
                            first_bin: jnp.ndarray, num_bins: jnp.ndarray,
                            zero_fix: jnp.ndarray, zero_bins: jnp.ndarray,
                            totals, B: int) -> jnp.ndarray:
    """Bundle histogram [G, Bg, C] → per-feature histogram [F, B, C].

    EFB support (reference: the per-feature slicing of FeatureGroup
    histograms + FixHistogram zero-bin reconstruction,
    src/io/dataset.cpp). A member of a bundle (``zero_fix``) keeps its
    non-zero bins, in their order, in the bundle bins from
    ``first_bin`` on (io/efb.py ``member_bin``), so its histogram is
    its bundle's row shifted left by ``first_bin`` with its zero bin
    put back in: bins below the zero bin read the shifted row, bins
    above it the shifted row one bin later, bins from ``num_bins`` on
    nothing. Its zero bin is leaf_total − Σ(non-zero bins) — exclusivity
    means rows under other members' bins are zero rows of this feature.
    A feature alone in its column reads the row as it is. No element
    gather: each feature takes its bundle's whole row, the shift is a
    fixed ladder of rolls by powers of two, chosen per feature by the
    bits of ``first_bin``.

    num_bins : [F] — each feature's bins (0 for padding features, whose
        histograms are then zero).
    totals : [C] — the leaf's (grad, hess, count, total) sums, in the
        histogram's own dtype (f32, or int32/int64 in quantized mode —
        where the zero-bin residual reconstruction is EXACT integer
        arithmetic instead of an f32 cancellation). None: the sums over
        the first bundle's bins, every row lying in one of them — the
        rows' own sums where the histogram is of integers, which is
        where a caller has no others to give.
    """
    if totals is None:
        totals = jnp.sum(bhist[0], axis=0)
    Bg = bhist.shape[1]
    W = max(B, Bg)
    zero = jnp.zeros((), dtype=bhist.dtype)
    # [C, F, W]: the bins along the lanes, so that the rolls are lane
    # rotations and no [.., 4]-wide minor dimension is laid out
    rows = jnp.take(jnp.transpose(bhist, (2, 0, 1)), group_of, axis=1)
    if W > Bg:
        rows = jnp.pad(rows, ((0, 0), (0, 0), (0, W - Bg)))
    shift = first_bin[None, :, None]
    for k in range((W - 1).bit_length()):
        rows = jnp.where((shift >> k) & 1 == 1,
                         jnp.roll(rows, -(1 << k), axis=2), rows)
    t = jnp.arange(B, dtype=jnp.int32)[None, None, :]
    member = zero_fix[None, :, None]
    zb = zero_bins[None, :, None]
    after = jnp.roll(rows, 1, axis=2)[..., :B]
    rows = rows[..., :B]
    hist = jnp.where(member & (t > zb), after, rows)
    keep = (t < num_bins[None, :, None]) & ~(member & (t == zb))
    hist = jnp.where(keep, hist, zero)
    resid = totals.astype(bhist.dtype)[:, None] - jnp.sum(hist, axis=2)
    hist = jnp.where(member & (t == zb), resid[..., None], hist)
    return jnp.transpose(hist, (1, 2, 0))
