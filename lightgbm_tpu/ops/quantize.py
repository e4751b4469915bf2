"""Gradient discretization for quantized histogram training.

TPU-native analogue of the reference's quantized training
(``use_quantized_grad``, src/treelearner/gradient_discretizer.cpp:
per-iteration max-|grad|/max-hess scales, stochastic rounding to a few
bits, integer histogram accumulation). The motivation is bandwidth, not
FLOPs: histogram construction is bandwidth-bound (arXiv 1706.08359,
1806.11248), and an int8 (grad, hess) row vector moves 4x fewer bytes
than f32 through every histogram pass, every sharded-mesh psum, and —
on TPU — feeds the MXU's int8 matmul path in the one-hot contraction.

Scheme (per boosting iteration / per tree):

- ``g_scale = max|g| / qmax``, ``h_scale = max|h| / qmax`` over in-bag
  rows (the reference's per-iteration scale, gradient_discretizer.cpp).
  With ``num_grad_quant_bins = B`` given, the levels are LightGBM's
  own (``published_levels``): ``g_scale = max|g| / (B/2)``, gradient in
  ``[-B/2, B/2]``; ``h_scale = max|h| / B``, hessian in ``[0, B]``.
  Without it both channels take ``quant_grad_bits``' symmetric range
  (``symmetric_levels``).
- stochastic rounding ``q = floor(x / scale + u)``, ``u ~ U[0, 1)`` —
  unbiased (``E[q * scale] = x``), seeded per tree so serial and mesh
  learners draw identical integers for identical rows (the draw happens
  on the UNPADDED [N] row vector: learners pad to different row
  multiples, and a padded-shape draw would make the quantized rows
  depend on the pad — the make_rand_bins padding-invariance lesson).
- histogram accumulation in int32 (int64 under ``jax_enable_x64`` for
  16-bit rows), which makes per-bin sums order-invariant and sibling
  subtraction BIT-EXACT — a correctness win over the f32 path, whose
  subtraction drifts by accumulation-order rounding.
- split gain dequantizes once per scan (ops/split.py): the integer bin
  sums convert to f32 and multiply by the scale a single time, so a
  deep leaf's tiny sums carry exactly one rounding instead of one per
  accumulated row.

Overflow discipline: a leaf's channel sum is bounded by ``qmax * rows``.
``effective_quant_max`` caps qmax so that bound stays inside the
accumulator dtype — with int32 accumulation a 16-bit request degrades
toward 8 bits as the row count grows past ~64k, and even the 8-bit
range shrinks below 127 past ~16.9M rows (a perf_warning event records
any cap); enabling ``jax_enable_x64`` lifts 16-bit accumulation to
int64 and restores the full range at any scale.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs import compile as obs_compile

# avoid a zero divisor when an iteration's gradients are identically 0
kTinyScale = 1e-30

_INT32_MAX = 2 ** 31 - 1


def quant_dtype(bits: int):
    """Row-vector dtype for a quant_grad_bits setting."""
    return jnp.int8 if bits <= 8 else jnp.int16


def acc_dtype(gh_dtype):
    """Histogram accumulator dtype for integer gh rows: int32, lifted
    to int64 for 16-bit rows when x64 is available (the int32 bound
    qmax*rows is handled by effective_quant_max otherwise)."""
    if jnp.dtype(gh_dtype).itemsize > 1 and jax.config.jax_enable_x64:
        return jnp.int64
    return jnp.int32


def effective_quant_max(bits: int, max_rows: int) -> int:
    """Largest per-row integer magnitude such that a sum over
    ``max_rows`` rows cannot overflow the accumulator. Full range
    (2^(bits-1) - 1) when the accumulator is int64 (16-bit rows under
    x64); under int32 accumulation the cap applies to BOTH widths —
    8-bit keeps its full 127 up to 2^31/127 ≈ 16.9M rows, beyond which
    the effective range shrinks too (a one-sided gradient channel can
    genuinely sum to qmax*rows, e.g. the root histogram of a skewed
    binary objective — silent wraparound is worse than coarser
    quantization, and quant_warn_capped records the cap)."""
    qmax = (1 << (bits - 1)) - 1
    if jnp.dtype(quant_dtype(bits)).itemsize > 1 \
            and jax.config.jax_enable_x64:
        return qmax
    cap = _INT32_MAX // max(int(max_rows), 1)
    return max(min(qmax, cap), 1)


def quant_warn_capped(bits: int, qmax: int, max_rows: int) -> None:
    """One warning + assertable event when the requested bit width was
    capped by the int32 accumulator bound (ops/histogram._warn_once
    carries the perf_warning event plumbing)."""
    full = (1 << (bits - 1)) - 1
    if qmax < full:
        from .histogram import _warn_once
        _warn_once("quant_grad_bits=%d capped to |q|<=%d for %d rows "
                   "(int32 histogram accumulation%s)"
                   % (bits, qmax, max_rows,
                      "; enable jax_enable_x64 for int64 accumulators "
                      "and the full range" if bits > 8 else ""),
                   component="ops.quantize")


class QuantLevels(NamedTuple):
    """STATIC integer ranges of one discretization: the gradient lands
    in ``[-grad, grad]``, the hessian in ``[hess_lo, hess]``; each
    channel's scale is its in-bag maximum over its upper level.
    ``stochastic`` False rounds to nearest (``u = 0.5``)."""
    grad: int
    hess: int
    hess_lo: int
    stochastic: bool = True


def symmetric_levels(qmax: int) -> QuantLevels:
    """The ``quant_grad_bits`` scheme: both channels to ``+-qmax``."""
    return QuantLevels(int(qmax), int(qmax), -int(qmax))


def published_levels(bins: int, stochastic: bool, bits: int,
                     max_rows: int) -> QuantLevels:
    """LightGBM's levels for ``num_grad_quant_bins = bins``
    (gradient_discretizer.cpp: ``max|g| / (bins / 2)``,
    ``max|h| / bins``): ``bins/2`` gradient levels a side and ``bins``
    one-sided hessian levels. Levels the row dtype or the int32
    accumulator cannot hold over ``max_rows`` rows are refused, not
    capped: a coarser grid is another model than the one asked for."""
    cap = effective_quant_max(bits, max_rows)
    if bins > cap:
        from ..utils import log
        log.fatal("num_grad_quant_bins=%d does not fit quant_grad_bits=%d "
                  "rows summed over %d rows (at most %d)"
                  % (bins, bits, max_rows, cap))
    return QuantLevels(bins // 2, bins, 0, bool(stochastic))


def _quantize_gh(grad, hess, ind, key, qmax, dtype) -> tuple:
    """Discretize per-row (grad, hess) to signed integers.

    Parameters
    ----------
    grad, hess : f32[N] (or any float dtype)
    ind : f32[N] in-bag indicator (0/1; GOSS amplification is already
        folded into grad/hess by the sample strategy)
    key : PRNG key for the stochastic rounding draw
    qmax : STATIC QuantLevels, or the target magnitude
        (effective_quant_max) of the symmetric scheme as an int
    dtype : STATIC row dtype (quant_dtype)

    Returns (gh int[N, 4] = (q_grad, q_hess, in-bag, 1),
             qscale f32[2] = (g_scale, h_scale)).
    """
    lv = qmax if isinstance(qmax, QuantLevels) else symmetric_levels(qmax)
    with jax.named_scope("obs_quantize"):
        g = grad * ind
        h = hess * ind
        gs = jnp.maximum(jnp.max(jnp.abs(g)), kTinyScale) \
            / jnp.float32(lv.grad)
        hs = jnp.maximum(jnp.max(jnp.abs(h)), kTinyScale) \
            / jnp.float32(lv.hess)
        if lv.stochastic:
            u = jax.random.uniform(key, (g.shape[0], 2))
            ug, uh = u[:, 0], u[:, 1]
        else:
            ug = uh = jnp.float32(0.5)
        qg = jnp.clip(jnp.floor(g / gs + ug), -jnp.float32(lv.grad),
                      jnp.float32(lv.grad))
        qh = jnp.clip(jnp.floor(h / hs + uh), jnp.float32(lv.hess_lo),
                      jnp.float32(lv.hess))
        gh = jnp.stack([qg, qh, ind,
                        jnp.ones_like(ind)], axis=1).astype(dtype)
        return gh, jnp.stack([gs, hs]).astype(jnp.float32)


quantize_gh = obs_compile.instrument_jit(
    "ops.quantize_gh", _quantize_gh, static_argnums=(4, 5))


def _tree_key(base_key, ctr):
    """Advance the device-side tree counter and derive the tree's
    stochastic-rounding key: ``fold_in(base, ctr + 1)``. The counter
    sequence (1, 2, ...) reproduces the host tree numbering the key
    derivation used before, bit-exactly — but the counter lives on
    device, so the steady-state training loop performs ZERO per-tree
    seed transfers (each new tree number used to be a fresh
    ``dev_u32`` device_put). The batched scan threads the same
    fold-in through its carry (parallel/data_parallel.py)."""
    nxt = ctr + jnp.uint32(1)
    return jax.random.fold_in(base_key, nxt), nxt


tree_key = obs_compile.instrument_jit("ops.quantize_tree_key", _tree_key)


def sum_gh(gh: jnp.ndarray) -> jnp.ndarray:
    """Channel sums with the overflow-safe accumulator: integer gh sums
    in acc_dtype (exact), float gh keeps its dtype (the existing f32
    behavior)."""
    if jnp.issubdtype(gh.dtype, jnp.integer):
        return jnp.sum(gh, axis=0, dtype=acc_dtype(gh.dtype))
    return jnp.sum(gh, axis=0)


def scale4(qscale) -> jnp.ndarray:
    """[4] channel dequantization vector: (g_scale, h_scale, 1, 1) —
    the count channels are already exact integers."""
    return jnp.concatenate(
        [jnp.asarray(qscale, dtype=jnp.float32),
         jnp.ones(2, dtype=jnp.float32)])


def dequantize_sums(sums: jnp.ndarray, qscale) -> jnp.ndarray:
    """[.., 4] integer channel sums → f32, one rounding per entry."""
    if not jnp.issubdtype(sums.dtype, jnp.integer):
        return sums
    return sums.astype(jnp.float32) * scale4(qscale)


def dequantize_hist(hist: jnp.ndarray, qscale) -> jnp.ndarray:
    """[.., 4] histogram → f32 for a split scan: integer (quantized)
    histograms scale by (g_scale, h_scale, 1, 1) — the single
    per-scan rounding — float histograms pass through untouched. The
    ones fallback for a missing scale exists only for trace-shaped
    callers in exact mode; quantized learners always pass their
    current ``_qscale``.

    The barrier pins the dequantized values: without it XLA is free to
    contract the scale multiply into the split scan's cumsum chains
    (an FMA), and WHETHER it does depends on the surrounding program —
    the same scan then returns different last-ulp gains inside the
    frontier-batched grower than inside the one-split finish,
    breaking the learners' bit-parity contract. Materializing the
    product makes every compile see the same f32 inputs."""
    if not jnp.issubdtype(hist.dtype, jnp.integer):
        return hist
    with jax.named_scope("obs_dequantize"):
        sv = (scale4(qscale) if qscale is not None
              else jnp.ones(4, dtype=jnp.float32))
        return jax.lax.optimization_barrier(
            hist.astype(jnp.float32) * sv)
