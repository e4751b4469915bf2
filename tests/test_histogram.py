"""Histogram op vs np.add.at oracle (the reference's scatter-add semantics,
src/io/dense_bin.hpp:99, reproduced exactly by the one-hot contraction)."""
import jax
import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.histogram import (PALLAS_ROW_TILE,
                                        PALLAS_ROW_TILE_INT,
                                        _from_kernel_layout,
                                        _kernel_zeros,
                                        _pallas_accumulate,
                                        _pallas_histogram_body,
                                        _segment_histogram,
                                        build_histogram,
                                        subtract_histogram)


def oracle(bins, gh, B):
    S, F = bins.shape
    C = gh.shape[1]
    out = np.zeros((F, B, C), dtype=np.float64)
    for f in range(F):
        for c in range(C):
            np.add.at(out[f, :, c], bins[:, f], gh[:, c])
    return out


@pytest.mark.parametrize("S,F,B", [(100, 3, 16), (1000, 7, 64), (5000, 2, 256)])
def test_matches_oracle(S, F, B):
    rng = np.random.RandomState(0)
    bins = rng.randint(0, B, size=(S, F)).astype(np.uint8 if B <= 256 else np.uint16)
    gh = rng.randn(S, 3).astype(np.float32)
    gh[:, 2] = 1.0
    hist = np.asarray(build_histogram(jnp.asarray(bins), jnp.asarray(gh), B))
    exp = oracle(bins, gh, B)
    np.testing.assert_allclose(hist, exp, rtol=2e-5, atol=2e-4)


def test_padding_rows_vanish():
    rng = np.random.RandomState(1)
    S, F, B = 700, 4, 32
    bins = rng.randint(0, B, size=(S, F)).astype(np.uint8)
    gh = rng.randn(S, 3).astype(np.float32)
    gh[500:] = 0.0  # "padding" rows
    hist = np.asarray(build_histogram(jnp.asarray(bins), jnp.asarray(gh), B))
    exp = oracle(bins[:500], gh[:500], B)
    np.testing.assert_allclose(hist, exp, rtol=2e-5, atol=2e-4)


def test_subtract():
    rng = np.random.RandomState(2)
    a = rng.rand(3, 8, 3).astype(np.float32)
    b = rng.rand(3, 8, 3).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(subtract_histogram(jnp.asarray(a + b), jnp.asarray(b))),
        a, rtol=1e-5, atol=1e-6)


def test_count_channel_exact():
    # counts are sums of exact 1.0s -> must be integral
    rng = np.random.RandomState(3)
    S, F, B = 4097, 2, 16
    bins = rng.randint(0, B, size=(S, F)).astype(np.uint8)
    gh = np.ones((S, 3), dtype=np.float32)
    hist = np.asarray(build_histogram(jnp.asarray(bins), jnp.asarray(gh), B))
    assert np.all(hist[..., 2] == np.round(hist[..., 2]))
    assert hist[..., 2].sum(axis=1).max() == S


# ----------------------------------------------------------------------
# Pallas kernel: the CPU cannot run it, but it can (i) push it through
# the Pallas -> Mosaic lowering for a TPU target and (ii) interpret the
# same body — so a CPU-only change cannot break the kernel unseen
# ----------------------------------------------------------------------

@pytest.mark.parametrize("gh_dtype,tile,features", [
    (jnp.float32, PALLAS_ROW_TILE, 28), (jnp.int8, PALLAS_ROW_TILE_INT, 28),
    (jnp.float32, PALLAS_ROW_TILE, 968), (jnp.float32, PALLAS_ROW_TILE, 2000),
    (jnp.int8, PALLAS_ROW_TILE_INT, 968)],
    ids=["higgs-f32", "higgs-int8", "bosch-f32", "epsilon-f32",
         "bosch-int8"])
def test_pallas_kernel_lowers_for_tpu(gh_dtype, tile, features):
    """Higgs width (F=28, B=255, C=4) and the benchmark cells' widths
    at the product row tiles: one block of features at F = 28 and at
    F = 968 in float32, several at F = 2,000 and for int8 rows at
    F = 968."""
    bins = jax.ShapeDtypeStruct((2 * tile, features), jnp.uint8)
    gh = jax.ShapeDtypeStruct((2 * tile, 4), gh_dtype)
    jax.jit(lambda b, g: _pallas_histogram_body(b, g, 255, tile)) \
        .trace(bins, gh).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("gh_dtype", [np.float32, np.int8])
@pytest.mark.parametrize("block", [8, 16, 24])
def test_feature_blocks_give_the_one_block_kernels_bits(gh_dtype, block):
    """The kernel interpreted with its grid's axis over blocks of
    features (40 features in blocks of 8, 16 with a ragged last block
    of 8, 24 with one of 16) against the one-block kernel, continuing a
    histogram that is not empty: every feature takes the same additions
    in the same order, so the bytes are equal, float32 too."""
    S, F, B, tile = 1024, 40, 255, 256
    rng = np.random.RandomState(5)
    bins = jnp.asarray(rng.randint(0, B, size=(S, F)).astype(np.uint8))
    if gh_dtype == np.int8:
        gh = rng.randint(-127, 128, size=(S, 4)).astype(np.int8)
    else:
        gh = rng.randn(S, 4).astype(np.float32)
    gh = jnp.asarray(gh)
    before = _pallas_accumulate(_kernel_zeros(F, B, 4, gh.dtype),
                                bins[-tile:], gh[:tile], tile,
                                interpret=True)
    one = _pallas_accumulate(before, bins, gh, tile, interpret=True)
    blocked = _pallas_accumulate(before, bins, gh, tile, interpret=True,
                                 feature_block=block)
    assert np.asarray(blocked).tobytes() == np.asarray(one).tobytes()
    np.testing.assert_allclose(
        np.asarray(_from_kernel_layout(blocked, B)),
        np.asarray(_segment_histogram(bins, gh, B)
                   + _segment_histogram(bins[-tile:], gh[:tile], B)),
        rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("S,F,B,tile", [(512, 5, 64, 256),
                                        (1000, 3, 255, 256)])
def test_pallas_kernel_interpreted_matches_segment_sum(S, F, B, tile):
    """interpret=True is an argument only this test passes. int8 rows
    must match exactly; f32 up to accumulation order. The second case
    has a ragged row count (1000 rows in 256-row tiles)."""
    rng = np.random.RandomState(4)
    bins = jnp.asarray(rng.randint(0, B, size=(S, F)).astype(np.uint8))
    gh_f = jnp.asarray(rng.randn(S, 4).astype(np.float32))
    gh_i = jnp.asarray(
        rng.randint(-127, 128, size=(S, 4)).astype(np.int8))
    got_i = _pallas_histogram_body(bins, gh_i, B, tile, interpret=True)
    assert got_i.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(got_i), np.asarray(_segment_histogram(bins, gh_i, B)))
    got_f = _pallas_histogram_body(bins, gh_f, B, tile, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got_f), np.asarray(_segment_histogram(bins, gh_f, B)),
        rtol=1e-5, atol=1e-4)
