"""Histogram op vs np.add.at oracle (the reference's scatter-add semantics,
src/io/dense_bin.hpp:99, reproduced exactly by the one-hot contraction)."""
import jax
import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.histogram import (PALLAS_ROW_TILE,
                                        PALLAS_ROW_TILE_INT,
                                        _bf16_pieces,
                                        _from_kernel_layout,
                                        _kernel_zeros,
                                        _pallas_accumulate,
                                        _pallas_feature_block,
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


# ----------------------------------------------------------------------
# the float32 kernel's three bf16 pieces of gh
# ----------------------------------------------------------------------

def _values(kind, rng):
    n = 4096
    if kind == "gradients":
        return rng.uniform(-1, 1, n)
    if kind == "hessians":
        return rng.uniform(0, 0.25, n) + 2.0 ** -30
    if kind == "goss-amplified":
        return 8 * np.concatenate([rng.uniform(-1, 1, n // 2),
                                   rng.uniform(0, 0.25, n // 2)])
    if kind == "indicators":
        return rng.randint(0, 2, n).astype(np.float64)
    if kind == "tiny-and-huge":
        mag = np.repeat([1e-30, 1e30], n // 2) * rng.uniform(1, 2, n)
        return mag * rng.choice([-1, 1], n) * (1 + rng.rand(n) * 2 ** -10)
    raise ValueError(kind)


@pytest.mark.parametrize("kind", ["gradients", "hessians", "goss-amplified",
                                  "indicators", "tiny-and-huge"])
def test_bf16_pieces_add_back_to_the_float32_value_bit_for_bit(kind):
    """hi + mid + lo in float32 is the value itself, for every kind the
    kernel meets: the split keeps the 24-bit significand HIGHEST keeps."""
    g = _values(kind, np.random.RandomState(7)).astype(np.float32)
    g[:2] = 0.0
    hi, mid, lo = jax.jit(_bf16_pieces)(jnp.asarray(g))
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    back = (np.asarray(hi, np.float32) + np.asarray(mid, np.float32)) \
        + np.asarray(lo, np.float32)
    assert back.tobytes() == g.tobytes()
    if kind != "indicators":
        # the pieces carry bits: a value of more than 8 significant bits
        # is not hi alone
        assert np.any(np.asarray(mid, np.float32) != 0)


def test_bf16_pieces_of_subnormal_remainders_stay_within_the_bound():
    """Where a remainder falls under bf16's smallest normal, 2^-126, its
    last bits cannot be held (and may be flushed): the sum is not bit
    for bit there, and is within 2^-126 of the value."""
    rng = np.random.RandomState(8)
    g = (rng.uniform(1, 2, 4096) * 2.0 ** rng.randint(-126, -100, 4096)
         * rng.choice([-1, 1], 4096)).astype(np.float32)
    hi, mid, lo = jax.jit(_bf16_pieces)(jnp.asarray(g))
    back = (np.asarray(hi, np.float32) + np.asarray(mid, np.float32)) \
        + np.asarray(lo, np.float32)
    gap = np.abs(back.astype(np.float64) - g.astype(np.float64))
    assert gap.max() < 2.0 ** -126


@pytest.mark.parametrize("pieces", ["hi+mid+lo", "hi alone"])
def test_float32_kernel_within_the_accumulation_bound(pieces, monkeypatch):
    """The interpreted float32 kernel against a float64 histogram: each
    cell within 2^-16 of its sum of |gh|, the float32 accumulation
    bound. The control keeps the hi piece alone (a kernel that rounds gh
    to bf16): it must fail the same bound, so a kernel that drops the
    pieces cannot pass."""
    import lightgbm_tpu.ops.histogram as histogram
    if pieces == "hi alone":
        split = histogram._bf16_pieces

        def hi_alone(g):
            hi, mid, lo = split(g)
            return hi, jnp.zeros_like(mid), jnp.zeros_like(lo)
        monkeypatch.setattr(histogram, "_bf16_pieces", hi_alone)
    S, F, B, tile = 1024, 6, 255, 256
    rng = np.random.RandomState(9)
    bins = rng.randint(0, B, size=(S, F)).astype(np.uint8)
    ind = (rng.rand(S) < 0.8).astype(np.float32)
    gh = np.stack([rng.uniform(-1, 1, S) * ind, rng.uniform(0, 0.25, S) * ind,
                   ind, np.ones(S)], axis=1).astype(np.float32)
    got = np.asarray(_pallas_histogram_body(
        jnp.asarray(bins), jnp.asarray(gh), B, tile, interpret=True),
        np.float64)
    want = oracle(bins, gh.astype(np.float64), B)
    bound = 2.0 ** -16 * oracle(bins, np.abs(gh).astype(np.float64), B)
    within = bool(np.all(np.abs(got - want) <= bound))
    assert within == (pieces == "hi+mid+lo")


def test_float32_kernel_keeps_every_bit_of_a_lone_row():
    """Each bin of each feature holds one row, so a cell's sum is the
    row's value with nothing to round: the interpreted float32 kernel
    gives it back bit for bit, which it can only do with all three
    pieces (hi and mid alone keep 16 of the 24 bits)."""
    S, F, B = 255, 3, 255
    rng = np.random.RandomState(10)
    bins = np.stack([rng.permutation(B) for _ in range(F)], 1) \
        .astype(np.uint8)
    gh = np.stack([rng.uniform(-1, 1, S), rng.uniform(0, 0.25, S),
                   8 * rng.uniform(-1, 1, S), np.ones(S)],
                  axis=1).astype(np.float32)
    got = np.asarray(_pallas_histogram_body(
        jnp.asarray(bins), jnp.asarray(gh), B, 256, interpret=True))
    want = np.zeros((F, B, 4), np.float32)
    for f in range(F):
        want[f, bins[:, f]] = gh
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("F,itemsize,tile,block", [
    (968, 4, PALLAS_ROW_TILE, 968), (2000, 4, PALLAS_ROW_TILE, 504),
    (968, 1, PALLAS_ROW_TILE_INT, 248)],
    ids=["bosch-f32", "epsilon-f32", "bosch-int8"])
def test_feature_block_of_the_cells_under_the_pieces_bound(F, itemsize, tile,
                                                           block):
    """The VMEM bound counts the float32 kernel's bf16 pieces and their
    W operands: F = 968 in one block, F = 2,000 in four of 504, int8
    rows at F = 968 in four of 248."""
    assert _pallas_feature_block(F, 255, 4, tile, itemsize, 1) == block
