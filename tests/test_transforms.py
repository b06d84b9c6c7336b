"""Round-trip + shared-bit guarantees for the four paper transforms (§3)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import transforms as T
from repro.core.float_bits import F16, F32, F64
from repro.core import pipeline

L = F64.man_bits
LO = 1 << L
HI = 1 << (L + 1)


def sig(vals):
    return jnp.asarray(np.asarray(vals, np.int64))


def rand_sig(n, rng, span=None, base=None):
    span = span or (HI - LO)
    base = base or LO
    return sig(rng.integers(base, min(base + span, HI), size=n))


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(42)


# ---------------------------------------------------------------------------
# compact bins
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 8, 64])
def test_compact_bins_roundtrip(k, rng):
    X = rand_sig(1000, rng)
    Xt, meta = T.compact_bins_forward(X, k)
    Xr = T.compact_bins_inverse(Xt, meta)
    assert jnp.all(Xr == X)


def test_compact_bins_clusters(rng):
    # clustered data: bins should pack the clusters together near the top
    centers = rng.integers(LO, HI - (1 << 40), 8)
    X = sig((centers[:, None] + rng.integers(0, 1 << 20, (8, 200))).ravel())
    Xt, meta = T.compact_bins_forward(X, 8)
    assert jnp.all(T.compact_bins_inverse(Xt, meta) == X)
    # packed span is ~sum of cluster widths, far below the original span
    assert int(Xt.max() - Xt.min()) < 8 * (1 << 20) + 32
    # entropy-packed metadata: bounded by the raw 8x64 + 7x64 layout
    assert 128 < meta.nbits() <= 128 + 8 * (64 * 8 + 64 * 7)


def test_compact_bins_constant_dataset():
    X = sig(np.full(100, LO + 12345))
    Xt, meta = T.compact_bins_forward(X, 4)
    assert jnp.all(T.compact_bins_inverse(Xt, meta) == X)


def test_compact_bins_too_many_bins():
    with pytest.raises(T.TransformError):
        T.compact_bins_forward(sig([LO + 1, LO + 2]), 5)


# ---------------------------------------------------------------------------
# multiply and shift
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("D", [2, 4, 8])
def test_multiply_shift_roundtrip(D, rng):
    # narrow dataset (paper's regime): range ~2^-(D+2) of the binade
    span = 1 << (L - D - 2)
    X = rand_sig(500, rng, span=span, base=LO + (1 << (L - 3)))
    Xt, off, meta = T.multiply_shift_forward(X, D)
    Xr = T.multiply_shift_inverse(Xt, off, meta)
    assert jnp.all(Xr == X)
    # captured window: top-D mantissa bits all ones
    man = np.asarray(Xt) - LO
    top_d = man >> (L - D)
    assert np.all(top_d == (1 << D) - 1)


def test_multiply_shift_full_binade_low_D(rng):
    X = rand_sig(2000, rng)  # full binade
    Xt, off, meta = T.multiply_shift_forward(X, 2, max_iter=16)
    assert jnp.all(T.multiply_shift_inverse(Xt, off, meta) == X)


def test_multiply_shift_nonconvergence_raises(rng):
    X = rand_sig(2000, rng)  # full binade, high D -> ~2^10 iters needed
    with pytest.raises(T.TransformError):
        T.multiply_shift_forward(X, 10, max_iter=32)


def test_offsets_past_the_exponent_field_raise():
    """A stored word carries its binade offset in the exponent field, which
    holds offsets up to 15 in f16: multiply&shift lifting values further is
    rejected, and auto-selection picks a transform that decodes bitwise."""
    from repro.data import gas_turbine_emissions

    x = gas_turbine_emissions(20000).astype(np.float16)
    with pytest.raises(T.TransformError, match="exponent field"):
        T.multiply_shift_forward(pipeline._prepare(x).X, 8, spec=F16)
    enc = pipeline.encode(x)
    assert np.array_equal(pipeline.decode(enc).view(np.uint16),
                          x.view(np.uint16))


def test_multiply_shift_binade_climb(rng):
    """Iterations climb one binade each — the paper's S_E loss trade-off."""
    span = 1 << (L - 4)  # = 4 capture windows at D=6
    X = rand_sig(500, rng, span=span, base=LO)
    Xt, off, meta = T.multiply_shift_forward(X, 6)
    assert int(off.max()) == meta.n_iter
    assert meta.n_iter >= 2  # range spans multiple capture windows


# ---------------------------------------------------------------------------
# shift and separate even from odd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("D", [2, 3, 4])
def test_shift_separate_roundtrip(D, rng):
    span = 1 << (L - D - 3)  # within convergence regime
    X = rand_sig(800, rng, span=span, base=LO + (1 << (L - 2)))
    Xt, off, meta = T.shift_separate_forward(X, D)
    Xr = T.shift_separate_inverse(Xt, off, meta)
    assert jnp.all(Xr == X)
    man = np.asarray(Xt) - LO
    assert np.all((man >> (L - D)) == (1 << D) - 1)


def test_shift_separate_parity_recovery(rng):
    """Odd/even sources must be recoverable from position alone (Eq. 11)."""
    span = 1 << (L - 8)
    X = rand_sig(1000, rng, span=span, base=LO + span)
    Xt, off, meta = T.shift_separate_forward(X, 4)
    assert jnp.all(T.shift_separate_inverse(Xt, off, meta) == X)


def test_shift_separate_diverges_raises(rng):
    X = rand_sig(1000, rng)  # full binade: W too large
    with pytest.raises(T.TransformError):
        T.shift_separate_forward(X, 8)


# ---------------------------------------------------------------------------
# shift and save evenness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("D", [1, 8, 16, 30])
def test_shift_save_even_roundtrip(D, rng):
    X = rand_sig(1000, rng)  # FULL binade: works for any D (paper's claim)
    Y, meta = T.shift_save_even_forward(X, D)
    Xr = T.shift_save_even_inverse(Y, meta)
    assert jnp.all(Xr == X)
    man = np.asarray(Y) - LO
    assert np.all((man >> (L - D)) == 0)  # top-D bits zero (Eq. 7 window)


def test_shift_save_even_metadata_scaling(rng):
    X = rand_sig(1000, rng)
    m8 = T.shift_save_even_forward(X, 8)[1]
    m20 = T.shift_save_even_forward(X, 20)[1]
    assert m20.nbits() > m8.nbits()           # paper: Z grows with D
    assert m20.n_chunks > m8.n_chunks


@given(st.integers(1, 40), st.integers(2, 200))
@settings(max_examples=60, deadline=None)
def test_shift_save_even_hypothesis(D, n):
    rng = np.random.default_rng(D * 1000 + n)
    X = sig(rng.integers(LO, HI, n))
    Y, meta = T.shift_save_even_forward(X, D)
    assert jnp.all(T.shift_save_even_inverse(Y, meta) == X)


def test_shift_save_even_equals_real_fp_addition(rng):
    """Fidelity closure (DESIGN §8b.4): the integer-significand transform
    must produce EXACTLY what the paper's fp op y = x ⊕ A produces, with A
    reconstructed from the metadata (parity-matched addend)."""
    X = rand_sig(500, rng)
    D = 10
    Y, meta = T.shift_save_even_forward(X, D)
    l = L
    w_eff = (1 << (l + 1 - D)) - 2
    Xn = np.asarray(X)
    j = (Xn - meta.x_min) // w_eff
    a_base = (1 << (l + 1)) - meta.x_min - j * w_eff
    a_even = a_base + (a_base & 1)
    A_int = a_even + (Xn & 1)
    # real IEEE-754 doubles at binade 0: value = significand * 2^-52
    x_f = jnp.asarray(Xn * 2.0 ** -52, jnp.float64)
    A_f = jnp.asarray(A_int * 2.0 ** -52, jnp.float64)
    y_f = x_f + A_f                        # the paper's ⊕
    # transform output as a float: Y at binade 1 => Y * 2^-51... Y is the
    # significand at scale 2q, i.e. value Y * 2^-51
    want = np.asarray(Y) * 2.0 ** -51
    assert np.array_equal(np.asarray(y_f), want)
    # and the addition was exact (2Sum error == 0) for every element
    from repro.core.lossless import add_is_exact

    assert bool(jnp.all(add_is_exact(x_f, A_f)))


# ---------------------------------------------------------------------------
# f32 spec variants (the accelerator-native dtype)
# ---------------------------------------------------------------------------

def test_transforms_f32_spec(rng):
    L32 = F32.man_bits
    X = sig(rng.integers(1 << L32, 1 << (L32 + 1), 500))
    Y, meta = T.shift_save_even_forward(X, 6, spec=F32)
    assert jnp.all(T.shift_save_even_inverse(Y, meta, spec=F32) == X)
    Xt, m2 = T.compact_bins_forward(X, 8, spec=F32)
    assert jnp.all(T.compact_bins_inverse(Xt, m2) == X)


# ---------------------------------------------------------------------------
# full pipeline: arbitrary arrays, bitwise round-trip
# ---------------------------------------------------------------------------

def test_pipeline_mixed_sign_exponent(rng):
    x = np.concatenate([
        rng.uniform(-1000, 1000, 500),
        rng.uniform(0.001, 0.1, 200),
        [0.0, -0.0, np.inf, -np.inf, np.nan, 5e-324, 1e308],
    ])
    enc = pipeline.encode(jnp.asarray(x, jnp.float64))
    dec = pipeline.decode(enc)
    assert np.array_equal(
        np.asarray(x, np.float64).view(np.uint64),
        np.asarray(dec, np.float64).view(np.uint64),
    )


def test_pipeline_f32(rng):
    x = jnp.asarray(rng.normal(0, 1, 1000), jnp.float32)
    enc = pipeline.encode(x)
    dec = pipeline.decode(enc)
    assert np.array_equal(
        np.asarray(x).view(np.uint32), np.asarray(dec, np.float32).view(np.uint32)
    )


def test_pipeline_every_method_roundtrips(rng):
    x = jnp.asarray(1.0 + rng.random(800) * 0.001, jnp.float64)  # narrow data
    for method, params in [
        ("identity", {}),
        ("compact_bins", {"n_bins": 8}),
        ("multiply_shift", {"D": 6}),
        ("shift_separate", {"D": 3}),
        ("shift_save_even", {"D": 12}),
    ]:
        enc = pipeline.encode(x, method=method, params=params)
        assert enc.method == method
        dec = pipeline.decode(enc)
        assert np.array_equal(
            np.asarray(x).view(np.uint64), np.asarray(dec, np.float64).view(np.uint64)
        ), method


@given(st.lists(st.floats(allow_nan=False, width=64), min_size=1, max_size=50))
@settings(max_examples=100, deadline=None)
def test_pipeline_hypothesis_bitwise(vals):
    x = jnp.asarray(vals, jnp.float64)
    enc = pipeline.encode(x)
    dec = pipeline.decode(enc)
    assert np.array_equal(
        np.asarray(x).view(np.uint64), np.asarray(dec, np.float64).view(np.uint64)
    )


def test_pipeline_metadata_accounting(rng):
    x = jnp.asarray(rng.uniform(1, 2, 1000), jnp.float64)
    enc = pipeline.encode(x, method="shift_save_even", params={"D": 12})
    assert enc.metadata_bytes() > 0
    assert enc.metadata_bytes() < 1000 * 8  # far below the dataset itself
