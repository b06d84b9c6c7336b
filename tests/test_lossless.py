"""Validate the paper's §2.1 losslessness conditions against real IEEE-754 ops.

These tests ARE the paper-claims check for Table 1, Eq.(4) and Eq.(6): we run
actual float ⊕/⊖/⊗ (f64, round-to-nearest) and compare against the bit-level
predicates used constructively by the transforms.
"""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.float_bits import (
    F64, from_bits, normalize_bits,
    denormalize_bits, pow2, scale_by_pow2, to_bits, ulp,
)
from repro.core.lossless import (
    add_is_exact, eq4_condition, mul_pow2_is_exact, same_evenness,
    significand_from_bits, significand_to_bits, two_sum,
)

L = F64.man_bits


def mk(e_star: int, man: int) -> float:
    """float with unbiased exponent e_star and mantissa field man."""
    return float(np.ldexp(1.0 + man * 2.0 ** -L, e_star))


# ---------------------------------------------------------------------------
# bit model basics
# ---------------------------------------------------------------------------

def test_roundtrip_bits():
    x = jnp.asarray([1.0, -3.5, 0.1, 1e300, 1e-300, 2.0 ** -1040], jnp.float64)
    assert jnp.all(from_bits(to_bits(x), F64) == x)


def test_ulp_matches_numpy_spacing():
    xs = jnp.asarray([1.0, 1.999, 2.0, 3.5, 1e10, 1e-10, 7.1e-300], jnp.float64)
    assert np.allclose(np.asarray(ulp(xs)), np.spacing(np.asarray(xs)), rtol=0)


def test_pow2_exact():
    es = jnp.arange(-1060, 1023)
    vals = pow2(es, F64)
    ref = np.ldexp(np.ones(len(es)), np.asarray(es))
    assert np.all(np.asarray(vals) == ref)


def test_scale_by_pow2_exact():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(1, 2, 100), jnp.float64)
    y = scale_by_pow2(x, 7)
    assert jnp.all(y == x * 128.0)
    assert jnp.all(scale_by_pow2(y, -7) == x)


@given(st.floats(min_value=1e-280, max_value=1e280, allow_nan=False))
@settings(max_examples=300, deadline=None)
def test_normalize_roundtrip(v):
    for s in (v, -v):
        x = np.asarray([s], np.float64)
        y, e, sg = normalize_bits(x.view(np.uint64), F64)
        assert 1.0 <= float(np.asarray(y).view(np.float64)[0]) < 2.0
        back = np.asarray(denormalize_bits(y, e, sg, F64)).view(np.float64)
        assert float(back[0]) == s


def test_normalize_subnormals_and_zero():
    x = np.asarray([0.0, 5e-324, 2.2250738585072014e-308, -3e-310], np.float64)
    y, e, sg = normalize_bits(x.view(np.uint64), F64)
    back = denormalize_bits(y, e, sg, F64)
    assert np.array_equal(np.asarray(back), x.view(np.uint64))


# ---------------------------------------------------------------------------
# Paper Table 1: same-binade addition crossing one exponent boundary
# exact iff m_52(x) == m_52(A)
# ---------------------------------------------------------------------------

def test_table1_exhaustive_low_bits():
    """Exhaustive over the low 2 mantissa bits of x and A (the axes of
    Table 1) × random high bits, requiring the sum to cross the binade."""
    rng = np.random.default_rng(1)
    for _ in range(200):
        hx = int(rng.integers(0, 1 << (L - 2))) << 2
        ha = int(rng.integers(0, 1 << (L - 2))) << 2
        for bx in range(4):
            for ba in range(4):
                x = mk(0, hx | bx)
                a = mk(0, ha | ba)
                if x + a < 2.0 * 2.0:  # must land in [2,4): always true here
                    xs = jnp.float64(x)
                    As = jnp.float64(a)
                    exact = bool(add_is_exact(xs, As))
                    pred = bool(same_evenness(xs, As))
                    # same evenness => exact (sufficiency; paper's condition)
                    if pred:
                        assert exact
                    # and when evenness differs the guard bit is 1 => inexact
                    else:
                        assert not exact


@given(
    st.integers(0, (1 << L) - 1),
    st.integers(0, (1 << L) - 1),
    st.integers(-100, 100),
)
@settings(max_examples=500, deadline=None)
def test_table1_hypothesis(mx, ma, e):
    x, a = mk(e, mx), mk(e, ma)
    s = jnp.float64(x) + jnp.float64(a)
    assert 2 ** (e + 1) <= float(s) < 2 ** (e + 2)
    assert bool(add_is_exact(jnp.float64(x), jnp.float64(a))) == ((mx & 1) == (ma & 1))


# ---------------------------------------------------------------------------
# Eq.(4): small addend, result stays in x's binade
# ---------------------------------------------------------------------------

@given(
    st.integers(0, (1 << L) - 1),        # x mantissa
    st.integers(1, (1 << L) - 1),        # A mantissa
    st.integers(1, 40),                  # exponent gap s
)
@settings(max_examples=500, deadline=None)
def test_eq4_hypothesis(mx, ma, s):
    e = 0
    x = mk(e, mx)
    a = mk(e - s, ma)
    if x + a >= 2.0 ** (e + 1):  # exclude carry (transforms exclude it too)
        return
    exact = bool(add_is_exact(jnp.float64(x), jnp.float64(a)))
    # tight condition: low s bits of A's mantissa zero  (multiple of ULP(x))
    tight = (ma & ((1 << min(s, L)) - 1)) == 0 if s <= L else False
    assert exact == tight
    # paper's Eq.(4) (one extra zero bit) implies exactness
    paper = (ma & ((1 << min(s + 1, L)) - 1)) == 0 if s + 1 <= L else False
    if paper:
        assert exact
    assert bool(eq4_condition(jnp.float64(a), e)) == tight


# ---------------------------------------------------------------------------
# Eq.(6): multiplication crossing one boundary, M >= 2; M = 2^k always exact
# ---------------------------------------------------------------------------

@given(st.integers(0, (1 << L) - 1), st.integers(-500, 500), st.integers(1, 8))
@settings(max_examples=300, deadline=None)
def test_mul_pow2_exact(mx, e, k):
    x = jnp.float64(mk(e, mx))
    y = x * jnp.float64(2.0 ** k)
    assert bool(mul_pow2_is_exact(x, k))
    assert float(y) / 2.0 ** k == float(x)


@given(st.integers(0, (1 << L) - 1), st.floats(2.0, 4.0, exclude_max=True))
@settings(max_examples=500, deadline=None)
def test_eq6_multiplication_M_ge_2(mx, M):
    """Paper §2.1: x in [2^E, 2^{E+1}), x ⊗ M in [2^{E+1}, 2^{E+2}), M >= 2 =>
    round-trip y ⊘ M == x (the paper's lossless criterion, Eq. 5-6)."""
    x = jnp.float64(mk(0, mx))
    y = x * jnp.float64(M)
    if not (2.0 <= float(y) < 4.0):  # Eq.(6) precondition: one-binade crossing
        return
    assert float(y / jnp.float64(M)) == float(x)


def test_paper_intro_loss_example():
    """§2.1 example: g(f(3.5)) = 4.0 != 3.5 with f = ⊕1e16."""
    x = jnp.float64(3.5)
    y = (x + jnp.float64(1e16)) - jnp.float64(1e16)
    assert float(y) == 4.0


def test_two_sum_error_is_exact():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.uniform(1, 2, 1000), jnp.float64)
    b = jnp.asarray(rng.uniform(1, 2, 1000) * 1e-12, jnp.float64)
    s, e = two_sum(a, b)
    # reconstruct in higher "precision" via integer significands
    import math
    for i in range(0, 1000, 97):
        af, bf = float(a[i]), float(b[i])
        sf, ef = float(s[i]), float(e[i])
        assert af + bf == sf + ef or math.isclose(af + bf, sf + ef, rel_tol=0, abs_tol=0)


def test_significand_int_roundtrip():
    rng = np.random.default_rng(3)
    x = rng.uniform(1, 2, 257)
    X = significand_from_bits(x.view(np.uint64))
    assert int(X.min()) >= 1 << L and int(X.max()) < 1 << (L + 1)
    back = significand_to_bits(X, jnp.zeros(257, jnp.int32))
    assert np.array_equal(np.asarray(back), x.view(np.uint64))
