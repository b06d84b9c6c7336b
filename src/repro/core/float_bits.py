"""IEEE-754 bit-level model used by the paper's transforms.

Everything is parametrized by a :class:`FloatSpec` so the paper's binary64
math (l=52, B=1023) and the accelerator-native binary32 variant (l=23, B=127)
share one implementation.  All functions are pure jnp and jit-safe.

Paper refs: Eq.(2) (IEEE-754 decomposition), Eq.(3) (ULP).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class FloatSpec:
    """Static description of an IEEE-754 binary format."""

    name: str
    width: int          # total bits
    man_bits: int       # explicit mantissa bits (l in the paper)
    exp_bits: int
    bias: int           # B in the paper

    @property
    def float_dtype(self):
        # two 16-bit formats share a width, so the float dtype is keyed by
        # name there; the integer views below stay width-keyed (both use
        # uint16/int16 bit containers)
        if self.name == "f16":
            return jnp.float16
        return {64: jnp.float64, 32: jnp.float32, 16: jnp.bfloat16}[self.width]

    @property
    def uint_dtype(self):
        return {64: jnp.uint64, 32: jnp.uint32, 16: jnp.uint16}[self.width]

    @property
    def int_dtype(self):
        return {64: jnp.int64, 32: jnp.int32, 16: jnp.int16}[self.width]

    @property
    def man_mask(self) -> int:
        return (1 << self.man_bits) - 1

    @property
    def exp_mask(self) -> int:
        return (1 << self.exp_bits) - 1

    @property
    def sign_shift(self) -> int:
        return self.width - 1

    @property
    def max_unbiased_exp(self) -> int:
        return self.exp_mask - 1 - self.bias  # all-ones exponent = inf/nan

    @property
    def min_unbiased_exp(self) -> int:
        return 1 - self.bias  # biased exponent 0 = subnormal


F64 = FloatSpec(name="f64", width=64, man_bits=52, exp_bits=11, bias=1023)
F32 = FloatSpec(name="f32", width=32, man_bits=23, exp_bits=8, bias=127)
BF16 = FloatSpec(name="bf16", width=16, man_bits=7, exp_bits=8, bias=127)
F16 = FloatSpec(name="f16", width=16, man_bits=10, exp_bits=5, bias=15)

_SPEC_BY_DTYPE = {
    jnp.dtype(jnp.float64): F64,
    jnp.dtype(jnp.float32): F32,
    jnp.dtype(jnp.bfloat16): BF16,
    jnp.dtype(jnp.float16): F16,
}


def spec_for(x) -> FloatSpec:
    return _SPEC_BY_DTYPE[jnp.dtype(x.dtype)]


# ---------------------------------------------------------------------------
# bit views
# ---------------------------------------------------------------------------

def to_bits(x, spec: FloatSpec | None = None):
    """Bitcast float array -> unsigned integer array of the same width."""
    spec = spec or spec_for(x)
    return lax.bitcast_convert_type(x.astype(spec.float_dtype), spec.uint_dtype)


def from_bits(b, spec: FloatSpec):
    """Bitcast unsigned integer array -> float array."""
    return lax.bitcast_convert_type(b.astype(spec.uint_dtype), spec.float_dtype)


def sign_bit(x, spec: FloatSpec | None = None):
    spec = spec or spec_for(x)
    return (to_bits(x, spec) >> spec.sign_shift).astype(jnp.uint32)


def biased_exponent(x, spec: FloatSpec | None = None):
    """E in Eq.(2) — the raw biased exponent field, as int32."""
    spec = spec or spec_for(x)
    b = to_bits(x, spec)
    return ((b >> spec.man_bits) & spec.exp_mask).astype(jnp.int32)


def unbiased_exponent(x, spec: FloatSpec | None = None):
    """E - B: for normal x, |x| in [2^e, 2^{e+1})."""
    spec = spec or spec_for(x)
    return biased_exponent(x, spec) - spec.bias


def mantissa(x, spec: FloatSpec | None = None):
    """M in Eq.(2): the explicit mantissa field as an unsigned integer."""
    spec = spec or spec_for(x)
    return to_bits(x, spec) & spec.uint_dtype(spec.man_mask)


def compose_bits(sign, biased_exp, man, spec: FloatSpec):
    """Assemble (S, E, M) fields into the format's unsigned bit word."""
    u = spec.uint_dtype
    return (
        (sign.astype(u) << spec.sign_shift)
        | ((biased_exp.astype(u) & u(spec.exp_mask)) << spec.man_bits)
        | (man.astype(u) & u(spec.man_mask))
    )


def compose(sign, biased_exp, man, spec: FloatSpec):
    """Assemble (S, E, M) fields into a float (inverse of the accessors)."""
    return from_bits(compose_bits(sign, biased_exp, man, spec), spec)


# ---------------------------------------------------------------------------
# ULP and exact power-of-two scaling
# ---------------------------------------------------------------------------

def ulp(x, spec: FloatSpec | None = None):
    """Eq.(3): ULP(x) = 2^(E - B - l) for normal x.

    For subnormals (biased exponent 0) the spacing is 2^(1 - B - l); we return
    that, which keeps `x + ulp(x)` = nextafter for all finite positives.
    """
    spec = spec or spec_for(x)
    e = jnp.maximum(biased_exponent(x, spec), 1) - spec.bias - spec.man_bits
    return pow2(e, spec)


def pow2(e, spec: FloatSpec):
    """Exact 2^e for integer e (array ok), incl. subnormal range."""
    e = jnp.asarray(e, jnp.int32)
    normal = compose(jnp.uint32(0), e + spec.bias, jnp.zeros_like(e), spec)
    # subnormal: 2^e = mantissa-only bit at position man_bits + e - (1 - bias)
    sub_shift = jnp.clip(e + spec.bias - 1 + spec.man_bits, 0, spec.man_bits - 1)
    subnormal = compose(
        jnp.uint32(0),
        jnp.zeros_like(e),
        (spec.uint_dtype(1) << sub_shift.astype(spec.uint_dtype)),
        spec,
    )
    return jnp.where(e + spec.bias >= 1, normal, subnormal)


def scale_by_pow2(x, k, spec: FloatSpec | None = None):
    """Exact multiplication by 2^k via exponent-field arithmetic.

    Exact for normal results (exponent stays in normal range). The caller is
    responsible for range checks; `normalize_to_binade` below always satisfies
    them because it maps into [1, 2).
    """
    spec = spec or spec_for(x)
    b = to_bits(x, spec)
    e = ((b >> spec.man_bits) & spec.uint_dtype(spec.exp_mask)).astype(jnp.int32)
    new_e = e + jnp.asarray(k, jnp.int32)
    u = spec.uint_dtype
    cleared = b & ~(u(spec.exp_mask) << spec.man_bits)
    out = cleared | ((new_e.astype(u) & u(spec.exp_mask)) << spec.man_bits)
    # preserve exact zeros
    return jnp.where(x == 0, x, from_bits(out, spec))


def next_float(x, spec: FloatSpec | None = None):
    """nextafter(x, +inf) for non-negative finite x, bitwise."""
    spec = spec or spec_for(x)
    return from_bits(to_bits(x, spec) + spec.uint_dtype(1), spec)


# ---------------------------------------------------------------------------
# dataset normalization (the paper's "store original exponent as metadata")
# ---------------------------------------------------------------------------

ZERO_EXP_SENTINEL = -(1 << 14)  # exponent marker for exact zeros


def _top_bit(v):
    """Index of the highest set bit of a positive int64 below 2^63, from
    32-bit count-leading-zeros of its two halves (integer ops only)."""
    hi = (v >> 32).astype(jnp.uint32)
    lo = v.astype(jnp.uint32)
    return jnp.where(hi != 0, 63 - lax.clz(hi).astype(jnp.int32),
                     31 - lax.clz(lo).astype(jnp.int32))


def normalize_bits(b, spec: FloatSpec):
    """Map every finite sample to [1, 2) by exact 2^-e scaling — pure bit ops.

    ``b`` holds the samples' unsigned bit words (the host takes them for
    free with ``ndarray.view``).  Returns (y, exponents, signs): y is the
    bit word of |x| / 2^e in [1,2); exponents (int32) and signs (uint32)
    are the per-sample metadata the paper mentions in §3 ("storing as
    metadata the information on the original exponent of each sample").
    Everything stays in the integer domain: XLA:CPU flushes subnormals to
    zero in float arithmetic (DAZ/FTZ), and the TPU compiler accepts no
    bitcast between f64 and u64.  Zeros map to (1.0, ZERO_EXP_SENTINEL)
    and survive the round-trip.
    """
    u = spec.uint_dtype
    b = jnp.asarray(b, u)
    s = (b >> spec.sign_shift).astype(jnp.uint32)
    man = (b & u(spec.man_mask)).astype(jnp.int64)
    be = ((b >> spec.man_bits) & u(spec.exp_mask)).astype(jnp.int32)

    is_zero = (man == 0) & (be == 0)
    is_sub = (man != 0) & (be == 0)

    # subnormal: value = man * 2^(1-bias-l); top set bit h gives e
    h = _top_bit(jnp.maximum(man, 1))
    sub_e = h + (1 - spec.bias - spec.man_bits)
    sub_man = (man << (spec.man_bits - h).astype(jnp.int64)) & jnp.int64(spec.man_mask)

    e = jnp.where(is_sub, sub_e, be - spec.bias)
    e = jnp.where(is_zero, ZERO_EXP_SENTINEL, e).astype(jnp.int32)
    out_man = jnp.where(is_sub, sub_man, man)
    out_man = jnp.where(is_zero, 0, out_man)
    y = (u(spec.bias) << spec.man_bits) | out_man.astype(u)
    return y, e, s


def denormalize_bits(y, exponents, signs, spec: FloatSpec):
    """Exact inverse of :func:`normalize_bits`, on bit words."""
    u = spec.uint_dtype
    e = jnp.asarray(exponents, jnp.int32)
    man = (jnp.asarray(y, u) & u(spec.man_mask)).astype(jnp.int64)

    is_zero = e == ZERO_EXP_SENTINEL
    is_sub = (~is_zero) & (e < (1 - spec.bias))

    normal_bits = ((e + spec.bias).astype(jnp.int64) << spec.man_bits) | man
    full = man | (jnp.int64(1) << spec.man_bits)
    shift = jnp.clip((1 - spec.bias) - e, 0, spec.man_bits + 1).astype(jnp.int64)
    sub_bits = full >> shift

    bits = jnp.where(is_sub, sub_bits, normal_bits)
    bits = jnp.where(is_zero, 0, bits).astype(u)
    return bits | (jnp.asarray(signs).astype(u) << spec.sign_shift)
