"""Losslessness conditions for IEEE-754 operations (paper §2.1).

The paper states three conditions:

* **Table 1** — same-binade addition crossing one exponent boundary
  (``x, A ∈ [2^E, 2^{E+1})``, ``x⊕A ∈ [2^{E+1}, 2^{E+2})``) is exact iff the
  last mantissa bits match: ``m_l(x) == m_l(A)`` ("same evenness").
* **Eq. (4)** — addition of a smaller-exponent addend with the result staying
  in x's binade is exact when the addend's low mantissa bits are zero.
* **Eq. (6)** — multiplication crossing one exponent boundary is exact for
  ``M >= 2`` (and exactly so for ``M = 2``, which never touches the mantissa).

All three are corollaries of one integer-domain fact that this module exposes
as the *unified predicate*: writing ``q = ULP(x)`` and viewing x and A as
integer multiples of q (``X = x/q``, ``a = A/q``), the sum is exact iff
``X + a`` is representable at the result's quantum — i.e. iff ``X + a`` is a
multiple of ``ULP(result)/q``.  For a one-binade crossing that quantum ratio
is 2, giving the parity rule that unifies Table 1 and Eq. (4).

`add_is_exact` is the authoritative *runtime* oracle (Knuth 2Sum: computes the
exact rounding error of ⊕ using only ⊕/⊖); the bit-level predicates are the
*constructive* rules used by the transforms to choose addends.
"""
from __future__ import annotations

import jax.numpy as jnp

from .float_bits import FloatSpec, F64, compose_bits, mantissa, spec_for, to_bits


# ---------------------------------------------------------------------------
# runtime oracle: exact error of floating-point addition (Knuth 2Sum)
# ---------------------------------------------------------------------------

def two_sum(a, b):
    """Return (s, e) with s = a ⊕ b and e = (a + b) - s exactly.

    Valid in round-to-nearest for any finite a, b (Knuth; Handbook of
    Floating-Point Arithmetic [10], §4.3.2).
    """
    s = a + b
    a1 = s - b
    b1 = s - a1
    da = a - a1
    db = b - b1
    return s, da + db


def add_is_exact(a, b):
    """True where a ⊕ b incurs no rounding error."""
    _, e = two_sum(a, b)
    return e == 0


def sub_is_exact(a, b):
    return add_is_exact(a, -b)


# ---------------------------------------------------------------------------
# constructive bit-level predicates
# ---------------------------------------------------------------------------

def same_evenness(x, a, spec: FloatSpec | None = None):
    """Table 1 condition: last mantissa bits equal.

    For x, A in the same binade with x⊕A crossing one exponent boundary, this
    is necessary & sufficient for exactness (the shifted-out guard bit is
    m_l(x) XOR m_l(A)).
    """
    spec = spec or spec_for(x)
    one = spec.uint_dtype(1)
    return (mantissa(x, spec) & one) == (mantissa(a, spec) & one)


def eq4_condition(a, e_star: int, spec: FloatSpec | None = None):
    """Paper Eq.(4) regime: x in binade e*, small addend A, result in binade e*.

    Exact iff A is an integer multiple of ULP(x) = 2^(e* - l): i.e. iff the
    low (e* - e_A) mantissa bits of A are zero.  (The paper's Eq.(4) asks for
    one extra zero bit — a conservative margin for a carry into binade e*+1;
    our transforms exclude the carry by construction and use the tight form.)
    """
    spec = spec or spec_for(a)
    e_a = (to_bits(a, spec) >> spec.man_bits).astype(jnp.int32) & spec.exp_mask
    s = (e_star + spec.bias) - e_a  # right-shift applied to A's significand
    man = mantissa(a, spec)
    shift = jnp.clip(s, 0, spec.man_bits).astype(spec.uint_dtype)
    low_bits = man & ((spec.uint_dtype(1) << shift) - spec.uint_dtype(1))
    return (s <= 0) | ((s <= spec.man_bits) & (low_bits == 0))


def round_addend_to_quantum(a, quantum_exp, spec: FloatSpec = F64):
    """Largest a' <= a that is an integer multiple of 2^quantum_exp.

    Used to "round A down ... to the first value fulfilling Eq.(4)" (§3.2).
    Positive a only.
    """
    spec = spec
    b = to_bits(a, spec)
    e_a = ((b >> spec.man_bits) & spec.uint_dtype(spec.exp_mask)).astype(jnp.int32)
    shift = (quantum_exp + spec.bias + spec.man_bits) - e_a  # low bits to clear
    shift_c = jnp.clip(shift, 0, spec.man_bits).astype(spec.uint_dtype)
    cleared = b & ~((spec.uint_dtype(1) << shift_c) - spec.uint_dtype(1))
    out = jnp.where(shift <= 0, b, cleared)
    # a < 2^quantum_exp  ->  0
    from .float_bits import from_bits, pow2

    res = from_bits(out, spec)
    return jnp.where(a < pow2(jnp.int32(quantum_exp), spec), spec.float_dtype(0), res)


def mul_pow2_is_exact(x, k: int, spec: FloatSpec | None = None):
    """x ⊗ 2^k is exact iff the result stays in the normal range.

    This is the paper's M = 2 case (Eq. 6 with equality): a power-of-two
    factor only changes the exponent field, never the mantissa.
    """
    spec = spec or spec_for(x)
    e = (to_bits(x, spec) >> spec.man_bits).astype(jnp.int32) & spec.exp_mask
    new_e = e + k
    ok = (new_e >= 1) & (new_e <= spec.exp_mask - 1)
    return ok | (x == 0)


# ---------------------------------------------------------------------------
# unified integer-significand view (used by the transforms)
# ---------------------------------------------------------------------------

def significand_from_bits(b, spec: FloatSpec = F64):
    """X = x / 2^(e*-l) as integer, for the bit words ``b`` of x in binade
    e* (|x| in [2^e*, 2^{e*+1})).

    X is in [2^l, 2^{l+1}).  The transforms do all their arithmetic on X
    (exact by construction); see module docstring.
    """
    man = (jnp.asarray(b, spec.uint_dtype) & spec.uint_dtype(spec.man_mask))
    return man.astype(jnp.int64) + (jnp.int64(1) << spec.man_bits)


def significand_to_bits(X, e_star, spec: FloatSpec = F64):
    """Inverse of :func:`significand_from_bits`, with per-element binade
    e_star: the bit words of the float with significand X at binade e_star.

    X in [2^l, 2^{l+1}) (int64), e_star int32 array or scalar.  The codec's
    device programs stop at the bit words; the float view is taken on the
    host (``ndarray.view``)."""
    X = jnp.asarray(X, jnp.int64)
    e = jnp.asarray(e_star, jnp.int32)
    man = (X - (jnp.int64(1) << spec.man_bits)).astype(spec.uint_dtype)
    return compose_bits(jnp.uint32(0), e + spec.bias, man, spec)
