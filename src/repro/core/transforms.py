"""The paper's four lossless preprocessing transforms (§3).

All four move a same-binade dataset into regions of the real line where the
top ``D`` mantissa bits are shared (Eq. 7 / Fig. 2-5), so that a downstream
compressor (GD / GreedyGD / zlib) sees more shared bits.

Implementation note (TPU-native adaptation, see DESIGN.md §4/§7):
the paper phrases each transform as IEEE-754 ⊕/⊗ with addends chosen so the
ops are exact (Table 1, Eq. 4, Eq. 6).  We implement the arithmetic on the
*integer significand* ``X = x / ULP(x)`` (int64 here; int32 lanes in the
Pallas kernels) — on that domain every step is exact **by construction**, and
equals what the exact fp op would produce whenever the paper's conditions
hold (validated in tests/test_lossless.py against real fp ⊕/⊖ via 2Sum).
This is both how a production codec would run on TPU VPU lanes and immune to
the representability corner cases of the single-fp-add formulation.

Input convention for the cores: ``X`` int64 in ``[2^l, 2^{l+1})`` — the
significand of a positive normal float in one binade (the paper's
"all numbers have the same exponent" setup; repro.core.pipeline handles
arbitrary sign/exponent via exact normalization metadata).

Window convention: *multiply & shift* and *shift & separate* target the TOP
of each binade (shared top-D mantissa bits all 1, as in Fig. 2/3);
*shift & save evenness* targets the BOTTOM window (shared bits all 0, Eq. 7).
*compact bins* packs toward the top of the source binade.  The compressor is
agnostic to the shared bit VALUE; only the count matters.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .float_bits import F64, FloatSpec

__all__ = [
    "TransformError",
    "CompactBinsMeta",
    "MultiplyShiftMeta",
    "ShiftSeparateMeta",
    "ShiftSaveEvenMeta",
    "compact_bins_forward",
    "compact_bins_inverse",
    "multiply_shift_forward",
    "multiply_shift_inverse",
    "shift_separate_forward",
    "shift_separate_inverse",
    "shift_save_even_forward",
    "shift_save_even_inverse",
    "TRANSFORMS",
]

_HEADER_BITS = 128  # transform id, e*, D/k, n — uniform small header accounting


class TransformError(ValueError):
    """Raised when a transform's domain conditions are not met.

    (e.g. multiply&shift / shift&separate not converging within max_iter —
    the paper's Fig. 7 plateaus; the pipeline treats this as "candidate
    rejected" and falls back to another technique.)
    """


def _as_i64(x):
    return jnp.asarray(x, jnp.int64)


def _check_domain(X, spec: FloatSpec, extrema=None):
    """Validate X in [2^l, 2^{l+1}); returns (min, max) so callers reuse the
    extrema instead of re-syncing (§Perf: one host round-trip per forward,
    no full-array device->host transfer).  ``extrema`` short-circuits the
    device round-trip entirely — the auto-candidate engine computes the
    sample extrema once and shares them across the whole candidate grid."""
    lo = 1 << spec.man_bits
    hi = lo << 1
    if np.size(X) == 0:
        raise TransformError("empty dataset")
    if extrema is not None:
        mn, mx = int(extrema[0]), int(extrema[1])
    else:
        mn, mx = jax.device_get((jnp.min(X), jnp.max(X)))
        mn, mx = int(mn), int(mx)
    if mn < lo or mx >= hi:
        raise TransformError("significands must lie in [2^l, 2^{l+1})")
    return mn, mx


def _check_offsets(n_iter: int, spec: FloatSpec, what: str) -> None:
    """The stored word carries each value's binade offset in its exponent
    field (data normalized to [1, 2) sits at unbiased exponent 0), so an
    offset above the format's largest normal exponent cannot be stored."""
    if n_iter > spec.max_unbiased_exp:
        raise TransformError(
            f"{what} lifts values {n_iter} binades, more than {spec.name}'s "
            f"exponent field holds ({spec.max_unbiased_exp})"
        )


# ===========================================================================
# §3.1 compact bins
# ===========================================================================

@dataclasses.dataclass
class CompactBinsMeta:
    e_star: int
    shifts: np.ndarray       # int64[k]  A_i (significand scale)
    thresholds: np.ndarray   # int64[k-1] transformed-space bin lower bounds

    def nbits(self) -> int:
        # k shift values + (k-1) thresholds (paper §3.1), entropy-packed
        from ..compression.bitplane import compress_int_stream

        return _HEADER_BITS + 8 * (
            len(compress_int_stream(self.shifts))
            + len(compress_int_stream(self.thresholds))
        )


@functools.partial(jax.jit, static_argnames=("k", "l"))
def _cb_core(X, k: int, l: int):
    """Fused §3.1 arithmetic, shared by the forward transform and the
    auto-candidate scorer (core/scoring.py) so the two can never drift.
    Returns (Xt, shifts, new_lo, fits)."""
    top = (jnp.int64(1) << (l + 1)) - 2

    Xs = jnp.sort(X)
    if k > 1:
        gaps = Xs[1:] - Xs[:-1]
        # k-1 largest gaps define bin boundaries (value starting a new bin)
        gi = jnp.argsort(gaps)[-(k - 1):]
        bounds = jnp.sort(Xs[gi + 1])                       # int64[k-1]
    else:
        bounds = jnp.zeros((0,), jnp.int64)

    # per-bin extrema
    lo_all = jnp.concatenate([Xs[:1], bounds])              # [k] bin min
    # bin max: predecessor of next boundary (or global max)
    idx = jnp.searchsorted(Xs, bounds, side="left")         # first elem of bin j+1
    hi_all = jnp.concatenate([Xs[idx - 1] if k > 1 else Xs[:0], Xs[-1:]])  # [k]
    # duplicate boundaries (fewer distinct gaps than k-1) give empty bins with
    # negative nominal width; clamp so packing stays ordered
    widths = jnp.maximum(hi_all - lo_all, 0)

    # pack from the top down with margin 2
    # new_hi[k-1] = top; new_lo[j] = new_hi[j] - width[j]; new_hi[j-1] = new_lo[j]-2
    rev_w = widths[::-1]
    occupied = jnp.cumsum(rev_w + 2)[::-1]                  # width+margin above lo_j
    new_lo = top + 2 - occupied
    shifts = new_lo - lo_all                                # int64[k], >= 0 iff fits

    fits = ~jnp.any(new_lo < (jnp.int64(1) << l))
    bin_id = jnp.searchsorted(bounds, X, side="right") if k > 1 else jnp.zeros(
        X.shape, jnp.int64
    )
    Xt = X + shifts[bin_id]
    return Xt, shifts, new_lo, fits


def compact_bins_forward(X, n_bins: int, spec: FloatSpec = F64, extrema=None):
    """Cluster into ``n_bins`` by largest gaps; pack bins toward binade top.

    In-binade shifts at the shared quantum are exact unconditionally
    (sums of multiples of ULP staying under 2^{E+1} are representable).
    """
    X = _as_i64(X)
    _check_domain(X, spec, extrema)
    k = int(n_bins)
    if k < 1:
        raise TransformError("n_bins must be >= 1")
    if k > int(X.shape[0]):
        raise TransformError("n_bins exceeds dataset size")
    Xt, shifts, new_lo, fits = _cb_core(X, k=k, l=spec.man_bits)
    if not bool(fits):
        raise TransformError("bins do not fit in one binade after packing")
    thresholds = new_lo[1:]                                 # transformed-space
    meta = CompactBinsMeta(
        e_star=0,
        shifts=np.asarray(shifts, np.int64),
        thresholds=np.asarray(thresholds, np.int64),
    )
    return Xt, meta


def _cb_inverse(Xt, shifts, thresholds):
    """§3.1 inverse on arrays, traceable: the public inverse and the
    decode program (core/pipeline.py) share it."""
    Xt = _as_i64(Xt)
    bin_id = (jnp.searchsorted(thresholds, Xt, side="right")
              if thresholds.shape[0] else jnp.zeros(Xt.shape, jnp.int64))
    return Xt - shifts[bin_id]


def compact_bins_inverse(Xt, meta: CompactBinsMeta):
    return _cb_inverse(Xt, jnp.asarray(meta.shifts, jnp.int64),
                       jnp.asarray(meta.thresholds, jnp.int64))


# ===========================================================================
# §3.2 multiply and shift
# ===========================================================================

@dataclasses.dataclass
class MultiplyShiftMeta:
    e_star: int
    D: int
    x_max: int        # defines A_1 (paper stores A_1; a_1 = 2^{l+1}-2-x_max)
    n_iter: int

    def nbits(self) -> int:
        return _HEADER_BITS + 64  # x_max


def _ms_schedule(D: int, x_max: int, spec: FloatSpec):
    l = spec.man_bits
    a1 = max((1 << (l + 1)) - 2 - x_max, 0)
    a_const = (1 << (l - D)) - 2
    thresh = (1 << (l + 1)) - (1 << (l - D))
    return a1, a_const, thresh


def _ms_feasible(D: int, x_min: int, x_max: int, max_iter: int,
                 spec: FloatSpec):
    """Shared host-side feasibility check + schedule for §3.2, used by both
    the forward transform and the phase-1 scorer (single source of truth)."""
    l = spec.man_bits
    if not (1 <= D <= l - 2):
        raise TransformError(f"multiply&shift needs 1 <= D <= {l-2}")
    a1, a_const, thresh = _ms_schedule(D, x_max, spec)
    # feasibility precheck (§Perf C): iterations ~ span / a_const
    if (x_max - x_min) // max(a_const, 1) > max_iter + 1:
        raise TransformError(
            f"multiply&shift would need > {max_iter} iterations (D={D})"
        )
    return a1, a_const, thresh


@functools.partial(jax.jit, static_argnames=("max_iter",))
def _ms_loop(X, a1, a_const, thresh, max_iter: int):
    """jit'd §3.2 iteration (§Perf C: the eager while_loop ran at 5 MB/s;
    jitted it runs two orders of magnitude faster on the same schedule)."""

    def cond(state):
        _, _, active, i = state
        return jnp.any(active) & (i <= max_iter)

    def body(state):
        Xc, off, active, i = state
        a = jnp.where(i == 1, a1, a_const).astype(jnp.int64)
        Xn = jnp.where(active, Xc + a, Xc)
        offn = off + active.astype(jnp.int32)
        cap = active & (Xn >= thresh)
        return Xn, offn, active & ~cap, i + 1

    off0 = jnp.zeros(X.shape, jnp.int32)
    act0 = jnp.ones(X.shape, bool)
    Xf, off, active, _ = lax.while_loop(cond, body, (X, off0, act0, jnp.int32(1)))
    return Xf, off, active


def multiply_shift_forward(X, D: int, max_iter: int = 4096, spec: FloatSpec = F64, extrema=None):
    """Eq.(8): f(x) = (2 ⊗ x) ⊕ A_i, iterated; capture at top-of-binade window.

    Integer domain: scale doubles each iteration (the ⊗2, exact — exponent
    increment), so the shift is the CONSTANT a = 2^(l-D)-2 after the first
    aligning iteration (paper: "store D and A_1; all A_i with i≠1 can be
    computed").  Returns (X', binade_offset, meta).
    """
    X = _as_i64(X)
    x_min, x_max = _check_domain(X, spec, extrema)
    a1, a_const, thresh = _ms_feasible(D, x_min, x_max, max_iter, spec)
    Xf, off, active = _ms_loop(
        X, jnp.int64(a1), jnp.int64(a_const), jnp.int64(thresh), max_iter
    )
    if bool(jnp.any(active)):
        raise TransformError(
            f"multiply&shift did not converge in {max_iter} iterations (D={D})"
        )
    n_iter = int(off.max())
    _check_offsets(n_iter, spec, f"multiply&shift (D={D})")
    meta = MultiplyShiftMeta(e_star=0, D=D, x_max=x_max, n_iter=n_iter)
    return Xf, off, meta


@jax.jit
def _ms_inv_loop(Xt, off, a1, a_const, n_iter):
    def body(k, state):
        Xc, offc = state
        it = n_iter - k                           # n_iter .. 1
        a = jnp.where(it == 1, a1, a_const).astype(jnp.int64)
        sel = offc == it
        return jnp.where(sel, Xc - a, Xc), jnp.where(sel, offc - 1, offc)

    Xr, _ = lax.fori_loop(0, n_iter, body, (Xt, off))
    return Xr


def _ms_inverse_schedule(meta: MultiplyShiftMeta, spec: FloatSpec):
    """Host half of the §3.2 inverse: the arguments after (Xt, off) of
    :func:`_ms_inv_loop`, replayed from the metadata."""
    a1, a_const, _ = _ms_schedule(meta.D, meta.x_max, spec)
    return np.int64(a1), np.int64(a_const), np.int32(meta.n_iter)


def multiply_shift_inverse(Xt, offsets, meta: MultiplyShiftMeta, spec: FloatSpec = F64):
    return _ms_inv_loop(_as_i64(Xt), jnp.asarray(offsets, jnp.int32),
                        *_ms_inverse_schedule(meta, spec))


# ===========================================================================
# §3.3 shift and separate even from odd
# ===========================================================================

@dataclasses.dataclass
class ShiftSeparateMeta:
    e_star: int
    D: int
    x_min: int        # A_align anchor (paper stores A_align, D, W_0)
    x_max: int
    n_iter: int

    def nbits(self) -> int:
        return _HEADER_BITS + 2 * 64  # x_min, x_max


def _ss_feasible(D: int, x_min: int, x_max: int, max_iter: int,
                 spec: FloatSpec):
    """Shared host-side feasibility check + schedule for §3.3, used by both
    the forward transform and the phase-1 scorer (single source of truth)."""
    l = spec.man_bits
    if not (1 <= D <= l - 2):
        raise TransformError(f"shift&separate needs 1 <= D <= {l-2}")
    a_align, thresh_cap, sched = _ss_schedule(D, x_min, x_max, max_iter, spec)
    if not sched or not sched[-1][3]:
        raise TransformError("shift&separate: domain violation (W too large)")
    return a_align, thresh_cap, sched


def _sse_feasible(D: int, spec: FloatSpec) -> int:
    """Shared host-side feasibility check for §3.4; returns w_eff."""
    l = spec.man_bits
    if not (1 <= D <= l - 1):
        raise TransformError(f"shift&save-evenness needs 1 <= D <= {l-1}")
    w_eff = (1 << (l + 1 - D)) - 2
    if w_eff < 1:
        raise TransformError("window too small")
    return w_eff


def _ss_schedule(D: int, x_min: int, x_max: int, n_iter: int, spec: FloatSpec):
    """Deterministic per-iteration (Ae, Ao, T, parity-threshold) schedule.

    Replayed identically by forward and inverse from the metadata.
    """
    l = spec.man_bits
    top2 = (1 << (l + 2)) - 2          # top of the next binade (y2 scale)
    thresh_cap = (1 << (l + 1)) - (1 << (l - D))
    a_align = (1 << (l + 1)) - 2 - x_max
    lo = x_min + a_align
    hi = (1 << (l + 1)) - 2
    sched = []
    for _ in range(n_iter):
        W = hi - lo
        Ae = (top2 - hi) & ~1
        Wsep = (W + 2) | 1
        Ao = Ae - Wsep
        T = (Ae + lo) >> 1             # y < T  <=>  source was odd
        if (Ao + lo) < (1 << (l + 1)):
            # odd image would fall below the next binade -> domain violation
            sched.append((Ae, Ao, T, False))
            break
        sched.append((Ae, Ao, T, True))
        lo = (Ao + lo) >> 1
        hi = thresh_cap - 1
        if hi - lo >= W:               # no progress: diverging
            break
    return a_align, thresh_cap, sched


@jax.jit
def _ss_loop(Xc, Ae, Ao, thresh_cap):
    """Fused §3.3 iteration: one `lax.scan` over the precomputed (Ae, Ao)
    schedule (§Perf: the eager loop synced host<->device with a
    `bool(jnp.any(...))` every iteration; mirrors the `_ms_loop` treatment).
    Returns (X', offsets, any_still_active, max_offset) as device values so
    the caller fetches everything in a single round-trip."""

    def step(carry, a):
        X, off, active = carry
        ae, ao = a
        A = jnp.where((X & 1).astype(bool), ao, ae)
        Y = (X + A) >> 1
        Xn = jnp.where(active, Y, X)
        offn = off + active.astype(jnp.int32)
        return (Xn, offn, active & (Xn < thresh_cap)), None

    init = (Xc, jnp.zeros(Xc.shape, jnp.int32), jnp.ones(Xc.shape, bool))
    (Xf, off, active), _ = lax.scan(step, init, (Ae, Ao))
    return Xf, off, jnp.any(active), off.max()


def shift_separate_forward(X, D: int, max_iter: int = 64, spec: FloatSpec = F64, extrema=None):
    """Eq.(9)/(10): parity-matched addends; even/odd images kept disjoint so
    the inverse recovers evenness from position (Eq. 11). Returns
    (X', binade_offset, meta)."""
    X = _as_i64(X)
    x_min, x_max = _check_domain(X, spec, extrema)
    a_align, thresh_cap, sched = _ss_feasible(D, x_min, x_max, max_iter, spec)

    valid = [(Ae, Ao) for (Ae, Ao, _T, ok) in sched if ok]
    Xf, off, any_active, max_off = _ss_loop(
        X + jnp.int64(a_align),
        jnp.asarray([a for a, _ in valid], jnp.int64),
        jnp.asarray([a for _, a in valid], jnp.int64),
        jnp.int64(thresh_cap),
    )
    any_active, max_off = jax.device_get((any_active, max_off))
    if bool(any_active):
        raise TransformError(
            f"shift&separate did not converge (D={D}); paper plateau regime"
        )
    n_iter = int(max_off)
    _check_offsets(n_iter, spec, f"shift&separate (D={D})")
    meta = ShiftSeparateMeta(e_star=0, D=D, x_min=x_min, x_max=x_max, n_iter=n_iter)
    return Xf, off, meta


@jax.jit
def _ss_inv_loop(Xt, off, a_align, Ae, Ao, T, its):
    def step(carry, a):
        X, offc = carry
        ae, ao, t, it = a
        sel = offc == it
        odd = X < t
        Xprev = (X << 1) - jnp.where(odd, ao, ae)
        return (jnp.where(sel, Xprev, X), jnp.where(sel, offc - 1, offc)), None

    (Xr, _), _ = lax.scan(step, (Xt, off), (Ae, Ao, T, its))
    return Xr - a_align


def _ss_inverse_schedule(meta: ShiftSeparateMeta, spec: FloatSpec):
    """Host half of the §3.3 inverse: the arguments after (Xt, off) of
    :func:`_ss_inv_loop`, the forward's schedule replayed from the metadata
    from iteration ``n_iter`` down to 1."""
    a_align, _, sched = _ss_schedule(meta.D, meta.x_min, meta.x_max, meta.n_iter, spec)
    steps = sched[: meta.n_iter][::-1]
    return (
        np.int64(a_align),
        np.asarray([s[0] for s in steps], np.int64),
        np.asarray([s[1] for s in steps], np.int64),
        np.asarray([s[2] for s in steps], np.int64),
        np.arange(meta.n_iter, 0, -1, dtype=np.int32),
    )


def shift_separate_inverse(Xt, offsets, meta: ShiftSeparateMeta, spec: FloatSpec = F64):
    return _ss_inv_loop(_as_i64(Xt), jnp.asarray(offsets, jnp.int32),
                        *_ss_inverse_schedule(meta, spec))


# ===========================================================================
# §3.4 shift and save evenness
# ===========================================================================

@dataclasses.dataclass
class ShiftSaveEvenMeta:
    e_star: int
    D: int
    x_min: int
    n_chunks: int
    chunk_ids: np.ndarray   # int64[n] — entropy-packed on disk
    evenness: np.ndarray    # uint8[n] (1 bit each, zlib'd on disk)

    def _packed(self):
        import zlib

        from ..compression.bitplane import compress_int_stream

        ids_z = compress_int_stream(self.chunk_ids)
        even_z = zlib.compress(np.packbits(self.evenness).tobytes(), 6)
        return ids_z, even_z

    def nbits(self) -> int:
        ids_z, even_z = self._packed()
        return _HEADER_BITS + 64 + 8 * (len(ids_z) + len(even_z))


@jax.jit
def _sse_core(X, x_min, w_eff, top):
    """Fused §3.4 arithmetic: one dispatch per candidate instead of ~10
    eager ops (§Perf — this runs once per D in the auto-candidate grid)."""
    j = (X - x_min) // w_eff
    a_base = top - x_min - j * w_eff
    a_even = a_base + (a_base & 1)            # round UP to even
    parity = X & 1
    A = a_even + parity                       # parity(A) == parity(X) => exact
    Y = (X + A) >> 1                          # significand at binade e*+1
    return Y, j, parity.astype(jnp.uint8), j.max()


def shift_save_even_forward(X, D: int, spec: FloatSpec = F64, extrema=None):
    """§3.4: single-pass chunk overlay with per-sample evenness metadata.

    Equivalent one-pass form of the paper's iteration (each iteration of the
    paper's formulation captures one more chunk into the window; the chunk
    index is exactly "the iteration at which a sample was captured", so we
    store ceil(log2 k) bits/sample instead of 1 bit × n_iter — never larger).
    All samples land in the bottom window of binade e*+1 (top-D mantissa
    bits = 0, Eq. 7). Returns (X', meta); binade offset is 1 for all samples.
    """
    X = _as_i64(X)
    x_min, _x_max = _check_domain(X, spec, extrema)
    l = spec.man_bits
    w_eff = _sse_feasible(D, spec)
    Y, j, parity, j_max = _sse_core(
        X, jnp.int64(x_min), jnp.int64(w_eff), jnp.int64(1) << (l + 1)
    )
    j_np, parity_np, j_max = jax.device_get((j, parity, j_max))
    meta = ShiftSaveEvenMeta(
        e_star=0,
        D=D,
        x_min=x_min,
        n_chunks=int(j_max) + 1,
        chunk_ids=np.asarray(j_np, np.int64),
        evenness=parity_np,
    )
    return Y, meta


def _sse_inverse(Yt, chunk_ids, evenness, x_min, D, l: int):
    """§3.4 inverse on arrays, traceable (``x_min`` and ``D`` may be traced
    scalars): the public inverse and the decode program share it."""
    Y2 = _as_i64(Yt) << 1
    j = _as_i64(chunk_ids)
    w_eff = (jnp.int64(1) << (l + 1 - D)) - 2
    a_base = (jnp.int64(1) << (l + 1)) - x_min - j * w_eff
    a_even = a_base + (a_base & 1)
    A = a_even + _as_i64(evenness)
    return Y2 - A


def shift_save_even_inverse(Yt, meta: ShiftSaveEvenMeta, spec: FloatSpec = F64):
    return _sse_inverse(Yt, meta.chunk_ids, meta.evenness, meta.x_min,
                        meta.D, spec.man_bits)


# ===========================================================================
# registry (unified (forward, inverse) returning (X', offsets, meta))
# ===========================================================================

def _cb_fwd(X, *, n_bins=8, spec=F64, extrema=None, **_):
    Xt, meta = compact_bins_forward(X, n_bins, spec, extrema)
    return Xt, jnp.zeros(Xt.shape, jnp.int32), meta


def _cb_inv(Xt, offsets, meta, spec=F64):
    return compact_bins_inverse(Xt, meta)


def _ms_fwd(X, *, D=8, max_iter=4096, spec=F64, extrema=None, **_):
    return multiply_shift_forward(X, D, max_iter, spec, extrema)


def _ss_fwd(X, *, D=4, max_iter=64, spec=F64, extrema=None, **_):
    return shift_separate_forward(X, D, max_iter, spec, extrema)


def _se_fwd(X, *, D=12, spec=F64, extrema=None, **_):
    Y, meta = shift_save_even_forward(X, D, spec, extrema)
    return Y, jnp.ones(Y.shape, jnp.int32), meta


def _se_inv(Yt, offsets, meta, spec=F64):
    return shift_save_even_inverse(Yt, meta, spec)


def _id_fwd(X, *, spec=F64, **_):
    return _as_i64(X), jnp.zeros(jnp.shape(X), jnp.int32), None


def _id_inv(Xt, offsets, meta, spec=F64):
    return _as_i64(Xt)


TRANSFORMS = {
    "identity": (_id_fwd, _id_inv),
    "compact_bins": (_cb_fwd, _cb_inv),
    "multiply_shift": (_ms_fwd, lambda Xt, off, m, spec=F64: multiply_shift_inverse(Xt, off, m, spec)),
    "shift_separate": (_ss_fwd, lambda Xt, off, m, spec=F64: shift_separate_inverse(Xt, off, m, spec)),
    "shift_save_even": (_se_fwd, _se_inv),
}
