"""End-to-end lossless codec: arbitrary float array -> transformed array + metadata.

Generalizes the paper's "all numbers have the same exponent, non-negative"
setup (§3) exactly the way the paper suggests: per-sample sign/exponent
stored as (compressed) metadata, plus a passthrough mask for zeros and
non-finite values (kept verbatim, excluded from the transform).  The
transform then operates on same-binade significands.

``encode(x, method=...)`` -> :class:`Encoded`;  ``decode(enc)`` -> x, bitwise.
``method="auto"`` implements the paper's Fig. 6 "best of the four techniques"
selection as a two-phase engine:

* **Phase 1 — sample-select.**  The WHOLE candidate grid runs as ONE
  stacked jit dispatch on a strided sample (:mod:`repro.core.scoring`:
  every family's forward arithmetic + the fused ``kernels/scoregrid``
  bit-statistics estimator over the stacked ``[n_candidates, sample]``
  word grid), fetched with a single ``device_get``.  The per-family jits
  of PR 1 stay selectable via ``engine="perfamily"`` (or the
  ``REPRO_SCORING_ENGINE`` env var) as the A/B flag and parity oracle —
  scores and winners are bitwise-identical between engines.  Only the top
  finalists (plus the identity no-prep baseline) are re-scored with the
  real compressor (zlib by default; any ``size_fn`` can be passed).
* **Phase 2 — chunked apply + verify.**  The winner is applied to the full
  array and round-trip verified chunk by chunk, with the verification
  verdicts reduced on device and fetched together with the transformed
  values — one round-trip.  A candidate that fails verification is
  *rejected, never shipped*; the engine falls back to the next finalist and
  ultimately to identity (which always round-trips).

When a custom ``size_fn`` is supplied, selection scores every candidate with
it exactly (the seed semantics, used by the compressor-matched metric tests);
the vectorized transform kernels keep that path fast too.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import zlib
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import plans
from . import scoring as S
from . import transforms as T
from .float_bits import (
    BF16,
    F16,
    F32,
    F64,
    FloatSpec,
    denormalize_bits,
    normalize_bits,
    spec_for,
)
from .lossless import significand_from_bits, significand_to_bits

SPECS = {"f64": F64, "f32": F32, "bf16": BF16, "f16": F16}

DEFAULT_CANDIDATES = (
    ("identity", {}),
    ("compact_bins", {"n_bins": 4}),
    ("compact_bins", {"n_bins": 16}),
    ("compact_bins", {"n_bins": 64}),
    ("multiply_shift", {"D": 4}),
    ("multiply_shift", {"D": 6}),
    ("multiply_shift", {"D": 8}),
    ("shift_separate", {"D": 2}),
    ("shift_separate", {"D": 3}),
    ("shift_separate", {"D": 4}),
    ("shift_save_even", {"D": 8}),
    ("shift_save_even", {"D": 12}),
    ("shift_save_even", {"D": 16}),
    ("shift_save_even", {"D": 24}),
    ("shift_save_even", {"D": 32}),
    ("shift_save_even", {"D": 40}),
    ("shift_save_even", {"D": 48}),
)

# phase-1 sample size (strided); full data below this is scored directly.
# 4096 keeps winner agreement with full-zlib scoring at 95% on the test
# corpus (tests/test_scoring.py) while halving phase-1 device compute.
DEFAULT_SAMPLE_ELEMS = 4096
# finalists re-scored with the real compressor (identity is always added).
# With family-diverse selection, 4 slots = the best candidate of each of the
# paper's four techniques — selection literally becomes Fig. 6's "best of
# the four", with the analytic proxy only choosing each family's parameter.
DEFAULT_TOP_K = 4
# measured residual error band of the analytic size proxy (docs/perf.md):
# when a family's top candidates rank within this relative margin, the
# per-sample metadata model is not trustworthy enough to pick between them
# — the engine probes the real (compressed) metadata streams instead.
PROXY_TIE_BAND = 0.05
# phase-2 verification chunk granularity (memory bound, not a perf knob)
DEFAULT_CHUNK_ELEMS = 1 << 20
# phase-1 scoring engine: "stacked" = the whole candidate grid in ONE jit
# dispatch + ONE device_get (core/scoring.py + kernels/scoregrid);
# "perfamily" = one fused jit per candidate (PR 1) — the A/B flag and the
# stacked engine's parity oracle.  Winners are identical by construction
# (asserted bitwise in tests/test_scoring.py).  The env var is read at
# call time so flipping it mid-process (tests, notebooks) takes effect.
_ENGINES = ("stacked", "perfamily")


def default_engine() -> str:
    return os.environ.get("REPRO_SCORING_ENGINE", "stacked")


@dataclasses.dataclass
class Encoded:
    """Transformed dataset + everything needed to invert it, with honest
    metadata accounting (Eq. 1 numerator's "+ Compression metadata")."""

    method: str
    params: dict
    data: np.ndarray            # transformed floats, same shape/dtype as input
    meta: object                # transform-specific meta (or None for identity)
    exponents_z: bytes          # zlib'd int16 per-sample unbiased exponents
    signs_z: bytes              # zlib'd packed sign bits
    passthrough_z: bytes        # zlib'd packed passthrough mask
    spec_name: str
    n: int                      # total element count
    n_active: int               # elements that went through the transform
    # fused-encode product: the data stream already entropy-coded on device
    # (one framed rANS payload, byte-identical to compressing ``data`` with
    # ``payload_backend`` on host).  ``serialize_chunk`` ships it verbatim
    # when the container backend matches; otherwise it is ignored.
    payload: bytes | None = None
    payload_backend: str = ""

    def metadata_bytes(self) -> int:
        return (_meta_bytes(self.meta) + len(self.exponents_z)
                + len(self.signs_z) + len(self.passthrough_z))


def _pack_z(bits: np.ndarray) -> bytes:
    return zlib.compress(np.packbits(bits.astype(np.uint8)).tobytes(), 6)


def _unpack_z(z: bytes, n: int) -> np.ndarray:
    # capped decompress: n is known, so a corrupt/hostile stream can never
    # expand past the ceil(n/8) packbits bytes it claims to hold
    from ..container.backends import zlib_decompress_capped

    raw = zlib_decompress_capped(z, -(-n // 8))
    return np.unpackbits(np.frombuffer(raw, np.uint8))[:n]


def _slice_meta(meta, s: int, e: int):
    """Slice per-sample metadata fields for chunked inverse verification."""
    if isinstance(meta, T.ShiftSaveEvenMeta):
        return dataclasses.replace(
            meta, chunk_ids=meta.chunk_ids[s:e], evenness=meta.evenness[s:e]
        )
    return meta


def _meta_bytes(meta) -> int:
    return -(-meta.nbits() // 8) if meta is not None else 16


# the classic path pads its significands to the rANS scans' length ladder,
# from a writer's default chunk up, so a family compiles one set of
# programs for every chunk of a default writer (its tail chunks included)
# and O(log) beyond; the pad repeats the chunk's last value, which leaves
# these families' elementwise steps and min/max/any reductions unchanged.
# Compact bins is not padded: copies of a value change its gap ranking.
CLASSIC_MIN_ELEMS = 1 << 16
_PAD_INVARIANT = frozenset({"multiply_shift", "shift_separate",
                            "shift_save_even"})


def _classic_pad(name: str, X):
    """``X`` padded to its classic-path length (a device array either
    way).  The pad is made on the host: a device-side pad would itself
    compile once per input length."""
    from ..kernels.rans.kernel import bucket_steps

    n = int(X.shape[0])
    n_pad = bucket_steps(n, floor=CLASSIC_MIN_ELEMS)
    if name not in _PAD_INVARIANT or n_pad == n:
        return X
    with obs.span("encode.device_wait"):
        xh = np.asarray(X)
    return jnp.asarray(np.concatenate([xh, np.full(n_pad - n, xh[-1])]))


@obs.span("encode.classic")
def _apply_and_verify(name, p, X, spec, chunk_elems=DEFAULT_CHUNK_ELEMS):
    """Run candidate `name` forward on the full significand array, verify the
    inverse chunk-by-chunk, and fetch (values, offsets, verdict) in a single
    device round-trip.  Returns None if the round-trip fails; raises
    TransformError if the transform's domain conditions reject the data."""
    fwd, inv = T.TRANSFORMS[name]
    n = int(X.shape[0])
    X = _classic_pad(name, X)
    n_pad = int(X.shape[0])
    Xt, off, meta = fwd(X, spec=spec, **p)
    ok = jnp.bool_(True)
    for s in range(0, n_pad, chunk_elems):
        e = min(s + chunk_elems, n_pad)
        Xr = inv(Xt[s:e], off[s:e], _slice_meta(meta, s, e), spec=spec)
        ok = ok & jnp.all(Xr == X[s:e])
    vals = significand_to_bits(Xt, off.astype(jnp.int32), spec)
    with obs.span("encode.device_wait"):
        vals_np, ok_np = jax.device_get((vals, ok))
    if not bool(ok_np):
        return None
    return vals_np[:n].view(spec.float_dtype), _slice_meta(meta, 0, n)


# ---------------------------------------------------------------------------
# fused device-resident encode: winner-apply + verify + byte-pack + rANS
# entropy coding in ONE jit dispatch, fetched with ONE device_get
# ---------------------------------------------------------------------------

# families whose forward AND inverse are fully traceable from in-graph
# state: identity (raw bytes), shift&save-evenness (x_min from jnp.min) and
# compact_bins (bin schedule from the in-graph sort).  multiply_shift /
# shift_separate derive their addend schedules on host from concrete
# extrema, so they ship through the classic path — a PHASE2 fallback.
# Phase 1 picks them only where their records are the smallest: on model
# weights drawn as models/common.py draws them, in f64, f32, bf16 and f16,
# identity's record is (tests/test_selection_dtypes.py), and on sensor
# archives shift&save-evenness's is, so a fallback should be rare.
FUSED_FAMILIES = ("identity", "shift_save_even", "compact_bins")
# below this many payload bytes the scan's fixed dispatch + compile cost
# beats the win; the classic host path is used (not counted as a fallback)
FUSED_MIN_BYTES = 4096


@functools.lru_cache(maxsize=64)
def _fused_program(method: str, pkey: tuple, spec_name: str, n_active: int,
                   n_bytes: int, steps: int, lanes: int):
    """Build (and cache per static shape) the fused encode program.

    The returned jit computes, in ONE dispatch: forward transform ->
    in-graph inverse round-trip verdict -> transformed values -> LE byte
    stream (``lax.bitcast_convert_type``) -> byte histogram ->
    ``quantize_freqs_dev`` frequency table -> reversed interleaved-lane
    rANS encode scan (``kernels/rans/kernel.encode_scan_body``).  The host
    side fetches everything with one ``device_get`` and finishes with
    ``ref.assemble_frame`` — byte-identical to the normative ``ref.py``
    producer by construction (same table, same emission order)."""
    from ..kernels.rans import kernel as K

    spec = SPECS[spec_name]
    p = dict(pkey)
    l = spec.man_bits

    def entropy(padded, n):
        """``padded``: the ``n`` stream bytes, zero-padded to ``steps *
        lanes``."""
        b = padded.astype(jnp.int32)
        with jax.named_scope("histogram"):
            # the padding's zeros are no symbols of the stream
            hist = jnp.bincount(b, length=256) - jnp.where(
                jnp.arange(256) == 0, b.shape[0] - n, 0)
            freq = K.quantize_freqs_dev(hist).astype(jnp.int32)
            cum = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                   jnp.cumsum(freq)[:-1]])
        sym = b.reshape(steps, lanes)

        def step(x, xs):
            t, s = xs
            return K.encode_scan_body(x, t, s, n, freq, cum, lanes)

        with jax.named_scope("lane_scan"):
            x, (b0, b1, e0, e1) = jax.lax.scan(
                step, jnp.full((lanes,), K.RANS_L, jnp.int32),
                (jnp.arange(steps, dtype=jnp.int32), sym), reverse=True,
            )
        return freq, b0, b1, e0, e1, x

    def val_entropy(vals):
        with jax.named_scope("pack"):
            b = jax.lax.bitcast_convert_type(vals, jnp.uint8).reshape(-1)
        return entropy(jnp.pad(b, (0, steps * lanes - n_bytes)),
                       jnp.int32(n_bytes))

    if method == "identity":
        # the length is an argument: every length of one step bucket shares
        # the program
        @jax.jit
        def run_id(padded, n):
            return (jnp.bool_(True),) + entropy(padded, n)

        return run_id

    if method == "shift_save_even":
        w_eff = T._sse_feasible(int(p["D"]), spec)   # static; may raise

        @jax.jit
        def run_sse(X):
            lo = jnp.int64(1) << l
            top = jnp.int64(1) << (l + 1)
            with jax.named_scope("apply"):
                x_min = jnp.min(X)
                ok = (x_min >= lo) & (jnp.max(X) < (lo << 1))
                Y, j, parity, j_max = T._sse_core(X, x_min,
                                                  jnp.int64(w_eff), top)
            with jax.named_scope("verify"):
                # in-graph inverse verification (same arithmetic as
                # shift_save_even_inverse, replayed from the traced meta)
                a_base = top - x_min - j * jnp.int64(w_eff)
                A = a_base + (a_base & 1) + parity.astype(jnp.int64)
                ok &= jnp.all((Y << 1) - A == X)
            with jax.named_scope("pack"):
                vals = significand_to_bits(Y, jnp.ones(Y.shape, jnp.int32),
                                           spec)
            return (ok, vals) + val_entropy(vals) + (x_min, j, parity, j_max)

        return run_sse

    if method == "compact_bins":
        k = int(p["n_bins"])
        if not (1 <= k <= n_active):
            return None

        @jax.jit
        def run_cb(X):
            lo = jnp.int64(1) << l
            with jax.named_scope("apply"):
                ok = (jnp.min(X) >= lo) & (jnp.max(X) < (lo << 1))
                Xt, shifts, new_lo, fits = T._cb_core(X, k=k, l=l)
                thr = new_lo[1:]
            with jax.named_scope("verify"):
                bin_id = (jnp.searchsorted(thr, Xt, side="right") if k > 1
                          else jnp.zeros(Xt.shape, jnp.int64))
                ok &= fits & jnp.all(Xt - shifts[bin_id] == X)
            with jax.named_scope("pack"):
                vals = significand_to_bits(
                    Xt, jnp.zeros(Xt.shape, jnp.int32), spec)
            return (ok, vals) + val_entropy(vals) + (shifts, thr)

        return run_cb

    return None


def _fell_back(prep: "_Prepared") -> None:
    """Count a chunk that the classic host path encoded in the fused
    path's stead.  Below ``FUSED_MIN_BYTES`` the classic path is the
    design for every method, identity's included, so it is not counted."""
    if prep.n_active * (prep.spec.width // 8) >= FUSED_MIN_BYTES:
        S.PHASE2.fallbacks += 1


@obs.span("encode.frame")
def _fused_frame(lanes: int, n_bytes: int, freq, b0, b1, e0, e1, x) -> bytes:
    from ..kernels.rans import ref as R

    head = R._HEADER.pack(R.FRAME_VERSION, lanes, n_bytes)
    return R.assemble_frame(head, np.asarray(freq, np.int64), x, b0, b1,
                            e0, e1)


def _fused_geometry(n_bytes: int):
    from ..kernels.rans import ops as rans_ops, ref as R
    from ..kernels.rans.kernel import bucket_steps

    lanes = R.clamp_lanes(rans_ops.default_lanes(), n_bytes)
    return lanes, bucket_steps(-(-n_bytes // lanes))


@obs.span("encode.fused")
def _fused_identity(xf: np.ndarray, shape, spec_name: str) -> Encoded | None:
    """Identity chunk with the data stream rANS-coded on device (stats pass
    + lane scan in one dispatch); None when too small to pay for a scan."""
    n_bytes = xf.nbytes
    if n_bytes < FUSED_MIN_BYTES:
        return None
    lanes, steps = _fused_geometry(n_bytes)
    prog = _fused_program("identity", (), spec_name, 0, 0, steps, lanes)
    padded = np.zeros(steps * lanes, np.uint8)
    padded[:n_bytes] = np.ascontiguousarray(xf).view(np.uint8)
    S.PHASE2.dispatches += 1
    out = prog(padded, np.int32(n_bytes))
    with obs.span("encode.device_wait"):
        out = jax.device_get(out)
    S.PHASE2.device_gets += 1
    _ok, freq, b0, b1, e0, e1, x = out
    return Encoded(
        method="identity", params={}, data=xf.copy().reshape(shape),
        meta=None, exponents_z=b"", signs_z=b"", passthrough_z=b"",
        spec_name=spec_name, n=int(xf.shape[0]), n_active=0,
        payload=_fused_frame(lanes, n_bytes, freq, b0, b1, e0, e1, x),
        payload_backend="rans",
    )


@obs.span("encode.fused")
def _fused_encode(prep: "_Prepared", name: str, p: dict) -> Encoded | None:
    """Encode one chunk through the fused device program; returns the
    Encoded carrying the framed rANS payload, or None when this
    (method, data) pair is not fusible (untraceable family, passthrough
    scatter, sub-threshold size) or the in-graph verification rejected the
    transform (the caller's classic path re-derives the verdict)."""
    if name not in FUSED_FAMILIES:
        return None
    if name == "identity":
        return _fused_identity(prep.xf, prep.shape, prep.spec.name)
    if prep.n_active != prep.n or prep.X is None:
        return None          # passthrough scatter stays on the classic path
    spec = prep.spec
    n_bytes = prep.n_active * (spec.width // 8)
    if n_bytes < FUSED_MIN_BYTES:
        return None
    lanes, steps = _fused_geometry(n_bytes)
    try:
        prog = _fused_program(name, tuple(sorted(p.items())), spec.name,
                              prep.n_active, n_bytes, steps, lanes)
    except T.TransformError:
        return None
    if prog is None:
        return None
    S.PHASE2.dispatches += 1
    out = prog(prep.X)
    with obs.span("encode.device_wait"):
        out = jax.device_get(out)
    S.PHASE2.device_gets += 1
    if not bool(out[0]):
        return None          # rejected in-graph: never shipped
    if name == "shift_save_even":
        _ok, vals, freq, b0, b1, e0, e1, x, x_min, j, parity, j_max = out
        meta = T.ShiftSaveEvenMeta(
            e_star=0, D=int(p["D"]), x_min=int(x_min),
            n_chunks=int(j_max) + 1, chunk_ids=np.asarray(j, np.int64),
            evenness=np.asarray(parity, np.uint8),
        )
    else:
        _ok, vals, freq, b0, b1, e0, e1, x, shifts, thr = out
        meta = T.CompactBinsMeta(
            e_star=0, shifts=np.asarray(shifts, np.int64),
            thresholds=np.asarray(thr, np.int64),
        )
    enc = prep.finish(name, dict(p), np.asarray(vals).view(spec.float_dtype),
                      meta)
    enc.payload = _fused_frame(lanes, n_bytes, freq, b0, b1, e0, e1, x)
    enc.payload_backend = "rans"
    return enc


# ---------------------------------------------------------------------------
# selection plan cache (§Perf PR 7, hardened PR 8): streaming writers and
# repeated small-chunk encodes re-run full phase-1 selection on identical
# content (probe samples, re-encoded chunks).  The ranked candidate list is
# cached by a digest of the exact strided sample plus every knob that shapes
# the plan; a hit skips phase 1 entirely.  Correctness is unaffected:
# whatever plan comes out, phase 2 still apply+verifies every shipped chunk.
# Direct `select_method` calls stay uncached, so the PHASE1 counter
# contracts (tests + CI `_counts`) keep their exact meaning.  The store
# itself is a locked LRU (`core.plans.PlanStore`): a hit refreshes recency
# — a hot key survives any number of cold inserts — and concurrent
# encoders (threaded checkpoint save/restore) mutate it safely.
# ---------------------------------------------------------------------------

_PLAN_CACHE = plans.PlanStore(max_items=128)


def _freeze_candidates(candidates) -> tuple:
    return tuple((n_, tuple(sorted(p_.items()))) for n_, p_ in candidates)


def _plan_key(xf, n: int, spec_name: str, candidates, sample_elems, top_k,
              engine, backend):
    s = _strided(xf, sample_elems)
    digest = hashlib.blake2b(
        np.ascontiguousarray(s).tobytes(), digest_size=16
    ).digest()
    return (digest, n, spec_name, _freeze_candidates(candidates),
            sample_elems, top_k, engine or default_engine(), backend)


# ---------------------------------------------------------------------------
# phase 0: normalization (shared by select_method / apply_transform / encode)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Prepared:
    """Normalized view of one input array: passthrough mask split off,
    active values moved to one binade, significands materialized.  The
    shared state behind the layered primitives (`select_method`,
    `apply_transform`, `encode`)."""

    xf: np.ndarray              # flat input values
    shape: tuple
    spec: FloatSpec
    finite: np.ndarray          # bool[n]: element goes through the transform
    pass_mask: np.ndarray       # ~finite
    X: object | None            # int64 significands (None when no active)
    exps_np: np.ndarray
    signs_np: np.ndarray
    _packed: list = dataclasses.field(default_factory=list)

    @property
    def n(self) -> int:
        return int(self.xf.shape[0])

    @property
    def n_active(self) -> int:
        return int(self.exps_np.shape[0])

    def pack_common(self):
        """Normalization metadata (exponents/signs/passthrough), packed
        lazily and once.  Every ranking pays the pack, identity winning or
        not (phase 1 prices the non-identity candidates' shared streams
        with it); a shipping non-identity candidate reuses it."""
        if not self._packed:
            from ..compression.bitplane import compress_int_stream

            self._packed.append((
                compress_int_stream(self.exps_np),
                _pack_z(self.signs_np),
                _pack_z(self.pass_mask),
            ))
        return self._packed[0]

    def identity_encoded(self) -> Encoded:
        return Encoded(
            method="identity", params={}, data=self.xf.copy().reshape(self.shape),
            meta=None, exponents_z=b"", signs_z=b"", passthrough_z=b"",
            spec_name=self.spec.name, n=self.n, n_active=0,
        )

    def finish(self, name, p, vals_np, meta) -> Encoded:
        data = self.xf.copy()
        data[self.finite] = vals_np
        exponents_z, signs_z, passthrough_z = self.pack_common()
        return Encoded(
            method=name, params=p, data=data.reshape(self.shape), meta=meta,
            exponents_z=exponents_z, signs_z=signs_z,
            passthrough_z=passthrough_z, spec_name=self.spec.name, n=self.n,
            n_active=self.n_active,
        )


@obs.span("encode.prepare")
def _prepare(x, spec: FloatSpec | None = None) -> _Prepared:
    xf = np.asarray(x).reshape(-1)
    spec = spec or spec_for(xf)
    finite = np.isfinite(xf.astype(np.float64)) & (xf != 0)
    pass_mask = ~finite
    # the device sees bit words only: the float view is the host's
    active = xf[finite].view(spec.uint_dtype)
    if active.shape[0]:
        y01, exps, signs = normalize_bits(jnp.asarray(active), spec)
        X = significand_from_bits(y01, spec)
        with obs.span("encode.device_wait"):
            exps_np = np.asarray(exps, np.int64)
            signs_np = np.asarray(signs, np.uint8)
    else:
        X = None
        exps_np = np.zeros(0, np.int64)
        signs_np = np.zeros(0, np.uint8)
    return _Prepared(
        xf=xf, shape=np.shape(x), spec=spec, finite=finite,
        pass_mask=pass_mask, X=X, exps_np=exps_np,
        signs_np=signs_np,
    )


# ---------------------------------------------------------------------------
# layered primitives
# ---------------------------------------------------------------------------

def _ships(fn):
    """Count each :class:`Encoded` that ``fn`` returns, by method, in
    ``scoring.PHASE2.shipped``."""
    @functools.wraps(fn)
    def counted(*a, **k) -> Encoded:
        enc = fn(*a, **k)
        S.PHASE2.shipped[enc.method] += 1
        return enc

    return counted


@_ships
def apply_transform(
    x,
    method: str,
    params: dict | None = None,
    spec: FloatSpec | None = None,
    chunk_elems: int = DEFAULT_CHUNK_ELEMS,
    backend: str | None = None,
) -> Encoded:
    """Apply one explicit transform with chunked round-trip verification.

    The phase-2 primitive: no selection, no fallback — a transform that
    rejects the data or fails verification raises
    :class:`~repro.core.transforms.TransformError` (callers choose the
    fallback policy; streaming writers fall back to identity per chunk).

    ``backend="rans"`` routes fusible methods through the device-resident
    encode (one jit dispatch, one device_get — ``scoring.PHASE2``): the
    returned Encoded then carries the framed rANS payload so
    :func:`serialize_chunk` ships it without re-compressing."""
    if method == "identity":
        # identity fast path (§Perf PR 7): stored verbatim — no finite
        # mask, no binade normalization, no significand materialization
        xf = np.asarray(x).reshape(-1)
        spec = spec or spec_for(xf)
        if backend == "rans":
            enc = _fused_identity(xf, np.shape(x), spec.name)
            if enc is not None:
                return enc
        return Encoded(
            method="identity", params={}, data=xf.copy().reshape(np.shape(x)),
            meta=None, exponents_z=b"", signs_z=b"", passthrough_z=b"",
            spec_name=spec.name, n=int(xf.shape[0]), n_active=0,
        )
    prep = _prepare(x, spec)
    if prep.n_active == 0:
        # all-passthrough data has nothing to transform: identity is the
        # only faithful encoding regardless of the requested method
        return prep.identity_encoded()
    if backend == "rans":
        enc = _fused_encode(prep, method, params or {})
        if enc is not None:
            return enc
        _fell_back(prep)
    applied = _apply_and_verify(method, params or {}, prep.X, prep.spec,
                                chunk_elems)
    if applied is None:
        raise T.TransformError(
            f"transform {method!r} failed round-trip verification"
        )
    return prep.finish(method, params or {}, *applied)


def select_method(
    x,
    candidates=DEFAULT_CANDIDATES,
    size_fn: Callable[[bytes], int] | None = None,
    spec: FloatSpec | None = None,
    sample_elems: int = DEFAULT_SAMPLE_ELEMS,
    top_k: int = DEFAULT_TOP_K,
    engine: str | None = None,
    backend: str | None = None,
) -> tuple[str, dict]:
    """Phase-1 primitive: rank candidates on ``x`` and return the winning
    ``(method, params)`` without applying it to anything.  Uncached, so the
    PHASE1 dispatch-counter contracts stay exact.

    ``backend`` names the byte-stream compressor the caller will feed
    (container writers pass theirs): ``"rans"`` switches the analytic
    ranking to the rANS size model (pooled byte entropy + frequency-table
    overhead, zero extra dispatches — it falls out of the same scoregrid
    histogram) and re-scores finalists with the real rANS coder."""
    prep = _prepare(x, spec)
    if prep.n_active == 0:
        return "identity", {}
    ranked, _first = _rank_candidates(prep, candidates, size_fn,
                                      sample_elems, top_k, engine, backend)
    if not ranked:
        raise T.TransformError("no feasible transform candidate")
    name, p = ranked[0]
    return name, dict(p)


def build_plan(
    x,
    candidates=DEFAULT_CANDIDATES,
    spec: FloatSpec | None = None,
    sample_elems: int = DEFAULT_SAMPLE_ELEMS,
    top_k: int = DEFAULT_TOP_K,
    engine: str | None = None,
    backend: str | None = None,
    step: int = 0,
) -> plans.EncodePlan:
    """Run phase-1 selection once and return the result as a first-class
    :class:`~repro.core.plans.EncodePlan`: winner + params + backend + the
    full ranked fallback order + a stream-statistics fingerprint of ``x``.

    The plan is the amortization artifact of the always-on compressed
    training step: callers hold it per bucket/leaf, re-encode every step
    through :func:`encode_with_plan` (phase 2 only), and rebuild it only
    when the fingerprint drifts or a refresh interval elapses
    (``distributed.steps.CompressedStepState`` implements that policy)."""
    xf = np.asarray(x).reshape(-1)
    fp = plans.StreamFingerprint.from_array(xf)
    prep = _prepare(x, spec)
    if prep.n_active == 0:
        ranked = [("identity", {})]
    else:
        ranked, _ = _rank_candidates(prep, candidates, None, sample_elems,
                                     top_k, engine, backend)
        if not ranked:
            raise T.TransformError("no feasible transform candidate")
    name, p = ranked[0]
    return plans.EncodePlan(
        method=name, params=dict(p), spec_name=prep.spec.name,
        backend=backend, fingerprint=fp,
        ranked=[(n_, dict(p_)) for n_, p_ in ranked], step=step,
    )


def encode_with_plan(
    x,
    plan: plans.EncodePlan,
    chunk_elems: int = DEFAULT_CHUNK_ELEMS,
) -> Encoded:
    """Phase-2-only encode under a pre-built plan: apply the plan's winner
    (falling back down the plan's ranked order, then identity) with full
    chunked round-trip verification.  Selection is skipped entirely; the
    verify contract is not — a stale plan whose winner no longer
    round-trips on this data is *rejected, never shipped*, and the encode
    degrades to the next-ranked candidate (ultimately identity).  A stale
    plan can therefore cost compression ratio, never correctness."""
    spec = SPECS[plan.spec_name]
    order = [(n_, dict(p_)) for n_, p_ in plan.ranked]
    if not order or order[0][0] != plan.method or order[0][1] != dict(plan.params):
        order.insert(0, (plan.method, dict(plan.params)))
    for name, p in order:
        if name == "identity":
            break
        try:
            return apply_transform(x, name, p, spec=spec,
                                   chunk_elems=chunk_elems,
                                   backend=plan.backend)
        except T.TransformError:
            continue
    # identity is the terminal fallback whether or not the plan listed it:
    # it always round-trips, so a plan-reuse encode can never fail
    return apply_transform(x, "identity", spec=spec, backend=plan.backend)


def _rank_candidates(prep: _Prepared, candidates, size_fn, sample_elems,
                     top_k, engine: str | None = None,
                     backend_hint: str | None = None):
    """Shared selection core -> (ranked candidate list, first_applied).

    ``size_fn is None`` selects the fused analytic engine (zlib finalists,
    or the real rANS coder when ``backend_hint == "rans"``); a custom
    ``size_fn`` keeps the seed's exact compressor-matched semantics (every
    candidate scored on the full array, pre-verified)."""
    engine = engine or default_engine()
    if engine not in _ENGINES:
        raise ValueError(f"unknown scoring engine {engine!r}; use {_ENGINES}")
    # the shared normalization streams (exponents, signs, passthrough mask)
    # cost every non-identity candidate the same; they are packed exactly,
    # with the record's own coders, since their packed width follows the
    # whole stream's range, which a sample understates.  The pack is cached
    # on ``prep``, so a non-identity winner ships it without packing again.
    common_meta = sum(map(len, prep.pack_common()))
    if size_fn is None:
        has_identity = any(n_ == "identity" for n_, _ in candidates)
        ranked = _select_analytic(
            prep.xf, prep.finite, prep.X, prep.spec, candidates,
            _sampled_size(backend_hint), common_meta, sample_elems, top_k,
            has_identity, engine=engine, backend_hint=backend_hint,
        )
        return ranked, None
    return _select_exact(
        prep.xf, prep.finite, prep.X, prep.spec, candidates, size_fn,
        common_meta,
    )


def _sampled_size(backend: str | None) -> Callable[..., float]:
    """``size(b, scale)``: the coded bytes of a stream ``scale`` times as
    long as the sample ``b`` and alike in its statistics, as the record's
    payload coder writes it.  The coder's body grows with the stream; a
    rANS frame (header, frequency table, per-lane length words and state
    flushes) is written once, at the lane count of the whole stream, so it
    is not scaled.  ``framed=False`` gives the body alone, for bytes that
    share another stream's frame."""
    if backend != "rans":
        return lambda b, scale, framed=True: len(zlib.compress(b, 6)) * scale
    from ..kernels.rans import ops as rans_ops, ref as R

    lanes = rans_ops.default_lanes()

    def frame(n_bytes: int, syms: int) -> int:
        return R.frame_overhead_bytes(syms, R.clamp_lanes(lanes, n_bytes))

    def size(b: bytes, scale: float, framed: bool = True) -> float:
        syms = int(np.count_nonzero(
            np.bincount(np.frombuffer(b, np.uint8), minlength=256)))
        body = (len(rans_ops.compress(b)) - frame(len(b), syms)) * scale
        return body + frame(round(len(b) * scale), syms) if framed else body

    return size


def serialize_chunk(enc: Encoded, backend: str = "zlib") -> bytes:
    """Serialize one :class:`Encoded` as a checksummed binary record of the
    container format (``docs/format.md``) — explicit fields, no pickle."""
    from ..container import format as _fmt

    return _fmt.serialize_chunk(enc, backend)


def deserialize_chunk(buf: bytes, spec_name: str, backend: str = "zlib") -> Encoded:
    """Inverse of :func:`serialize_chunk` (spec/backend travel in the
    container header, so standalone records need them passed back in)."""
    from ..container import format as _fmt

    enc = _fmt.deserialize_chunk(buf, backend, spec_name=spec_name)
    return enc


def encode(
    x,
    method: str = "auto",
    params: dict | None = None,
    candidates=DEFAULT_CANDIDATES,
    size_fn: Callable[[bytes], int] | None = None,
    spec: FloatSpec | None = None,
    presample: int | None = None,
    sample_elems: int = DEFAULT_SAMPLE_ELEMS,
    top_k: int = DEFAULT_TOP_K,
    chunk_elems: int = DEFAULT_CHUNK_ELEMS,
    engine: str | None = None,
    backend: str | None = None,
) -> Encoded:
    """presample: if set and method=='auto', candidate selection runs on a
    strided sample of `presample` elements first (legacy §Perf C knob — the
    analytic engine already samples internally), then the winner is applied
    (and round-trip verified) on the full array, falling back to full auto
    on failure."""
    if presample and method == "auto":
        xf = np.asarray(x).reshape(-1)
        if xf.size > presample:
            step = xf.size // presample
            pick = encode(
                xf[:: step][:presample], method="auto",
                candidates=candidates, size_fn=size_fn, spec=spec,
                sample_elems=sample_elems, top_k=top_k,
                chunk_elems=chunk_elems, engine=engine, backend=backend,
            )
            try:
                return encode(
                    x, method=pick.method, params=pick.params,
                    size_fn=size_fn, spec=spec, chunk_elems=chunk_elems,
                    backend=backend,
                )
            except T.TransformError:
                pass  # sampled pick infeasible on full data: full search
    return _encode_full(
        x, method, params, candidates, size_fn, spec,
        sample_elems=sample_elems, top_k=top_k, chunk_elems=chunk_elems,
        engine=engine, backend=backend,
    )


def _encode_full(
    x,
    method: str = "auto",
    params: dict | None = None,
    candidates=DEFAULT_CANDIDATES,
    size_fn: Callable[[bytes], int] | None = None,
    spec: FloatSpec | None = None,
    sample_elems: int = DEFAULT_SAMPLE_ELEMS,
    top_k: int = DEFAULT_TOP_K,
    chunk_elems: int = DEFAULT_CHUNK_ELEMS,
    engine: str | None = None,
    backend: str | None = None,
) -> Encoded:
    if method != "auto":
        # explicit method: phase 2 only (identity and all-passthrough
        # inputs short-circuit inside apply_transform)
        return apply_transform(x, method, params, spec, chunk_elems, backend)
    return _encode_auto(x, candidates, size_fn, spec, sample_elems, top_k,
                        chunk_elems, engine, backend)


@_ships
def _encode_auto(x, candidates, size_fn, spec, sample_elems, top_k,
                 chunk_elems, engine, backend) -> Encoded:
    prep = _prepare(x, spec)
    if prep.n_active == 0:
        # nothing to transform: pure passthrough
        return prep.identity_encoded()

    # identity participates (as scored baseline and terminal fallback) only
    # when the caller's candidate list includes it — a restricted candidate
    # list must never ship an unlisted method (seed semantics).  A custom
    # size_fn keeps the seed's exact compressor-matched selection.
    has_identity = any(n_ == "identity" for n_, _ in candidates)
    ranked = first_applied = None
    key = None
    if size_fn is None:
        # repeated encodes of identical content (writer probes, re-encoded
        # chunks, small-chunk streams) skip phase 1 via the plan cache;
        # phase 2 below still apply+verifies whatever plan comes out
        key = _plan_key(prep.xf, prep.n, prep.spec.name, candidates,
                        sample_elems, top_k, engine, backend)
        cached = _PLAN_CACHE.get(key)
        if cached is not None:
            ranked = list(cached)
    if ranked is None:
        with obs.span("select.probe"):
            ranked, first_applied = _rank_candidates(
                prep, candidates, size_fn, sample_elems, top_k, engine,
                backend)
        if key is not None:
            _PLAN_CACHE.put(key, list(ranked))

    # phase 2: apply + verify finalists in rank order (fused device encode
    # for rans-backend callers; classic host path otherwise)
    for i, (name, p) in enumerate(ranked):
        if name == "identity":
            if backend == "rans":
                enc = _fused_identity(prep.xf, prep.shape, prep.spec.name)
                if enc is not None:
                    return enc
            return prep.identity_encoded()
        if i == 0 and first_applied is not None:
            # exact path: _select_exact already round-trip verified the
            # winner on the full array — don't redo the transform
            return prep.finish(name, p, *first_applied)
        if backend == "rans":
            enc = _fused_encode(prep, name, p)
            if enc is not None:
                return enc
        try:
            applied = _apply_and_verify(name, p, prep.X, prep.spec,
                                        chunk_elems)
        except T.TransformError:
            continue
        if applied is None:
            continue  # failed round-trip: rejected, never shipped
        if backend == "rans":
            _fell_back(prep)
        return prep.finish(name, p, *applied)
    if has_identity:
        return prep.identity_encoded()
    raise T.TransformError("no transform candidate round-tripped")


# ---------------------------------------------------------------------------
# phase 1: candidate selection
# ---------------------------------------------------------------------------

def _strided(a, limit: int):
    if a.shape[0] <= limit:
        return a
    step = -(-a.shape[0] // limit)   # ceil: the sample spans the whole array
    return a[::step][:limit]


def _scaled_meta_bytes(meta, scale: float) -> float:
    """Candidate metadata cost extrapolated from the sample to the full set.

    Per-sample metadata (shift&save-evenness chunk ids / evenness bits)
    grows with n and must be scaled; the other transforms carry fixed-size
    headers."""
    mb = _meta_bytes(meta)
    if isinstance(meta, T.ShiftSaveEvenMeta):
        return mb * scale
    return float(mb)




def _generic_score(name, p, Xs, spec, extrema, scale):
    """Score a transform without a fused builder: generic forward +
    `score_significands` (its own dispatch; the estimate handle joins the
    engine's single fetch).  Returns None when the forward rejects."""
    fwd, _ = T.TRANSFORMS[name]
    try:
        Xt, off, meta = fwd(Xs, spec=spec, extrema=extrema, **p)
    except T.TransformError:
        return None
    S.PHASE1.dispatches += 1
    return S.CandidateScore(
        name=name, params=p,
        meta_bytes=_scaled_meta_bytes(meta, scale),
        _dev=S.score_significands(Xt, off, spec),
    )


def _probe_meta_bytes(s: "S.CandidateScore", Xs, spec, extrema,
                      scale: float) -> float:
    """Real (compressed) candidate metadata cost, replacing the analytic
    per-sample model for proxy tie-breaks.  The stacked engine reads the
    metadata streams retained from the grid fetch (zero dispatches); the
    per-family oracle re-runs the forward on the sample (counted)."""
    if s.meta_streams is not None:
        return S.meta_bytes_from_streams(s.name, s.meta_streams, scale)
    S.PHASE1.probe_dispatches += 1
    fwd, _ = T.TRANSFORMS[s.name]
    _Xt, _off, meta = fwd(Xs, spec=spec, extrema=extrema, **s.params)
    return _scaled_meta_bytes(meta, scale)


def _select_analytic(
    xf, finite, X, spec, candidates, size_fn, common_meta,
    sample_elems, top_k, has_identity=True, engine: str = "stacked",
    backend_hint: str | None = None,
):
    """Analytic sample-select: rank candidates by the fused plane-stats size
    estimate; re-score the top finalists (+ identity) with the real
    compressor.  Returns candidate (name, params) in preference order.

    ``size_fn(b, scale, framed=True)`` is :func:`_sampled_size`'s model of
    the payload coder: the coded bytes of a stream ``scale`` times ``b``."""
    n_active = int(X.shape[0])
    Xs = _strided(X, sample_elems)
    n_s = int(Xs.shape[0])
    scale = n_active / n_s

    # sample extrema computed ONCE and shared by the whole candidate grid;
    # the single domain check below covers every fused scorer dispatch
    mn, mx = jnp.min(Xs), jnp.max(Xs)
    with obs.span("select.device_wait"):
        mn, mx = jax.device_get((mn, mx))
    extrema = (int(mn), int(mx))
    T._check_domain(Xs, spec, extrema)

    scores: list[S.CandidateScore] = []
    deferred: list[tuple[str, dict]] = []  # valid on full, unscorable on sample
    if engine == "stacked":
        # the whole candidate grid in ONE stacked jit dispatch + ONE
        # device_get (scoring.score_candidates_stacked); a transform
        # without a fused builder gets its own dispatch but its estimate
        # handle resolves inside that same single fetch
        scores, deferred = S.score_candidates_stacked(
            candidates, Xs, spec, extrema, full_n=n_active,
            generic_score_fn=lambda name, p: _generic_score(
                name, p, Xs, spec, extrema, scale
            ),
        )
    else:
        for name, p in candidates:
            if name == "identity":
                continue
            try:
                dev = S.score_candidate(name, p, Xs, spec, extrema,
                                        full_n=n_active)
            except T.TransformError:
                continue
            if dev == "defer":
                deferred.append((name, p))
                continue
            if dev is not None:
                scores.append(S.CandidateScore(name=name, params=p, _dev=dev))
                continue
            s = _generic_score(name, p, Xs, spec, extrema, scale)
            if s is not None:
                scores.append(s)
    S.fetch_scores(scores)  # single device round-trip for all estimates
    scores = [s for s in scores if s.valid]
    for s in scores:
        s.est_bytes *= scale
        s.meta_bytes += s.per_sample_bytes * scale
        s.byte_bytes *= scale

    # proxy tie-break (ROADMAP PR 1 open item): within shift&save-evenness
    # the analytic per-sample metadata model can misrank D on smooth streams
    # (metadata compressibility is data-dependent: the model prices chunk
    # ids at a fixed bit width, real zlib can be 3x off either way).  The
    # model is untrusted — and replaced by a real sampled-zlib probe of the
    # metadata streams — when the family's top two rank inside the proxy's
    # ~5% error band OR the modelled metadata is itself a material share of
    # the total (then the model's own error exceeds the band).  Free on the
    # stacked engine: the streams rode the single grid fetch.
    sse = sorted((s for s in scores if s.name == "shift_save_even"),
                 key=lambda s: s.total)
    if len(sse) >= 2 and (
        sse[1].total <= sse[0].total * (1 + PROXY_TIE_BAND)
        or max(sse[0].meta_bytes, sse[1].meta_bytes)
        > PROXY_TIE_BAND * sse[0].total
    ):
        for s in sse:
            s.meta_bytes = _probe_meta_bytes(s, Xs, spec, extrema, scale)

    if backend_hint == "rans":
        # rANS size model from the SAME grid fetch: pooled byte entropy is
        # what an order-0 rANS coder reaches, plus frame overhead from the
        # distinct-symbol count (no plane-run term: rANS has no LZ layer)
        from ..kernels.rans import ops as _rans_ops, ref as _rans_ref

        r_lanes = _rans_ref.clamp_lanes(
            _rans_ops.default_lanes(), n_active * (spec.width // 8)
        )

        def _rank_key(s):
            data = s.byte_bytes if s.table_syms else s.est_bytes
            return data + _rans_ref.frame_overhead_bytes(
                s.table_syms, r_lanes
            ) + s.meta_bytes
    else:
        def _rank_key(s):
            return s.total

    ranked = sorted(scores, key=_rank_key)
    # family-diverse finalists: the proxy's residual error is correlated
    # within a transform family (same structural model), so the top-k slots
    # go to the best candidate of k DIFFERENT families first, then refill
    # by rank.  The exact re-scoring below absorbs family-level proxy bias.
    def _ckey(s):
        return (s.name, tuple(sorted(s.params.items())))

    finalists: list[S.CandidateScore] = []
    taken: set = set()
    seen_families: set[str] = set()
    for s in ranked:
        if len(finalists) >= max(top_k, 1):
            break
        if s.name in seen_families:
            continue
        seen_families.add(s.name)
        finalists.append(s)
        taken.add(_ckey(s))
    for s in ranked:
        if len(finalists) >= max(top_k, 1):
            break
        if _ckey(s) not in taken:
            finalists.append(s)
            taken.add(_ckey(s))

    # exact scoring of finalists + identity baseline, on the sampled stream
    exact: list[tuple[float, str, dict]] = []
    if has_identity:
        xs_all = _strided(xf, sample_elems)
        exact.append(
            (size_fn(np.ascontiguousarray(xs_all).tobytes(),
                     xf.shape[0] / xs_all.shape[0]) + 16, "identity", {})
        )
    # passthrough bytes ship verbatim in every non-identity candidate's data
    # stream too (seed scored xf with data[finite]=vals); a constant term,
    # but identity's estimate includes those bytes so finalists must as well
    xp = xf[~finite]
    if xp.size:
        xps = _strided(xp, sample_elems)
        pass_cost = size_fn(np.ascontiguousarray(xps).tobytes(),
                            xp.size / xps.size, framed=False)
    else:
        pass_cost = 0.0
    for s in finalists:
        name, p = s.name, s.params
        if s.words is not None:
            # stacked engine: the grid already transformed this candidate —
            # feed the retained word stream and metadata arrays to the real
            # compressor instead of re-running the forward (ROADMAP PR 4
            # open item; pinned at 0 finalist dispatches by the CI gate)
            data_bytes = S.payload_bytes_from_words(s.words, spec)
            meta_cost = S.meta_bytes_from_streams(name, s.meta_streams, scale)
        else:
            S.PHASE1.finalist_dispatches += 1
            fwd, _ = T.TRANSFORMS[name]
            try:
                Xt, off, meta = fwd(Xs, spec=spec, extrema=extrema, **p)
            except T.TransformError:
                continue
            vals = significand_to_bits(Xt, off.astype(jnp.int32), spec)
            with obs.span("select.device_wait"):
                data_bytes = np.asarray(vals).tobytes()
            meta_cost = _scaled_meta_bytes(meta, scale)
        exact.append(
            (size_fn(data_bytes, scale) + pass_cost + meta_cost
             + common_meta, name, p)
        )
    exact.sort(key=lambda t: t[0])
    head = [(name, p) for _, name, p in exact]
    # preserve the seed's try-every-candidate guarantee: if every finalist
    # fails full-array apply/verify, phase 2 falls through to the remaining
    # scored candidates (analytic order) and then the sample-unscorable ones
    tail = [(s.name, s.params) for s in ranked
            if (s.name, s.params) not in head]
    return head + tail + deferred


def _select_exact(xf, finite, X, spec, candidates, size_fn, common_meta):
    """Seed-exact selection: score every candidate with the real compressor
    on the full array (used when a custom size_fn is supplied, so
    compressor-matched selection keeps its semantics).

    Returns (ranked, first_applied): every candidate here is already
    round-trip verified on the full array, so the best non-identity
    candidate's (values, meta) ride along for phase 2 to ship directly
    instead of recomputing the winning transform."""
    trials = list(candidates)
    scored: list[tuple[float, str, dict]] = []
    best = None  # (score, name, params, vals, meta) of best non-identity
    for name, p in trials:
        if name == "identity":
            scored.append((size_fn(xf.tobytes()) + 16, "identity", {}))
            continue
        fwd, inv = T.TRANSFORMS[name]
        try:
            Xt, off, meta = fwd(X, spec=spec, **p)
            Xr = inv(Xt, off, meta, spec=spec)
        except T.TransformError:
            continue
        if not bool(jnp.all(Xr == X)):
            continue  # reject candidates that do not round-trip, never ship
        vals = np.asarray(significand_to_bits(
            Xt, off.astype(jnp.int32), spec)).view(spec.float_dtype)
        data = xf.copy()
        data[finite] = vals
        score = size_fn(data.tobytes()) + _meta_bytes(meta) + common_meta
        scored.append((score, name, p))
        if best is None or score < best[0]:
            best = (score, name, p, vals, meta)
    if not scored:
        raise T.TransformError("no transform candidate round-tripped")
    scored.sort(key=lambda t: t[0])
    ranked = [(name, p) for _, name, p in scored]
    first_applied = None
    if best is not None and ranked[0] == (best[1], best[2]):
        first_applied = (best[3], best[4])
    return ranked, first_applied


# ---------------------------------------------------------------------------
# decode: the device half of the inverse as ONE compiled program per chunk
# (binade offsets, the family's inverse, significand -> bit words,
# denormalization), run with one dispatch and fetched with one wait
# ---------------------------------------------------------------------------

def _lanes(a, n: int, n_pad: int, dtype=None) -> np.ndarray:
    """The ``n`` values of a per-sample stream in the first lanes of a
    zeroed ``n_pad``-long array (the decode program drops the rest)."""
    a = np.asarray(a, dtype)
    if a.shape != (n,):
        raise ValueError(f"per-sample stream holds {a.shape[0]} values, "
                         f"expected {n}")
    out = np.zeros(n_pad, a.dtype)
    out[:n] = a
    return out


def _inverse_args(method: str, meta, spec: FloatSpec, n: int,
                  n_pad: int) -> tuple:
    """The family's metadata as the decode program's arguments: per-sample
    arrays padded to the program's length, scalars and schedules replayed
    on the host."""
    if method == "shift_save_even":
        return (_lanes(meta.chunk_ids, n, n_pad, np.int64),
                _lanes(meta.evenness, n, n_pad, np.uint8),
                np.int64(meta.x_min), np.int64(meta.D))
    if method == "compact_bins":
        return (np.asarray(meta.shifts, np.int64),
                np.asarray(meta.thresholds, np.int64))
    if method == "multiply_shift":
        return T._ms_inverse_schedule(meta, spec)
    if method == "shift_separate":
        from ..kernels.rans.kernel import bucket_steps

        a_align, *sched = T._ss_inverse_schedule(meta, spec)
        # no-op steps (iteration -1 selects no lane) pad the schedule to a
        # bucket, so chunks whose iteration counts differ share a program
        k = bucket_steps(meta.n_iter, floor=8) - meta.n_iter
        return (a_align,) + tuple(
            np.pad(a, (0, k), constant_values=fill)
            for a, fill in zip(sched, (0, 0, 0, -1)))
    if method != "identity":
        raise ValueError(f"no inverse transform named {method!r}")
    return ()


@functools.lru_cache(maxsize=64)
def _inverse_program(method: str, spec_name: str, n_pad: int,
                     meta_shapes: tuple):
    """Build (and cache per family, format, bucketed length and the shapes
    of :func:`_inverse_args`) the decode program: ``(bit words, int32
    exponents, uint8 signs, *meta) -> output bit words``.  Every traced
    input and output is an integer array: the float views are the host's."""
    S.DECODE.add(programs=1)
    spec = SPECS[spec_name]
    l = spec.man_bits

    def inverse(X, off, args):
        if method == "shift_save_even":
            return T._sse_inverse(X, *args, l)
        if method == "compact_bins":
            return T._cb_inverse(X, *args)
        if method == "multiply_shift":
            return T._ms_inv_loop(X, off, *args)
        if method == "shift_separate":
            return T._ss_inv_loop(X, off, *args)
        return X

    @jax.jit
    def run_inverse(b, exps, signs, *args):
        with jax.named_scope("inverse"):
            # the transform landed each value at binade `off`
            off = ((b >> l) & spec.uint_dtype(spec.exp_mask)).astype(
                jnp.int32) - spec.bias
            X = inverse(significand_from_bits(b, spec), off, args)
        with jax.named_scope("denormalize"):
            y01 = significand_to_bits(X, jnp.zeros_like(off), spec)
            return denormalize_bits(y01, exps, signs, spec)

    return run_inverse


@obs.span("decode.inverse")
def decode(enc: Encoded) -> np.ndarray:
    spec = SPECS[enc.spec_name]
    flat = np.asarray(enc.data).reshape(-1)
    out = flat.copy()
    n = enc.n_active
    if not n:  # identity / all-passthrough: stored verbatim
        return out.reshape(np.shape(enc.data))
    from ..compression.bitplane import decompress_int_stream
    from ..kernels.rans.kernel import bucket_steps

    pass_mask = _unpack_z(enc.passthrough_z, enc.n).astype(bool)
    # lengths share programs on the rANS scans' quarter-power-of-two ladder
    n_pad = bucket_steps(n)
    # bit words in, bit words out: the float views are taken on the host
    b = _lanes(flat[~pass_mask].view(spec.uint_dtype), n, n_pad)
    exps = _lanes(decompress_int_stream(enc.exponents_z, n), n, n_pad,
                  np.int32)
    signs = _lanes(_unpack_z(enc.signs_z, n), n, n_pad, np.uint8)
    args = _inverse_args(enc.method, enc.meta, spec, n, n_pad)
    prog = _inverse_program(enc.method, spec.name, n_pad,
                            tuple((a.shape, a.dtype.str) for a in args))
    S.DECODE.add(dispatches=1)
    vals = prog(b, exps, signs, *args)
    with obs.span("decode.device_wait"):
        vals = np.asarray(vals)
    out[~pass_mask] = vals[:n].view(spec.float_dtype)
    return out.reshape(np.shape(enc.data))
