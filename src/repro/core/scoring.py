"""Analytic candidate scoring for the auto-selection engine (§Perf).

The paper's Fig. 6 "best of the four techniques" selection needs a size
estimate for every (transform, parameter) candidate.  Compressing the full
transformed stream per candidate (the seed behaviour) makes selection cost
``O(candidates x zlib(n))`` and dominates end-to-end encode time.  This
module replaces that with a cheap analytic proxy computed on device:

* per-bitplane set-bit counts  -> order-0 entropy H(p1) per plane,
* per-bitplane transition counts -> first-order (run-length) entropy H(pt),
* the shared-bit mask           -> constant planes cost exactly 0 bits.

The estimated stream size is ``max(sum_p n * min(H0_p, Ht_p), pooled byte
entropy)`` bits — the plane model captures the run/repeat structure LZ77
exploits, the pooled byte histogram bounds what a single Huffman literal
table reaches; both are optimistic, so the tighter (larger) bound predicts
— plus the candidate's metadata bytes.  The proxy only has to *rank*
candidates: the pipeline re-scores the top finalists (plus the identity
baseline when listed) with the real compressor and round-trip-verifies the
winner before shipping, so a proxy mistake can cost ratio, never
correctness.

Two engines share one set of family "builders" (forward arithmetic +
metadata model + feasibility verdict, all traceable):

* **stacked** (default) — the WHOLE candidate grid runs as ONE jit dispatch
  (:func:`score_candidates_stacked`): every family's forward transform plus
  the fused bit-statistics estimator of ``kernels/scoregrid`` over the
  stacked ``[n_candidates, sample]`` word grid, fetched with ONE
  ``device_get``.  On TPU the statistics pass is the ``scoregrid`` Pallas
  kernel; on CPU the batched-jnp twin (identical integers) fuses into the
  same dispatch.
* **perfamily** — one fused jit per candidate (:func:`score_candidate`,
  the PR 1 engine), kept as the A/B flag and the stacked engine's parity
  oracle (tests assert bitwise-equal scores and winners).

:data:`PHASE1` counts scoring dispatches and host fetches so tests and the
CI bench gate can pin the single-dispatch property instead of trusting it.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels import INTERPRET_DEFAULT
from ..kernels.scoregrid.ops import (
    byte_entropy_bits,
    finalize_bits_grid,
    plane_byte_stats_grid,
)
from ..kernels.sharedbits.ops import plane_stats_u64
from .float_bits import FloatSpec
from .lossless import significand_to_bits

# on TPU the stacked estimator runs the compiled Pallas scoregrid kernel;
# on CPU its batched-jnp twin fuses into the same stacked dispatch
_USE_PALLAS_GRID = not INTERPRET_DEFAULT


@dataclasses.dataclass
class Phase1Stats:
    """Observable phase-1 cost model: how many device dispatches and host
    round-trips candidate scoring actually issued (cumulative; callers
    reset).  The stacked engine must show (1, 1) per selection — asserted in
    tests/test_scoring.py and compared exactly by the CI bench gate."""

    dispatches: int = 0     # jitted scorer invocations (grid or per-family)
    device_gets: int = 0    # host fetches of scoring results
    # finalist exact re-scoring forward runs: 0 on the stacked engine (it
    # reuses the grid's already-transformed word streams); ~top_k on the
    # per-family oracle.  Pinned exactly by the CI bench gate.
    finalist_dispatches: int = 0
    # sampled-zlib metadata probe forward runs (proxy tie-break): 0 on the
    # stacked engine (meta streams ride the grid fetch), one per probed
    # candidate on the per-family oracle.
    probe_dispatches: int = 0

    def reset(self) -> None:
        self.dispatches = 0
        self.device_gets = 0
        self.finalist_dispatches = 0
        self.probe_dispatches = 0


PHASE1 = Phase1Stats()


@dataclasses.dataclass
class Phase2Stats:
    """Observable phase-2 (winner apply + pack + entropy encode) cost model,
    same contract as :class:`Phase1Stats`: cumulative counters, callers
    reset.  The fused encode path must show exactly (1, 1, 0) per encoded
    chunk — one jitted transform+pack+rANS dispatch, one ``device_get`` of
    the emission buffers, zero host fallbacks — asserted in
    tests/test_pipeline_fused.py and compared exactly by the CI bench
    gate (``encode_dispatches`` / ``encode_device_gets``)."""

    dispatches: int = 0     # fused encode jit invocations
    device_gets: int = 0    # host fetches of fused encode results
    # encodes that could not fuse (transform needs host-side scheduling,
    # non-rans backend, ...) and took the eager multi-dispatch path instead
    fallbacks: int = 0

    def reset(self) -> None:
        self.dispatches = 0
        self.device_gets = 0
        self.fallbacks = 0


PHASE2 = Phase2Stats()


@dataclasses.dataclass
class CandidateScore:
    """One candidate's phase-1 (analytic) scoring result."""

    name: str
    params: dict
    est_bytes: float = 0.0    # analytic data-stream estimate (bytes)
    meta_bytes: float = 0.0   # fixed candidate metadata estimate (bytes)
    per_sample_bytes: float = 0.0  # per-sample metadata (scaled by the engine)
    valid: bool = True        # device-side feasibility verdict
    # rANS size model (zero extra dispatches: both derive from the byte
    # histogram the scoregrid pass already accumulates): pooled-entropy data
    # bytes + the number of distinct byte values (frequency-table size)
    byte_bytes: float = 0.0
    table_syms: int = 0
    # stacked engine only: the candidate's already-transformed sample word
    # stream and per-sample metadata arrays, retained from the grid fetch so
    # finalist re-scoring and the metadata probe never re-run a forward
    words: object = None
    meta_streams: object = None
    # stacked engine only: the candidate's pooled byte histogram (int[256]),
    # retained from the same grid fetch — the rANS statistics pass for
    # finalist re-scoring (ops.compress(counts=...) skips its own bincount)
    byte_hist: object = None
    # device handles kept so the engine can fetch all scores in ONE round-trip
    _dev: object = None

    @property
    def total(self) -> float:
        return self.est_bytes + self.meta_bytes


@functools.partial(jax.jit, static_argnames=("lanes",))
def _pooled_byte_hist(words, lanes: int = 8):
    """256-bin histogram of the POOLED byte stream (all byte positions in
    one table).  DEFLATE codes literals with a single Huffman table over
    the mixed stream, so per-lane entropy systematically undershoots what
    zlib can reach on high-entropy mantissas; the pooled histogram is the
    honest Huffman-literal bound.

    ``lanes`` = real bytes per value: uint64-zero-extended f32/bf16 words
    must not count their padding bytes (zlib never sees them)."""
    sh = jnp.arange(lanes, dtype=jnp.uint64) * jnp.uint64(8)
    by = ((words[:, None] >> sh[None, :]) & jnp.uint64(0xFF)).astype(jnp.int32)
    return jnp.bincount(by.reshape(-1), length=256)


@functools.partial(jax.jit, static_argnames=("lanes",))
def _estimate_words(words, lanes: int = 8):
    """Full fused estimate for a uint64 stream.

    Both component models are *optimistic* bounds of what DEFLATE reaches:
    the bit-plane run model assumes a bit-granular coder (zlib is
    byte-granular), the pooled byte-entropy model assumes order-0 literals
    only (LZ77 matching can beat it on repeats).  The tighter (larger) bound
    is the better size predictor — measured on the test corpus it ranks
    candidates the way full zlib does, where either model alone inverts the
    shift&save-evenness family's D ordering.

    The entropy finalization is THE shared implementation
    (``scoregrid.ops.finalize_bits_grid``) consumed by both this per-family
    estimator and the stacked grid — the bitwise winner-parity contract
    rests on there being exactly one copy of the formula."""
    ones, transitions, _ = plane_stats_u64(words)
    hist = _pooled_byte_hist(words, lanes)
    return finalize_bits_grid(ones, transitions, hist, words.shape[0], lanes)


def estimate_stream_bits(words) -> float:
    """Analytic compressed-size estimate (bits) of a uint64 word stream."""
    w = jnp.asarray(np.ascontiguousarray(words).view(np.uint64).reshape(-1))
    return float(_estimate_words(w))


@functools.partial(jax.jit, static_argnames=("spec",))
def score_significands(Xt, off, spec: FloatSpec) -> jnp.ndarray:
    """Fused compose+score: significands/offsets -> estimated bits, one
    dispatch per candidate (bit-word composition, plane stats and byte
    histogram all inside a single jit)."""
    w = _candidate_words(Xt, off, spec)
    return _estimate_words(w, lanes=spec.width // 8)


def fetch_scores(scores: list[CandidateScore]) -> None:
    """Resolve all pending device estimates with one `jax.device_get`.

    A pending handle is either a scalar (data-bits estimate only, metadata
    already costed on host) or a ``[data_bits, fixed_meta_bits,
    per_sample_meta_bits, valid, byte_bits, table_syms]`` lane vector from
    the fused family scorers below."""
    pending = [s for s in scores if s._dev is not None]
    if not pending:
        return
    vals = jax.device_get([s._dev for s in pending])
    PHASE1.device_gets += 1
    for s, v in zip(pending, vals):
        v = np.atleast_1d(np.asarray(v, np.float64))
        s.est_bytes = float(v[0]) / 8.0
        if v.size >= 4:
            s.meta_bytes = float(v[1]) / 8.0
            s.per_sample_bytes = float(v[2]) / 8.0
            s.valid = bool(v[3] > 0.5)
        if v.size >= 6:
            s.byte_bytes = float(v[4]) / 8.0
            s.table_syms = int(v[5])
        s._dev = None


# ---------------------------------------------------------------------------
# family builders: forward arithmetic + metadata model + feasibility verdict
# as traceable functions returning (words_u64, fixed_meta_bits,
# per_sample_meta_bits, valid).  The per-family jits below and the stacked
# grid jit both consume these, so the two engines can never drift.
# ---------------------------------------------------------------------------

def _bit_length(v):
    """ceil bit-length of a non-negative device scalar (0 -> 0)."""
    vf = jnp.maximum(v.astype(jnp.float64), 1.0)
    return jnp.where(v > 0, jnp.floor(jnp.log2(vf)) + 1.0, 0.0)


def _candidate_words(Xt, off, spec: FloatSpec):
    """Compose a candidate's (significands, binade offsets) into the uint64
    word stream the analytic estimator consumes."""
    return significand_to_bits(Xt, jnp.asarray(off, jnp.int32),
                               spec).astype(jnp.uint64)


def _sse_build(X, x_min, w_eff, top, spec: FloatSpec):
    """shift&save-evenness: the transform's own `_sse_core` + metadata model
    (zigzag-delta chunk-id width + 1 evenness bit per sample).  The chunk-id
    and evenness streams ride along as the candidate's ``extras`` so the
    stacked engine can probe/score real metadata without a second forward."""
    from . import transforms as T

    Y, j, parity, j_max = T._sse_core(X, x_min, w_eff, top)
    off = jnp.ones(X.shape, jnp.int32)
    n = X.shape[0]
    zz_max = 2 * jnp.max(jnp.abs(jnp.diff(j)), initial=jnp.int64(0))
    w_dense = jnp.maximum(_bit_length(j_max), 1.0)
    w = jnp.minimum(jnp.maximum(_bit_length(zz_max), 1.0), w_dense)
    return (_candidate_words(Y, off, spec), 128.0 + 64.0, n * (w + 1.0),
            jnp.bool_(True), (j, parity))


def _ms_build(X, a1, a_const, thresh, max_iter: int, spec: FloatSpec):
    """multiply&shift: fused §3.2 loop; the convergence verdict rides along
    as the `valid` lane instead of a host sync."""
    from . import transforms as T

    Xf, off, active = T._ms_loop(X, a1, a_const, thresh, max_iter)
    return (_candidate_words(Xf, off, spec), 128.0 + 64.0, 0.0,
            ~jnp.any(active), ())


def _ss_loop_masked(Xc, Ae, Ao, enabled, thresh_cap):
    """``transforms._ss_loop`` with a per-step validity lane.

    The schedule length is data-dependent (derived from the sample
    extrema), and anything data-dependent in the stacked grid's static plan
    would re-trace and re-compile the WHOLE grid per distinct span.  The
    scorers therefore scan a schedule padded to the candidate's static
    ``max_iter`` with disabled tail steps — integer-exact no-ops (a
    disabled step leaves X and the offsets untouched, and every
    still-active element satisfies ``X < thresh_cap`` after the last real
    step, so the active mask is preserved too)."""

    def step(carry, a):
        X, off, active = carry
        ae, ao, en = a
        A = jnp.where((X & 1).astype(bool), ao, ae)
        Y = (X + A) >> 1
        act = active & en
        Xn = jnp.where(act, Y, X)
        offn = off + act.astype(jnp.int32)
        return (Xn, offn, active & (Xn < thresh_cap)), None

    init = (Xc, jnp.zeros(Xc.shape, jnp.int32), jnp.ones(Xc.shape, bool))
    (Xf, off, active), _ = lax.scan(step, init, (Ae, Ao, enabled))
    return Xf, off, jnp.any(active)


def _ss_build(X, a_align, Ae, Ao, enabled, thresh_cap, spec: FloatSpec):
    """shift&separate: fused masked scan over the padded schedule."""
    Xf, off, any_active = _ss_loop_masked(
        X + a_align, Ae, Ao, enabled, thresh_cap
    )
    return (_candidate_words(Xf, off, spec), 128.0 + 128.0, 0.0,
            ~any_active, ())


def _cb_build(X, k: int, spec: FloatSpec):
    """compact bins: the transform's own fused `_cb_core`.

    The bins-don't-fit check becomes the `valid` lane.  Metadata modelled
    as raw (unpacked) shift + threshold words — an upper bound that only
    matters vs. the k-free families when the data estimates are nearly
    tied.  The shift/packed-floor arrays ride along as ``extras`` (they are
    the transform's exact metadata streams)."""
    from . import transforms as T

    Xt, shifts, new_lo, fits = T._cb_core(X, k=k, l=spec.man_bits)
    off = jnp.zeros(X.shape, jnp.int32)
    return (_candidate_words(Xt, off, spec), 128.0 + 64.0 * (2 * k - 1), 0.0,
            fits, (shifts, new_lo))


def _stack_lanes(words, meta_fixed_bits, meta_persample_bits, valid, spec):
    """[data_bits, fixed_meta_bits, per_sample_meta_bits, valid, byte_bits,
    table_syms] — the per-sample lane is scaled by n_full/n_sample on the
    host, the fixed lane is not.  ``byte_bits`` (pooled byte entropy) and
    ``table_syms`` (distinct byte values) are the rANS size model, free
    by-products of the histogram the zlib proxy already accumulates."""
    lanes = spec.width // 8
    ones, transitions, _ = plane_stats_u64(words)
    hist = _pooled_byte_hist(words, lanes)
    return jnp.stack([
        finalize_bits_grid(ones, transitions, hist, words.shape[0], lanes),
        jnp.asarray(meta_fixed_bits, jnp.float64),
        jnp.asarray(meta_persample_bits, jnp.float64),
        valid.astype(jnp.float64),
        byte_entropy_bits(hist, words.shape[0], lanes),
        (hist > 0).sum().astype(jnp.float64),
    ])


# ---------------------------------------------------------------------------
# per-family fused scorers (§Perf, PR 1: each candidate runs with ZERO
# per-candidate host round-trips; the engine fetches every candidate's lane
# vector in one device_get).  Kept as the A/B flag + stacked-parity oracle.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("spec",))
def _sse_score(X, x_min, w_eff, top, spec: FloatSpec):
    return _stack_lanes(*_sse_build(X, x_min, w_eff, top, spec)[:4], spec)


@functools.partial(jax.jit, static_argnames=("max_iter", "spec"))
def _ms_score(X, a1, a_const, thresh, max_iter: int, spec: FloatSpec):
    return _stack_lanes(
        *_ms_build(X, a1, a_const, thresh, max_iter, spec)[:4], spec
    )


@functools.partial(jax.jit, static_argnames=("spec",))
def _ss_score(X, a_align, Ae, Ao, enabled, thresh_cap, spec: FloatSpec):
    return _stack_lanes(
        *_ss_build(X, a_align, Ae, Ao, enabled, thresh_cap, spec)[:4], spec
    )


@functools.partial(jax.jit, static_argnames=("k", "spec"))
def _cb_score(X, k: int, spec: FloatSpec):
    return _stack_lanes(*_cb_build(X, k, spec)[:4], spec)


# ---------------------------------------------------------------------------
# candidate planning (host side): schedule/feasibility arithmetic from the
# shared sample extrema — no device syncs; single source of truth for both
# engines
# ---------------------------------------------------------------------------

def _plan_candidate(name: str, p: dict, spec: FloatSpec, extrema,
                    n_sample: int, full_n: int):
    """Host-side plan for one (transform, params) candidate.

    Returns ``("grid", entry, dyn)`` where ``entry`` is the hashable static
    piece (family tag + static schedule params) and ``dyn`` the dynamic
    operands, ``("defer",)`` when the candidate is valid on the full array
    but cannot be evaluated on the sample (e.g. compact_bins with more bins
    than sample elements), or ``("generic",)`` for transforms without a
    fused builder.  Raises TransformError for infeasibility on the FULL
    array."""
    from . import transforms as T

    l = spec.man_bits
    x_min, x_max = int(extrema[0]), int(extrema[1])
    if name == "shift_save_even":
        w_eff = T._sse_feasible(int(p["D"]), spec)
        return ("grid", ("sse", w_eff, 1 << (l + 1)), ())
    if name == "multiply_shift":
        max_iter = int(p.get("max_iter", 4096))
        a1, a_const, thresh = T._ms_feasible(
            int(p["D"]), x_min, x_max, max_iter, spec
        )
        # plain numpy scalars go straight into the jit call — no eager
        # device_put dispatches (they cost ~0.3ms each, x4 per candidate)
        return ("grid", ("ms", max_iter),
                (np.int64(a1), np.int64(a_const), np.int64(thresh)))
    if name == "shift_separate":
        max_iter = int(p.get("max_iter", 64))
        a_align, cap, sched = T._ss_feasible(
            int(p["D"]), x_min, x_max, max_iter, spec
        )
        ok = [(ae, ao) for ae, ao, _t, is_ok in sched if is_ok]
        # schedule padded to the STATIC max_iter with disabled tail steps:
        # its data-dependent length must not leak into the grid plan (a
        # distinct plan re-compiles the whole stacked jit)
        Ae = np.zeros(max_iter, np.int64)
        Ao = np.zeros(max_iter, np.int64)
        enabled = np.zeros(max_iter, bool)
        Ae[: len(ok)] = [a for a, _ in ok]
        Ao[: len(ok)] = [a for _, a in ok]
        enabled[: len(ok)] = True
        return ("grid", ("ss", max_iter),
                (np.int64(a_align), Ae, Ao, enabled, np.int64(cap)))
    if name == "compact_bins":
        k = int(p["n_bins"])
        if k < 1:
            raise T.TransformError("n_bins must be >= 1")
        if k > full_n:
            raise T.TransformError("n_bins exceeds dataset size")
        if k > n_sample:
            return ("defer",)  # feasible on full data, unscorable on sample
        return ("grid", ("cb", k), ())
    return ("generic",)


def score_candidate(name: str, p: dict, X, spec: FloatSpec, extrema,
                    full_n: int | None = None):
    """Dispatch one (transform, params) candidate onto its fused per-family
    scorer (the ``perfamily`` engine).

    Returns a device lane vector for `fetch_scores`, None when the transform
    has no fused scorer (the engine then falls back to the generic forward +
    `score_significands`), or the string ``"defer"`` when the candidate must
    be tried unscored in phase 2.  Raises TransformError for infeasibility
    on the FULL array."""
    n_sample = int(X.shape[0])
    plan = _plan_candidate(
        name, p, spec, extrema,
        n_sample, n_sample if full_n is None else int(full_n),
    )
    if plan[0] == "defer":
        return "defer"
    if plan[0] == "generic":
        return None
    entry, dyn = plan[1], plan[2]
    fam = entry[0]
    PHASE1.dispatches += 1
    if fam == "sse":
        return _sse_score(X, int(extrema[0]), entry[1], entry[2], spec=spec)
    if fam == "ms":
        a1, a_const, thresh = dyn
        return _ms_score(X, a1, a_const, thresh, max_iter=entry[1], spec=spec)
    if fam == "ss":
        a_align, Ae, Ao, enabled, cap = dyn
        return _ss_score(X, a_align, Ae, Ao, enabled, cap, spec=spec)
    return _cb_score(X, k=entry[1], spec=spec)


# ---------------------------------------------------------------------------
# stacked engine: the WHOLE candidate grid in one dispatch + one device_get
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("spec", "plan"))
def _grid_score(Xs, x_min, dyn, spec: FloatSpec, plan: tuple):
    """ONE device dispatch for the whole candidate grid.

    Every planned family's forward arithmetic runs on the shared sample,
    the transformed streams stack into a ``[n_candidates, n]`` uint64 word
    grid, and the fused bit-statistics estimator (``kernels/scoregrid``:
    per-plane run model + pooled byte-entropy accumulation) scores all rows
    together.  Returns ``(lanes, W, extras)``: float64[n_candidates, 6]
    lanes ``[data_bits, fixed_meta_bits, per_sample_meta_bits, valid,
    byte_bits, table_syms]``, the stacked word grid itself (retained so
    finalist re-scoring reuses the already-transformed streams instead of
    re-running forwards), the per-candidate pooled byte histograms (the
    rANS statistics pass, retained for the same reason), and each
    candidate's per-sample metadata arrays (sse chunk-ids/evenness, cb
    shifts/floors) for the metadata probe."""
    words, fixed, psamp, valid, extras = [], [], [], [], []
    for entry, d in zip(plan, dyn):
        fam = entry[0]
        if fam == "sse":
            built = _sse_build(Xs, x_min, entry[1], entry[2], spec)
        elif fam == "ms":
            a1, a_const, thresh = d
            built = _ms_build(Xs, a1, a_const, thresh, entry[1], spec)
        elif fam == "ss":
            a_align, Ae, Ao, enabled, cap = d
            built = _ss_build(Xs, a_align, Ae, Ao, enabled, cap, spec)
        else:
            built = _cb_build(Xs, entry[1], spec)
        w, f, s_, v, ex = built
        words.append(w)
        fixed.append(jnp.asarray(f, jnp.float64))
        psamp.append(jnp.asarray(s_, jnp.float64))
        valid.append(jnp.asarray(v).astype(jnp.float64))
        extras.append(ex)
    W = jnp.stack(words)
    n = W.shape[1]
    lanes = spec.width // 8
    ones, trans, hist = plane_byte_stats_grid(
        W, lanes=lanes, use_pallas=_USE_PALLAS_GRID,
        interpret=INTERPRET_DEFAULT,
    )
    mat = jnp.stack([
        finalize_bits_grid(ones, trans, hist, n, lanes),
        jnp.stack(fixed),
        jnp.stack(psamp),
        jnp.stack(valid),
        byte_entropy_bits(hist, n, lanes),
        (hist > 0).sum(axis=-1).astype(jnp.float64),
    ], axis=1)
    return mat, W, hist, tuple(extras)


def score_candidates_stacked(candidates, Xs, spec: FloatSpec, extrema,
                             full_n: int, generic_score_fn=None):
    """Score every candidate with ONE stacked jit dispatch and ONE
    ``device_get``.

    Grid-able candidates (the four built-in families) run inside the single
    :func:`_grid_score` dispatch; a transform without a fused builder is
    scored through ``generic_score_fn(name, params)`` (its own dispatch,
    returning a :class:`CandidateScore` with a pending ``_dev`` estimate, or
    None when the forward rejects) and its handle is resolved in the SAME
    ``device_get`` as the grid — the single-fetch invariant holds for every
    candidate mix.  With no ``generic_score_fn``, builder-less candidates
    are skipped.

    Returns ``(scores, deferred)``: fully resolved scores in candidate
    order, plus the candidates that must be tried unscored in phase 2."""
    from . import transforms as T

    entries: list[tuple] = []          # ("grid", name, p) | ("generic", score)
    plan, dyn = [], []
    deferred: list[tuple[str, dict]] = []
    n_sample = int(Xs.shape[0])
    for name, p in candidates:
        if name == "identity":
            continue
        try:
            cand = _plan_candidate(name, p, spec, extrema, n_sample, full_n)
        except T.TransformError:
            continue
        if cand[0] == "defer":
            deferred.append((name, p))
        elif cand[0] == "generic":
            if generic_score_fn is None:
                continue
            s = generic_score_fn(name, p)
            if s is not None:
                entries.append(("generic", s))
        else:
            plan.append(cand[1])
            dyn.append(cand[2])
            entries.append(("grid", name, p))
    pending = [e[1] for e in entries if e[0] == "generic"]
    handles = [s._dev for s in pending]
    if plan:
        out, W, hist, extras = _grid_score(Xs, int(extrema[0]), tuple(dyn),
                                           spec=spec, plan=tuple(plan))
        PHASE1.dispatches += 1
    else:
        out, W, hist, extras = np.zeros((0, 6), np.float64), None, None, ()
    if plan or handles:
        # ONE device_get resolves the score lanes, the retained word grid +
        # byte histograms + metadata extras (finalist reuse), and every
        # generic handle
        mat, W_np, hist_np, extras_np, vals = jax.device_get(
            (out, W, hist, extras, handles)
        )
        PHASE1.device_gets += 1
    else:
        mat, W_np, hist_np, extras_np, vals = out, None, None, (), []
    mat = np.asarray(mat, np.float64)
    scores: list[CandidateScore] = []
    ri = gi = 0
    for e in entries:
        if e[0] == "grid":
            row = mat[ri]
            scores.append(CandidateScore(
                name=e[1], params=e[2],
                est_bytes=float(row[0]) / 8.0,
                meta_bytes=float(row[1]) / 8.0,
                per_sample_bytes=float(row[2]) / 8.0,
                valid=bool(row[3] > 0.5),
                byte_bytes=float(row[4]) / 8.0,
                table_syms=int(row[5]),
                words=W_np[ri],
                meta_streams=extras_np[ri],
                byte_hist=hist_np[ri],
            ))
            ri += 1
        else:
            s = e[1]
            s.est_bytes = float(np.asarray(vals[gi], np.float64)) / 8.0
            s._dev = None
            gi += 1
            scores.append(s)
    return scores, deferred


# ---------------------------------------------------------------------------
# host-side reuse of retained grid streams (finalist re-scoring + the
# metadata probe).  Everything here replicates the transforms' own metadata
# packing bit-for-bit, so a score computed from retained streams equals the
# score a fresh forward run would produce — the engines stay winner-identical.
# ---------------------------------------------------------------------------

_WIDTH_DTYPES = {8: "<u8", 4: "<u4", 2: "<u2"}


def payload_bytes_from_words(words, spec: FloatSpec) -> bytes:
    """A retained uint64 word row -> the exact bytes the real compressor
    would see for that candidate's transformed stream (LE, spec width)."""
    w = np.asarray(words, np.uint64)
    return w.astype(_WIDTH_DTYPES[spec.width // 8]).tobytes()


def meta_bytes_from_streams(name: str, streams, scale: float) -> float:
    """Exact candidate metadata cost from retained grid streams — the same
    quantity ``pipeline._scaled_meta_bytes(meta, scale)`` computes from a
    forward run's meta object (sse/cb pack their streams with the identical
    codecs the container format uses)."""
    import zlib as _zlib

    from ..compression.bitplane import compress_int_stream

    if name == "multiply_shift":
        return float(-(-(128 + 64) // 8))
    if name == "shift_separate":
        return float(-(-(128 + 2 * 64) // 8))
    if name == "compact_bins":
        shifts, new_lo = streams
        nbits = 128 + 8 * (
            len(compress_int_stream(np.asarray(shifts, np.int64)))
            + len(compress_int_stream(np.asarray(new_lo, np.int64)[1:]))
        )
        return float(-(-nbits // 8))
    if name == "shift_save_even":
        ids, parity = streams
        ids_z = compress_int_stream(np.asarray(ids, np.int64))
        even_z = _zlib.compress(
            np.packbits(np.asarray(parity, np.uint8)).tobytes(), 6
        )
        nbits = 128 + 64 + 8 * (len(ids_z) + len(even_z))
        return -(-nbits // 8) * scale
    raise KeyError(f"no metadata stream model for transform {name!r}")
