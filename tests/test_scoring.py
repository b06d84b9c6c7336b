"""Tests for the fused auto-candidate search engine (core/scoring.py +
pipeline two-phase selection): plane-stats correctness vs the numpy
reference, estimator sanity, winner agreement with full-zlib scoring on the
test corpus, selection safety (never ships a non-round-tripping candidate),
the `presample` infeasible-pick fallback, and the stacked single-dispatch
grid engine's bitwise parity with the per-family oracle."""
import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compression.bitplane import shared_bit_mask, words_to_bitplanes
from repro.core import pipeline, scoring, transforms as T
from repro.data import chicago_taxi_fares, gas_turbine_emissions
from repro.kernels.sharedbits.ops import plane_stats_u64, shared_mask_u64


def _smooth(n):
    t = np.linspace(0, 4, n)
    return (20.0 + np.sin(t) + 1e-5 * t).astype(np.float64)


# ---------------------------------------------------------------------------
# plane stats
# ---------------------------------------------------------------------------

def test_plane_stats_matches_reference():
    rng = np.random.default_rng(0)
    w = rng.integers(0, 1 << 63, 513, dtype=np.uint64)
    ones, trans, mask = map(np.asarray, plane_stats_u64(jnp.asarray(w)))
    planes = words_to_bitplanes(w)          # [64, n], plane 0 = MSB
    for p in range(64):
        bits = planes[63 - p]               # significance p
        assert ones[p] == bits.sum()
        assert trans[p] == int(np.count_nonzero(bits[1:] != bits[:-1]))
    assert int(mask) == int(shared_bit_mask(w))


def test_plane_stats_mask_matches_kernel():
    rng = np.random.default_rng(1)
    w = rng.integers(0, 1 << 63, 4096, dtype=np.uint64) | np.uint64(0x30 << 40)
    _, _, mask = plane_stats_u64(jnp.asarray(w))
    assert int(mask) == int(shared_mask_u64(jnp.asarray(w)))


def test_estimate_bounds():
    """The estimator is a zlib-surrogate *rank*, not a tight size: random
    words must estimate near-raw, structured streams far below them."""
    rng = np.random.default_rng(2)
    n = 4096
    rand = rng.integers(0, 1 << 63, n, dtype=np.uint64) * 2 + 1
    est_rand = scoring.estimate_stream_bits(rand)
    assert 0.8 * 62 * n < est_rand <= 64.5 * n  # near-raw for random words
    const = np.full(n, 0x12345678ABCD, np.uint64)
    assert scoring.estimate_stream_bits(const) < 0.5 * est_rand
    # shared top 48 bits: only the low planes should cost anything
    shared = (rand & np.uint64(0xFFFF)) | np.uint64(0x1234 << 48)
    assert scoring.estimate_stream_bits(shared) < 0.5 * est_rand


# ---------------------------------------------------------------------------
# engine behaviour
# ---------------------------------------------------------------------------

def _corpus():
    out = []
    for n in (1000, 5000):
        for s in (0, 1):
            out.append(chicago_taxi_fares(n, seed=s))
            out.append(gas_turbine_emissions(n, seed=s))
    out.append(chicago_taxi_fares(20000))
    out.append(gas_turbine_emissions(20000))
    out.append(_smooth(4000))
    out.append(np.full(2000, 3.14159))
    out.append((np.random.default_rng(7).standard_normal(8192) * 1e-3))
    return out


def test_analytic_winner_agreement():
    """Acceptance: the analytic scorer's shipped winner equals the full-zlib
    exact scorer's on >= 90% of the corpus — and every encode round-trips."""
    zfn = lambda b: len(zlib.compress(b, 6))
    agree = total = 0
    for x in _corpus():
        a = pipeline.encode(x)                  # analytic two-phase engine
        e = pipeline.encode(x, size_fn=zfn)     # exact full scoring
        total += 1
        agree += (a.method, a.params) == (e.method, e.params)
        assert np.array_equal(
            pipeline.decode(a).view(np.uint64), x.view(np.uint64)
        )
    assert agree / total >= 0.9, f"agreement {agree}/{total}"


def test_engine_never_ships_broken_candidate():
    """Adversarial inputs: zeros, infs, nans, subnormals, mixed signs —
    whatever the scorer ranks, the shipped encoding must invert bitwise."""
    rng = np.random.default_rng(11)
    cases = [
        np.asarray([0.0, -0.0, np.inf, -np.inf, np.nan, 5e-324, -5e-324]),
        rng.standard_normal(3000),
        np.frombuffer(rng.bytes(8 * 2048), np.float64),
        np.concatenate([np.zeros(100), 1e300 * rng.random(100)]),
    ]
    for x in cases:
        enc = pipeline.encode(np.asarray(x, np.float64))
        assert np.array_equal(
            pipeline.decode(enc).view(np.uint64),
            np.asarray(x, np.float64).view(np.uint64),
        )


def test_family_diverse_finalists():
    """Phase 1 must hand phase 2 at most one finalist per transform family
    before refilling (so exact re-scoring sees diverse structures)."""
    x = gas_turbine_emissions(5000)
    xf = x.reshape(-1)
    finite = np.isfinite(xf) & (xf != 0)
    from repro.core.float_bits import normalize_bits, spec_for
    from repro.core.lossless import significand_from_bits

    spec = spec_for(x)
    y01, e, s = normalize_bits(xf[finite].view(np.uint64), spec)
    X = significand_from_bits(y01, spec)
    ranked = pipeline._select_analytic(
        xf, finite, X, spec, pipeline.DEFAULT_CANDIDATES,
        pipeline._sampled_size(None), 100.0,
        pipeline.DEFAULT_SAMPLE_ELEMS, pipeline.DEFAULT_TOP_K,
    )
    # the head (exact-scored finalists + identity) is family-diverse; the
    # tail after it is the deliberate try-everything fallback chain
    k = pipeline.DEFAULT_TOP_K
    head_families = [n for n, _ in ranked[: k + 1] if n != "identity"]
    assert len(set(head_families)) == len(head_families)
    # fallback chain covers every feasible candidate exactly once
    assert len(ranked) == len(set((n, repr(p)) for n, p in ranked))


def test_restricted_candidates_never_ship_unlisted_method():
    """A candidate list without identity must ship a listed method or raise
    (seed semantics) — never silently substitute identity."""
    x = gas_turbine_emissions(3000)
    enc = pipeline.encode(x, candidates=(("shift_save_even", {"D": 8}),))
    assert enc.method == "shift_save_even"
    assert np.array_equal(
        pipeline.decode(enc).view(np.uint64), x.view(np.uint64)
    )
    wide = np.asarray(1.0 + np.random.default_rng(0).random(4000))
    with pytest.raises(T.TransformError):
        pipeline.encode(
            wide,
            candidates=(("multiply_shift", {"D": 8, "max_iter": 16}),),
        )


def test_large_n_bins_candidate_not_excluded():
    """compact_bins with more bins than the phase-1 sample (but fewer than
    the full array) must still be reachable by auto-selection: it is
    deferred to phase-2 full-array apply+verify, not silently dropped."""
    x = gas_turbine_emissions(50_000)
    enc = pipeline.encode(x, candidates=(("compact_bins", {"n_bins": 6000}),))
    assert enc.method == "compact_bins"
    assert enc.params == {"n_bins": 6000}
    assert np.array_equal(
        pipeline.decode(enc).view(np.uint64), x.view(np.uint64)
    )


def test_high_passthrough_not_worse_than_identity():
    """Selection estimates must account for passthrough bytes and the full
    passthrough mask: with ~half the stream non-finite, auto must not ship
    an encoding larger than no-prep + slack (the identity guarantee)."""
    rng = np.random.default_rng(5)
    n = 60000
    x = 2.0 + rng.random(n) * 1e-4
    nanbits = rng.integers(0, 1 << 51, n, dtype=np.uint64) | np.uint64(
        0x7FF8 << 48
    )  # NaNs with high-entropy payloads
    mask = rng.random(n) < 0.5
    x[mask] = nanbits[mask].view(np.float64)[: int(mask.sum())]
    enc = pipeline.encode(x)
    assert np.array_equal(
        pipeline.decode(enc).view(np.uint64), x.view(np.uint64)
    )
    zfn = lambda b: len(zlib.compress(b, 6))
    shipped = zfn(np.asarray(enc.data).tobytes()) + enc.metadata_bytes()
    noprep = zfn(x.tobytes()) + 16
    assert shipped <= noprep * 1.02 + 64, (enc.method, shipped, noprep)


# ---------------------------------------------------------------------------
# stacked single-dispatch grid engine vs the per-family oracle
# ---------------------------------------------------------------------------

# per-spec candidate lists that keep every transform family feasible (the
# D-grids shrink with the mantissa width: bf16 has l=7, so the f64 defaults
# would leave whole families infeasible and untested there)
_GRID_CANDIDATES = {
    "f64": pipeline.DEFAULT_CANDIDATES,
    "f32": (
        ("compact_bins", {"n_bins": 4}),
        ("compact_bins", {"n_bins": 16}),
        ("multiply_shift", {"D": 4}),
        ("multiply_shift", {"D": 6}),
        ("shift_separate", {"D": 2}),
        ("shift_separate", {"D": 3}),
        ("shift_save_even", {"D": 8}),
        ("shift_save_even", {"D": 12}),
    ),
    "bf16": (
        ("compact_bins", {"n_bins": 4}),
        ("compact_bins", {"n_bins": 8}),
        ("multiply_shift", {"D": 2}),
        ("multiply_shift", {"D": 3}),
        ("shift_separate", {"D": 2}),
        ("shift_save_even", {"D": 2}),
        ("shift_save_even", {"D": 4}),
    ),
}
_GRID_DTYPES = {"f64": np.float64, "f32": np.float32, "bf16": jnp.bfloat16}


def _perfamily_scores(candidates, Xs, spec, extrema, full_n):
    out = []
    for name, p in candidates:
        if name == "identity":
            continue
        try:
            dev = scoring.score_candidate(name, p, Xs, spec, extrema,
                                          full_n=full_n)
        except T.TransformError:
            continue
        if dev == "defer" or dev is None:
            continue
        out.append(scoring.CandidateScore(name=name, params=p, _dev=dev))
    scoring.fetch_scores(out)
    return out


@pytest.mark.parametrize("spec_name", ["f64", "f32", "bf16"])
def test_stacked_scores_bitwise_equal_perfamily(spec_name):
    """The stacked grid must reproduce the per-family engine's phase-1 lanes
    BITWISE — estimate, metadata model and feasibility verdict — for every
    candidate family, in every float spec."""
    if spec_name == "bf16":
        # 7 mantissa bits: a full-binade stream leaves shift&separate
        # infeasible everywhere, so use a narrow-span stream that keeps
        # every family on the grid
        rng = np.random.default_rng(0)
        x = 1.0 + rng.integers(0, 12, 3000) / 128.0
    else:
        x = gas_turbine_emissions(3000)
    prep = pipeline._prepare(jnp.asarray(x, _GRID_DTYPES[spec_name]))
    Xs = pipeline._strided(prep.X, pipeline.DEFAULT_SAMPLE_ELEMS)
    mn, mx = jax.device_get((jnp.min(Xs), jnp.max(Xs)))
    extrema = (int(mn), int(mx))
    candidates = _GRID_CANDIDATES[spec_name]

    stacked, deferred = scoring.score_candidates_stacked(
        candidates, Xs, prep.spec, extrema, full_n=prep.n_active
    )
    perfam = _perfamily_scores(candidates, Xs, prep.spec, extrema,
                               prep.n_active)
    assert [(s.name, s.params) for s in stacked] == \
           [(s.name, s.params) for s in perfam]
    # every family must actually be on the grid (else the parity is vacuous)
    assert {s.name for s in stacked} == {
        n for n, _ in candidates if n != "identity"
    }
    for a, b in zip(stacked, perfam):
        tag = (a.name, str(a.params))
        assert a.est_bytes == b.est_bytes, tag
        assert a.meta_bytes == b.meta_bytes, tag
        assert a.per_sample_bytes == b.per_sample_bytes, tag
        assert a.valid == b.valid, tag
        # the rANS size-model lanes (pooled byte entropy + distinct symbol
        # count) ride the same parity contract
        assert a.byte_bytes == b.byte_bytes, tag
        assert a.table_syms == b.table_syms, tag
        # only the stacked engine retains streams; the oracle re-runs
        assert a.words is not None and b.words is None


def test_stacked_phase1_single_dispatch():
    """Acceptance: phase-1 of encode(method='auto') issues exactly ONE
    stacked jit dispatch and ONE device_get for the whole candidate grid
    (the per-family engine issues one dispatch per candidate) — and the
    finalist exact re-scoring adds ZERO forward dispatches on the stacked
    engine (it reuses the grid's already-transformed word streams; the
    per-family oracle re-runs one forward per finalist)."""
    x = gas_turbine_emissions(50_000)
    scoring.PHASE1.reset()
    picked = pipeline.select_method(x)  # stacked is the default engine
    assert scoring.PHASE1.dispatches == 1
    assert scoring.PHASE1.device_gets == 1
    assert scoring.PHASE1.finalist_dispatches == 0
    assert scoring.PHASE1.probe_dispatches == 0  # meta streams ride the grid

    scoring.PHASE1.reset()
    picked_pf = pipeline.select_method(x, engine="perfamily")
    assert picked_pf == picked
    assert scoring.PHASE1.dispatches == 16  # one per non-identity candidate
    assert scoring.PHASE1.device_gets == 1
    # the oracle pays one forward per non-identity finalist (identity is
    # scored from the raw sample, not a transform run)
    assert scoring.PHASE1.finalist_dispatches == pipeline.DEFAULT_TOP_K

    # the full auto encode keeps the property (phase 2 adds no scoring cost)
    scoring.PHASE1.reset()
    enc = pipeline.encode(x)
    assert scoring.PHASE1.dispatches == 1
    assert scoring.PHASE1.device_gets == 1
    assert scoring.PHASE1.finalist_dispatches == 0
    assert np.array_equal(
        pipeline.decode(enc).view(np.uint64), x.view(np.uint64)
    )


def test_stacked_winner_matches_perfamily_corpus():
    """Acceptance: selected winners are identical between the stacked engine
    and the per-family engine on the full test corpus."""
    for x in _corpus():
        got = pipeline.select_method(x, engine="stacked")
        want = pipeline.select_method(x, engine="perfamily")
        assert got == want, (got, want)


def test_sse_proxy_tiebreak_smooth_stream():
    """Regression (ROADMAP PR 1 open item): on smooth streams the analytic
    per-sample metadata model misranks D within shift&save-evenness (it
    prices chunk ids at a fixed bit width; real zlib is ~3x off either
    way).  The sampled-zlib metadata probe must recover the D that full
    exact zlib scoring picks — at zero extra dispatches on the stacked
    engine (the metadata streams ride the single grid fetch)."""
    import zlib as _z

    zfn = lambda b: len(_z.compress(b, 6))
    sse_only = tuple(
        ("shift_save_even", {"D": d}) for d in (8, 12, 16, 24, 32, 40, 48)
    )
    for n in (4000, 20000):
        x = _smooth(n)
        scoring.PHASE1.reset()
        probed = pipeline.encode(x, candidates=sse_only)
        assert scoring.PHASE1.dispatches == 1
        assert scoring.PHASE1.device_gets == 1
        assert scoring.PHASE1.probe_dispatches == 0
        exact = pipeline.encode(x, candidates=sse_only, size_fn=zfn)
        assert probed.params == exact.params, (n, probed.params, exact.params)
        assert np.array_equal(
            pipeline.decode(probed).view(np.uint64), x.view(np.uint64)
        )
        # engine parity holds through the probe (perfamily probes by
        # re-running forwards on the sample — counted, same outcome)
        scoring.PHASE1.reset()
        pf = pipeline.select_method(x, candidates=sse_only,
                                    engine="perfamily")
        assert pf == (probed.method, probed.params)
        assert scoring.PHASE1.probe_dispatches > 0


def test_unknown_engine_rejected():
    with pytest.raises(ValueError):
        pipeline.select_method(gas_turbine_emissions(1000), engine="nope")


def test_generic_candidate_keeps_single_fetch(monkeypatch):
    """A candidate without a fused builder costs its own dispatch, but its
    estimate handle must resolve inside the stacked engine's single
    device_get (grid + generic = 2 dispatches, still 1 fetch)."""
    def dummy_fwd(X, *, spec=None, extrema=None, **_):
        return jnp.asarray(X, jnp.int64), jnp.zeros(jnp.shape(X), jnp.int32), None

    def dummy_inv(Xt, offsets, meta, spec=None):
        return jnp.asarray(Xt, jnp.int64)

    monkeypatch.setitem(T.TRANSFORMS, "dummy_copy", (dummy_fwd, dummy_inv))
    x = gas_turbine_emissions(3000)
    candidates = (("shift_save_even", {"D": 8}), ("dummy_copy", {}))
    scoring.PHASE1.reset()
    name, _p = pipeline.select_method(x, candidates=candidates)
    assert name in ("shift_save_even", "dummy_copy")
    assert scoring.PHASE1.dispatches == 2
    assert scoring.PHASE1.device_gets == 1


# ---------------------------------------------------------------------------
# presample fallback (sampled pick infeasible on the full array)
# ---------------------------------------------------------------------------

def test_presample_fallback_infeasible_pick(monkeypatch):
    rng = np.random.default_rng(0)
    x = np.asarray(1.0 + rng.random(20000), np.float64)  # full-binade span

    # multiply&shift D=8 capped at 16 iterations is infeasible on this span
    with pytest.raises(T.TransformError):
        pipeline.encode(x, method="multiply_shift",
                        params={"D": 8, "max_iter": 16})

    real_encode = pipeline.encode

    def fake_encode(xx, method="auto", **kw):
        if method == "auto" and np.size(xx) == 512 and "presample" not in kw:
            # the inner presample selection: force an infeasible pick
            pick = real_encode(xx, method="identity")
            return dataclasses.replace(
                pick, method="multiply_shift",
                params={"D": 8, "max_iter": 16},
            )
        return real_encode(xx, method=method, **kw)

    monkeypatch.setattr(pipeline, "encode", fake_encode)
    enc = fake_encode(x, method="auto", presample=512)
    # fell back to a full search instead of shipping the infeasible pick
    assert enc.params.get("max_iter") != 16
    assert np.array_equal(
        pipeline.decode(enc).view(np.uint64), x.view(np.uint64)
    )
