"""Phase 1 ranks candidates by the bytes their records store, in every float
format: on 65,536-value weight draws of f64, f32, bf16 and f16 the analytic
winner's rANS record is within 1% of the exact selection's winner (every
candidate applied to the whole chunk and coded by the same rANS coder), the
shared normalization streams are priced as the record packs them, the
payload's frame is counted once, and phase 2 counts what it shipped by
method."""
import ml_dtypes
import numpy as np
import pytest

from repro.core import pipeline, scoring, transforms as T
from repro.kernels.rans import ops as rans_ops

N = 65_536
DTYPES = {"f64": np.float64, "f32": np.float32, "bf16": ml_dtypes.bfloat16,
          "f16": np.float16}
# the laws a model's weights are drawn from (models/common.py): a 512-wide
# projection, and a LayerNorm weight
LAWS = {"matrix": lambda r: r.standard_normal(N) / np.sqrt(512),
        "norm_weight": lambda r: 1.0 + 0.02 * r.standard_normal(N)}


def _draw(dtype, law, seed=17):
    x = LAWS[law](np.random.default_rng(seed))
    if dtype == "f64":
        return x.astype(np.float64)
    return x.astype(np.float32).astype(DTYPES[dtype])


def _record(enc) -> int:
    return len(pipeline.serialize_chunk(enc, "rans"))


def _rans_size(b: bytes) -> int:
    return len(rans_ops.compress(b))


@pytest.mark.parametrize("law", sorted(LAWS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_analytic_winner_stores_what_the_exact_winner_stores(dtype, law):
    x = _draw(dtype, law)
    analytic = pipeline.encode(x, backend="rans")
    prep = pipeline._prepare(x)
    ranked, applied = pipeline._select_exact(
        prep.xf, prep.finite, prep.X, prep.spec, pipeline.DEFAULT_CANDIDATES,
        _rans_size, sum(map(len, prep.pack_common())))
    name, params = ranked[0]
    exact = (prep.finish(name, params, *applied) if applied is not None
             and name != "identity" else
             pipeline.apply_transform(x, name, params, backend="rans"))
    assert _record(analytic) <= 1.01 * _record(exact), (
        (analytic.method, analytic.params), (name, params))
    assert np.array_equal(pipeline.decode(analytic).view(np.uint8),
                          np.asarray(x).view(np.uint8))


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_common_streams_are_priced_as_the_record_packs_them(dtype):
    """Phase 1 packs the exponent, sign and passthrough streams with the
    record's own coders, once: the shipped record carries those very
    bytes."""
    x = _draw(dtype, "matrix")
    prep = pipeline._prepare(x)
    pipeline._rank_candidates(prep, pipeline.DEFAULT_CANDIDATES, None,
                              pipeline.DEFAULT_SAMPLE_ELEMS,
                              pipeline.DEFAULT_TOP_K, backend_hint="rans")
    priced = prep._packed[0]
    for name, params in pipeline.DEFAULT_CANDIDATES[1:]:
        try:
            enc = pipeline.apply_transform(x, name, params, backend="rans")
        except T.TransformError:
            continue
        assert (enc.exponents_z, enc.signs_z, enc.passthrough_z) == priced
        return
    pytest.fail("no transform applies")


def test_rans_frame_is_counted_once():
    """The payload model scales the coder's body with the stream and adds
    the frame (header, table, lane words and flushes) once."""
    size = pipeline._sampled_size("rans")
    rng = np.random.default_rng(3)
    # the bytes of a bf16 phase-1 sample, standing for a 65,536-value chunk
    b = (rng.standard_normal(8192) * 3).astype(np.int8).tobytes()
    assert size(b, 1.0) == len(rans_ops.compress(b))
    whole = len(rans_ops.compress(b * 16))
    assert abs(size(b, 16.0) - whole) <= 0.01 * whole
    # the frame scaled with the body overshoots by a seventh
    assert len(rans_ops.compress(b)) * 16 - whole > 0.1 * whole


def test_phase2_counts_what_it_shipped():
    x = _draw("bf16", "matrix")
    turbine = _draw("f64", "norm_weight")
    scoring.PHASE2.reset()
    pipeline.apply_transform(x, "identity", backend="rans")      # fused
    pipeline.apply_transform(x[:100], "identity")                # plain
    pipeline.apply_transform(turbine, "compact_bins", {"n_bins": 4},
                             backend="rans")
    enc = pipeline.encode(x, backend="rans")
    assert scoring.PHASE2.shipped == {
        "identity": 3 if enc.method == "identity" else 2,
        "compact_bins": 1,
        **({} if enc.method == "identity" else {enc.method: 1})}
    scoring.PHASE2.reset()
    assert not scoring.PHASE2.shipped
