"""The classic encode path pads its significands to one length per family for
every chunk up to a writer's default chunk (the rANS scans' ladder above it):
the padded path writes the same record as an unpadded run, chunks of any
length up to ``CLASSIC_MIN_ELEMS`` share one program, and compact bins, whose
gap ranking copies of a value would change, is left at its own length."""
import ml_dtypes
import numpy as np
import pytest

from repro.core import pipeline, transforms as T

FAMILIES = {"multiply_shift": {"D": 4}, "shift_separate": {"D": 2},
            "shift_save_even": {"D": 4}}
DTYPES = {"f64": np.float64, "f32": np.float32, "bf16": ml_dtypes.bfloat16}


def _draw(dtype, n, seed=5):
    x = np.random.default_rng(seed).normal(0.0, 0.05, n)
    return x.astype(np.float32).astype(DTYPES[dtype])


def _unpadded(monkeypatch):
    monkeypatch.setattr(pipeline, "_classic_pad", lambda name, X: X)


def _record(x, family):
    try:
        enc = pipeline.apply_transform(x, family, FAMILIES[family])
    except T.TransformError as e:
        return None, str(e)
    return enc, pipeline.serialize_chunk(enc)


@pytest.mark.parametrize("n", [300, 2048, 47_104, 70_000])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_padded_record_equals_the_unpadded_record(family, dtype, n,
                                                  monkeypatch):
    """Where the family rejects the draw, it rejects it padded too."""
    x = _draw(dtype, n)
    padded, record = _record(x, family)
    with monkeypatch.context() as m:
        _unpadded(m)
        _, plain = _record(x, family)
    assert record == plain
    if padded is not None:
        assert np.array_equal(pipeline.decode(padded).view(np.uint8),
                              x.view(np.uint8))


def test_chunk_lengths_up_to_the_floor_share_one_program():
    pipeline.apply_transform(_draw("f64", 1000), "multiply_shift", {"D": 4})
    before = T._ms_loop._cache_size()
    for n in (300, 2048, 12_800, 47_104, pipeline.CLASSIC_MIN_ELEMS):
        pipeline.apply_transform(_draw("f64", n), "multiply_shift", {"D": 4})
    assert T._ms_loop._cache_size() == before


def test_compact_bins_keeps_its_own_length():
    x = _draw("f64", 6)
    with pytest.raises(T.TransformError, match="exceeds dataset size"):
        pipeline.apply_transform(x, "compact_bins", {"n_bins": 8})
    assert pipeline._classic_pad("compact_bins", x) is x
