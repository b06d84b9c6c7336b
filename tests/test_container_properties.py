"""Hypothesis property suite for the container codec (satellite of the
parallel-decode PR): ``dumps``/``loads`` and the streaming writer/reader
round-trip **bitwise** across every ``METHOD_IDS`` entry × dtype
(f64/f32/bf16/i32) × registered backend × chunk count — including empty and
1-element arrays.  Runs against real `hypothesis` when installed, else the
deterministic miniature shim in ``tests/conftest.py`` (positional ``given``
only; ``integers``/``floats``/``lists``/``sampled_from``/``booleans``).

Sizes are drawn from a small fixed set so the jitted transforms compile a
bounded number of shapes; the *values* (and via them, feasibility /
identity-fallback behavior) are what hypothesis explores.
"""
import io

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.container import (
    METHOD_IDS,
    ContainerReader,
    ContainerWriter,
    available_backends,
    dumps,
    loads,
)
from repro.core import pipeline
from repro.core import transforms as T
from tests._helpers import words as _words

BACKENDS = available_backends()
METHODS = sorted(METHOD_IDS)
# float16 is the ROADMAP item 4 dtype-widening slice: transform families
# that are infeasible for a given f16 draw fall back to identity inside
# _encode_forced (exactly the writer's own policy), so every cell of the
# matrix still asserts the bitwise round-trip
FLOAT_DTYPES = ("float64", "float32", "float16", "bfloat16")

# one feasible parameter set per method (matching the golden fixtures)
PARAMS = {
    "identity": {},
    "compact_bins": {"n_bins": 4},
    "multiply_shift": {"D": 4},
    "shift_separate": {"D": 2},
    "shift_save_even": {"D": 8},
}

# fixed size alphabet: bounds the jit compile cache while covering the
# degenerate shapes (empty, single element, sub-chunk, non-power-of-two)
SIZES = (0, 1, 2, 33, 257)


def _resolve(dtype: str):
    if dtype == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(dtype)


def _data(dtype: str, n: int, seed: int, specials: bool) -> np.ndarray:
    """Deterministic same-binade-heavy data with optional special values
    (zeros / NaN / infinities / negatives) to exercise the passthrough and
    identity-fallback paths."""
    rng = np.random.default_rng(seed)
    if dtype == "int32":
        return rng.integers(-(1 << 30), 1 << 30, n, dtype=np.int64).astype(
            np.int32
        )
    x = 1.0 + rng.integers(0, 1 << 16, n) / float(1 << 18)
    if specials and n:
        x[:: max(n // 7, 1)] = 0.0
        x[n // 2] = np.nan if n > 2 else x[n // 2]
        if n > 3:
            x[n // 3] = np.inf
            x[1] *= -1.0
    return x.astype(_resolve(dtype))


def _encode_forced(x, method: str):
    """Force one transform family; data the family rejects falls back to
    identity (the writer's own policy) — the *round-trip* property is what
    must hold unconditionally."""
    try:
        return pipeline.apply_transform(x, method, PARAMS[method])
    except T.TransformError:
        return pipeline.apply_transform(x, "identity")


@pytest.fixture(scope="module", autouse=True)
def _compiled_shapes():
    """Compile the codec once for every chunk shape the strategies can
    draw, so hypothesis's per-example deadline times the round trip and
    not a first-call XLA compile."""
    for dtype in FLOAT_DTYPES:
        for method in METHODS:
            for nchunks in range(1, 5):
                for per_chunk in SIZES[1:]:
                    for specials in (False, True):
                        x = _data(dtype, per_chunk * nchunks, 0, specials)
                        for c in range(nchunks):
                            chunk = x[c * per_chunk:(c + 1) * per_chunk]
                            pipeline.decode(_encode_forced(chunk, method))


# ---------------------------------------------------------------------------
# dumps / loads: single-record containers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("method", METHODS)
@given(st.sampled_from(SIZES), st.integers(0, 2**31 - 1), st.booleans())
@settings(max_examples=10)
def test_dumps_loads_bitwise_every_method(backend, method, n, seed, specials):
    for dtype in FLOAT_DTYPES:
        x = _data(dtype, n, seed, specials)
        enc = _encode_forced(x, method)
        enc2 = loads(dumps(enc, backend=backend))
        assert enc2.method == enc.method
        assert enc2.params == enc.params
        assert enc2.n == enc.n and enc2.n_active == enc.n_active
        assert enc2.spec_name == enc.spec_name
        back = pipeline.decode(enc2)
        assert np.array_equal(_words(back), _words(x)), (
            f"dumps/loads not bitwise for method={method} dtype={dtype} "
            f"n={n} seed={seed}"
        )


@given(st.sampled_from(SIZES), st.integers(0, 2**31 - 1))
@settings(max_examples=10)
def test_loads_rejects_multichunk(n, seed):
    x = _data("float64", max(n, 2), seed, False)
    bio = io.BytesIO()
    with ContainerWriter(bio, dtype=np.float64, method="identity") as w:
        w.append(x[: x.size // 2])
        w.append(x[x.size // 2 :])
    with pytest.raises(Exception, match="single-chunk"):
        loads(bio.getvalue())


# ---------------------------------------------------------------------------
# streaming writer/reader: dtype × backend × chunk count
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", FLOAT_DTYPES + ("int32",))
@given(
    st.integers(1, 4),
    st.sampled_from(SIZES),
    st.integers(0, 2**31 - 1),
    st.sampled_from(METHODS),
    st.booleans(),
)
@settings(max_examples=10)
def test_container_roundtrip_chunked(backend, dtype, nchunks, per_chunk,
                                     seed, method, parallel):
    x = _data(dtype, per_chunk * nchunks, seed, specials=(seed % 3 == 0))
    kw = {} if dtype == "int32" else {"method": method, "params": PARAMS[method]}
    bio = io.BytesIO()
    with ContainerWriter(bio, dtype=x.dtype, backend=backend, **kw) as w:
        for c in range(nchunks):
            w.append(x[c * per_chunk : (c + 1) * per_chunk])
    with ContainerReader(bio.getvalue()) as r:
        assert r.nchunks == nchunks
        assert r.n == x.size
        got = r.read_all(parallel=parallel)
        # random access agrees with the stream position
        if r.nchunks and per_chunk:
            i = seed % r.nchunks
            ci = r.read_chunk(i).reshape(-1)
            assert np.array_equal(
                _words(ci), _words(x[i * per_chunk : (i + 1) * per_chunk])
            )
    assert got.size == x.size
    assert np.array_equal(_words(got), _words(x)), (
        f"writer/reader not bitwise for dtype={dtype} backend={backend} "
        f"nchunks={nchunks} per_chunk={per_chunk} seed={seed} "
        f"method={method} parallel={parallel}"
    )


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", FLOAT_DTYPES + ("int32",))
def test_container_empty_and_single_element(backend, dtype):
    """The edge cases named by the issue, deterministically (not left to
    the strategy draw): zero chunks, empty chunks, and 1-element chunks."""
    # zero-chunk container
    bio = io.BytesIO()
    with ContainerWriter(bio, dtype=_resolve(dtype), backend=backend) as w:
        pass
    with ContainerReader(bio.getvalue()) as r:
        assert r.nchunks == 0
        for parallel in (False, True):
            assert r.read_all(parallel=parallel).size == 0
    # one single-element chunk + one empty chunk
    x = _data(dtype, 1, seed=5, specials=False)
    bio = io.BytesIO()
    with ContainerWriter(bio, dtype=x.dtype, backend=backend) as w:
        w.append(x)
        w.append(x[:0])
    with ContainerReader(bio.getvalue()) as r:
        assert r.nchunks == 2
        for parallel in (False, True):
            assert np.array_equal(_words(r.read_all(parallel=parallel)),
                                  _words(x))


# ---------------------------------------------------------------------------
# parallel/serial/prefetch equivalence as a property
# ---------------------------------------------------------------------------

@given(
    st.integers(1, 5),
    st.integers(0, 4),
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=10)
def test_iter_chunks_matches_read_all(nchunks, prefetch, seed):
    x = _data("float64", 64 * nchunks, seed, specials=(seed % 2 == 0))
    bio = io.BytesIO()
    with ContainerWriter(bio, dtype=np.float64, method="identity") as w:
        for c in range(nchunks):
            w.append(x[c * 64 : (c + 1) * 64])
    with ContainerReader(bio.getvalue()) as r:
        serial = r.read_all()
        par = r.read_all(parallel=True)
        it = np.concatenate(
            [c.reshape(-1) for c in r.iter_chunks(prefetch=prefetch)]
        )
    assert np.array_equal(_words(serial), _words(par))
    assert np.array_equal(_words(serial), _words(it))
