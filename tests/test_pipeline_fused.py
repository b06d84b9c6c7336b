"""Fused device-resident encode (PR 7): winner-apply + verify + byte-pack
+ interleaved rANS entropy coding in ONE jit dispatch, fetched with ONE
device_get (``scoring.PHASE2``), emitting a framed payload byte-identical
to host-side backend compression of the same record."""
import numpy as np
import pytest

from repro.container import ContainerReader, ContainerWriter, get_backend
from repro.container import format as FF
from repro.core import pipeline as P, scoring as S
from repro.data import gas_turbine_emissions

# every family here is fusible: auto-encode must never take a fallback
FUSIBLE_CANDIDATES = (
    ("identity", {}),
    ("shift_save_even", {"D": 16}),
    ("compact_bins", {"n_bins": 16}),
)


@pytest.fixture()
def turbine():
    return gas_turbine_emissions(20_000)


def _payload_matches(enc) -> bool:
    be = get_backend("rans")
    return enc.payload == be.compress(np.ascontiguousarray(enc.data).tobytes())


@pytest.mark.parametrize("method,params", [
    ("identity", {}),
    ("shift_save_even", {"D": 16}),
    ("compact_bins", {"n_bins": 16}),
])
def test_fused_apply_one_dispatch_one_get(method, params, turbine):
    S.PHASE2.reset()
    enc = P.apply_transform(turbine, method, params, backend="rans")
    assert (S.PHASE2.dispatches, S.PHASE2.device_gets,
            S.PHASE2.fallbacks) == (1, 1, 0)
    assert enc.payload is not None and enc.payload_backend == "rans"
    assert _payload_matches(enc)
    back = P.decode(enc)
    assert np.array_equal(back.view(np.uint64),
                          np.asarray(turbine).view(np.uint64))


def test_fused_auto_encode_counters(turbine):
    S.PHASE2.reset()
    enc = P.encode(turbine, backend="rans", candidates=FUSIBLE_CANDIDATES)
    assert (S.PHASE2.dispatches, S.PHASE2.device_gets,
            S.PHASE2.fallbacks) == (1, 1, 0)
    assert _payload_matches(enc)
    assert np.array_equal(P.decode(enc).view(np.uint64),
                          np.asarray(turbine).view(np.uint64))


def test_fused_record_byte_identical_to_classic(turbine):
    """The frame is producer-agnostic: a record serialized from the fused
    device payload equals, byte for byte, the record the classic host path
    produces for the same chunk."""
    fused = P.apply_transform(turbine, "shift_save_even", {"D": 16},
                              backend="rans")
    classic = P.apply_transform(turbine, "shift_save_even", {"D": 16})
    assert classic.payload is None
    assert FF.serialize_chunk(fused, "rans") == FF.serialize_chunk(
        classic, "rans"
    )


def test_payload_ignored_on_backend_mismatch(turbine):
    """A rans payload must never leak into a zlib container record."""
    fused = P.apply_transform(turbine, "shift_save_even", {"D": 16},
                              backend="rans")
    rec = FF.serialize_chunk(fused, "zlib")
    classic = P.apply_transform(turbine, "shift_save_even", {"D": 16})
    assert rec == FF.serialize_chunk(classic, "zlib")
    enc = FF.deserialize_chunk(rec, "zlib", spec_name="f64")
    assert np.array_equal(P.decode(enc).view(np.uint64),
                          np.asarray(turbine).view(np.uint64))


def test_passthrough_scatter_falls_back(turbine):
    """Chunks with passthrough elements (zeros/non-finite) take the classic
    path and are counted as PHASE2 fallbacks — still bitwise lossless."""
    x = np.asarray(turbine).copy()
    x[::97] = 0.0
    S.PHASE2.reset()
    enc = P.apply_transform(x, "shift_save_even", {"D": 16}, backend="rans")
    assert S.PHASE2.dispatches == 0
    assert S.PHASE2.fallbacks == 1
    assert enc.payload is None
    assert np.array_equal(P.decode(enc).view(np.uint64), x.view(np.uint64))


def test_tiny_chunk_skips_fusion_without_fallback():
    x = gas_turbine_emissions(256)
    S.PHASE2.reset()
    enc = P.apply_transform(x, "identity", backend="rans")
    assert (S.PHASE2.dispatches, S.PHASE2.fallbacks) == (0, 0)
    assert enc.payload is None
    assert np.array_equal(P.decode(enc).view(np.uint64),
                          np.asarray(x).view(np.uint64))


def test_container_rans_stream_fused_and_lossless(tmp_path, turbine):
    x = np.asarray(turbine)
    path = tmp_path / "fused.fpc"
    S.PHASE2.reset()
    with ContainerWriter(path, dtype=np.float64, backend="rans") as w:
        for s in range(0, x.size, 8192):
            w.append(x[s: s + 8192])
    assert S.PHASE2.dispatches >= 1       # chunks rode the fused path
    with ContainerReader(path) as r:
        back = r.read_all()
        assert r.backend == "rans"
    assert np.array_equal(back.view(np.uint64), x.view(np.uint64))


def test_append_accepts_device_arrays(tmp_path, turbine):
    import jax.numpy as jnp

    x = np.asarray(turbine)
    dev = jnp.asarray(x)
    path = tmp_path / "dev.fpc"
    with ContainerWriter(path, dtype=np.float64, backend="rans") as w:
        w.append(dev)
    with ContainerReader(path) as r:
        back = r.read_all()
    assert np.array_equal(back.view(np.uint64), x.view(np.uint64))


def test_device_append_rejects_dtype_mismatch(tmp_path, turbine):
    import jax.numpy as jnp
    from repro.container import ContainerError

    with ContainerWriter(tmp_path / "m.fpc", dtype=np.float64) as w:
        with pytest.raises(ContainerError):
            w.append(jnp.zeros(64, jnp.float32))
        w.append(np.asarray(turbine)[:64])  # writer still usable


def test_plan_cache_skips_reselection(turbine):
    P._PLAN_CACHE.clear()
    S.PHASE1.reset()
    first = P.encode(turbine)
    assert S.PHASE1.dispatches >= 1
    S.PHASE1.reset()
    second = P.encode(turbine)
    assert S.PHASE1.dispatches == 0       # plan cache hit: phase 1 skipped
    assert second.method == first.method and second.params == first.params
    assert np.array_equal(P.decode(second).view(np.uint64),
                          np.asarray(turbine).view(np.uint64))


def test_select_method_stays_uncached_by_default(turbine):
    P._PLAN_CACHE.clear()
    S.PHASE1.reset()
    pick1 = P.select_method(turbine)
    d1 = S.PHASE1.dispatches
    S.PHASE1.reset()
    pick2 = P.select_method(turbine)
    assert S.PHASE1.dispatches == d1      # no hidden caching on the primitive
    assert pick1 == pick2


def test_identity_fast_path_matches_prepared_identity(turbine):
    x = np.asarray(turbine).copy()
    x[::53] = np.inf                       # passthrough rides along verbatim
    enc = P.apply_transform(x, "identity")
    assert enc.method == "identity" and enc.n_active == 0
    assert np.array_equal(P.decode(enc).view(np.uint64), x.view(np.uint64))
