"""Host spans (``repro.obs``): recorded only while a profiler collects, on
the ingest and serve paths where the work happens; and the stable scope
names of the device programs' stages."""
from __future__ import annotations

import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.lib import _profiler
from jax.profiler import ProfileData

from repro import obs
from repro.core import pipeline as P
from repro.core import scoring as S
from repro.data.dataset import DatasetWriter
from repro.kernels.rans import kernel as K
from repro.serving import TensorServer

CHUNK = 32768
# one part of two chunks: the first is probed (phase 1) and fused-encoded,
# the second takes the pick
INGEST_SPANS = {
    "dataset.create", "dataset.part", "dataset.manifest",
    "stream.encode_record", "stream.queue_wait", "stream.write_record",
    "select.probe", "select.device_wait", "encode.prepare",
    "encode.fused", "encode.device_wait", "encode.frame",
    "container.serialize", "rans.encode",
    "durable.fsync", "durable.replace", "durable.fsync_dir",
}
SERVE_MISS_SPANS = {
    "server.read_slice", "cache.get", "server.decode_span",
    "container.read_record", "rans.decode", "rans.device_wait",
    "decode.inverse", "decode.device_wait",
}


def _values(seed: int, n: int = 40000) -> np.ndarray:
    """A smooth 4-decimal sensor series (the fused shift&save-evenness
    path), new content per seed so no phase-1 plan is cached."""
    rng = np.random.default_rng(seed)
    return np.round(2.4 + np.cumsum(rng.normal(0.0, 0.03, n)), 4)


def _write(root, x) -> None:
    DatasetWriter(root, dtype=x.dtype, chunk=CHUNK, backend="rans").write([x])


def _host_lines(trace_dir) -> list[set]:
    pb = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(pb))
    return [{e.name for e in line.events} for p in pd.planes
            if p.name.startswith("/host:") for line in p.lines]


@pytest.fixture(scope="module")
def traced_write(tmp_path_factory):
    """One dataset written under the profiler: (store dir, trace dir,
    span totals of the write)."""
    d = tmp_path_factory.mktemp("obs")
    obs.reset()
    with jax.profiler.trace(str(d / "trace")):
        _write(d / "store" / "t", _values(11))
    totals = obs.totals()
    obs.reset()
    return d / "store", d / "trace", totals


def test_switch_is_the_profilers_own(tmp_path):
    # the private attribute this module reads, as jax 0.9.0 has it
    assert obs._collecting is _profiler.TraceMe.is_enabled
    assert not obs._collecting()
    with jax.profiler.trace(str(tmp_path)):
        assert obs._collecting()
    assert not obs._collecting()


def test_no_profiler_records_nothing(tmp_path, traced_write):
    store, _trace, _totals = traced_write
    obs.reset()
    _write(tmp_path / "t", _values(12))
    with TensorServer(store, cache_bytes=1 << 20) as srv:
        srv.read_slice("t", 100, 111)
    assert obs.totals() == {}


def test_ingest_records_each_span(traced_write):
    _store, _trace, t = traced_write
    assert INGEST_SPANS <= set(t), INGEST_SPANS - set(t)
    # three durable commits: the first manifest, the part, the final one
    assert t["durable.fsync"][0] == 3
    assert t["durable.fsync_dir"][0] == 3
    assert t["durable.replace"][0] == 3
    assert t["dataset.create"][0] == t["dataset.part"][0] == 1
    assert t["dataset.manifest"][0] == 2
    assert t["stream.encode_record"][0] == t["stream.write_record"][0] == 2


def test_self_time_within_total(traced_write):
    _store, _trace, t = traced_write
    for name, (count, total, self_s) in t.items():
        assert count >= 1, name
        assert -1e-9 <= self_s <= total, name
    # a parent's self time excludes its children
    assert t["dataset.part"][2] < t["dataset.part"][1]


def test_write_record_runs_on_the_write_behind_thread(traced_write):
    _store, trace, _t = traced_write
    lines = _host_lines(trace)
    writer = [names for names in lines if "stream.write_record" in names]
    assert writer
    assert all("dataset.part" not in names and "stream.encode_record"
               not in names for names in writer)
    assert any("dataset.part" in names for names in lines)


def test_server_decode_span_per_miss(tmp_path, traced_write):
    store, _trace, _t = traced_write
    with TensorServer(store, cache_bytes=1 << 20) as srv:
        obs.reset()
        with jax.profiler.trace(str(tmp_path)):
            srv.read_slice("t", 100, 111)           # miss: chunk 0 decodes
            miss = obs.totals()
            srv.read_slice("t", 200, 211)           # hit: same chunk
            both = obs.totals()
        decodes = srv.stats()["decodes"]
    obs.reset()
    assert SERVE_MISS_SPANS <= set(miss), SERVE_MISS_SPANS - set(miss)
    assert miss["server.decode_span"][0] == decodes == 1
    assert both["server.decode_span"][0] == 1
    assert both["server.read_slice"][0] == both["cache.get"][0] == 2


def test_flight_wait_records_the_follower(tmp_path, traced_write):
    store, _trace, _t = traced_write
    release = threading.Event()

    class Gated(TensorServer):
        def _decode_span(self, name, lo, hi):
            release.wait(30)
            return super()._decode_span(name, lo, hi)

    with Gated(store, cache_bytes=1 << 20) as srv:
        obs.reset()
        with jax.profiler.trace(str(tmp_path)):
            threads = [threading.Thread(target=srv.read_slice,
                                        args=("t", 10 * k, 10 * k + 5))
                       for k in range(2)]
            for th in threads:
                th.start()
            for _ in range(3000):
                if srv.stats()["coalesced"]:
                    break
                threading.Event().wait(0.01)
            release.set()
            for th in threads:
                th.join(30)
                assert not th.is_alive()
            t = obs.totals()
    obs.reset()
    assert t["flight.wait"][0] == 1
    assert t["server.decode_span"][0] == 1


def _scopes(text: str, program: str) -> set:
    return set(re.findall(rf"jit\({program}\)/([a-z_]+)/", text))


def test_device_stages_carry_stable_scope_names():
    n = 4096
    n_bytes = n * 8
    lanes, steps = P._fused_geometry(n_bytes)
    run_sse = P._fused_program("shift_save_even", (("D", 16),), "f64", n,
                               n_bytes, steps, lanes)
    text = run_sse.lower(jax.ShapeDtypeStruct((n,), jnp.int64)).as_text(
        debug_info=True)
    assert {"apply", "verify", "pack", "histogram",
            "lane_scan"} <= _scopes(text, "run_sse")

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    text = K.encode_scan.lower(i32(512, 8), i32(), i32(256), i32(256),
                               steps=512, lanes=8).as_text(debug_info=True)
    assert "encode_scan" in _scopes(text, "encode_scan")
    text = K.decode_scan.lower(i32(8), i32(8, 64), i32(8), i32(), i32(4096),
                               i32(256), i32(256), steps=512,
                               lanes=8).as_text(debug_info=True)
    assert "decode_scan" in _scopes(text, "decode_scan")

    X = P._prepare(_values(13, n)).X
    Xs = P._strided(X, P.DEFAULT_SAMPLE_ELEMS)
    extrema = (int(jnp.min(Xs)), int(jnp.max(Xs)))
    cand = S._plan_candidate("shift_save_even", {"D": 16}, P.SPECS["f64"],
                             extrema, Xs.shape[0], X.shape[0])
    text = S._grid_score.lower(Xs, extrema[0], (cand[2],),
                               spec=P.SPECS["f64"],
                               plan=(cand[1],)).as_text(debug_info=True)
    assert "grid_score" in _scopes(text, "_grid_score")

    enc = P.apply_transform(_values(13, n), "shift_save_even", {"D": 16})
    args = P._inverse_args(enc.method, enc.meta, P.SPECS["f64"], n, n)
    run_inverse = P._inverse_program(
        enc.method, "f64", n, tuple((a.shape, a.dtype.str) for a in args))
    text = run_inverse.lower(np.zeros(n, np.uint64), np.zeros(n, np.int32),
                             np.zeros(n, np.uint8), *args).as_text(
                                 debug_info=True)
    assert {"inverse", "denormalize"} <= _scopes(text, "run_inverse")
