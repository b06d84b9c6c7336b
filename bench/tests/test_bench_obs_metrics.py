"""The per-layer metrics that read the program's host spans (``spans.py``):
nothing to read gives ``None``, a hand-made table gives its arithmetic."""
from __future__ import annotations

import sys

import pytest

import spec

# (count, total_s, self_s) of each span over a traced segment
TABLE = {
    "durable.fsync": (6, 0.012, 0.012),
    "durable.fsync_dir": (6, 0.006, 0.006),
    "durable.replace": (6, 0.003, 0.003),
    "select.probe": (1, 0.050, 0.020),
    "encode.prepare": (2, 0.010, 0.004),
    "encode.fused": (2, 0.040, 0.010),
    "encode.frame": (2, 0.002, 0.002),
    "container.serialize": (2, 0.008, 0.006),
    "rans.encode": (5, 0.010, 0.005),
    "encode.device_wait": (4, 0.030, 0.030),
    "select.device_wait": (2, 0.020, 0.020),
    "rans.device_wait": (9, 0.010, 0.010),
    "decode.device_wait": (4, 0.020, 0.020),
    "server.read_slice": (10, 0.250, 0.010),
    "server.decode_span": (4, 0.200, 0.020),
    "container.read_record": (4, 0.004, 0.004),
    "rans.decode": (4, 0.100, 0.060),
    "decode.inverse": (4, 0.050, 0.030),
    "dataset.part": (3, 0.300, 0.010),
}
INGEST = {"segment": {"work_mib": 2.0, "items": 3}}
SERVE = {"segment": {"requests": 10, "decoded_mib": 2.0}}

CASES = [
    # name, ctx, value of TABLE
    ("durable_ms_per_mib", INGEST, (12 + 6 + 3) / 2),
    ("fsyncs_per_mib", INGEST, 12 / 2),
    # self times: probe, prepare, fused, frame, serialize, rans.encode
    ("encode_host_ms_per_mib", INGEST, (20 + 4 + 10 + 2 + 6 + 5) / 2),
    # every *.device_wait: encode, select, rans, decode
    ("device_wait.ingest", INGEST, (30 + 20 + 10 + 20) / 2),
    # self times per decode_span: decode_span, read_record, rans, inverse
    ("miss_host_ms", SERVE, (20 + 4 + 60 + 30) / 4),
    ("device_wait.serve", SERVE, (30 + 20 + 10 + 20) / 4),
]


@pytest.mark.parametrize("name,ctx,want", CASES, ids=[c[0] for c in CASES])
def test_span_metric(monkeypatch, name, ctx, want):
    from repro import obs

    read = spec.metric_reader(name)
    monkeypatch.setattr(obs, "totals", lambda: {})
    assert read(ctx) is None
    monkeypatch.setattr(obs, "totals", lambda: dict(TABLE))
    assert read(ctx) == pytest.approx(want)
    assert read({"segment": None}) is None
    assert read({"segment": {}}) is None
    # a program without repro.obs (an older checkout)
    import repro

    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(ctx) is None
