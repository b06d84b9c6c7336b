"""The trace reduction: busy union, idle share, device time by program and
op, idle gaps labelled by the host's spans."""
from __future__ import annotations

import pytest

import tracereduce as tr

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def small_trace():
    """Four host spans, two programs, four ops (one clipped by the window's
    start, one in the second program only)."""
    return tr.from_excerpt({"planes": [
        {"name": HOST, "lines": [{"name": "python", "events": [
            ["bench.window", 1000, 10000, {}],
            ["bench.write_item", 1000, 6000, {}],
            ["fsync", 5000, 2000, {}],
            ["bench.write_item", 7000, 4000, {}]]}]},
        {"name": DEV, "lines": [
            {"name": tr.MODULES_LINE, "events": [
                ["jit_run_sse(17)", 500, 3500, {}],
                ["jit__grid_score(3)", 8000, 1000, {}]]},
            {"name": tr.OPS_LINE, "events": [
                ["fusion.1", 500, 1500, {}],
                ["while.2", 2000, 1500, {}],
                ["while.2", 3500, 500, {}],
                ["scoregrid", 8000, 1000, {"long_name": "custom-call"}]]}]},
    ]})


def test_program_names_lose_their_ids():
    assert tr.program_name("jit_run_sse(17)") == "jit_run_sse"
    assert tr.program_name("jit_decode_scan.3") == "jit_decode_scan"
    assert tr.program_name("jit__grid_score") == "jit__grid_score"


def test_union_and_gaps():
    s, e = [0, 5, 20, 29, 40], [10, 15, 30, 31, 50]
    assert tr.union_ns(s, e, 0, 100) == 15 + 11 + 10
    assert tr.union_ns(s, e, 8, 45) == 7 + 11 + 5
    assert tr.union_ns([], [], 0, 5) == 0
    assert tr.gaps(s, e, 0, 60) == [(15, 20), (31, 40), (50, 60)]
    assert tr.gaps([], [], 0, 5) == [(0, 5)]


def test_summary_of_a_small_trace():
    s = tr.summarize(small_trace(), want_stats=r"^jit__grid_score$")
    assert s.window_s == pytest.approx(10e-6)
    # busy: [1000, 4000] of the clipped sse program, [8000, 9000] of the grid
    assert s.busy_s == pytest.approx(4000e-9)
    assert s.idle_share == pytest.approx(0.6)
    assert s.seconds_in(r"^jit_run_sse$") == pytest.approx(3000e-9)
    assert s.seconds_in(r"^jit__grid_score$") == pytest.approx(1000e-9)
    assert s.op_s[("jit_run_sse", "fusion.1")] == pytest.approx(1000e-9)
    assert s.top_ops(1) == [["jit_run_sse/while.2", pytest.approx(2000e-9)]]
    assert s.kernel_ops == [("jit__grid_score", "scoregrid",
                             pytest.approx(1e-6), {"long_name": "custom-call"})]
    # idle: [4000, 8000] and [9000, 11000], labelled by the spans that hold
    # their middles (6000: the fsync inside the first write)
    assert dict(s.gaps) == {
        "bench.write_item > fsync": pytest.approx(4000e-9),
        "bench.write_item": pytest.approx(2000e-9)}


def test_a_real_profile_object_reads_the_same():
    """``ProfileData`` built from a text proto goes through the same code."""
    from jax.profiler import ProfileData

    proto = """
    planes { id: 1 name: "/host:CPU"
      lines { id: 1 name: "python" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
      event_metadata { key: 1 value { id: 1 name: "bench.window" } } }
    planes { id: 2 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Modules" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
        events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 } }
      lines { id: 2 name: "XLA Ops" timestamp_ns: 0
        events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
        events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 } }
      event_metadata { key: 1 value { id: 1 name: "jit_decode_scan(4)" } }
      event_metadata { key: 2 value { id: 2 name: "while.1" } } }
    """
    s = tr.summarize(ProfileData.from_text_proto(proto))
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(3e-6)
    assert s.program_s == {"jit_decode_scan": pytest.approx(3e-6)}
    assert s.top_ops() == [["jit_decode_scan/while.1", pytest.approx(3e-6)]]


def test_a_trace_without_the_window_span_is_an_error():
    t = small_trace()
    t.planes[0].lines[0].events = t.planes[0].lines[0].events[1:]
    with pytest.raises(ValueError):
        tr.summarize(t)


def recorded():
    """The first 50 ms of a traced ingest window on one TPU v5 lite chip
    (`tracereduce.excerpt`; files of 16 chunks of 65,536 f64), every line as
    recorded."""
    import gzip
    import json
    from pathlib import Path

    path = Path(__file__).parent / "data" / "ingest_v5e_50ms.json.gz"
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_trace_reduces_as_counted_by_hand():
    raw = recorded()
    s = tr.summarize(tr.from_excerpt(raw), want_stats=r"^jit__grid_score$")
    win = [e for p in raw["planes"] for l in p["lines"] for e in l["events"]
           if e[0] == tr.WINDOW_SPAN][0]
    lo, hi = win[1], win[1] + win[2]
    mods = sorted((max(e[1], lo), min(e[1] + e[2], hi))
                  for p in raw["planes"] if p["name"].startswith("/device:")
                  for l in p["lines"] if l["name"] == tr.MODULES_LINE
                  for e in l["events"] if e[1] < hi and e[1] + e[2] > lo)
    busy, end = 0.0, lo
    for a, b in mods:                  # the union, merged by hand
        busy += max(0.0, b - max(a, end))
        end = max(end, b)
    assert s.window_s == pytest.approx(0.05)
    assert s.busy_s == pytest.approx(busy * 1e-9)
    assert 0 < s.idle_share < 1
    assert "jit__grid_score" in s.program_s
    # the Pallas scoring kernel is found by name, with its grid's shape
    import spec

    kernel = [k for k in s.kernel_ops if "tpu_custom_call" in k[1]]
    assert kernel and "scoregrid_blocks" in kernel[0][1]
    share = spec.metric_reader("scoregrid_roofline")(
        {"trace": s, "device_kind": "TPU v5 lite"})
    assert 0 < share < 100


def test_a_cut_trace_ends_its_window_at_its_last_op(monkeypatch):
    """Past the profiler's op limit nothing is recorded: the window is the
    stretch the trace still covers, and the idle after it is not counted."""
    monkeypatch.setattr(tr, "OPS_KEPT", 4)
    s = tr.summarize(small_trace())
    assert s.cut
    assert s.window_s == pytest.approx(8000e-9)     # [1000, 9000]
    assert s.busy_s == pytest.approx(4000e-9)
    assert s.idle_share == pytest.approx(0.5)
    monkeypatch.setattr(tr, "OPS_KEPT", 5)
    s = tr.summarize(small_trace())
    assert not s.cut and s.window_s == pytest.approx(10e-6)


WHOLE_SEGMENT = ["selection_ms_per_mib", "encode_ms_per_mib",
                 "decode_ms_per_mib", "device_idle.ingest",
                 "device_idle.serve"]


@pytest.mark.parametrize("name", WHOLE_SEGMENT)
def test_a_cut_trace_gives_no_metric_of_the_whole_segment(name, monkeypatch):
    """The work a cut trace's clipped window holds is not known, so the
    metrics per MiB, and the idle share, read nothing from it."""
    import spec

    seg = {"work_mib": 1.0, "decoded_mib": 1.0}
    ctx = {"segment": seg, "window": {}}
    read = spec.metric_reader(name)
    assert read(dict(ctx, trace=tr.summarize(small_trace()))) is not None
    monkeypatch.setattr(tr, "OPS_KEPT", 4)
    assert read(dict(ctx, trace=tr.summarize(small_trace()))) is None


def test_per_mib_metrics_divide_by_the_segments_work():
    import spec

    s = tr.summarize(small_trace())
    ctx = {"trace": s, "segment": {"work_mib": 2.0, "decoded_mib": 4.0},
           "window": {"work_mib": 50.0}}
    assert spec.metric_reader("encode_ms_per_mib")(ctx) == pytest.approx(
        3000e-9 * 1e3 / 2.0)
    assert spec.metric_reader("selection_ms_per_mib")(ctx) == pytest.approx(
        1000e-9 * 1e3 / 2.0)
    assert spec.metric_reader("decode_ms_per_mib")(ctx) == pytest.approx(
        4000e-9 * 1e3 / 4.0)
