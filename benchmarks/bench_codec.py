"""Codec-path benchmarks: transform throughput, GD/zlib/zstd sizing,
checkpoint save/restore, kernel micro-timings (interpret-mode noted).

Emits ``BENCH_codec.json`` (name -> {us, mbps, derived}) so the perf
trajectory is machine-readable across PRs; the CSV printed by
``benchmarks.run`` is unchanged.  Two underscore-prefixed sections ride
along for the CI regression gate (``benchmarks.check_regression``):

* ``_env``    — host attribution (cpu count, jax/numpy versions, backend)
  so timing deltas can be blamed on hardware vs. code;
* ``_counts`` — structural cost counters (phase-1 scoring dispatches /
  device_gets per auto-encode) compared EXACTLY by the gate: a timing may
  drift with the host, a dispatch count may not.
"""
from __future__ import annotations

import json
import os
import platform
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression.gd import gd_compress, gd_decompress
from repro.compression.greedy_gd import greedy_gd_compress
from repro.core import pipeline, transforms as T
from repro.core.float_bits import F64, normalize_bits
from repro.core.lossless import significand_from_bits
from repro.data import gas_turbine_emissions

# anchored to the repo root so the tracked baseline updates regardless of cwd;
# smoke runs write a separate file so the 100k baseline is never clobbered.
# BOTH files are committed: the smoke JSON is the baseline the CI bench-smoke
# gate compares against (benchmarks/check_regression.py) — refresh it
# deliberately when a PR changes codec-path performance.
BENCH_JSON = Path(__file__).resolve().parent.parent / "BENCH_codec.json"
BENCH_JSON_SMOKE = BENCH_JSON.with_suffix(".smoke.json")

_records: dict[str, dict] = {}
_counts: dict[str, int] = {}


def _env_info() -> dict:
    """Host/environment attribution embedded in the emitted JSON so the CI
    gate and docs/perf.md can tell hardware deltas from code deltas."""
    return {
        "cpu_count": os.cpu_count(),
        "jax": jax.__version__,
        "numpy": np.__version__,
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "platform": platform.platform(),
    }


def _timeit(fn, n=3):
    fn()  # warm
    t0 = time.time()
    for _ in range(n):
        fn()
    return (time.time() - t0) / n * 1e6  # us


def _record(rows, name, us, derived="", nbytes=None):
    mbps = nbytes / (us / 1e6) / 1e6 if nbytes else None
    _records[name] = {
        "us": round(us, 1),
        "mbps": round(mbps, 1) if mbps else None,
        "derived": derived,
    }
    rows.append((name, us, derived))


def bench_transforms(rows: list, n_elems: int = 100_000):
    tag = f"{n_elems // 1000}k"
    x = gas_turbine_emissions(n_elems)
    y, e, s = normalize_bits(x.view(np.uint64), F64)
    X = significand_from_bits(y, F64)
    for name, fn in [
        ("compact_bins", lambda: T.compact_bins_forward(X, 16)),
        ("multiply_shift", lambda: T.multiply_shift_forward(X, 2, max_iter=64)),
        ("shift_separate", lambda: T.shift_separate_forward(X, 2)),
        ("shift_save_even", lambda: T.shift_save_even_forward(X, 16)),
    ]:
        us = _timeit(fn)
        _record(rows, f"transform_{name}_{tag}", us,
                f"{x.nbytes / (us / 1e6) / 1e6:.0f} MB/s fwd", x.nbytes)

    # the headline: full auto-candidate selection at scale (two-phase
    # engine).  These ~50ms rows are gated by CI, so average over ~10 reps:
    # a 3-rep window on a shared host is pure noise-roulette (same treatment
    # as the container read rows below).
    enc = pipeline.encode(x)
    us = _timeit(lambda: pipeline.encode(x), n=10)
    _record(rows, f"pipeline_encode_auto_{tag}", us,
            f"picked={enc.method}", x.nbytes)
    us = _timeit(lambda: pipeline.decode(enc), n=10)
    _record(rows, f"pipeline_decode_{tag}", us, "bitwise-lossless", x.nbytes)

    # phase-1 A/B: stacked single-dispatch grid vs per-family jits, plus the
    # structural counters the CI gate compares exactly
    from repro.core import scoring

    for eng in ("stacked", "perfamily"):
        pipeline.select_method(x, engine=eng)  # warm
        scoring.PHASE1.reset()
        pipeline.select_method(x, engine=eng)
        _counts[f"phase1_dispatches_{eng}"] = scoring.PHASE1.dispatches
        _counts[f"phase1_device_gets_{eng}"] = scoring.PHASE1.device_gets
        # finalist exact re-scoring must cost 0 forwards on the stacked
        # engine (grid-stream reuse); probe = the sse metadata tie-break
        _counts[f"phase1_finalist_dispatches_{eng}"] = (
            scoring.PHASE1.finalist_dispatches
        )
        _counts[f"phase1_probe_dispatches_{eng}"] = (
            scoring.PHASE1.probe_dispatches
        )
        us = _timeit(lambda: pipeline.select_method(x, engine=eng), n=10)
        _record(rows, f"select_auto_{tag}_{eng}", us,
                f"dispatches={_counts[f'phase1_dispatches_{eng}']}", x.nbytes)

    # PR 7 fused device-resident encode: winner-apply + byte-pack + lane
    # rANS in ONE jit dispatch, framed from ONE device_get.  The PHASE2
    # triple is the structural contract the CI gate compares exactly:
    # (1, 1, 0) = one dispatch, one get, zero host fallbacks per chunk.
    enc_r = pipeline.encode(x, backend="rans")  # warm: jit + plan cache
    scoring.PHASE2.reset()
    enc_r = pipeline.encode(x, backend="rans")
    _counts["encode_dispatches"] = scoring.PHASE2.dispatches
    _counts["encode_device_gets"] = scoring.PHASE2.device_gets
    _counts["encode_fallbacks"] = scoring.PHASE2.fallbacks
    us = _timeit(lambda: pipeline.encode(x, backend="rans"), n=10)
    _record(rows, f"pipeline_encode_auto_rans_{tag}", us,
            f"picked={enc_r.method} fused-1-dispatch", x.nbytes)

    if n_elems <= 10_000:
        return
    x10 = x[:10_000]
    enc10 = pipeline.encode(x10)
    us = _timeit(lambda: pipeline.encode(x10))
    _record(rows, "pipeline_encode_auto_10k", us,
            f"picked={enc10.method}", x10.nbytes)
    us = _timeit(lambda: pipeline.decode(enc10))
    _record(rows, "pipeline_decode_10k", us, "bitwise-lossless", x10.nbytes)


def bench_container(rows: list, n_elems: int = 100_000):
    """Container serialization overhead (write = select+transform+serialize,
    read = parse+verify+inverse): the cost of the I/O layer itself is now a
    tracked quantity in BENCH_codec.json."""
    import tempfile

    from repro.container import ContainerReader, ContainerWriter

    tag = f"{n_elems // 1000}k"
    x = gas_turbine_emissions(n_elems)
    chunk = 32_768

    with tempfile.TemporaryDirectory() as d:
        path = f"{d}/bench.fpc"

        def write():
            with ContainerWriter(path, dtype=np.float64) as w:
                for i in range(0, x.size, chunk):
                    w.append(x[i : i + chunk])

        us = _timeit(write)
        with ContainerReader(path) as r:
            ratio = r.ratio()
        _record(rows, f"container_write_{tag}", us,
                f"ratio={ratio:.3f} chunk={chunk // 1024}k", x.nbytes)

        # same stream through the rANS backend: each chunk's winner is
        # applied, packed, and entropy-coded on device (PR 7 fused path),
        # so the writer never re-compresses on the host
        path_r = f"{d}/bench_rans.fpc"

        def write_rans():
            with ContainerWriter(path_r, dtype=np.float64,
                                 backend="rans") as w:
                for i in range(0, x.size, chunk):
                    w.append(x[i : i + chunk])

        us = _timeit(write_rans)
        with ContainerReader(path_r) as r:
            ratio_r = r.ratio()
            back_r = r.read_all()
        assert np.array_equal(back_r.view(np.uint64), x.view(np.uint64))
        _record(rows, f"container_write_rans_{tag}", us,
                f"ratio={ratio_r:.3f} fused chunk={chunk // 1024}k", x.nbytes)

        def read():
            with ContainerReader(path) as r:
                return r.read_all()

        back = read()
        assert np.array_equal(back.view(np.uint64), x.view(np.uint64))
        # ms-scale rows get many reps: 3 reps = a ~10 ms window, pure
        # noise-roulette on a shared host; 25 reps averages over ~100 ms
        us = _timeit(read, n=25)
        _record(rows, f"container_read_{tag}", us, "bitwise-lossless",
                x.nbytes)

        # parallel decode over a finer-chunked stream (more records ->
        # more decompress/inverse overlap for the decode pool; chunk size
        # is clamped to [2048, 16384] elements — n/4 in between — so the
        # stream is always multi-chunk without making records so small the
        # pool's per-span sync cost dominates; docs/perf.md has the
        # measured crossover)
        from repro.container import default_decode_workers

        chunk_par = max(2048, min(16384, n_elems // 4))
        path_par = f"{d}/bench_par.fpc"
        with ContainerWriter(path_par, dtype=np.float64) as w:
            for i in range(0, x.size, chunk_par):
                w.append(x[i : i + chunk_par])

        def read_parallel():
            with ContainerReader(path_par) as r:
                return r.read_all(parallel=True)

        with ContainerReader(path_par) as r:
            nchunks_par = r.nchunks
            serial_par_stream = r.read_all()
        back = read_parallel()
        assert np.array_equal(back.view(np.uint64), x.view(np.uint64))
        assert np.array_equal(back.view(np.uint64),
                              serial_par_stream.view(np.uint64))
        us = _timeit(read_parallel, n=25)
        _record(
            rows, f"container_read_parallel_{tag}", us,
            f"bitwise==serial chunks={nchunks_par} "
            f"workers={default_decode_workers()}",
            x.nbytes,
        )

        # reliability rows (docs/reliability.md): the salvage engine's
        # clean-container walk (forward record validation, CRC32 over every
        # record — the verify cost `scrub` pays per file), and the fsync
        # premium of the durable write recipe that container_write_* above
        # now pays by default.  The premium is a fixed ~2 ms per stream
        # (flush + fsync + dir fsync), so its *relative* cost grows as the
        # write itself speeds up — ~1.4% against the PR 6 102 ms write,
        # ~6% against the PR 7 32 ms write; the absolute delta is the
        # quantity to watch
        from repro.reliability import repair

        rep = repair.salvage(path)
        assert rep.ok
        us = _timeit(lambda: repair.salvage(path), n=10)
        _record(rows, f"container_salvage_{tag}", us,
                f"chunks={len(rep.entries)} clean-walk", x.nbytes)

        path_nd = f"{d}/bench_nd.fpc"

        def write_nd():
            with ContainerWriter(path_nd, dtype=np.float64,
                                 durable=False) as w:
                for i in range(0, x.size, chunk):
                    w.append(x[i : i + chunk])

        # interleave the two variants and compare MEDIANS: the write itself
        # drifts ~10% across separate timing windows (selection/jit/host
        # noise), which would swamp the ~2 ms fsync premium being measured
        write_nd()
        write()  # warm both
        d_ts, nd_ts = [], []
        for _ in range(7):
            t0 = time.time()
            write()
            d_ts.append(time.time() - t0)
            t0 = time.time()
            write_nd()
            nd_ts.append(time.time() - t0)
        us_d = sorted(d_ts)[3] * 1e6
        us_nd = sorted(nd_ts)[3] * 1e6
        over = (us_d - us_nd) / max(us_nd, 1.0) * 100
        _record(rows, f"durable_write_overhead_{tag}", us_d,
                f"{over:+.1f}% vs durable=False ({us_nd / 1e3:.1f}ms)",
                x.nbytes)


def bench_streaming(rows: list, n_elems: int = 100_000):
    """Bounded-memory streaming ingest (core/streaming + data/dataset).

    Two rows + deterministic counters:

    * ``streaming_write_{tag}`` — ShardStore.write_stream throughput over a
      generator of ragged pieces (re-chunk + window policy + write-behind).
    * ``dataset_stream_4x_budget`` — a FRESH subprocess (ru_maxrss is
      lifetime-monotonic, so the parent process can't measure its own
      delta) streams a dataset 4× larger than the RAM budget and reports
      peak-RSS growth; the budget is asserted IN-BENCH — a regression that
      materializes the stream fails the bench, not just drifts a number.
    * ``stream_*`` counts — WindowPlanner decisions on a seeded drifting
      stream, compared exactly by the CI gate (the drift-refresh policy is
      deterministic; a changed count means a changed policy).
    """
    import subprocess
    import sys
    import tempfile

    from repro.core import streaming as S
    from repro.core.float_bits import F64
    from repro.data.shard_store import ShardStore

    tag = f"{n_elems // 1000}k"
    x = gas_turbine_emissions(n_elems)
    chunk = max(2048, min(32_768, n_elems // 4))
    piece = max(1, (n_elems // 7) | 1)  # ragged on purpose

    with tempfile.TemporaryDirectory() as d:
        store = ShardStore(d)

        def write():
            pieces = (x[i * piece : (i + 1) * piece]
                      for i in range(-(-x.size // piece)))
            store.write_stream("bench", pieces, np.float64, chunk=chunk)

        us = _timeit(write)
        _record(rows, f"streaming_write_{tag}", us,
                f"ragged-pieces chunk={chunk // 1024}k write-behind",
                x.nbytes)

    # window-policy decision counters: seeded drifting stream, 16 chunks of
    # 8192 elems with a distribution jump halfway — counts are a pure
    # function of the data and the policy, so the gate compares them exactly
    rng = np.random.default_rng(1234)
    base = 1.0 + rng.integers(0, 1 << 12, 8192 * 16) / float(1 << 14)
    base[8192 * 8 :] = base[8192 * 8 :] * 4096.0 + 3.0
    planner = S.WindowPlanner(spec=F64, probe_elems=1024,
                              probe_threshold=4096,
                              window_bytes=8192 * 8 * 2)  # every 2 chunks
    for i in range(16):
        planner.encode(base[i * 8192 : (i + 1) * 8192])
    for key, val in planner.stats.items():
        _counts[f"stream_{key}"] = val

    # 4x-budget bounded-memory proof: subprocess streams `logical` bytes of
    # f64 through a DatasetWriter under a `budget = logical / 4` ceiling
    logical = (16 << 20) if n_elems <= 10_000 else (64 << 20)
    child = (
        "import json, resource, sys, tempfile\n"
        "import numpy as np\n"
        "from repro.data.dataset import DatasetWriter\n"
        "logical = int(sys.argv[1]); budget = logical // 4\n"
        "piece = 1 << 16\n"
        "def pieces(n):\n"
        "    for i in range(n):\n"
        "        yield 1.0 + np.arange(piece, dtype=np.float64) / (i + 2.0)\n"
        "with tempfile.TemporaryDirectory() as d:\n"
        "    DatasetWriter(d + '/warm', dtype=np.float64,\n"
        "                  chunk=1 << 14).write(pieces(2))\n"
        "    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024\n"
        "    import time; t0 = time.time()\n"
        "    DatasetWriter(d + '/ds', dtype=np.float64, chunk=1 << 14,\n"
        "                  part_elems=1 << 18, method='identity'\n"
        "                  ).write(pieces(logical // (piece * 8)))\n"
        "    us = (time.time() - t0) * 1e6\n"
        "    rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024\n"
        "print(json.dumps({'us': us, 'rss_delta': rss1 - rss0,\n"
        "                  'budget': budget}))\n"
    )
    # the child measures host memory only: it stays off the accelerator,
    # which belongs to this process
    r = subprocess.run([sys.executable, "-c", child, str(logical)],
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, f"4x-budget child failed:\n{r.stderr}"
    stats = json.loads(r.stdout.strip().splitlines()[-1])
    assert stats["rss_delta"] < stats["budget"], (
        f"streaming a {logical >> 20} MiB dataset grew RSS by "
        f"{stats['rss_delta'] >> 20} MiB — over the "
        f"{stats['budget'] >> 20} MiB budget; ingestion is not bounded"
    )
    _record(rows, "dataset_stream_4x_budget", stats["us"],
            f"rss+{stats['rss_delta'] >> 20}MiB<"
            f"{stats['budget'] >> 20}MiB logical={logical >> 20}MiB",
            logical)


def bench_shard_prefetch(rows: list, n_elems: int = 100_000):
    """Prefetched shard iteration vs lazy iteration: the data-path consumer
    of the prefetching reader (`ShardStore.iter_chunks`)."""
    import tempfile

    from repro.data.shard_store import ShardStore

    x = gas_turbine_emissions(n_elems)
    with tempfile.TemporaryDirectory() as d:
        store = ShardStore(d)
        store.write("bench", x, chunk=max(2048, min(16384, n_elems // 4)))

        def drain(prefetch):
            return np.concatenate(
                list(store.iter_chunks("bench", prefetch=prefetch))
            )

        back = drain(4)
        assert np.array_equal(back.view(np.uint64), x.view(np.uint64))
        us_lazy = _timeit(lambda: drain(0), n=25)
        us = _timeit(lambda: drain(4), n=25)
        _record(rows, "shard_iter_prefetch", us,
                f"prefetch=4 lazy={us_lazy / 1e3:.1f}ms", x.nbytes)


def bench_rans(rows: list, n_elems: int = 100_000):
    """The rANS entropy-coder backend on the raw float byte stream: encode
    (host lane loop + statistics pass) and decode (lockstep lane loop)
    throughput, with zlib as the ratio yardstick."""
    import zlib

    from repro.kernels.rans import ops as rans_ops

    tag = f"{n_elems // 1000}k"
    data = gas_turbine_emissions(n_elems).tobytes()
    comp = rans_ops.compress(data)
    zl = len(zlib.compress(data, 6))
    us = _timeit(lambda: rans_ops.compress(data))
    _record(rows, f"rans_encode_{tag}", us,
            f"ratio={len(comp) / len(data):.3f} zlib={zl / len(data):.3f}",
            len(data))
    assert rans_ops.decompress(comp) == data
    us = _timeit(lambda: rans_ops.decompress(comp))
    _record(rows, f"rans_decode_{tag}", us, "bitwise", len(data))


def bench_gd(rows: list):
    x = gas_turbine_emissions(10_000)
    us = _timeit(lambda: gd_compress(x))
    _record(rows, "gd_compress_10k", us,
            f"bits={gd_compress(x).size_bits()}", x.nbytes)
    c = greedy_gd_compress(x)
    us = _timeit(lambda: greedy_gd_compress(x), n=1)
    _record(rows, "greedy_gd_select+compress_10k", us,
            f"bits={c.size_bits()}", x.nbytes)
    us = _timeit(lambda: gd_decompress(c))
    _record(rows, "gd_decompress_10k", us, "", x.nbytes)


def bench_kernels(rows: list):
    """Pallas kernels in interpret mode (CPU container; TPU is the target —
    these timings validate plumbing, not TPU perf)."""
    from repro.kernels.bitplane_transpose.ops import to_bitplanes
    from repro.kernels.mshift.ops import mshift
    from repro.kernels.sharedbits.ops import shared_mask_u32

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.integers(0, 2**32, 256 * 32, dtype=np.uint32))
    us = _timeit(lambda: jax.block_until_ready(to_bitplanes(w)))
    _record(rows, "pallas_bitplane_transpose_8k(interp)", us, "vs ref in tests")

    x = jnp.asarray(rng.integers(1 << 23, (1 << 23) + (1 << 12), 128 * 128),
                    jnp.int32)
    us = _timeit(lambda: jax.block_until_ready(mshift(x, 4, 16)))
    _record(rows, "pallas_mshift_16k(interp)", us, "fused iterations")

    us = _timeit(lambda: jax.block_until_ready(shared_mask_u32(w)))
    _record(rows, "pallas_sharedbits_8k(interp)", us, "")


def bench_checkpoint(rows: list):
    import tempfile

    from repro.checkpoint import save_tree, restore_tree
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("minicpm_2b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        t0 = time.time()
        stats = save_tree(params, f"{d}/ck")
        us = (time.time() - t0) * 1e6
        _record(rows, "checkpoint_save_reduced_model", us,
                f"ratio={stats['ratio']:.3f}")
        t0 = time.time()
        restore_tree(f"{d}/ck")
        _record(rows, "checkpoint_restore_reduced_model",
                (time.time() - t0) * 1e6, "bitwise")


def bench_grad_compress(rows: list):
    from repro.distributed.compress import bucket_report

    rng = np.random.default_rng(1)
    # gradient-like bucket: heavy-tailed, shared exponent structure
    g = (rng.standard_normal(1 << 18) * 1e-3).astype(np.float32)
    t0 = time.time()
    rep = bucket_report(g)
    _record(rows, "grad_bucket_compress_256k", (time.time() - t0) * 1e6,
            f"ratio={rep['ratio']:.3f} method={rep['method']}", g.nbytes)
    # bucket encode through the fused rANS path (one dispatch per bucket);
    # cold timing includes the one-off jit compile for the f32 geometry
    bucket_report(g, backend="rans")  # warm
    t0 = time.time()
    rep_r = bucket_report(g, backend="rans")
    _record(rows, "grad_bucket_compress_256k_rans", (time.time() - t0) * 1e6,
            f"ratio={rep_r['ratio']:.3f} method={rep_r['method']}", g.nbytes)


def _dump_json(smoke: bool):
    path = BENCH_JSON_SMOKE if smoke else BENCH_JSON
    payload = dict(_records)
    payload["_env"] = _env_info()
    payload["_counts"] = dict(_counts)
    path.write_text(json.dumps(payload, indent=2, sort_keys=True))


def run(rows: list, smoke: bool = False):
    """smoke=True: 10k-element CI-sized pass over the codec path only
    (skips model checkpoint / gradient-bucket benches); results go to
    BENCH_codec.smoke.json so the tracked 100k baseline stays intact."""
    from . import bench_serve, bench_step

    if smoke:
        bench_transforms(rows, n_elems=10_000)
        bench_container(rows, n_elems=10_000)
        bench_streaming(rows, n_elems=10_000)
        bench_shard_prefetch(rows, n_elems=10_000)
        bench_rans(rows, n_elems=10_000)
        bench_gd(rows)
        bench_kernels(rows)
        bench_step.run(rows, smoke=True)
        bench_serve.run(rows, smoke=True)
    else:
        bench_transforms(rows)
        bench_container(rows)
        bench_streaming(rows)
        bench_shard_prefetch(rows)
        bench_rans(rows)
        bench_gd(rows)
        bench_kernels(rows)
        bench_checkpoint(rows)
        bench_grad_compress(rows)
        bench_step.run(rows)
        bench_serve.run(rows)
    _dump_json(smoke)
