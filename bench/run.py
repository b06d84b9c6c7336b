#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are read by name
from ``BENCHMARK.json`` and the files under ``bench/`` (see ``spec.py``).
One run is one process that holds the chip:

1. JAX's compilation cache goes to ``<checkout>/.jax_cache`` (or where
   ``JAX_COMPILATION_CACHE_DIR`` says);
2. without a TPU, or with fewer chips than the cell asks for, the run
   exits non-zero and prints no result;
3. set-up makes the inputs from ``--seed`` and warms every shape the
   window uses; ``setup_s`` runs from the start of this script to here;
4. the window runs for ``--seconds``; with ``--trace 1`` a segment of a
   fixed amount of work (the traffic's ``trace_work``) follows it under
   the profiler, and the per-layer metrics are read from that trace and
   from the counters of window and segment together;
5. after the window, what the timed path produced is compared with the
   reference (``reference.py``) and each number compared is printed beside
   its limit, on standard error and as the result's last key;
6. the last line of standard output is the result as one JSON object.

``--control`` puts the plain reference, one precision down, in the
program's place: that run has to come out not correct.  It and
``--dump-trace`` are for building the benchmark; its own runs use neither.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import spec  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


# programs compiled (or loaded from the persistent cache) while the window
# runs; there should be none
_COMPILES = {"listening": False, "on": False, "n": 0, "names": []}


def _on_event(event, _secs, **kw):
    if event == COMPILE_EVENT and _COMPILES["on"]:
        _COMPILES["n"] += 1
        _COMPILES["names"].append(str(kw.get("fun_name", "?")))


def _program_counters() -> dict:
    from repro.core import scoring

    return {"phase1_dispatches": scoring.PHASE1.dispatches,
            "phase2_dispatches": scoring.PHASE2.dispatches,
            "phase2_fallbacks": scoring.PHASE2.fallbacks}


def _reset_program_counters() -> None:
    from repro.core import scoring

    scoring.PHASE1.reset()
    scoring.PHASE2.reset()


def prepare_jax(chips: int) -> None:
    """Compilation cache in the checkout, and a TPU with ``chips`` chips or
    :class:`NoChip`."""
    import jax

    from repro.launch.compile_cache import use_checkout_cache

    use_checkout_cache()
    # every program, however quick to compile, goes to the cache, so only a
    # checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")


def _reduce_trace(trace_dir: Path, cell, dump: Path | None):
    """The window's trace, reduced; the ops of the programs any of the
    cell's metrics names in ``STATS_PROGRAMS`` keep their stats."""
    import tracereduce as tr
    from jax.profiler import ProfileData

    t0 = time.perf_counter()
    pb = sorted(trace_dir.rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(pb[-1]))
    t1 = time.perf_counter()
    want = [getattr(spec.metric_module(m["name"]), "STATS_PROGRAMS", None)
            for m in cell.per_layer]
    want = "|".join(f"(?:{w})" for w in want if w) or r"$^"
    summary = tr.summarize(pd, want_stats=want)
    print(f"trace: {pb[-1].stat().st_size / 2**20:.1f} MiB, "
          f"{summary.n_ops} op events, read {t1 - t0:.1f} s, reduced "
          f"{time.perf_counter() - t1:.1f} s", flush=True)
    if summary.cut:
        print(f"trace: the profiler's op-event limit was reached; the traced "
              f"window ends with its last op, at {summary.window_s:.3f} s, "
              f"and the metrics of the whole segment read nothing",
              flush=True)
    if dump is not None:
        import gzip

        dump.parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(dump, "wt") as f:
            json.dump(tr.excerpt(pd), f)
        with open(dump.with_suffix(".summary.json"), "w") as f:
            json.dump({"programs": summary.program_s,
                       "top_ops": summary.top_ops(40),
                       "n_op_names": len(summary.op_s),
                       "kernel_ops": summary.kernel_ops[:20],
                       "gaps": summary.gaps, "busy_s": summary.busy_s,
                       "window_s": summary.window_s,
                       "planes": [[p.name, [[l.name, sum(1 for _ in l.events)]
                                            for l in p.lines][:12]]
                                  for p in pd.planes]}, f, default=str)
    return summary


def run_cell(cell, seed: int, seconds: float, trace: bool, work: Path,
             control: bool = False, dump_trace: Path | None = None) -> dict:
    """One run of ``cell`` on the devices JAX has; returns the result
    object (without printing it)."""
    import jax
    from jax import monitoring

    devs = jax.devices()
    compiles = _COMPILES
    compiles.update(on=False, n=0, names=[])
    if not compiles["listening"]:
        monitoring.register_event_duration_secs_listener(_on_event)
        compiles["listening"] = True

    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    drv = spec.driver(cell)(cell, seed, work, control)
    drv.setup()
    setup_s = time.perf_counter() - T_START

    _reset_program_counters()
    compiles["on"] = True
    win = drv.window(seconds, jax.profiler.TraceAnnotation)
    seg = None
    if trace:
        # the traced segment is a fixed amount of work, so the profiler's
        # buffer holds all of it however fast the program runs
        trace_dir = work / "trace"
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        seg = drv.window(0.0, jax.profiler.TraceAnnotation,
                         limit=int(cell.traffic["trace_work"]))
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"trace: stop {time.perf_counter() - t_stop:.1f} s", flush=True)
    compiles["on"] = False
    counters = _program_counters()
    mem = [d.memory_stats() or {} for d in devs[:max(cell.chips, 1)]]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    print(f"window: {win['window_s']:.3f} s, programs compiled or loaded "
          f"inside it: {compiles['n']} {compiles['names'][:20]}, counters: {counters}", flush=True)

    summary = None
    if trace:
        summary = _reduce_trace(trace_dir, cell, dump_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)

    drv.free()
    from reference import Checks

    checks = Checks()
    drv.check(checks)

    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    metrics = {}
    result = {"correct": checks.correct, "attempted": drv.attempted,
              "failed": drv.failed}
    if trace:
        ctx = {"cell": cell, "window": win, "segment": seg,
               "counters": counters, "trace": summary,
               "device_kind": d0.device_kind}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": [list(g) for g in summary.gaps]}
    else:
        values = dict(win["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["window"] = {k: v for k, v in win.items()
                        if isinstance(v, (int, float))}
    if seg is not None:
        result["segment"] = {k: v for k, v in seg.items()
                             if isinstance(v, (int, float))}
    result["compiles_in_window"] = compiles["n"]
    result["checks"] = checks.items
    shutil.rmtree(work, ignore_errors=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--dump-trace", type=Path, default=None)
    args = ap.parse_args(argv)
    try:
        cell = spec.cell(args.workload)
        prepare_jax(cell.chips)
        work = BENCH / "out" / args.workload
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          work, control=args.control,
                          dump_trace=args.dump_trace)
    except (NoChip, spec.SpecError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from reference import Checks

    checks = Checks()
    checks.items = result["checks"]
    for line in checks.lines():
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
