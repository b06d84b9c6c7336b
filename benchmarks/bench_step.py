"""Compressed-training-step benchmarks (PR 8).

Two questions, answered with numbers the CI gate can hold:

1. **What does plan reuse buy per step?**  A/B on the same gradient-like
   stream: full phase-1 re-selection every step (fresh noise draw each step,
   so the content-digest cache misses — the pre-PR-8 behaviour of a training
   loop whose bucket bytes change every step) vs
   :class:`repro.distributed.steps.CompressedStepState` reuse (fingerprint
   hit, pure phase-2 encode).  The acceptance bar is >= 5x.

2. **Does the steady state really do zero selection work?**  Structural
   counters ride into ``_counts`` and are compared EXACTLY by
   ``benchmarks.check_regression``: steady-stream re-selections pinned to 0,
   plan-cache hits pinned to the step count, phase-1 dispatches pinned to 0,
   fused-encode dispatches per step pinned to the chunk count.

The multi-process harness (``bench_step_harness``) runs an n-workers x
bucket-size grid under ``multiprocessing`` *spawn* (jax is not fork-safe):
each worker owns a CompressedStepState and drives steady steps; the parent
aggregates per-step time and the same exact counters per grid point.
"""
from __future__ import annotations

import time

import numpy as np

from .bench_codec import _counts, _record

# one pool of distinct same-distribution draws, cycled so every step sees
# NEW bytes (digest caches cannot help) from the SAME stream (fingerprints
# match — which is the property plan reuse banks on)
_N_DRAWS = 4


def _draws(n_elems: int, seed: int, scale: float = 1e-3) -> list:
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal(n_elems) * scale).astype(np.float32)
            for _ in range(_N_DRAWS)]


def bench_step_ab(rows: list, smoke: bool = False):
    """Single-process steady-stream A/B: re-selection per step vs plan reuse."""
    from repro.core import scoring
    from repro.distributed.compress import compress_bucket
    from repro.distributed.steps import CompressedStepState

    n = 16_384 if smoke else 1 << 18
    tag = f"{n // 1024}k"
    draws = _draws(n, seed=7)
    nbytes = draws[0].nbytes

    # -- A: phase-1 selection every step (fresh bytes => digest miss) — the
    # pre-PR-8 cost of compressing a gradient bucket inside a training loop
    compress_bucket(draws[0], method="auto")  # warm the selection jits
    reps_a = 2 if smoke else 3
    t0 = time.time()
    for i in range(reps_a):
        compress_bucket(draws[(i + 1) % _N_DRAWS], method="auto")
    us_sel = (time.time() - t0) / reps_a * 1e6
    _record(rows, f"grad_bucket_step_reselect_{tag}", us_sel,
            "phase-1 per step", nbytes)

    # -- B: CompressedStepState reuse (fingerprint hit, pure phase 2) -------
    st = CompressedStepState(backend="zlib")
    st.begin_step()
    compress_bucket(draws[0], plan=st.plan_for("g0", draws[0]))  # cold
    scoring.PHASE1.reset()
    st.plans.reset_stats()
    reps_b = 6 if smoke else 10
    t0 = time.time()
    for i in range(reps_b):
        st.begin_step()
        d = draws[(i + 1) % _N_DRAWS]
        compress_bucket(d, plan=st.plan_for("g0", d))
    us_reuse = (time.time() - t0) / reps_b * 1e6
    c = st.counters()
    _record(rows, f"grad_bucket_step_reuse_{tag}", us_reuse,
            f"{us_sel / max(us_reuse, 1e-9):.1f}x vs reselect", nbytes)
    # exact structural contract of the steady state: the stream did not
    # drift, so reuse does NO selection work at all
    _counts["step_reselects_steady"] = (
        c["reselections"] - c["cold_selections"]
    )
    _counts["step_plan_hits_steady"] = st.plans.hits
    _counts["step_phase1_dispatches_steady"] = scoring.PHASE1.dispatches

    # -- end-to-end wire blob per step (plan reuse + chunked container +
    # zlib): the honest DCN-path number — the backend compressor floor
    # dominates at this size, which is exactly what the row should show
    st.begin_step()
    st.to_wire("g0", draws[0])  # warm the writer path
    t0 = time.time()
    for i in range(reps_b):
        st.begin_step()
        st.to_wire("g0", draws[(i + 1) % _N_DRAWS])
    us_wire = (time.time() - t0) / reps_b * 1e6
    _record(rows, f"grad_bucket_step_wire_{tag}", us_wire,
            "plan reuse + container + zlib", nbytes)

    # -- same reuse loop through the fused rANS device encode --------------
    # per steady step the ONLY device work is the fused phase-2 encode:
    # one dispatch per wire chunk, zero selection dispatches
    st_r = CompressedStepState(backend="rans")
    st_r.begin_step()
    st_r.to_wire("g0", draws[0])  # cold selection + fused-encode jit warm
    scoring.PHASE1.reset()
    scoring.PHASE2.reset()
    st_r.begin_step()
    st_r.to_wire("g0", draws[1])
    _counts["step_phase2_dispatches_per_step"] = scoring.PHASE2.dispatches
    _counts["step_phase1_dispatches_steady_rans"] = scoring.PHASE1.dispatches
    t0 = time.time()
    for i in range(reps_b):
        st_r.begin_step()
        st_r.to_wire("g0", draws[(i + 1) % _N_DRAWS])
    us_r = (time.time() - t0) / reps_b * 1e6
    _record(rows, f"grad_bucket_step_reuse_rans_{tag}", us_r,
            f"fused {_counts['step_phase2_dispatches_per_step']} "
            "dispatch/step", nbytes)


def _harness_worker(args):
    """Top-level (spawn-picklable) worker: one CompressedStepState driving
    steady steps over its own gradient stream; returns per-step time and the
    exact counters."""
    seed, n_elems, steps = args
    from repro.core import scoring
    from repro.distributed.steps import CompressedStepState

    draws = _draws(n_elems, seed=seed)
    st = CompressedStepState(backend="zlib")
    st.begin_step()
    st.to_wire("g", draws[0])  # cold selection + jit warm, outside timing
    scoring.PHASE1.reset()
    st.plans.reset_stats()
    t0 = time.time()
    for i in range(steps):
        st.begin_step()
        st.to_wire("g", draws[(i + 1) % _N_DRAWS])
    us = (time.time() - t0) / steps * 1e6
    c = st.counters()
    return {
        "us": us,
        "hits": st.plans.hits,
        "reselects_steady": c["reselections"] - c["cold_selections"],
        "phase1_dispatches": scoring.PHASE1.dispatches,
    }


def _pin_cpu():
    """Pool initializer: keep a worker off the accelerator, which belongs
    to the parent process (one process per chip)."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def bench_step_harness(rows: list, smoke: bool = False):
    """n-workers x bucket-size grid, each worker a separate *spawned*
    process (jax + fork is unsafe) pinned to the CPU backend.  Gates
    end-to-end steady step time and plan-cache hit rate per grid point."""
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    # (workers, bucket elems, steady steps); the cold step (selection + jit
    # compile) is warmed inside each worker before its timing window
    grid = ([(2, 16_384, 4)] if smoke
            else [(1, 65_536, 6), (2, 65_536, 6), (4, 1 << 18, 6)])
    for workers, n_elems, steps in grid:
        argv = [(100 + w, n_elems, steps) for w in range(workers)]
        t0 = time.time()
        with ctx.Pool(workers, initializer=_pin_cpu) as pool:
            res = pool.map(_harness_worker, argv)
        wall_s = time.time() - t0
        tag = f"w{workers}_{n_elems // 1024}k"
        us = float(np.mean([r["us"] for r in res]))
        hits = sum(r["hits"] for r in res)
        _record(rows, f"step_harness_{tag}", us,
                f"cpu workers hits={hits} steps={steps}/worker "
                f"wall={wall_s:.1f}s",
                n_elems * 4)
        _counts[f"step_harness_hits_{tag}"] = hits
        _counts[f"step_harness_reselects_steady_{tag}"] = sum(
            r["reselects_steady"] for r in res
        )
        _counts[f"step_harness_phase1_dispatches_{tag}"] = sum(
            r["phase1_dispatches"] for r in res
        )


def run(rows: list, smoke: bool = False):
    bench_step_ab(rows, smoke)
    bench_step_harness(rows, smoke)
