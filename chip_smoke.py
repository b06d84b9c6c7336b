#!/usr/bin/env python3
"""Run the codec, its storage paths and the trainer once on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: data-parallel training and
                                     # elastic checkpoint resume only

One chip, in one process, phase by phase:

  a. device check: JAX runs on a TPU and the Pallas kernels are compiled,
     not interpreted;
  b. the paper's domain: 64 MiB of seeded f64 sensor series and f32/bf16
     weight-like streams go through ``DatasetWriter`` with the rANS and the
     zlib backend and read back bitwise; every rANS write takes the fused
     device encode, and the phase-1 program holds the Pallas kernel;
  d. ``TensorServer`` serves the datasets of (b) bitwise;
  c. ``whisper-base`` at its published widths trains through
     ``repro.launch.train``, checkpoints through ``CheckpointManager`` and
     resumes, and the resumed losses match an uninterrupted run.

Four chips: ``whisper-base`` with ``--data-par 4`` against one chip, and a
one-chip checkpoint resumed on a 2x2 (data x model) mesh.

Each phase prints its wall time and counters on a line of its own.  The
last line is the JSON result, printed only when every phase passed; any
failure exits non-zero.  Without a TPU the script exits non-zero at once.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import re
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
F64_BYTES = 64 << 20          # the sensor archive, per backend
WEIGHT_ELEMS = {"f32": 1 << 22, "bf16": 1 << 22}
TRAIN_ARGS = ["--arch", "whisper-base", "--batch", "8", "--seq", "448",
              "--lr", "1e-3", "--log-every", "1"]
RESUME_TOL = 2e-4             # tests/test_fault_tolerance.py, elastic resume


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def report(phase: str, t0: float, **counters) -> None:
    items = " ".join(f"{k}={v}" for k, v in counters.items())
    print(f"[{phase}] {time.perf_counter() - t0:.1f}s {items}", flush=True)


def same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    a, b = np.ascontiguousarray(a).reshape(-1), np.ascontiguousarray(b).reshape(-1)
    return a.dtype == b.dtype and a.shape == b.shape and bool(
        np.array_equal(a.view(np.uint8), b.view(np.uint8)))


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------

def device_check(chips: int):
    import jax

    import repro.kernels

    t0 = time.perf_counter()
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", f"JAX found no TPU (platform {d.platform!r})")
    check(repro.kernels.INTERPRET_DEFAULT is False,
          "Pallas kernels would run in interpret mode")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    report("device", t0, platform=d.platform, kind=repr(d.device_kind),
           count=len(devs))
    return d, len(devs)


# ---------------------------------------------------------------------------
# (b) the codec through DatasetWriter
# ---------------------------------------------------------------------------

def streams(seed: int = 0) -> dict:
    """The seeded inputs: a sensor series in the paper's f64 domain and two
    weight-like streams (normal(0, 0.02), a common weight init)."""
    import ml_dtypes

    from repro.data import gas_turbine_emissions

    rng = np.random.default_rng(seed)
    w = rng.standard_normal(max(WEIGHT_ELEMS.values())) * 0.02
    return {
        "f64": gas_turbine_emissions(F64_BYTES // 8, seed=seed + 1),
        "f32": w[:WEIGHT_ELEMS["f32"]].astype(np.float32),
        "bf16": w[:WEIGHT_ELEMS["bf16"]].astype(ml_dtypes.bfloat16),
    }


def chunk_methods(root: Path) -> list[str]:
    from repro.container import ContainerReader

    man = json.loads((root / "manifest.json").read_bytes())
    out = []
    for part in man["parts"]:
        with ContainerReader(root / part["name"]) as r:
            out += [r.chunk_info(i)["method"] for i in range(r.nchunks)]
    return out


def phase1_holds_kernel(x: np.ndarray) -> bool:
    """Lower the phase-1 grid exactly as selection dispatches it on ``x``
    and look for the compiled Pallas kernel in the chip's program."""
    import jax.numpy as jnp

    from repro.core import pipeline as P
    from repro.core import scoring as S
    from repro.core.transforms import TransformError

    prep = P._prepare(x)
    Xs = P._strided(prep.X, P.DEFAULT_SAMPLE_ELEMS)
    extrema = (int(jnp.min(Xs)), int(jnp.max(Xs)))
    plan, dyn = [], []
    for name, p in P.DEFAULT_CANDIDATES:
        if name == "identity":
            continue
        try:
            cand = S._plan_candidate(name, p, prep.spec, extrema,
                                     Xs.shape[0], prep.n_active)
        except TransformError:
            continue
        if cand[0] == "grid":
            plan.append(cand[1])
            dyn.append(cand[2])
    text = S._grid_score.lower(Xs, extrema[0], tuple(dyn), spec=prep.spec,
                               plan=tuple(plan)).compile().as_text()
    return "tpu_custom_call" in text


def codec_phase(root: Path, data: dict) -> None:
    from repro.core import scoring as S
    from repro.core.pipeline import FUSED_FAMILIES
    from repro.data.dataset import DatasetReader, DatasetWriter

    fused_total = 0
    for spec_name, x in data.items():
        t0 = time.perf_counter()
        check(phase1_holds_kernel(x[:1 << 16]),
              f"{spec_name}: phase-1 program has no tpu_custom_call")
        report(f"phase1 {spec_name}", t0, tpu_custom_call=True)
        for backend in ("rans", "zlib"):
            name = f"{spec_name}_{backend}"
            S.PHASE1.reset()
            S.PHASE2.reset()
            t0 = time.perf_counter()
            DatasetWriter(root / name, dtype=x.dtype,
                          backend=backend).write([x])
            t_write = time.perf_counter() - t0
            with DatasetReader(root / name) as r:
                back = r.read_all()
            check(same_bits(back, x), f"{name}: read-back differs bitwise")
            methods = chunk_methods(root / name)
            unfused = sum(m not in FUSED_FAMILIES for m in methods)
            if backend == "rans":
                # every chunk whose winner has a fused program was encoded
                # by it; only winners without one (multiply&shift,
                # shift&separate) take the host path
                check(S.PHASE2.dispatches >= len(methods) - unfused,
                      f"{name}: {S.PHASE2.dispatches} fused dispatches for "
                      f"{len(methods) - unfused} fusible chunks")
                check(S.PHASE2.fallbacks == unfused,
                      f"{name}: {S.PHASE2.fallbacks} fallbacks for "
                      f"{unfused} unfusible chunks")
                fused_total += S.PHASE2.dispatches
            ratio = sum(p.stat().st_size for p in (root / name).glob(
                "*.fpc")) / x.nbytes
            report(f"write+read {name}", t0, mib=x.nbytes >> 20,
                   write_s=f"{t_write:.1f}", chunks=len(methods),
                   methods=",".join(sorted(set(methods))),
                   ratio=f"{ratio:.4f}", bitwise=True,
                   phase1_dispatches=S.PHASE1.dispatches,
                   fused_dispatches=S.PHASE2.dispatches,
                   fused_device_gets=S.PHASE2.device_gets,
                   fallbacks=S.PHASE2.fallbacks)
    check(fused_total >= 1, "no rANS write took the fused device encode")


# ---------------------------------------------------------------------------
# (d) serving
# ---------------------------------------------------------------------------

def serving_phase(root: Path, data: dict) -> None:
    from repro.serving import TensorServer

    t0 = time.perf_counter()
    reads = 0
    with TensorServer(root) as srv:
        names = srv.names()
        for name in names:
            x = data[name.split("_")[0]]
            check(same_bits(srv.read(name), x), f"served {name} differs")
            lo, hi = x.size // 3, x.size // 3 + 100_003
            check(same_bits(srv.read_slice(name, lo, hi), x[lo:hi]),
                  f"served slice of {name} differs")
            reads += 2
        st = srv.stats()
    report("serve", t0, tensors=len(names), reads=reads, bitwise=True,
           decodes=st["decodes"])


# ---------------------------------------------------------------------------
# (c) the trainer and its checkpoints
# ---------------------------------------------------------------------------

def train(argv: list[str]) -> tuple[dict[int, float], str]:
    """One in-process run of the training launcher -> ({step: loss}, log)."""
    from repro.launch import train as launcher

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = launcher.main(TRAIN_ARGS + argv)
    text = out.getvalue()
    print(text, end="", flush=True)
    check(rc == 0, f"train {' '.join(argv)} returned {rc}")
    return {int(m.group(1)): float(m.group(2)) for m in re.finditer(
        r"step\s+(\d+) \| loss ([0-9.]+)", text)}, text


def match(got: dict, ref: dict, steps, tol: float, what: str) -> float:
    check(all(s in got and s in ref for s in steps), f"{what}: missing steps")
    diff = max(abs(got[s] - ref[s]) for s in steps)
    check(diff <= tol, f"{what}: loss differs by {diff} (> {tol})")
    return diff


def trainer_phase(root: Path) -> None:
    t0 = time.perf_counter()
    ref, _ = train(["--steps", "6"])
    report("train uninterrupted", t0, steps=6, loss0=ref[0], loss5=ref[5])
    t0 = time.perf_counter()
    ck = str(root / "ckpt")
    first, _ = train(["--steps", "3", "--save-every", "3", "--ckpt-dir", ck])
    match(first, ref, range(3), 1e-6, "steps before the save")
    report("train+save", t0, steps=3, ckpt_mib=sum(
        p.stat().st_size for p in Path(ck).rglob("*") if p.is_file()) >> 20)
    t0 = time.perf_counter()
    resumed, log = train(["--steps", "6", "--resume", "--ckpt-dir", ck])
    check("[resume] restored step 3" in log, "the run did not resume")
    diff = match(resumed, ref, range(3, 6), 1e-6, "resumed run")
    report("resume", t0, steps="3-5", max_loss_diff=diff)


def four_chip_phase(root: Path) -> None:
    t0 = time.perf_counter()
    ref, _ = train(["--steps", "6"])
    report("one chip", t0, steps=6, loss0=ref[0], loss5=ref[5])
    t0 = time.perf_counter()
    dp, _ = train(["--steps", "3", "--data-par", "4"])
    diff = match(dp, ref, range(3), RESUME_TOL, "--data-par 4")
    report("data-par 4", t0, steps=3, max_loss_diff=diff)
    t0 = time.perf_counter()
    ck = str(root / "ckpt")
    train(["--steps", "3", "--save-every", "3", "--ckpt-dir", ck])
    resumed, log = train(["--steps", "6", "--resume", "--ckpt-dir", ck,
                          "--data-par", "2", "--model-par", "2"])
    check("[resume] restored step 3" in log, "the 2x2 run did not resume")
    diff = match(resumed, ref, range(3, 6), RESUME_TOL,
                 "one-chip checkpoint resumed on 2x2")
    report("elastic resume 2x2", t0, steps="3-5", max_loss_diff=diff)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(REPO / "src"))
    try:
        from repro.launch.compile_cache import use_checkout_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script: {e}",
              file=sys.stderr)
        return 2
    use_checkout_cache()
    try:
        dev, count = device_check(args.chips)
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            root = Path(tmp)
            if args.chips == 4:
                four_chip_phase(root)
            else:
                data = streams()
                (root / "served").mkdir()
                codec_phase(root / "served", data)
                serving_phase(root / "served", data)
                trainer_phase(root)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
