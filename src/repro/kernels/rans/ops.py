"""Platform dispatch for the rANS entropy-coder backend.

Small streams run through the numpy reference (``ref.py``); large streams
route through the compiled lane scans (``kernel.py``) on every platform —
on CPU the XLA-native ``lax.scan`` loops beat the vectorized numpy step
loop by an order of magnitude, on TPU they are the device-resident path.
Both producers emit byte-identical frames (asserted in
``tests/test_rans.py``): the scans record dense per-step emissions and
``ref.assemble_frame`` is the single bitstream assembly point.

Two carve-outs keep the scan routing honest:

* container decode-pool worker threads stay on the numpy reference — the
  pool's parallelism comes from numpy releasing the GIL, while jit
  dispatch would serialize the workers;
* step counts are padded to :func:`kernel.bucket_steps` buckets (exact
  no-op steps) so the scans compile O(log) programs, not one per length.

``REPRO_RANS_LANES`` overrides the encode-side interleave width (decode
always honours the lane count stored in the frame).
"""
from __future__ import annotations

import os

import numpy as np

from ... import obs
from .. import INTERPRET_DEFAULT
from . import ref
from .ref import RansError  # noqa: F401  (re-exported for callers)

_ON_TPU = not INTERPRET_DEFAULT

# route through the compiled scans only when the scan is long enough to
# amortize dispatch + possible compile (one bucket's worth of steps)
SCAN_MIN_STEPS = 512


def default_lanes() -> int:
    """Encode-side interleave width (``REPRO_RANS_LANES`` env override)."""
    v = os.environ.get("REPRO_RANS_LANES", "").strip()
    return int(v) if v else ref.DEFAULT_LANES


def _use_scan(steps: int) -> bool:
    if steps < SCAN_MIN_STEPS:
        return False
    if _ON_TPU:
        return True
    from ...container.io import in_decode_pool

    return not in_decode_pool()


@obs.span("rans.encode")
def compress(data: bytes, lanes: int | None = None,
             counts=None) -> bytes:
    """bytes -> framed rANS stream.

    ``counts`` feeds a precomputed byte histogram into the frequency pass
    (e.g. phase-1's scoregrid histogram or the fused encode dispatch);
    otherwise the statistics pass runs on device on TPU and as
    ``np.bincount`` on CPU."""
    arr = np.frombuffer(data, np.uint8)
    n = arr.size
    lanes = ref.clamp_lanes(lanes or default_lanes(), n)
    steps = -(-n // lanes) if n else 0
    if n and _use_scan(steps):
        return _compress_scan(arr, lanes, counts)
    if counts is None and _ON_TPU and n:
        from .kernel import byte_hist

        counts = byte_hist(arr, use_pallas=True, interpret=INTERPRET_DEFAULT)
        with obs.span("rans.device_wait"):
            counts = np.asarray(counts, np.int64)
    return ref.encode(arr, lanes=lanes, counts=counts)


def _compress_scan(arr: np.ndarray, lanes: int, counts) -> bytes:
    """Encode through the compiled lane scan (byte-identical to ref)."""
    from .kernel import bucket_steps, encode_scan

    n = arr.size
    if counts is None:
        counts = np.bincount(arr, minlength=256)
    freq = ref.quantize_freqs(np.asarray(counts, np.int64))
    cum = ref.cum_from_freq(freq)
    steps = bucket_steps(-(-n // lanes))
    sym = np.zeros(steps * lanes, np.int32)
    sym[:n] = arr
    out = encode_scan(
        sym.reshape(steps, lanes), n, freq.astype(np.int32),
        cum.astype(np.int32), steps=steps, lanes=lanes,
    )
    with obs.span("rans.device_wait"):
        b0, b1, e0, e1, x = map(np.asarray, out)
    head = ref._HEADER.pack(ref.FRAME_VERSION, lanes, n)
    return ref.assemble_frame(head, freq, x, b0, b1, e0, e1)


@obs.span("rans.decode")
def decompress(buf: bytes) -> bytes:
    """Framed rANS stream -> bytes (compiled lane loop for large frames,
    numpy reference for small frames and decode-pool workers)."""
    n = ref.peek_raw_len(bytes(buf))
    lanes = max(bytes(buf)[1], 1)
    if n and _use_scan(-(-n // lanes)):
        return decompress_device(buf)
    return ref.decode(buf).tobytes()


def decompress_device(buf: bytes, interpret: bool | None = None) -> bytes:
    """Decode with the device lane loop: host framing parse, one
    ``decode_scan`` program for the payload, host termination checks."""
    from .kernel import bucket_steps, decode_scan

    lanes, n, freq, cum, states, bodies, body_lens = ref.parse_frame(bytes(buf))
    if n == 0:
        return b""
    steps = bucket_steps(-(-n // lanes), 1)
    # bucket the body width too: decode_scan recompiles per body shape
    maxw = bucket_steps(bodies.shape[1], 64)
    if maxw != bodies.shape[1]:
        bodies = np.ascontiguousarray(
            np.pad(bodies, ((0, 0), (0, maxw - bodies.shape[1])))
        )
    syms, x, ptr = decode_scan(
        states, bodies, body_lens, n,
        np.repeat(np.arange(256, dtype=np.int32), freq), freq, cum,
        steps=steps, lanes=lanes,
    )
    with obs.span("rans.device_wait"):
        syms, x, ptr = map(np.asarray, (syms, x, ptr))
    ref.check_final(x, ptr, body_lens)
    return syms.astype(np.uint8).reshape(-1)[:n].tobytes()


def decompress_capped(buf: bytes, max_out: int) -> bytes:
    """Decode at most ``max_out + 1`` bytes: the frame header states the
    payload length up front, so an oversized claim is refused before any
    allocation (decompression-bomb guard, same contract as zlib/zstd)."""
    if ref.peek_raw_len(bytes(buf)) > max(int(max_out), 0) + 1:
        raise RansError("rans frame claims more bytes than the record expects")
    return decompress(buf)


def decompress_into(buf: bytes, out) -> int:
    """Decode directly into a writable buffer; returns the true payload
    length (a value != len(out) signals a mismatch without overrunning).

    Same bomb guard as :func:`decompress_capped`: a frame whose header
    claims a different length than the buffer expects is refused BEFORE the
    lane loop runs or anything is allocated."""
    mv = memoryview(out).cast("B")
    claimed = ref.peek_raw_len(bytes(buf))
    if claimed != len(mv):
        return claimed          # mismatch: caller raises, nothing decoded
    data = np.frombuffer(decompress(bytes(buf)), np.uint8)
    np.frombuffer(mv, np.uint8)[:] = data
    return int(data.size)
