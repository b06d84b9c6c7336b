"""Device side of the rANS backend: Pallas encode-statistics pass, the
interleaved-lane encode scan, and the batched-jnp decode lane loop.

Encode's data-parallel stages all run on device: the symbol-statistics
(byte histogram) pass runs as a Pallas kernel with the same
``(ROWS, 128)``-tile same-output-block accumulation as
``kernels/scoregrid`` (interpret mode on CPU, TPU compile target, plus a
fused-jnp twin producing identical integers); :func:`quantize_freqs_dev` is
the traceable twin of the normative ``ref.quantize_freqs`` (same integers,
asserted in ``tests/test_rans.py``); and :func:`encode_scan` is the
reversed lockstep mirror of :func:`decode_scan` — all lanes push one symbol
per step with up to :data:`MAX_RENORM` masked byte emissions, recorded into
dense per-step buffers that ``ref.assemble_frame`` compacts into the
byte-identical normative bitstream.

Decode is lane-parallel by construction (each lane owns an independent
stream), so the decode lane loop is a ``lax.scan`` over symbol steps with
every lane advanced vectorially per step — one device program for the whole
payload, TPU-compilable, asserted byte-identical to ``ref.decode`` in
``tests/test_rans.py``.  All state arithmetic fits int32 (states live in
``[2^23, 2^31)``), keeping both scans TPU-native; the encode renorm compare
``x >= (RANS_L >> PROB_BITS << 8) * f`` is computed as
``(x >> 8) >= (RANS_L >> PROB_BITS) * f`` because the direct product hits
exactly 2^31 for a single-symbol table (f = PROB_SCALE) — the shifted form
is exact (the bound is a multiple of 256) and stays in int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..scoregrid.kernel import byte_hist_block
from .ref import MAX_RENORM, PROB_BITS, PROB_SCALE, RANS_L

ROWS = 8        # uint32 sublanes per histogram grid step (int32 min tile)
_BLK = ROWS * 128


# ---------------------------------------------------------------------------
# encode symbol-statistics pass: 256-bin byte histogram
# ---------------------------------------------------------------------------

def _hist_kernel(x_ref, out_ref):
    i = pl.program_id(0)
    bins = (lax.broadcasted_iota(jnp.int32, (2, 128), 0) * 128
            + lax.broadcasted_iota(jnp.int32, (2, 128), 1))
    blk = byte_hist_block(x_ref[...], jnp.zeros((2, 128), jnp.int32), bins)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = blk

    @pl.when(i > 0)
    def _acc():
        out_ref[...] = out_ref[...] + blk


@functools.partial(jax.jit, static_argnames=("interpret",))
def _hist_blocks(x3: jnp.ndarray, interpret: bool = True) -> jnp.ndarray:
    """uint32[r, 128] (r % ROWS == 0) -> int32[2, 128] histogram halves."""
    return pl.pallas_call(
        _hist_kernel,
        grid=(x3.shape[0] // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, 128), lambda i: (i, jnp.int32(0)))],
        out_specs=pl.BlockSpec((2, 128), lambda i: (jnp.int32(0), jnp.int32(0))),
        out_shape=jax.ShapeDtypeStruct((2, 128), jnp.int32),
        interpret=interpret,
    )(x3)


@jax.jit
def _hist_jnp(data: jnp.ndarray) -> jnp.ndarray:
    """Fused-jnp twin (identical integers): uint8[n] -> int32[256]."""
    return jnp.bincount(data.astype(jnp.int32), length=256).astype(jnp.int32)


def byte_hist(data, use_pallas: bool = False, interpret: bool = True):
    """uint8[n] -> int32[256] byte histogram on device.

    The Pallas path packs the byte stream into (ROWS, 128) uint32 tiles and
    subtracts the statically known zero padding from bin 0."""
    import numpy as np

    data = jnp.asarray(np.ascontiguousarray(data).view(np.uint8))
    n = int(data.shape[0])
    if n == 0:
        return jnp.zeros(256, jnp.int32)
    if not use_pallas:
        return _hist_jnp(data)
    npad = -(-n // (4 * _BLK)) * (4 * _BLK)
    padded = jnp.zeros(npad, jnp.uint8).at[:n].set(data)
    words = lax.bitcast_convert_type(
        padded.reshape(-1, 4), jnp.uint32
    ).reshape(-1, 128)
    out = _hist_blocks(words, interpret=interpret)
    hist = jnp.concatenate([out[0], out[1]])
    return hist.at[0].add(jnp.int32(n - npad))      # remove zero padding


# ---------------------------------------------------------------------------
# frequency quantization (traceable twin of ref.quantize_freqs)
# ---------------------------------------------------------------------------

_FAR = jnp.int64(1) << 60       # sort key for excluded slots: always last


def _rank_by(key: jnp.ndarray) -> jnp.ndarray:
    """rank[i] = position of slot i in the stable ascending sort of key
    (ties resolved by lower slot index, matching np.lexsort((arange, -k)))."""
    order = jnp.argsort(key, stable=True)
    return jnp.zeros(256, jnp.int64).at[order].set(jnp.arange(256, dtype=jnp.int64))


def quantize_freqs_dev(counts: jnp.ndarray) -> jnp.ndarray:
    """Traceable twin of ``ref.quantize_freqs``: int[256] counts (sum > 0)
    -> int64[256] table summing exactly to :data:`PROB_SCALE`.

    Same integers on every input: largest-remainder distribution with ties
    by lower symbol, overshoot stolen from the largest frequencies via a
    ``lax.while_loop`` over the 256-wide table.  Runs inside the fused
    encode dispatch so the frequency table never forces a host round-trip.
    """
    counts = jnp.asarray(counts, jnp.int64)
    n = counts.sum()
    nz = counts > 0
    freq = jnp.where(nz, jnp.maximum(counts * PROB_SCALE // jnp.maximum(n, 1), 1), 0)
    diff = PROB_SCALE - freq.sum()
    # shortfall: distribute by largest truncation remainder
    rem = counts * PROB_SCALE % jnp.maximum(n, 1)
    rank = _rank_by(jnp.where(nz, -rem, _FAR))
    k = jnp.maximum(nz.sum(), 1)
    add = jnp.where(nz, diff // k + (rank < diff % k), 0)
    freq = jnp.where(diff > 0, freq + add, freq)

    def cond(state):
        return state[1] < 0

    def body(state):
        f, d = state
        # steal from the largest frequencies (> 1), ties by lower symbol
        gt1 = f > 1
        rank = _rank_by(jnp.where(gt1, -f, _FAR))
        take = jnp.minimum(-d, gt1.sum())
        dec = (gt1 & (rank < take)).astype(jnp.int64)
        return f - dec, d + take

    freq, _ = lax.while_loop(cond, body, (freq, diff))
    return freq


# ---------------------------------------------------------------------------
# encode lane loop (reversed mirror of decode_scan)
# ---------------------------------------------------------------------------

def encode_scan_body(x, t, s, n, freq, cum, lanes: int):
    """One reversed encode step for all lanes in lockstep (shared by the
    standalone :func:`encode_scan` jit and the fused pipeline dispatch).

    ``x`` int32[lanes] states, ``t`` the step index, ``s`` int32[lanes]
    symbols.  Inactive slots (``t*lanes + lane >= n`` — the interleave
    remainder and any step-bucket padding) carry frequency
    :data:`PROB_SCALE`, whose renorm bound (2^31) no state can reach, and a
    masked push — exact no-ops, so padded steps leave the bitstream
    byte-identical.  Returns ``(x, (b0, b1, e0, e1))`` dense emission
    records for ``ref.assemble_frame``."""
    lane = jnp.arange(lanes, dtype=jnp.int32)
    act = t * lanes + lane < n
    f = jnp.where(act, freq[s], jnp.int32(PROB_SCALE))
    ge_lim = jnp.int32(RANS_L >> PROB_BITS) * f      # renorm bound / 256
    m0 = (x >> 8) >= ge_lim
    b0 = (x & 0xFF).astype(jnp.uint8)
    x = jnp.where(m0, x >> 8, x)
    m1 = (x >> 8) >= ge_lim
    b1 = (x & 0xFF).astype(jnp.uint8)
    x = jnp.where(m1, x >> 8, x)
    q = x // f
    pushed = (q << PROB_BITS) + (x - q * f) + cum[s]
    x = jnp.where(act, pushed, x)
    return x, (b0, b1, m0, m1)


@functools.partial(jax.jit, static_argnames=("steps", "lanes"))
def encode_scan(sym, n, freq, cum, steps: int, lanes: int):
    """The rANS encode lane loop as one device scan (reverse order).

    ``sym`` int32[steps, lanes] holds symbol ``i`` at ``[i // lanes,
    i % lanes]`` with arbitrary padding past ``n``; ``steps`` may exceed
    ``ceil(n / lanes)`` (step-bucket padding for bounded recompiles) — the
    extra trailing steps are processed first by the reversed scan as exact
    no-ops.  Returns ``(b0, b1, e0, e1, x_final)`` in ascending step order,
    ready for ``ref.assemble_frame``."""
    sym = jnp.asarray(sym, jnp.int32)
    n = jnp.asarray(n, jnp.int32)
    freq = jnp.asarray(freq, jnp.int32)
    cum = jnp.asarray(cum, jnp.int32)

    def step(x, xs):
        t, s = xs
        return encode_scan_body(x, t, s, n, freq, cum, lanes)

    with jax.named_scope("encode_scan"):
        x, (b0, b1, e0, e1) = lax.scan(
            step, jnp.full((lanes,), RANS_L, jnp.int32),
            (jnp.arange(steps, dtype=jnp.int32), sym),
            reverse=True,
        )
    return b0, b1, e0, e1, x


def bucket_steps(steps: int, floor: int = 512) -> int:
    """Round a step count up to a {1, 1.25, 1.5, 1.75}·2^k bucket so the
    encode scan compiles O(log) distinct programs instead of one per
    payload length, with at most 25% padded no-op steps (padding is exact —
    see :func:`encode_scan`)."""
    if steps <= floor:
        return floor
    b = floor
    while b * 2 < steps:
        b <<= 1
    q = b >> 2
    return -(-steps // q) * q


# ---------------------------------------------------------------------------
# decode lane loop
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("steps", "lanes"))
def decode_scan(states, bodies, body_lens, n, slot2sym, freq, cum,
                steps: int, lanes: int):
    """The rANS decode lane loop as one device scan.

    All lanes advance in lockstep: per step each lane maps its state's low
    12 bits through the slot table, pops the symbol, and renormalizes with
    up to :data:`MAX_RENORM` byte reads from its own body stream.  Inactive
    lane slots (the interleave remainder past ``n``) are masked no-ops.

    Returns ``(syms int32[steps, lanes], x_final, ptr_final)``; the caller
    verifies the termination invariants (pointer == body length, state back
    at ``RANS_L``) on host via :func:`ref.check_final`."""
    x0 = jnp.asarray(states, jnp.int32)
    bod = jnp.asarray(bodies, jnp.int32)
    blen = jnp.asarray(body_lens, jnp.int32)
    n = jnp.asarray(n, jnp.int32)
    slot2sym = jnp.asarray(slot2sym, jnp.int32)
    freq = jnp.asarray(freq, jnp.int32)
    cum = jnp.asarray(cum, jnp.int32)
    maxw = bod.shape[1]
    lane = jnp.arange(lanes, dtype=jnp.int32)

    def step(carry, t):
        x, ptr = carry
        act = t * lanes + lane < n
        slot = x & jnp.int32(PROB_SCALE - 1)
        s = slot2sym[slot]
        popped = freq[s] * (x >> PROB_BITS) + slot - cum[s]
        x = jnp.where(act, popped, x)
        for _ in range(MAX_RENORM):
            m = act & (x < RANS_L) & (ptr < blen)
            b = jnp.take_along_axis(
                bod, jnp.minimum(ptr, maxw - 1)[:, None], axis=1
            )[:, 0]
            x = jnp.where(m, (x << 8) | b, x)
            ptr = ptr + m.astype(jnp.int32)
        return (x, ptr), jnp.where(act, s, 0)

    with jax.named_scope("decode_scan"):
        (x, ptr), syms = lax.scan(
            step, (x0, jnp.zeros(lanes, jnp.int32)),
            jnp.arange(steps, dtype=jnp.int32),
        )
    return syms, x, ptr
