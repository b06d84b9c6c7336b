"""Pallas kernel: fused bit-statistics for the stacked candidate scoring grid.

Phase-1 of ``encode(method="auto")`` scores every (transform, parameter)
candidate with ``max(bit-plane run model, pooled byte entropy)``
(core/scoring.py).  Both models consume the same raw statistics of a
candidate's transformed word stream:

* per-plane set-bit counts   (``ones[p]``   — order-0 plane entropy),
* per-plane flip counts      (``trans[p]``  — first-order run model),
* the pooled byte histogram  (``hist[256]`` — Huffman-literal bound).

This kernel gathers all three for EVERY candidate row of a stacked
``[rows, n]`` uint32 word grid in one VMEM-resident pass: each grid step
reduces an ``(ROWS, 128)`` tile of one candidate row into that row's
``(4, 128)`` stats block (planes 0..31 lane-packed in rows 0-1, the 256-bin
histogram in rows 2-3), accumulated across steps with the same
same-output-block pattern as the ``sharedbits`` AND/OR kernel.  Transition
counts need the predecessor of each word, which arrives as a second,
one-element-shifted copy of the grid so every step stays purely blockwise
(no cross-block carry state).

uint64 streams are scored as two u32 rows (lo/hi lanes, TPU-native) and
recombined by the ops layer.  Interpret mode on CPU; TPU is the compile
target.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

ROWS = 8        # words-tile sublanes per grid step (int32 min tile height)
OUT_ROWS = 4    # ones | transitions | hist[:128] | hist[128:]


def _total(v):
    """Sum of an int32 tile as a ``(1, 1)`` array (2-D reductions only:
    Mosaic lowers neither 1-D vectors nor 3-D reductions)."""
    col = jnp.sum(v, axis=0, keepdims=True, dtype=jnp.int32)
    return jnp.sum(col, axis=1, keepdims=True, dtype=jnp.int32)


def byte_hist_block(x, blk, bins):
    """Add the 256-bin histogram of the four bytes of every uint32 word of
    ``x`` into ``blk``: the count of byte value ``v`` lands in the cells
    where ``bins == v`` (cells with a negative bin are left as they are).

    The output block is written through ``jnp.where`` masks over iotas, not
    ``.at[].set`` (Mosaic has no scatter), and the byte planes stay a
    lane-dense ``(4 * rows, 128)`` tile."""
    by = jnp.concatenate(
        [((x >> jnp.uint32(8 * b)) & jnp.uint32(0xFF)).astype(jnp.int32)
         for b in range(4)], axis=0)

    def body(v, acc):
        return jnp.where(bins == v, acc + _total((by == v).astype(jnp.int32)),
                         acc)

    return lax.fori_loop(jnp.int32(0), jnp.int32(256), body, blk)


def _kernel(x_ref, xp_ref, out_ref):
    i = pl.program_id(1)
    x = x_ref[0]                      # (ROWS, 128) uint32
    flips = x ^ xp_ref[0]

    row = lax.broadcasted_iota(jnp.int32, (OUT_ROWS, 128), 0)
    lane = lax.broadcasted_iota(jnp.int32, (OUT_ROWS, 128), 1)
    blk = jnp.zeros((OUT_ROWS, 128), jnp.int32)
    for p in range(32):
        bit = jnp.uint32(p)
        ones = _total(((x >> bit) & jnp.uint32(1)).astype(jnp.int32))
        trans = _total(((flips >> bit) & jnp.uint32(1)).astype(jnp.int32))
        blk = jnp.where((row == 0) & (lane == p), ones, blk)
        blk = jnp.where((row == 1) & (lane == p), trans, blk)
    # rows 2-3 hold bins 0..255; rows 0-1 map to negative bins (untouched)
    blk = byte_hist_block(x, blk, (row - 2) * 128 + lane)

    @pl.when(i == 0)
    def _init():
        out_ref[0] = blk

    @pl.when(i > 0)
    def _acc():
        out_ref[0] = out_ref[0] + blk


@functools.partial(jax.jit, static_argnames=("interpret",))
def scoregrid_blocks(
    x: jnp.ndarray, xprev: jnp.ndarray, interpret: bool = True
) -> jnp.ndarray:
    """x, xprev: uint32[rows, r, 128] with r % ROWS == 0 (xprev = x shifted by
    one word within each row) -> int32[rows, 4, 128] stats blocks."""
    rows, r, _ = x.shape
    grid = (rows, r // ROWS)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ROWS, 128), lambda c, i: (c, i, jnp.int32(0))),
            pl.BlockSpec((1, ROWS, 128), lambda c, i: (c, i, jnp.int32(0))),
        ],
        out_specs=pl.BlockSpec((1, OUT_ROWS, 128), lambda c, i: (c, jnp.int32(0), jnp.int32(0))),
        out_shape=jax.ShapeDtypeStruct((rows, OUT_ROWS, 128), jnp.int32),
        interpret=interpret,
    )(x, xprev)
