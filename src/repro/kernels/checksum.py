"""Pallas TPU kernel: Fletcher-style dual checksum for snapshot validation.

The paper's handshake (Algorithm 2) must verify that every process created a
consistent snapshot before the double-buffer swap; the checksum is what the
handshake exchanges/compares. Linearity of both sums means per-tile partials
(computed in VMEM) reduce exactly outside the kernel.

Layout: buffer viewed as uint32 (rows, LANE_COLS). The grid walks row tiles
sequentially and folds each tile's lane-column slices into one resident
(2, SUBLANES, 128) accumulator — plane 0 holds partials of sum(x), plane 1 of
sum((global_index+1) * x), both mod 2^32 — so every block stays on the native
(8, 128) tiling and the output is written back once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SUBLANES = 8
LANES = 128
LANE_COLS = LANES * 8  # 1024 columns per tile -> 32 KiB tiles


def _checksum_kernel(x_ref, o_ref, *, cols: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.uint32)

    x = x_ref[...]  # (SUBLANES, LANE_COLS) uint32
    # Global word index + 1, in int32 two's-complement arithmetic (the same
    # bits as uint32 mod 2^32, which is what the reference computes).
    rows_idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    cols_idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    gidx = (i * SUBLANES + rows_idx) * cols + cols_idx + 1
    wx = x * jax.lax.bitcast_convert_type(gidx, jnp.uint32)
    s1 = x[:, :LANES]
    s2 = wx[:, :LANES]
    for c in range(LANES, cols, LANES):  # lane-aligned static slices
        s1 = s1 + x[:, c : c + LANES]
        s2 = s2 + wx[:, c : c + LANES]
    o_ref[0] += s1
    o_ref[1] += s2


def checksum_pallas(x2d: jax.Array, *, interpret: bool) -> jax.Array:
    """x2d: (rows, LANE_COLS) uint32, rows % SUBLANES == 0 -> (2,) uint32."""
    rows, cols = x2d.shape
    assert rows % SUBLANES == 0 and cols == LANE_COLS, (rows, cols)
    grid = (rows // SUBLANES,)
    partials = pl.pallas_call(
        functools.partial(_checksum_kernel, cols=cols),
        grid=grid,
        in_specs=[pl.BlockSpec((SUBLANES, LANE_COLS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((2, SUBLANES, LANES), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, SUBLANES, LANES), jnp.uint32),
        interpret=interpret,
    )(x2d)
    return jnp.sum(partials.reshape(2, -1), axis=1, dtype=jnp.uint32)
