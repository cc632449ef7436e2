"""Pallas TPU kernel: XOR parity encode / reconstruct over k snapshot shards.

The erasure-coded redundancy mode (DESIGN.md §4, EXPERIMENTS beyond-paper
opt) XORs k equally-sized checkpoint shards into one parity shard. The
operation is pure bandwidth — the kernel's job is to stream all k shards
through VMEM exactly once with lane-aligned tiles.

Layout: shards are viewed as uint32 and shaped (k, n). Tiles are
(k, 8, LANE*COLS) so the XOR chain over k runs in registers per tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# 8 sublanes x 128 lanes is the native f32/u32 TPU tile; 16 column-tiles per
# block keeps the per-tile VMEM footprint at k * 8 * 2048 * 4B (k=4 -> 256 KiB).
SUBLANES = 8
BLOCK_COLS = 128 * 16


def _xor_kernel(x_ref, o_ref, *, k: int):
    acc = x_ref[0]
    for i in range(1, k):  # k is static: unrolled XOR chain in VREGs
        acc = jnp.bitwise_xor(acc, x_ref[i])
    o_ref[...] = acc


def xor_reduce_pallas(stacked: jax.Array, *, interpret: bool) -> jax.Array:
    """stacked: (k, rows, cols) uint32 with rows % 8 == 0, cols % BLOCK_COLS == 0.

    Returns (rows, cols) uint32 = XOR over axis 0. Wrapper-level padding and
    flattening live in ops.xor_reduce.
    """
    k, rows, cols = stacked.shape
    assert rows % SUBLANES == 0 and cols % BLOCK_COLS == 0, (rows, cols)
    grid = (rows // SUBLANES, cols // BLOCK_COLS)
    return pl.pallas_call(
        functools.partial(_xor_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, SUBLANES, BLOCK_COLS), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((SUBLANES, BLOCK_COLS), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.uint32),
        interpret=interpret,
    )(stacked)
