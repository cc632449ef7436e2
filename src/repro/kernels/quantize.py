"""Pallas TPU kernel: fused blockwise int8 quantize / dequantize.

Used for compressed snapshot exchange and gradient compression: max-abs
scale per 256-element block, symmetric int8. The fusion matters on TPU —
max-abs + scale + round + cast in one VMEM pass instead of three HBM trips.

Layout: x viewed as (n_blocks, QBLOCK); tiles are (ROWS_PER_TILE, QBLOCK) so
each row's reduction stays within a tile row. Scales travel as an
(n_blocks, 1) column, whose (ROWS_PER_TILE, 1) blocks satisfy the TPU
tiling rule; ops.py exposes them as the flat (n_blocks,) vector.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

QBLOCK = 256          # quantization block (elements per scale)
ROWS_PER_TILE = 32    # (32, 256) f32 tiles = 32 KiB in, 8 KiB + (32, 1) scales out


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)  # (R, QBLOCK)
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    o_ref[...] = q * s_ref[...]


def quantize_pallas(xb: jax.Array, *, interpret: bool) -> tuple[jax.Array, jax.Array]:
    """xb: (n_blocks, QBLOCK) float, n_blocks % ROWS_PER_TILE == 0.

    Returns (q (n_blocks, QBLOCK) int8, scales (n_blocks, 1) f32)."""
    n, b = xb.shape
    assert b == QBLOCK and n % ROWS_PER_TILE == 0, (n, b)
    grid = (n // ROWS_PER_TILE,)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS_PER_TILE, QBLOCK), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((ROWS_PER_TILE, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, QBLOCK), jnp.int8),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xb)


def dequantize_pallas(q: jax.Array, scale: jax.Array, *, interpret: bool) -> jax.Array:
    """q: (n_blocks, QBLOCK) int8, scale: (n_blocks, 1) f32."""
    n, b = q.shape
    assert b == QBLOCK and n % ROWS_PER_TILE == 0 and scale.shape == (n, 1)
    grid = (n // ROWS_PER_TILE,)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS_PER_TILE, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS_PER_TILE, QBLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, QBLOCK), jnp.float32),
        interpret=interpret,
    )(q, scale)
