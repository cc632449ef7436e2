"""Pallas TPU kernel: GF(2^8) matmul with RUNTIME coefficients — erasure decode.

The encode kernel (kernels/rs_encode.py) bakes the Cauchy generator into the
program as compile-time constants — correct for creation, where the generator
never changes. Decode cannot: the coefficient matrix depends on *which* ranks
died (gf256.erasure_decode_matrix precomputes one row per lost shard from the
inverted Cauchy submatrix), and recompiling the restore program per failure
pattern would put an XLA compile on the recovery critical path. So this
kernel takes the (m, k) coefficient matrix as a runtime SMEM operand and
multiplies by a *data-dependent* scalar: the xtime (·α) shift-XOR chain runs
all 8 steps, each term masked by the corresponding bit of the coefficient —
8 fixed VPU steps per (i, j) pair instead of the encode kernel's pruned
chain. Data streams through VMEM as packed uint32 SWAR lanes exactly like
the encode kernel; one program serves every failure combination.

Layout matches rs_encode: (k, 8, LANE*COLS) tiles, XOR chains in VREGs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8
BLOCK_COLS = 128 * 16

_LOW7 = 0x7F7F7F7F
_HIGH = 0x01010101
_POLY_LOW8 = 0x1D  # 0x11D with the (shifted-out) x^8 term dropped


def _xtime_u32(x: jax.Array) -> jax.Array:
    """Multiply 4 packed GF(2^8) bytes by α in one SWAR step."""
    return ((x & _LOW7) << 1) ^ (((x >> 7) & _HIGH) * _POLY_LOW8)


def _gf_scale_dyn_u32(x: jax.Array, c: jax.Array) -> jax.Array:
    """x · c for a runtime uint32 scalar c: all 8 xtime powers, each masked
    by the matching bit of c (0/1 multiply keeps it branch- and gather-free)."""
    acc = jnp.zeros_like(x)
    t = x
    for bit in range(8):
        sel = (c >> bit) & jnp.uint32(1)
        acc = acc ^ (t * sel)
        if bit < 7:
            t = _xtime_u32(t)
    return acc


def _rs_decode_kernel(c_ref, x_ref, o_ref, *, m: int, k: int):
    for j in range(m):  # m and k are static shapes: fully unrolled
        acc = None
        for i in range(k):
            c = c_ref[j, i]  # runtime SMEM scalar — the failure-dependent coef
            term = _gf_scale_dyn_u32(x_ref[i], c)
            acc = term if acc is None else acc ^ term
        o_ref[j] = jnp.zeros_like(x_ref[0]) if acc is None else acc


def rs_decode_pallas(
    stacked: jax.Array, coefs: jax.Array, *, interpret: bool
) -> jax.Array:
    """stacked: (k, rows, cols) uint32, rows % 8 == 0, cols % BLOCK_COLS == 0.

    coefs: (m, k) uint32 runtime decode matrix (erasure_decode_matrix rows).
    Returns (m, rows, cols) uint32. Padding/flattening in ops.gf256_matmul_dyn.
    """
    k, rows, cols = stacked.shape
    m = coefs.shape[0]
    assert coefs.shape == (m, k), (coefs.shape, k)
    assert rows % SUBLANES == 0 and cols % BLOCK_COLS == 0, (rows, cols)
    grid = (rows // SUBLANES, cols // BLOCK_COLS)
    return pl.pallas_call(
        functools.partial(_rs_decode_kernel, m=m, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, k), lambda i, j: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((k, SUBLANES, BLOCK_COLS), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((m, SUBLANES, BLOCK_COLS), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((m, rows, cols), jnp.uint32),
        interpret=interpret,
    )(coefs.astype(jnp.uint32), stacked)
