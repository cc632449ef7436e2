"""Pallas TPU kernel: Reed-Solomon GF(2^8) parity encode over k shards.

Computes the m parity blobs of the RS redundancy codec (core/codec.py):
``out[j] = ⊕_i C[j][i] · x[i]`` with · in GF(2^8) — the multi-failure
generalization of the XOR kernel (kernels/xor_parity.py), to which it
degenerates when C is all-ones.

The host reference (core/gf256.py, kernels/ref.py) multiplies through
log/antilog tables; per-element 256-entry gathers are hostile to the VPU, so
the kernel is **matmul-free and gather-free**: the Cauchy coefficients are
compile-time constants, and multiplication by a constant c unrolls into an
xtime (·α) shift-XOR chain — at most 8 VPU ops per (i, j) pair, selected by
the bits of c at trace time. Shards stream through VMEM as uint32 lanes
carrying 4 packed GF(2^8) bytes each (SWAR): xtime on a packed word is

    ((x & 0x7f7f7f7f) << 1) ^ (((x >> 7) & 0x01010101) * 0x1d)

i.e. shift every byte left and reduce overflowing bytes by the field
polynomial 0x11D, with the inter-byte carry masked off.

Layout matches the XOR kernel: (k, 8, LANE*COLS) tiles, XOR chains in VREGs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SUBLANES = 8
BLOCK_COLS = 128 * 16

_LOW7 = 0x7F7F7F7F
_HIGH = 0x01010101
_POLY_LOW8 = 0x1D  # 0x11D with the (shifted-out) x^8 term dropped


def _xtime_u32(x: jax.Array) -> jax.Array:
    """Multiply 4 packed GF(2^8) bytes by α in one SWAR step."""
    return ((x & _LOW7) << 1) ^ (((x >> 7) & _HIGH) * _POLY_LOW8)


def _gf_scale_u32(x: jax.Array, c: int) -> jax.Array:
    """x · c for a compile-time constant c: XOR of the set-bit xtime powers."""
    acc = None
    t = x
    for bit in range(8):
        if c >> bit & 1:
            acc = t if acc is None else acc ^ t
        if c >> (bit + 1) == 0:
            break
        t = _xtime_u32(t)
    return jnp.zeros_like(x) if acc is None else acc


def _rs_kernel(x_ref, o_ref, *, coefs: tuple[tuple[int, ...], ...]):
    k = len(coefs[0])
    for j, row in enumerate(coefs):  # m and k are static: fully unrolled
        acc = None
        for i in range(k):
            if row[i] == 0:
                continue
            term = _gf_scale_u32(x_ref[i], row[i])
            acc = term if acc is None else acc ^ term
        o_ref[j] = jnp.zeros_like(x_ref[0]) if acc is None else acc


def rs_encode_pallas(
    stacked: jax.Array, coefs: tuple[tuple[int, ...], ...], *, interpret: bool
) -> jax.Array:
    """stacked: (k, rows, cols) uint32, rows % 8 == 0, cols % BLOCK_COLS == 0.

    coefs: static (m, k) GF(2^8) generator rows (hashable tuple of tuples).
    Returns (m, rows, cols) uint32 parity. Padding/flattening in ops.gf256_matmul.
    """
    k, rows, cols = stacked.shape
    m = len(coefs)
    assert all(len(row) == k for row in coefs), (coefs, k)
    assert rows % SUBLANES == 0 and cols % BLOCK_COLS == 0, (rows, cols)
    grid = (rows // SUBLANES, cols // BLOCK_COLS)
    return pl.pallas_call(
        functools.partial(_rs_kernel, coefs=coefs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, SUBLANES, BLOCK_COLS), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((m, SUBLANES, BLOCK_COLS), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((m, rows, cols), jnp.uint32),
        interpret=interpret,
    )(stacked)
