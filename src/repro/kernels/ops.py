"""Jit'd public wrappers around the Pallas kernels.

Handles padding/reshaping to kernel-native tiles and dtype views.
``_interpret`` is the one place that decides how a kernel runs: compiled on
a TPU backend, in Pallas interpret mode anywhere else (so CPU tests validate
the kernel *bodies*). The jnp oracles in ``ref.py`` are test references only.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import checksum as _checksum_k
from repro.kernels import quantize as _quantize_k
from repro.kernels import reshard as _reshard_k
from repro.kernels import rs_encode as _rs_k
from repro.kernels import xor_parity as _xor_k


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# uint32 viewing helpers
# ---------------------------------------------------------------------------

def as_u32(x: jax.Array) -> jax.Array:
    """Bitcast any array to a flat uint32 vector (pad odd tails with zeros)."""
    flat = x.reshape(-1)
    itemsize = np.dtype(flat.dtype).itemsize
    if itemsize == 4:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32)
    u8 = jax.lax.bitcast_convert_type(flat, jnp.uint8).reshape(-1)
    pad = (-u8.shape[0]) % 4
    if pad:
        u8 = jnp.pad(u8, (0, pad))
    return jax.lax.bitcast_convert_type(u8.reshape(-1, 4), jnp.uint32).reshape(-1)


def _pad_to(x: jax.Array, multiple: int) -> jax.Array:
    pad = (-x.shape[0]) % multiple
    return jnp.pad(x, (0, pad)) if pad else x


# ---------------------------------------------------------------------------
# XOR parity
# ---------------------------------------------------------------------------

@jax.jit
def xor_reduce(stacked: jax.Array) -> jax.Array:
    """XOR over axis 0 of (k, n) uint32. Returns (n,) uint32."""
    assert stacked.ndim == 2 and stacked.dtype == jnp.uint32
    k, n = stacked.shape
    tile = _xor_k.SUBLANES * _xor_k.BLOCK_COLS
    npad = (-n) % tile
    padded = jnp.pad(stacked, ((0, 0), (0, npad))) if npad else stacked
    rows = padded.shape[1] // _xor_k.BLOCK_COLS
    x3 = padded.reshape(k, rows, _xor_k.BLOCK_COLS)
    out = _xor_k.xor_reduce_pallas(x3, interpret=_interpret())
    return out.reshape(-1)[:n]


def xor_encode_arrays(arrays: list[jax.Array]) -> jax.Array:
    """Parity of equally-sized arrays of any dtype -> (n,) uint32 parity."""
    views = [as_u32(a) for a in arrays]
    n = max(v.shape[0] for v in views)
    views = [_pad_to(v, n) if v.shape[0] < n else v for v in views]
    return xor_reduce(jnp.stack(views))


# ---------------------------------------------------------------------------
# Reed-Solomon GF(2^8) parity (multi-failure redundancy codec)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("coefs",))
def gf256_matmul(stacked: jax.Array, coefs: tuple[tuple[int, ...], ...]) -> jax.Array:
    """RS parity over axis 0 of (k, n) uint32 (4 packed GF bytes per word).

    coefs is the static (m, k) generator (tuple of tuples, hashable for jit).
    Returns (m, n) uint32; the Pallas kernel consumes the packed words directly.
    """
    assert stacked.ndim == 2 and stacked.dtype == jnp.uint32
    k, n = stacked.shape
    assert len(coefs[0]) == k, (len(coefs[0]), k)
    tile = _rs_k.SUBLANES * _rs_k.BLOCK_COLS
    npad = (-n) % tile
    padded = jnp.pad(stacked, ((0, 0), (0, npad))) if npad else stacked
    rows = padded.shape[1] // _rs_k.BLOCK_COLS
    x3 = padded.reshape(k, rows, _rs_k.BLOCK_COLS)
    out = _rs_k.rs_encode_pallas(x3, coefs, interpret=_interpret())
    return out.reshape(len(coefs), -1)[:, :n]


def rs_encode_arrays(arrays: list[jax.Array], coefs: tuple[tuple[int, ...], ...]) -> jax.Array:
    """RS parity of arrays of any dtype/length -> (m, n) uint32 blobs."""
    views = [as_u32(a) for a in arrays]
    n = max(v.shape[0] for v in views)
    views = [_pad_to(v, n) if v.shape[0] < n else v for v in views]
    return gf256_matmul(jnp.stack(views), coefs)


@jax.jit
def gf256_matmul_dyn(stacked: jax.Array, coefs: jax.Array) -> jax.Array:
    """Erasure DECODE over axis 0 of (k, n) uint32 with a runtime (m, k)
    coefficient matrix (gf256.erasure_decode_matrix rows — which ranks died
    is data, not a compile-time constant, so the decode program compiles once
    and serves every failure combination). Returns (m, n) uint32."""
    from repro.kernels import rs_decode as _rsd_k

    assert stacked.ndim == 2 and stacked.dtype == jnp.uint32
    k, n = stacked.shape
    assert coefs.ndim == 2 and coefs.shape[1] == k, (coefs.shape, k)
    m = coefs.shape[0]
    tile = _rsd_k.SUBLANES * _rsd_k.BLOCK_COLS
    npad = (-n) % tile
    padded = jnp.pad(stacked, ((0, 0), (0, npad))) if npad else stacked
    rows = padded.shape[1] // _rsd_k.BLOCK_COLS
    x3 = padded.reshape(k, rows, _rsd_k.BLOCK_COLS)
    out = _rsd_k.rs_decode_pallas(x3, coefs, interpret=_interpret())
    return out.reshape(m, -1)[:, :n]


def rs_decode_arrays(arrays: list[jax.Array], coefs: jax.Array) -> jax.Array:
    """Erasure decode of arrays of any dtype/length -> (m, n) uint32 rebuilt
    shards: stack [survivors ‖ intact blobs] and apply the decode matrix."""
    views = [as_u32(a) for a in arrays]
    n = max(v.shape[0] for v in views)
    views = [_pad_to(v, n) if v.shape[0] < n else v for v in views]
    return gf256_matmul_dyn(jnp.stack(views), jnp.asarray(coefs))


# ---------------------------------------------------------------------------
# Reshard row gather (elastic N-to-M recovery)
# ---------------------------------------------------------------------------

@jax.jit
def gather_rows(src: jax.Array, idx: jax.Array) -> jax.Array:
    """out[i] = src[idx[i]] for src (rows, cols), idx (rows_out,) int32.

    The device-tier move of the elastic reshard executor: the repartition
    plan's row segments flatten into ``idx`` and one gather builds the new
    shard. Columns are lane-padded here; callers keep the original width.
    """
    assert src.ndim == 2 and idx.ndim == 1
    cols = src.shape[1]
    pad = (-cols) % _reshard_k.LANE_COLS
    padded = jnp.pad(src, ((0, 0), (0, pad))) if pad else src
    out = _reshard_k.gather_rows_pallas(padded, idx, interpret=_interpret())
    return out[:, :cols]


# ---------------------------------------------------------------------------
# Checksum
# ---------------------------------------------------------------------------

@jax.jit
def checksum(x: jax.Array) -> jax.Array:
    """Fletcher-style dual checksum of any array -> (2,) uint32."""
    u = as_u32(x)
    tile = _checksum_k.SUBLANES * _checksum_k.LANE_COLS
    # Zero padding contributes 0 to both sums (0 * idx == 0), so padding is
    # checksum-transparent even for the weighted sum.
    u = _pad_to(u, tile)
    x2 = u.reshape(-1, _checksum_k.LANE_COLS)
    return _checksum_k.checksum_pallas(x2, interpret=_interpret())


def tree_checksum(tree) -> jax.Array:
    """Combined (2,) uint32 checksum over all leaves (order-dependent mix)."""
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.zeros((2,), jnp.uint32)
    acc = jnp.zeros((2,), jnp.uint32)
    for i, leaf in enumerate(leaves):
        c = checksum(leaf)
        # Order-sensitive mix (multiplier keeps leaf order significant).
        acc = acc * jnp.uint32(1000003) + c * jnp.uint32(i + 1)
    return acc


# ---------------------------------------------------------------------------
# Quantization
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("block",))
def quantize_blockwise(x: jax.Array, block: int = 256) -> tuple[jax.Array, jax.Array]:
    """x: (n,) float -> (q (n_pad,) int8, scales (n_pad/block,) f32).

    n is padded up to a ROWS_PER_TILE*block multiple; dequantize_blockwise
    returns the padded length — callers slice back to the original size.
    """
    assert x.ndim == 1
    assert block == _quantize_k.QBLOCK, "kernel is specialized to QBLOCK"
    xpad = _pad_to(x, block * _quantize_k.ROWS_PER_TILE)
    xb = xpad.reshape(-1, block)
    q, s = _quantize_k.quantize_pallas(xb, interpret=_interpret())
    return q.reshape(-1), s.reshape(-1)


@jax.jit
def dequantize_blockwise(q: jax.Array, scale: jax.Array) -> jax.Array:
    block = q.shape[0] // scale.shape[0]
    assert block == _quantize_k.QBLOCK
    out = _quantize_k.dequantize_pallas(
        q.reshape(-1, block), scale.reshape(-1, 1), interpret=_interpret()
    )
    return out.reshape(-1)
