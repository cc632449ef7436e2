"""Pallas TPU kernels for the checkpointing hot path.

  * xor_parity — erasure-coded snapshot redundancy (encode/reconstruct)
  * checksum   — Fletcher-style snapshot validation for the handshake
  * quantize   — fused int8 snapshot/gradient compression

Each kernel ships with a pure-jnp oracle in ``ref.py`` and a jit'd public
wrapper in ``ops.py``; off a TPU backend the kernels execute in interpret mode.
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
