"""Compile the checkpoint hot path for a TPU v5e that is described, not attached.

Interpret mode (every other kernel test) runs a kernel's body but never asks
the Pallas TPU lowering about its block shapes or its VMEM use; this file
does. Each of the seven Pallas kernels is compiled at a real width for one
v5e chip, and one fused device-tier snapshot program for a 2x2 v5e mesh.
Nothing runs: a pass says the chip's compiler accepts the program, not that
it is fast or right.

The topology is described inside a module-scoped fixture (never at import),
and all of it stays in this one file, so only the xdist worker that is given
this file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

WORDS = 1 << 20  # 4 MiB of uint32 per operand row


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        from jax.experimental import topologies

        try:
            described = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_ops(monkeypatch):
    """``repro.kernels.ops`` with interpret mode off (this process's default
    backend is the CPU) and the persistent compile cache off, which cannot
    read back what it would store for a described chip."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()  # no trace made in interpret mode may be reused
    yield ops
    jax.clear_caches()  # and none made here may leak into later CPU tests
    jax.config.update("jax_enable_compilation_cache", enabled)


# name -> (call on ops, operand (shape, dtype) list)
KERNELS = {
    "xor_reduce": (lambda ops: ops.xor_reduce, [((4, WORDS), jnp.uint32)]),
    "rs_encode": (
        lambda ops: lambda x: ops.gf256_matmul(x, ((1, 1, 1, 1), (1, 2, 4, 8))),
        [((4, WORDS), jnp.uint32)],
    ),
    "rs_decode": (
        lambda ops: ops.gf256_matmul_dyn,
        [((4, WORDS), jnp.uint32), ((2, 4), jnp.uint32)],
    ),
    "checksum": (lambda ops: ops.checksum, [((4 * WORDS,), jnp.uint32)]),
    "quantize": (lambda ops: ops.quantize_blockwise, [((4 * WORDS,), jnp.float32)]),
    "dequantize": (
        lambda ops: ops.dequantize_blockwise,
        [((4 * WORDS,), jnp.int8), ((4 * WORDS // 256,), jnp.float32)],
    ),
    "gather_rows": (
        lambda ops: ops.gather_rows,
        [((4096, 1024), jnp.float32), ((2048,), jnp.int32)],
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, tpu_ops):
    make, operands = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in operands]
    hlo = jax.jit(make(tpu_ops)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, f"{name}: no Pallas kernel in the compiled program"


@pytest.mark.parametrize("codec", ["xor", "rs"])
def test_snapshot_program_compiles_for_v5e_mesh(codec, topo, tpu_ops):
    """The fused snapshot program of a ZeRO-1-like state (f32 and bf16
    leaves split over ``data``, one replicated leaf) on a data=4 mesh: ring
    permutes between chips and the parity kernel on each."""
    from repro.core.device_tier import build_snapshot_program
    from repro.sharding.mesh import make_mesh

    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)
    sds = {
        "m": jax.ShapeDtypeStruct((4096, 1024), jnp.float32),
        "p": jax.ShapeDtypeStruct((2048, 1024), jnp.bfloat16),
        "rep": jax.ShapeDtypeStruct((1024,), jnp.float32),
    }
    ps = {"m": P("data", None), "p": P("data", "model"), "rep": P()}
    prog = build_snapshot_program(
        mesh, sds, ps, validate=False, include_own_copy=False,
        codec=codec, parity_group=2, rs_parity=2,
    )
    placed = {
        k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, ps[k]))
        for k, s in sds.items()
    }
    hlo = jax.jit(prog.snapshot_fn).lower(placed).compile().as_text()
    assert "collective-permute" in hlo
    assert "tpu_custom_call" in hlo
    assert prog.pcie_bytes > 0
