"""Sharding rules, ZeRO-1 spec derivation, HLO collective parsing, and a
small-mesh (8 virtual device) lower/compile of the real step functions."""

import dataclasses
import os
import subprocess
import sys
import textwrap
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.mesh import abstract_mesh, make_mesh
from repro.sharding.axes import (
    FSDP_RULES,
    TP_RULES,
    rules_for_shape,
    spec_to_pspec,
    zero1_pspec,
)
from repro.sharding.spec import ParamSpec


MESH = abstract_mesh(("data", 16), ("model", 16))
POD_MESH = abstract_mesh(("pod", 2), ("data", 16), ("model", 16))


def test_tp_param_spec():
    s = ParamSpec((4096, 16384), ("embed", "mlp"))
    assert spec_to_pspec(s, TP_RULES, MESH) == P(None, "model")


def test_fsdp_param_spec():
    s = ParamSpec((4096, 16384), ("embed", "mlp"))
    assert spec_to_pspec(s, FSDP_RULES, MESH) == P("data", "model")
    assert spec_to_pspec(s, FSDP_RULES, POD_MESH) == P(("pod", "data"), "model")


def test_uneven_dims_stay_replicated():
    # 8 kv heads cannot shard 16 ways -> replicated, NOT uneven.
    s = ParamSpec((2048, 8, 64), ("embed", "kv_heads", "head_dim"))
    assert spec_to_pspec(s, TP_RULES, MESH) == P()


def test_zero1_shards_largest_replicated_dim():
    s = ParamSpec((4096, 16384), ("embed", "mlp"))
    ps = zero1_pspec(s, TP_RULES, MESH)
    assert ps == P("data", "model")


def test_zero1_respects_divisibility():
    # Stacked dim 9 (jamba periods) is not divisible by 16 -> skip to a
    # dividing dim or stay replicated.
    s = ParamSpec((9, 256), ("layers", "ssm_heads"))
    ps = zero1_pspec(s, TP_RULES, MESH)
    assert ps in (P(None, "model"), P())  # heads already sharded; 9 stays whole
    s2 = ParamSpec((9, 48), ("layers", None))
    ps2 = zero1_pspec(s2, TP_RULES, MESH)
    assert ps2 == P(None, "data")  # 48 % 16 == 0


def test_zero1_never_duplicates_axes():
    s = ParamSpec((4096, 8, 128), ("embed", "kv_heads", "head_dim"))
    ps = zero1_pspec(s, FSDP_RULES, POD_MESH)
    used = []
    for e in ps:
        if e is None:
            continue
        used.extend([e] if isinstance(e, str) else list(e))
    assert len(used) == len(set(used))


def test_decode_rules_no_duplicate_model_axis():
    rules = rules_for_shape(TP_RULES, "decode", 128)
    spec = ParamSpec((128, 32768, 16, 256), ("batch", "kv_seq", "kv_heads", None))
    ps = spec_to_pspec(spec, rules, MESH)
    assert ps == P("data", "model")


def test_long_decode_rules():
    rules = rules_for_shape(TP_RULES, "decode", 1)
    spec = ParamSpec((1, 524288, 8, 128), ("batch", "kv_seq", "kv_heads", None))
    ps = spec_to_pspec(spec, rules, MESH)
    assert ps == P(None, ("data", "model"))


# ---------------------------------------------------------------------------
# HLO collective analysis
# ---------------------------------------------------------------------------

def test_hlo_parser_on_real_module():
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.sharding.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        def f(x, w):
            y = x @ w
            return jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh, P("data", None))).sum()
        xs = jax.ShapeDtypeStruct((64, 32), jnp.float32)
        ws = jax.ShapeDtypeStruct((32, 16), jnp.float32)
        c = jax.jit(f, in_shardings=(
            NamedSharding(mesh, P("data", None)),
            NamedSharding(mesh, P(None, "model")))).lower(xs, ws).compile()
        print(c.as_text())
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    from repro.utils.hlo import analyze_hlo_collectives

    stats = analyze_hlo_collectives(out.stdout)
    assert stats.count_by_kind.get("all-gather", 0) >= 1
    # all-gather of the (32,16) f32 weight shard: operand 32*8*4 = 1KiB
    assert stats.bytes_by_kind["all-gather"] >= 1024


def test_hlo_while_trip_weighting():
    hlo = textwrap.dedent(
        """
        HloModule test
        %body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
          %ar = f32[8]{0} all-reduce(%x), replica_groups={}, to_apply=%add
        }
        %cond.1 (p: (s32[], f32[8])) -> pred[] {
          %lt = pred[] compare(%a, %b), direction=LT
        }
        ENTRY %main (p0: f32[8]) -> f32[8] {
          %w = (s32[], f32[8]) while(%init), condition=%cond.1, body=%body.1
          %ar2 = f32[8]{0} all-reduce(%p0), replica_groups={}, to_apply=%add
        }
        """
    )
    from repro.utils.hlo import analyze_hlo_collectives

    stats = analyze_hlo_collectives(hlo, while_trip=10)
    # in-loop all-reduce weighted 10x, entry one 1x: 32 * 10 + 32
    assert stats.bytes_by_kind["all-reduce"] == 32 * 10 + 32
    assert stats.static_bytes_by_kind["all-reduce"] == 64
    assert stats.n_while == 1


# ---------------------------------------------------------------------------
# small-mesh lower+compile of the real step builders (fast dry-run analogue)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape_kind", ["train_4k", "decode_32k"])
def test_small_mesh_compile_reduced(shape_kind):
    """Exercise build_step end-to-end on a tiny mesh with a reduced config and
    scaled-down shape (the 512-device version runs in the dry-run)."""
    from repro.configs import CONFIGS, SHAPES
    from repro.launch.steps import build_step

    cfg = CONFIGS["llama3.2-1b"].reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    small = dataclasses.replace(SHAPES[shape_kind], seq_len=64, global_batch=2)
    # SHAPES is one shared dict across modules; patching it here patches the
    # view build_step reads.
    with mock.patch.dict(SHAPES, {shape_kind: small}):
        bundle = build_step(cfg, shape_kind, mesh)
        jitted = jax.jit(
            bundle.fn,
            in_shardings=bundle.in_shardings,
            out_shardings=bundle.out_shardings,
            donate_argnums=bundle.donate_argnums,
        )
        compiled = jitted.lower(*bundle.args_sds).compile()
        assert compiled.cost_analysis() is not None


def test_spec_dedupe_across_dims():
    """A mesh axis claimed by an earlier dim is dropped from later dims."""
    s = ParamSpec((16, 8192, 24576), ("experts", "embed", "mlp"))
    rules = FSDP_RULES.override(experts="data")
    ps = spec_to_pspec(s, rules, MESH)
    assert ps == P("data", None, "model")  # embed's ("pod","data") deduped


def test_ep_rules_on_model():
    from repro.configs import CONFIGS
    from repro.models import build_model

    m = build_model(CONFIGS["jamba-1.5-large-398b"].with_(moe_mode="ep"))
    assert m.rules.get("experts") == "data"
    m2 = build_model(CONFIGS["jamba-1.5-large-398b"])
    assert m2.rules.get("experts") is None
