"""One transformer-stack "slot": pre-norm mixer (attn/local/cross/mamba) +
optional FFN (dense MLP or MoE). A period = cfg.layer_pattern of slots; the
model scans over periods with per-slot parameters stacked."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, mlp, moe, ssm
from repro.models.common import ShardCtx, rmsnorm, rmsnorm_spec
from repro.sharding.spec import ParamSpec


def slot_is_moe(cfg: ModelConfig, slot: int) -> bool:
    return cfg.has_moe and cfg.d_ff > 0 and (slot % cfg.moe_every == cfg.moe_every - 1)


def _residual(h: jax.Array, branch: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.residual_multiplier != 1.0:
        branch = branch * cfg.residual_multiplier
    return h + branch


def block_specs(cfg: ModelConfig, slot: int) -> dict[str, Any]:
    kind = cfg.layer_pattern[slot]
    specs: dict[str, Any] = {"ln1": rmsnorm_spec(cfg.d_model)}
    if kind == "mamba":
        specs["mixer"] = ssm.abstract_params(cfg)
    else:
        specs["mixer"] = attention.abstract_params(cfg, cross=(kind == "cross"))
    if cfg.d_ff > 0:
        specs["ln2"] = rmsnorm_spec(cfg.d_model)
        specs["ffn"] = moe.abstract_params(cfg) if slot_is_moe(cfg, slot) else mlp.abstract_params(cfg)
    return specs


def apply_block(
    params: dict[str, Any],
    h: jax.Array,
    cfg: ModelConfig,
    slot: int,
    *,
    ctx: ShardCtx | None,
    vision_kv: jax.Array | None = None,
    q_offset: int = 0,
) -> tuple[jax.Array, dict[str, Any], jax.Array]:
    """Full-sequence block application. Returns (h, cache_entry, moe_aux)."""
    kind = cfg.layer_pattern[slot]
    aux = jnp.zeros((), jnp.float32)

    hin = rmsnorm(h, params["ln1"], cfg.norm_eps)
    if kind == "mamba":
        with jax.named_scope("ssm"):
            mixed, cache = ssm.apply(params["mixer"], hin, cfg, ctx=ctx)
    else:
        with jax.named_scope("attention"):
            mixed, cache = attention.apply(
                params["mixer"], hin, cfg, kind=kind, ctx=ctx,
                kv_src=vision_kv if kind == "cross" else None, q_offset=q_offset,
            )
    h = _residual(h, mixed, cfg)

    if cfg.d_ff > 0:
        hin = rmsnorm(h, params["ln2"], cfg.norm_eps)
        if slot_is_moe(cfg, slot):
            out, aux = moe.apply(params["ffn"], hin, cfg, ctx=ctx)
        else:
            out = mlp.apply(params["ffn"], hin, cfg, ctx=ctx)
        h = _residual(h, out, cfg)
    return h, cache, aux


def decode_block(
    params: dict[str, Any],
    h: jax.Array,
    cache: dict[str, Any],
    pos: jax.Array,
    cfg: ModelConfig,
    slot: int,
    *,
    ctx: ShardCtx | None,
) -> tuple[jax.Array, dict[str, Any]]:
    kind = cfg.layer_pattern[slot]
    hin = rmsnorm(h, params["ln1"], cfg.norm_eps)
    if kind == "mamba":
        with jax.named_scope("ssm"):
            mixed, new_cache = ssm.decode(params["mixer"], hin, cache, cfg, ctx=ctx)
    else:
        with jax.named_scope("attention"):
            mixed, new_cache = attention.decode(params["mixer"], hin, cache, pos, cfg, kind=kind, ctx=ctx)
    h = _residual(h, mixed, cfg)

    if cfg.d_ff > 0:
        hin = rmsnorm(h, params["ln2"], cfg.norm_eps)
        if slot_is_moe(cfg, slot):
            out, _ = moe.apply(params["ffn"], hin, cfg, ctx=ctx, num_groups=1)
        else:
            out = mlp.apply(params["ffn"], hin, cfg, ctx=ctx)
        h = _residual(h, out, cfg)
    return h, new_cache


def block_cache_spec(cfg: ModelConfig, slot: int, batch: int, max_seq: int) -> dict[str, ParamSpec]:
    kind = cfg.layer_pattern[slot]
    if kind == "mamba":
        return ssm.cache_spec(cfg, batch)
    return attention.decode_cache_spec(cfg, batch, max_seq, kind)
