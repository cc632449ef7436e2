"""The full model: abstract params, train loss, prefill, decode — for all ten
architectures (decoder LM, MoE, SSM, hybrid, encoder-only, VLM backbone).

Layers are applied as a ``lax.scan`` over periods of ``cfg.layer_pattern``
with per-slot parameters stacked along a leading "layers" dim; the scan body
is rematerialized per ``cfg.remat``. This keeps HLO size O(period) instead of
O(num_layers) — essential for compiling 100-layer models on 512 devices.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks
from repro.models.common import ShardCtx, constrain, embed_spec, rmsnorm, rmsnorm_spec, softcap
from repro.sharding.spec import ParamSpec, stack_tree

LOSS_CHUNK = 512  # sequence-chunked cross-entropy (bounds logits memory)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def abstract_params(cfg: ModelConfig) -> dict[str, Any]:
    p: dict[str, Any] = {
        "embed": embed_spec(cfg.padded_vocab, cfg.d_model, cfg.param_dtype),
        "final_norm": rmsnorm_spec(cfg.d_model),
    }
    if cfg.is_encoder and cfg.frontend_stub_dim:
        p["in_proj"] = ParamSpec((cfg.frontend_stub_dim, cfg.d_model), (None, "embed"), dtype=cfg.param_dtype)
    if cfg.vision_tokens:
        p["vision_proj"] = ParamSpec((cfg.frontend_stub_dim, cfg.d_model), (None, "embed"), dtype=cfg.param_dtype)
    if not cfg.tie_embeddings:
        p["head"] = ParamSpec((cfg.d_model, cfg.padded_vocab), ("embed", "vocab"), dtype=cfg.param_dtype)
    layers = {}
    for j in range(cfg.period):
        layers[f"slot{j}"] = stack_tree(blocks.block_specs(cfg, j), cfg.num_periods)
    p["layers"] = layers
    return p


def param_count(cfg: ModelConfig) -> int:
    from repro.sharding.spec import tree_count

    return tree_count(abstract_params(cfg))


def active_param_count(cfg: ModelConfig) -> int:
    """Parameters touched per token (MoE: top-k experts only) — for MODEL_FLOPS."""
    total = param_count(cfg)
    if not cfg.has_moe:
        return total
    from repro.sharding.spec import tree_count
    from repro.models import moe as moe_mod

    moe_slots = [j for j in range(cfg.period) if blocks.slot_is_moe(cfg, j)]
    spec = moe_mod.abstract_params(cfg)
    per_slot = tree_count({k: v for k, v in spec.items() if k not in ("router", "shared")})
    inactive_frac = 1.0 - cfg.experts_per_tok / cfg.num_experts
    inactive = int(len(moe_slots) * cfg.num_periods * per_slot * inactive_frac)
    return total - inactive


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed_tokens(params: dict[str, Any], tokens: jax.Array, cfg: ModelConfig, ctx: ShardCtx | None) -> jax.Array:
    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.scale_embed:
        h = h * math.sqrt(cfg.d_model)
    if cfg.embedding_multiplier != 1.0:
        h = h * cfg.embedding_multiplier
    return constrain(h, ctx, ("batch", "seq", "act_embed"))


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def _per_layer_gather(cfg: ModelConfig, ctx: ShardCtx | None):
    """FSDP: returns a fn that constrains one period's weight slices to the
    gathered (data-replicated) view inside the scan body, pinning the
    all-gather to the loop iteration (XLA would otherwise hoist a whole-stack
    gather out of the loop). No-op for TP presets. Under remat the gather is
    recomputed in the backward pass — standard FSDP behaviour."""
    if ctx is None or cfg.sharding_preset != "fsdp" or not cfg.fsdp_gather_per_layer:
        return lambda lp: lp
    from repro.models import blocks as blocks_mod
    from repro.sharding.spec import ParamSpec

    gathered_rules = ctx.rules.override(embed=None)
    gctx = ShardCtx(ctx.mesh, gathered_rules)
    dims_tree = {
        f"slot{j}": blocks_mod.block_specs(cfg, j) for j in range(cfg.period)
    }

    def gather(layer_params):
        return jax.tree.map(
            lambda x, s: constrain(x, gctx, s.dims),
            layer_params,
            dims_tree,
            is_leaf=lambda v: isinstance(v, ParamSpec),
        )

    return gather


def forward(
    params: dict[str, Any],
    cfg: ModelConfig,
    *,
    tokens: jax.Array | None = None,        # (B, S) int32
    frames: jax.Array | None = None,        # (B, S, stub) encoder inputs
    vision: jax.Array | None = None,        # (B, V, stub) VLM patch embeds
    ctx: ShardCtx | None = None,
    collect_cache: bool = False,
) -> tuple[jax.Array, Any, jax.Array]:
    """Returns (hidden (B,S,D), cache-or-None, moe_aux)."""
    if cfg.is_encoder and cfg.frontend_stub_dim:
        assert frames is not None
        h = jnp.einsum("bse,ed->bsd", frames.astype(cfg.compute_dtype), params["in_proj"])
        h = constrain(h, ctx, ("batch", "seq", "act_embed"))
    else:
        assert tokens is not None
        h = _embed_tokens(params, tokens, cfg, ctx)

    vision_kv = None
    if cfg.vision_tokens:
        assert vision is not None, f"{cfg.name} requires vision embeddings"
        vision_kv = jnp.einsum("bve,ed->bvd", vision.astype(cfg.compute_dtype), params["vision_proj"])
        vision_kv = constrain(vision_kv, ctx, ("batch", "vision", "act_embed"))

    gather = _per_layer_gather(cfg, ctx)

    def period_body(carry, layer_params):
        h, aux = carry
        layer_params = gather(layer_params)
        caches = {}
        for j in range(cfg.period):
            h, cache_j, aux_j = blocks.apply_block(
                layer_params[f"slot{j}"], h, cfg, j, ctx=ctx, vision_kv=vision_kv
            )
            aux = aux + aux_j
            if collect_cache:
                caches[f"slot{j}"] = cache_j
        return (h, aux), caches if collect_cache else None

    body = _remat(period_body, cfg)
    aux0 = jnp.zeros((), jnp.float32)
    if cfg.scan_layers and cfg.num_periods > 1:
        (h, aux), cache = jax.lax.scan(body, (h, aux0), params["layers"])
    else:
        cache_list = []
        carry = (h, aux0)
        for i in range(cfg.num_periods):
            sliced = jax.tree.map(lambda x: x[i], params["layers"])
            carry, c = body(carry, sliced)
            cache_list.append(c)
        h, aux = carry
        cache = (
            jax.tree.map(lambda *xs: jnp.stack(xs), *cache_list) if collect_cache else None
        )

    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return h, cache, aux


def _head_weight(params: dict[str, Any], cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T  # (D, V)
    return params["head"]


def logits_fn(params: dict[str, Any], h: jax.Array, cfg: ModelConfig, ctx: ShardCtx | None) -> jax.Array:
    w = _head_weight(params, cfg)
    logits = jnp.einsum("...d,dv->...v", h, w).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    logits = softcap(logits, cfg.final_softcap)
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    dims = ("batch", "seq", "vocab") if h.ndim == 3 else ("batch", "vocab")
    return constrain(logits, ctx, dims)


# ---------------------------------------------------------------------------
# Loss (sequence-chunked cross-entropy, gather-free on a sharded vocab)
# ---------------------------------------------------------------------------

def _ce_chunk(params, h_c, labels_c, mask_c, cfg, ctx):
    logits = logits_fn(params, h_c, cfg, ctx)  # (B, Sc, V_pad) f32
    lse = jax.nn.logsumexp(logits, axis=-1)
    # Gather-free label logit on a vocab-sharded tensor: iota+select fuse into
    # the reduction (a one-hot einsum would materialize a (B,S,V) f32 temp).
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    label_logit = jnp.sum(
        jnp.where(iota == labels_c[..., None], logits, 0.0), axis=-1
    )
    nll = (lse - label_logit) * mask_c
    return nll.sum(), mask_c.sum()


def loss_fn(
    params: dict[str, Any],
    batch: dict[str, jax.Array],
    cfg: ModelConfig,
    ctx: ShardCtx | None = None,
    aux_weight: float = 0.01,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Mean next-token (or masked-prediction) cross-entropy + MoE aux loss."""
    h, _, aux = forward(
        params, cfg,
        tokens=batch.get("tokens"),
        frames=batch.get("frames"),
        vision=batch.get("vision"),
        ctx=ctx,
    )
    labels = batch["labels"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    mask = mask.astype(jnp.float32)

    S = h.shape[1]
    chunk = min(LOSS_CHUNK, S)
    n = S // chunk if S % chunk == 0 else 1
    if n == 1:
        chunk = S
    total, denom = jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)
    ce = jax.checkpoint(partial(_ce_chunk, cfg=cfg, ctx=ctx)) if cfg.remat != "none" else partial(_ce_chunk, cfg=cfg, ctx=ctx)
    for i in range(n):
        sl = slice(i * chunk, (i + 1) * chunk)
        t, d = ce(params, h[:, sl], labels[:, sl], mask[:, sl])
        total, denom = total + t, denom + d
    loss = total / jnp.maximum(denom, 1.0)
    moe_aux = aux / max(cfg.num_layers, 1)
    full = loss + aux_weight * moe_aux
    return full, {"ce_loss": loss, "moe_aux": moe_aux, "tokens": denom}


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode
# ---------------------------------------------------------------------------

def prefill(
    params: dict[str, Any],
    cfg: ModelConfig,
    *,
    tokens: jax.Array | None = None,
    frames: jax.Array | None = None,
    vision: jax.Array | None = None,
    ctx: ShardCtx | None = None,
) -> tuple[jax.Array, Any]:
    """Returns (last-position logits (B, V), cache). Encoder: (all logits, None)."""
    h, cache, _ = forward(
        params, cfg, tokens=tokens, frames=frames, vision=vision, ctx=ctx,
        collect_cache=not cfg.is_encoder,
    )
    if cfg.is_encoder:
        return logits_fn(params, h, cfg, ctx), None
    logits = logits_fn(params, h[:, -1, :], cfg, ctx)
    return logits, cache


def decode_step(
    params: dict[str, Any],
    cache: Any,
    token: jax.Array,    # (B,) int32 — the token at position `pos`
    pos: jax.Array,      # scalar int32
    cfg: ModelConfig,
    *,
    ctx: ShardCtx | None = None,
) -> tuple[jax.Array, Any]:
    """One decode step: returns (logits (B, V) for position pos, new cache)."""
    assert not cfg.is_encoder, "encoder-only archs have no decode step"
    h = _embed_tokens(params, token[:, None], cfg, ctx)  # (B, 1, D)
    gather = _per_layer_gather(cfg, ctx)

    def period_body(h, xs):
        layer_params, cache_in = xs
        layer_params = gather(layer_params)
        cache_out = {}
        for j in range(cfg.period):
            h, c = blocks.decode_block(
                layer_params[f"slot{j}"], h, cache_in[f"slot{j}"], pos, cfg, j, ctx=ctx
            )
            cache_out[f"slot{j}"] = c
        return h, cache_out

    if cfg.scan_layers and cfg.num_periods > 1:
        h, new_cache = jax.lax.scan(period_body, h, (params["layers"], cache))
    else:
        outs = []
        for i in range(cfg.num_periods):
            sliced = jax.tree.map(lambda x: x[i], (params["layers"], cache))
            h, c = period_body(h, sliced)
            outs.append(c)
        new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, h[:, 0, :], cfg, ctx)
    return logits, new_cache


def abstract_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict[str, Any]:
    """ParamSpec pytree for the decode cache (dry-run stand-ins + allocation)."""
    out = {}
    for j in range(cfg.period):
        out[f"slot{j}"] = stack_tree(blocks.block_cache_spec(cfg, j, batch, max_seq), cfg.num_periods)
    return out
