"""Attention: GQA/MQA, sliding-window, softcap, cross-attention, KV-cache decode.

Prefill/training uses a blockwise (memory-efficient, flash-style) formulation:
a static python loop over query chunks, each running an online-softmax
``lax.scan`` over its causally-reachable KV chunks. Sliding-window layers skip
KV chunks outside the window entirely (a real FLOP saving, not just masking),
which is what makes 32k-prefill feasible for the local-attention archs.

Decode attends a single query over the full cache with a position mask; for
``long_500k`` the cache's sequence dim is sharded over "data" and XLA realizes
a distributed (flash-decode-style) softmax via small all-reduces.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ShardCtx, apply_rope, constrain, softcap
from repro.sharding.spec import ParamSpec

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def abstract_params(cfg: ModelConfig, cross: bool = False) -> dict[str, ParamSpec]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = cfg.param_dtype
    return {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim"), dtype=dt),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), dtype=dt),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), dtype=dt),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed"), dtype=dt),
    }


# ---------------------------------------------------------------------------
# Blockwise attention core
# ---------------------------------------------------------------------------

def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, KV, D) -> (B, S, KV*n_rep, D). Head h reads kv head h // n_rep.

    TP-friendly GQA: expanding KV to the full head count keeps one uniformly
    model-sharded head axis through the whole attention computation (the
    grouped (KV, G) layout forces {8,2}-style split shardings that GSPMD can
    only fix with involuntary full rematerializations).
    """
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def _chunk_attn_scores(q, k, scale, cap):
    # q: (B, Bq, H, D)  k: (B, Bk, H, D) -> scores (B, H, Bq, Bk) f32
    s = jnp.einsum("bqhd,bshd->bhqs", q, k, preferred_element_type=jnp.float32)
    return softcap(s * scale, cap)


def _mask(q_pos, k_pos, causal: bool, window: int | None):
    # (Bq, Bk) boolean validity mask.
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


def blockwise_attention(
    q: jax.Array,          # (B, Sq, H, D)
    k: jax.Array,          # (B, Sk, KV, D)
    v: jax.Array,          # (B, Sk, KV, D)
    *,
    causal: bool,
    window: int | None = None,
    attn_cap: float | None = None,
    q_offset: int = 0,
    q_chunk: int = 1024,
    k_chunk: int = 1024,
    scale: float | None = None,
) -> jax.Array:
    """Online-softmax blockwise attention. Returns (B, Sq, H, D). Scores are
    q.k times ``scale`` (default 1/sqrt(D)).

    k/v arrive with KV heads and are expanded to H (repeat_kv) so the head
    axis shards uniformly over "model"."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    k = repeat_kv(k, H // KV)
    v = repeat_kv(v, H // KV)
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    # Pad to chunk multiples (static).
    sq_pad = (-Sq) % q_chunk
    sk_pad = (-Sk) % k_chunk
    if sq_pad:
        q = jnp.pad(q, ((0, 0), (0, sq_pad), (0, 0), (0, 0)))
    if sk_pad:
        k = jnp.pad(k, ((0, 0), (0, sk_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad), (0, 0), (0, 0)))
    nq, nk = (Sq + sq_pad) // q_chunk, (Sk + sk_pad) // k_chunk

    k_ch = k.reshape(B, nk, k_chunk, H, D)
    v_ch = v.reshape(B, nk, k_chunk, H, D)

    outs = []
    for i in range(nq):
        qi = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, axis=1)
        q_pos = q_offset + i * q_chunk + jnp.arange(q_chunk)
        # Static chunk range reachable from this query chunk.
        if causal:
            j_hi = min(nk, (q_offset + (i + 1) * q_chunk + k_chunk - 1) // k_chunk)
        else:
            j_hi = nk
        if window is not None:
            j_lo = max(0, (q_offset + i * q_chunk - window) // k_chunk)
        else:
            j_lo = 0
        j_hi = max(j_hi, j_lo + 1)

        def kv_step(carry, inputs):
            m_prev, l_prev, acc = carry
            kj, vj, j = inputs
            k_pos = j * k_chunk + jnp.arange(k_chunk)
            s = _chunk_attn_scores(qi, kj, scale, attn_cap)  # (B,H,Bq,Bk)
            valid = _mask(q_pos, k_pos, causal, window)
            # Padded KV rows (beyond Sk) are invalid.
            valid &= (k_pos < Sk)[None, :]
            s = jnp.where(valid[None, None], s, NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhqs,bshd->bhqd", p.astype(vj.dtype), vj,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, D), jnp.float32)
        ks = k_ch[:, j_lo:j_hi]
        vs = v_ch[:, j_lo:j_hi]
        js = jnp.arange(j_lo, j_hi)
        (m, l, acc), _ = jax.lax.scan(
            kv_step,
            (m0, l0, a0),
            (jnp.moveaxis(ks, 1, 0), jnp.moveaxis(vs, 1, 0), js),
        )
        oi = acc / jnp.maximum(l[..., None], 1e-37)  # (B,H,Bq,D)
        outs.append(jnp.moveaxis(oi, 2, 1).astype(v.dtype))  # (B,Bq,H,D)

    out = jnp.concatenate(outs, axis=1)[:, :Sq]
    return out


# ---------------------------------------------------------------------------
# Full-sequence (train/prefill) layer application
# ---------------------------------------------------------------------------

def apply(
    params: dict[str, jax.Array],
    x: jax.Array,                 # (B, S, d_model)
    cfg: ModelConfig,
    *,
    kind: str,                    # attn | local | cross
    ctx: ShardCtx | None = None,
    kv_src: jax.Array | None = None,   # cross-attn source (B, S_kv, d_model)
    positions: jax.Array | None = None,
    q_offset: int = 0,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Returns (output, kv) where kv holds this layer's k/v for cache building."""
    B, S, _ = x.shape
    if positions is None:
        positions = q_offset + jnp.arange(S)

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"]).astype(cfg.compute_dtype)
    src = x if kv_src is None else kv_src
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"]).astype(cfg.compute_dtype)
    v = jnp.einsum("bsd,dhk->bshk", src, params["wv"]).astype(cfg.compute_dtype)
    q = constrain(q, ctx, ("batch", "seq", "heads", None))
    k = constrain(k, ctx, ("batch", "seq", "kv_heads", None))
    v = constrain(v, ctx, ("batch", "seq", "kv_heads", None))

    causal = kind != "cross" and not cfg.is_encoder
    if kind != "cross" and cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    window = cfg.sliding_window if kind == "local" else None

    o = blockwise_attention(
        q, k, v,
        causal=causal,
        window=window,
        attn_cap=cfg.attn_softcap,
        q_offset=q_offset,
        q_chunk=cfg.attn_chunk,
        k_chunk=cfg.attn_chunk,
        scale=cfg.attention_multiplier,
    )
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"]).astype(x.dtype)
    return constrain(out, ctx, ("batch", "seq", "act_embed")), {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Decode step (single token, KV cache)
# ---------------------------------------------------------------------------

def decode_cache_spec(cfg: ModelConfig, batch: int, max_seq: int, kind: str) -> dict[str, ParamSpec]:
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    seq = cfg.vision_tokens if kind == "cross" else max_seq
    return {
        "k": ParamSpec((batch, seq, kv, hd), ("batch", "kv_seq", "kv_heads", None), dtype=cfg.compute_dtype, init="zeros"),
        "v": ParamSpec((batch, seq, kv, hd), ("batch", "kv_seq", "kv_heads", None), dtype=cfg.compute_dtype, init="zeros"),
    }


def decode(
    params: dict[str, jax.Array],
    x: jax.Array,                  # (B, 1, d_model)
    cache: dict[str, jax.Array],   # k/v: (B, S_max, KV, D)
    pos: jax.Array,                # scalar int32: index of the new token
    cfg: ModelConfig,
    *,
    kind: str,
    ctx: ShardCtx | None = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"]).astype(cfg.compute_dtype)

    if kind == "cross":
        # Cross KV was filled at prefill; it is static during decode.
        k, v = cache["k"], cache["v"]
        new_cache = cache
        valid = jnp.ones((k.shape[1],), bool)
    else:
        knew = jnp.einsum("bsd,dhk->bshk", x, params["wk"]).astype(cfg.compute_dtype)
        vnew = jnp.einsum("bsd,dhk->bshk", x, params["wv"]).astype(cfg.compute_dtype)
        if cfg.use_rope:
            positions = pos[None, None] if pos.ndim == 0 else pos
            q = apply_rope(q, positions, cfg.rope_theta)
            knew = apply_rope(knew, positions, cfg.rope_theta)
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], knew, pos, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], vnew, pos, axis=1)
        new_cache = {"k": k, "v": v}
        idx = jnp.arange(k.shape[1])
        valid = idx <= pos
        if kind == "local":
            valid &= idx > pos - cfg.sliding_window

    KV, D = k.shape[2], k.shape[3]
    H = q.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32)
    s = s * cfg.attention_multiplier if cfg.attention_multiplier is not None else s / math.sqrt(D)
    s = softcap(s, cfg.attn_softcap)
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    o = o.reshape(B, 1, H, D).astype(cfg.compute_dtype)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"]).astype(x.dtype)
    return constrain(out, ctx, ("batch", None, "act_embed")), new_cache
