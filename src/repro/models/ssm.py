"""Mamba2 SSD (state-space duality) block — chunked dual form for train/prefill,
O(1)-state recurrence for decode.

TPU adaptation: the chunked SSD algorithm is exactly the MXU-friendly
formulation (intra-chunk quadratic einsums + inter-chunk ``lax.scan`` over
chunk states), so it maps to TPU without a custom kernel; chunk length is the
VMEM-tiling knob (default 128 keeps the (Q,Q,H) decay tensor modest).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ShardCtx, constrain
from repro.sharding.spec import ParamSpec

CHUNK = 128


def conv_dim(cfg: ModelConfig) -> int:
    # x (d_inner) + B (N) + C (N), single SSD group.
    return cfg.d_inner + 2 * cfg.ssm_state


def in_proj_dim(cfg: ModelConfig) -> int:
    # z (d_inner) + xBC (conv_dim) + dt (heads)
    return cfg.d_inner + conv_dim(cfg) + cfg.ssm_heads


def abstract_params(cfg: ModelConfig) -> dict[str, ParamSpec]:
    # in_proj is split into z / xBC / dt projections so each output dim has a
    # clean shard boundary on the "model" axis (a fused in_proj would slice
    # across shards and force GSPMD reshards).
    d, dt = cfg.d_model, cfg.param_dtype
    H = cfg.ssm_heads
    return {
        "z_proj": ParamSpec((d, cfg.d_inner), ("embed", "ssm_inner"), dtype=dt),
        "xBC_proj": ParamSpec((d, conv_dim(cfg)), ("embed", "ssm_inner"), dtype=dt),
        "dt_proj": ParamSpec((d, H), ("embed", "ssm_heads"), dtype=dt),
        "conv_w": ParamSpec((conv_dim(cfg), cfg.ssm_conv), ("ssm_inner", "conv"), dtype=dt, init="normal", scale=0.1),
        "conv_b": ParamSpec((conv_dim(cfg),), ("ssm_inner",), dtype=jnp.float32, init="zeros"),
        "A_log": ParamSpec((H,), ("ssm_heads",), dtype=jnp.float32, init="zeros"),
        "dt_bias": ParamSpec((H,), ("ssm_heads",), dtype=jnp.float32, init="zeros"),
        "D": ParamSpec((H,), ("ssm_heads",), dtype=jnp.float32, init="ones"),
        "norm": ParamSpec((cfg.d_inner,), ("ssm_inner",), dtype=jnp.float32, init="zeros"),
        "out_proj": ParamSpec((cfg.d_inner, d), ("ssm_inner", "embed"), dtype=dt),
    }


def cache_spec(cfg: ModelConfig, batch: int) -> dict[str, ParamSpec]:
    H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    return {
        "conv": ParamSpec((batch, cfg.ssm_conv - 1, conv_dim(cfg)), ("batch", None, "ssm_inner"), dtype=cfg.compute_dtype, init="zeros"),
        "state": ParamSpec((batch, H, P, N), ("batch", "ssm_heads", None, None), dtype=jnp.float32, init="zeros"),
    }


def _project(params: dict[str, jax.Array], x: jax.Array):
    z = jnp.einsum("...d,de->...e", x, params["z_proj"])
    xBC = jnp.einsum("...d,de->...e", x, params["xBC_proj"])
    dt = jnp.einsum("...d,de->...e", x, params["dt_proj"])
    return z, xBC, dt


def _causal_conv(xBC: jax.Array, w: jax.Array, b: jax.Array, history: jax.Array | None = None) -> jax.Array:
    """Depthwise causal conv along seq. xBC: (B, L, C); w: (C, K)."""
    B, L, C = xBC.shape
    K = w.shape[1]
    if history is None:
        history = jnp.zeros((B, K - 1, C), xBC.dtype)
    xp = jnp.concatenate([history, xBC], axis=1)  # (B, L+K-1, C)
    out = jnp.zeros((B, L, C), jnp.float32)
    for i in range(K):  # K=4: tiny static unroll, fuses into one kernel
        out = out + xp[:, i : i + L, :].astype(jnp.float32) * w[:, i].astype(jnp.float32)
    return jax.nn.silu(out + b).astype(xBC.dtype)


def _gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    return (y * jax.lax.rsqrt(var + eps) * (1.0 + scale)).astype(z.dtype)


def apply(
    params: dict[str, jax.Array],
    x: jax.Array,  # (B, L, d_model)
    cfg: ModelConfig,
    ctx: ShardCtx | None = None,
    chunk: int = CHUNK,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Chunked SSD forward. Returns (out, final_cache)."""
    Bsz, L, _ = x.shape
    H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q

    z, xBC_raw, dt = _project(params, x)
    xBC_raw = constrain(xBC_raw, ctx, ("batch", "seq", "ssm_inner"))
    z = constrain(z, ctx, ("batch", "seq", "ssm_inner"))
    xBC = _causal_conv(xBC_raw, params["conv_w"], params["conv_b"])

    xs = xBC[..., : cfg.d_inner].reshape(Bsz, L, H, P)
    Bm = xBC[..., cfg.d_inner : cfg.d_inner + N]  # (B, L, N) single group
    Cm = xBC[..., cfg.d_inner + N :]

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B, L, H)
    A = -jnp.exp(params["A_log"])  # (H,) negative
    dA = dt * A  # (B, L, H), <= 0

    # Chunked views.
    xc = xs.reshape(Bsz, nc, Q, H, P)
    Bc = Bm.reshape(Bsz, nc, Q, N).astype(jnp.float32)
    Cc = Cm.reshape(Bsz, nc, Q, N).astype(jnp.float32)
    dtc = dt.reshape(Bsz, nc, Q, H)
    dAc = dA.reshape(Bsz, nc, Q, H)
    dAcs = jnp.cumsum(dAc, axis=2)  # inclusive cumsum within chunk

    # ---- intra-chunk (quadratic, masked decay matrix) --------------------
    seg = dAcs[:, :, :, None, :] - dAcs[:, :, None, :, :]  # (B,nc,Q,Q,H) = a_i - a_j
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    # Mask before the exp: above the diagonal seg = a_i - a_j > 0 grows with
    # the chunk length and overflows f32, and where(mask, inf, 0) has a NaN
    # gradient even though its value is 0.
    Ldecay = jnp.exp(jnp.where(mask[None, None, :, :, None], seg, -jnp.inf))
    att = jnp.einsum("bcln,bcsn->bcls", Cc, Bc)  # (B,nc,Q,Q)
    xdt = xc.astype(jnp.float32) * dtc[..., None]  # (B,nc,Q,H,P)
    y_diag = jnp.einsum("bcls,bclsh,bcshp->bclhp", att, Ldecay, xdt)

    # ---- chunk states + inter-chunk recurrence ---------------------------
    decay_states = jnp.exp(dAcs[:, :, -1:, :] - dAcs)  # (B,nc,Q,H)
    states = jnp.einsum("bcsn,bcsh,bcshp->bchpn", Bc, decay_states * dtc, xc.astype(jnp.float32))
    chunk_decay = jnp.exp(dAcs[:, :, -1, :])  # (B,nc,H)

    def chunk_step(carry, inp):
        s_c, d_c = inp  # (B,H,P,N), (B,H)
        new = carry * d_c[:, :, None, None] + s_c
        return new, carry  # emit the state *entering* this chunk

    init = jnp.zeros((Bsz, H, P, N), jnp.float32)
    final_state, prev_states = jax.lax.scan(
        chunk_step, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # (B,nc,H,P,N)

    state_decay_out = jnp.exp(dAcs)  # (B,nc,Q,H)
    y_off = jnp.einsum("bcln,bchpn,bclh->bclhp", Cc, prev_states, state_decay_out)

    y = (y_diag + y_off).reshape(Bsz, L, H, P)
    y = y + params["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(Bsz, L, cfg.d_inner)
    y = _gated_norm(y, z, params["norm"], cfg.norm_eps)
    out = jnp.einsum("ble,ed->bld", y, params["out_proj"]).astype(x.dtype)
    out = constrain(out, ctx, ("batch", "seq", "act_embed"))

    cache = {
        "conv": xBC_raw[:, -(cfg.ssm_conv - 1) :, :].astype(cfg.compute_dtype),
        "state": final_state,
    }
    return out, cache


def decode(
    params: dict[str, jax.Array],
    x: jax.Array,  # (B, 1, d_model)
    cache: dict[str, jax.Array],
    cfg: ModelConfig,
    ctx: ShardCtx | None = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Single-token recurrent update: state' = state * exp(dt*A) + dt * B (x) ."""
    Bsz = x.shape[0]
    H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state

    z, xBC_raw, dt = _project(params, x[:, 0])  # (B, ·)

    # Causal conv at one position using the rolling history.
    hist = cache["conv"]  # (B, K-1, C)
    w, b = params["conv_w"], params["conv_b"]
    K = w.shape[1]
    full = jnp.concatenate([hist, xBC_raw[:, None, :].astype(hist.dtype)], axis=1)  # (B,K,C)
    conv_out = jnp.einsum("bkc,ck->bc", full.astype(jnp.float32), w.astype(jnp.float32))
    xBC = jax.nn.silu(conv_out + b)

    xt = xBC[:, : cfg.d_inner].reshape(Bsz, H, P)
    Bt = xBC[:, cfg.d_inner : cfg.d_inner + N]
    Ct = xBC[:, cfg.d_inner + N :]

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,H)
    A = -jnp.exp(params["A_log"])
    dA = jnp.exp(dt * A)  # (B,H)

    state = cache["state"] * dA[:, :, None, None] + jnp.einsum(
        "bh,bn,bhp->bhpn", dt, Bt, xt.astype(jnp.float32)
    )
    y = jnp.einsum("bn,bhpn->bhp", Ct, state)  # (B,H,P)
    y = y + params["D"][None, :, None] * xt.astype(jnp.float32)
    y = y.reshape(Bsz, cfg.d_inner)
    y = _gated_norm(y, z, params["norm"], cfg.norm_eps)
    out = jnp.einsum("be,ed->bd", y, params["out_proj"]).astype(x.dtype)[:, None, :]

    new_cache = {
        "conv": full[:, 1:, :].astype(cache["conv"].dtype),
        "state": state,
    }
    return constrain(out, ctx, ("batch", None, "act_embed")), new_cache
