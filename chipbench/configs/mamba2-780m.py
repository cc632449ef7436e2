"""mamba2-780m: the seeded weights the benchmark serves and trains, the plain
reference (Mamba-2 SSD, arXiv 2405.21060, in float32), and the FLOPs of one
train step. Imports nothing of the program under test.

Parameters are laid out as the program stores them (its names and shapes;
the harness checks the layout against the program before handing them over):

    embed (V_pad, D) bf16, tied with the output head; final_norm (D,)
    layers/slot0/ln1 (L, D); layers/slot0/mixer/{z_proj (L, D, E),
    xBC_proj (L, D, E + 2N), dt_proj (L, D, H), conv_w (L, E + 2N, K),
    conv_b (L, E + 2N), A_log (L, H), dt_bias (L, H), D (L, H),
    norm (L, E), out_proj (L, E, D)}

with E = expand * D, H = E / headdim, N = state size, K = conv width. One
layer: h += out_proj(gated_rmsnorm(SSD(x, dt, A, B, C) + D * x, z)), where
z, (x, B, C) after a causal depthwise conv and SiLU, and dt come from the
RMS-normed input; dt = softplus(dt + dt_bias), A = -exp(A_log).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

REF_CHUNK = 64  # the reference's own SSD chunk length; any length gives the same sums


def dims(cfg: dict) -> dict[str, int]:
    D = cfg["d_model"]
    E = cfg["ssm_expand"] * D
    N = cfg["ssm_state"]
    return {
        "L": cfg["num_layers"], "D": D, "E": E, "N": N, "H": E // cfg["ssm_headdim"],
        "P": cfg["ssm_headdim"], "K": cfg["ssm_conv"], "C": E + 2 * N,
        "V": cfg["vocab_size"], "Vp": -(-cfg["vocab_size"] // 256) * 256,
    }


def param_layout(cfg: dict) -> dict:
    d = dims(cfg)
    L, D, E, H, C, K = d["L"], d["D"], d["E"], d["H"], d["C"], d["K"]
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "embed": ((d["Vp"], D), bf),
        "final_norm": ((D,), f32),
        "layers": {"slot0": {
            "ln1": ((L, D), f32),
            "mixer": {
                "z_proj": ((L, D, E), bf), "xBC_proj": ((L, D, C), bf),
                "dt_proj": ((L, D, H), bf), "conv_w": ((L, C, K), bf),
                "conv_b": ((L, C), f32), "A_log": ((L, H), f32),
                "dt_bias": ((L, H), f32), "D": ((L, H), f32),
                "norm": ((L, E), f32), "out_proj": ((L, E, D), bf),
            },
        }},
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(key: jax.Array, cfg: dict) -> dict:
    """Seeded weights in the type they are served in (call under jit)."""
    d = dims(cfg)
    layout = param_layout(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(layout, is_leaf=_is_spec)
    keys = jax.random.split(key, len(paths))
    out = []
    for k, (path, (shape, dtype)) in zip(keys, paths):
        name = jax.tree_util.keystr(path).split("'")[-2]
        n = jax.random.normal(k, shape, jnp.float32)
        if name == "embed":
            x = 0.02 * n
        elif name in ("final_norm", "ln1", "norm", "conv_b"):
            x = 0.1 * n
        elif name in ("z_proj", "xBC_proj", "dt_proj"):
            x = n / math.sqrt(d["D"])
        elif name == "out_proj":
            x = n / math.sqrt(d["E"])
        elif name == "conv_w":
            x = n / math.sqrt(d["K"])
        elif name == "A_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
        elif name == "D":
            x = 1.0 + 0.1 * n
        else:
            raise KeyError(name)
        out.append(x.astype(dtype))
    return jax.tree.unflatten(treedef, out)


# --------------------------------------------------------------------------
# plain reference
# --------------------------------------------------------------------------

def _ssd(x, dt, A, B, C, mm, Q: int = REF_CHUNK):
    """y_t = sum_{s<=t} C_t . (prod_{s<r<=t} exp(dt_r A)) B_s dt_s x_s,
    computed by chunks: within a chunk quadratically, across chunks by the
    recurrence on the chunk-end states. x (b,l,h,p), dt (b,l,h), A (h,),
    B, C (b,l,n)."""
    b, l, h, p = x.shape
    n = B.shape[-1]
    pad = (-l) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    c = (l + pad) // Q
    x = x.reshape(b, c, Q, h, p)
    dt = dt.reshape(b, c, Q, h)
    B = B.reshape(b, c, Q, n)
    C = C.reshape(b, c, Q, n)
    a = jnp.cumsum(dt * A, axis=2)                                  # (b,c,Q,h)
    seg = a[:, :, :, None, :] - a[:, :, None, :, :]                 # (b,c,Q,Q,h)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    xdt = x * dt[..., None]
    cb = mm("bcln,bcsn->bcls", C, B)
    y_diag = mm("bclsh,bcshp->bclhp", cb[..., None] * decay, xdt)
    to_end = jnp.exp(a[:, :, -1:, :] - a)                          # (b,c,Q,h)
    states = mm("bcsn,bcshp->bchpn", B, xdt * to_end[..., None])  # (b,c,h,p,n)
    chunk_decay = jnp.exp(a[:, :, -1, :])                          # (b,c,h)

    def step(carry, inp):
        s_c, d_c = inp
        return carry * d_c[:, :, None, None] + s_c, carry

    _, entering = jax.lax.scan(
        step, jnp.zeros((b, h, p, n), jnp.float32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                         # (b,c,h,p,n)
    y_off = mm("bcln,bchpn->bclhp", C, entering) * jnp.exp(a)[..., None]
    return (y_diag + y_off).reshape(b, c * Q, h, p)[:, :l]


def _layer(h, p, cfg: dict, mm):
    d = dims(cfg)
    E, N, H, P, K = d["E"], d["N"], d["H"], d["P"], d["K"]
    eps = cfg["norm_eps"]
    b, l, _ = h.shape
    x = _rms(h, p["ln1"], eps)
    z = mm("bld,de->ble", x, p["mixer"]["z_proj"])
    xbc = mm("bld,de->ble", x, p["mixer"]["xBC_proj"])
    dt = mm("bld,de->ble", x, p["mixer"]["dt_proj"])
    w = p["mixer"]["conv_w"].astype(jnp.float32)                     # (C, K)
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + l, :] * w[:, i] for i in range(K)) + p["mixer"]["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :E].reshape(b, l, H, P)
    Bm, Cm = xbc[..., E:E + N], xbc[..., E + N:]
    dt = jax.nn.softplus(dt + p["mixer"]["dt_bias"])
    A = -jnp.exp(p["mixer"]["A_log"].astype(jnp.float32))
    y = _ssd(xs, dt, A, Bm, Cm, mm) + p["mixer"]["D"][None, None, :, None] * xs
    y = y.reshape(b, l, E) * jax.nn.silu(z)
    y = _rms(y, p["mixer"]["norm"], eps)
    return h + mm("ble,ed->bld", y, p["mixer"]["out_proj"])


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + scale)


def logits(params, tokens, cfg: dict, mm):
    """Full-sequence logits (b, l, V) in float32: tokens (b, l) int32."""
    h = params["embed"].astype(jnp.float32)[tokens]
    layer = jax.checkpoint(partial(_layer, cfg=cfg, mm=mm))

    def body(h, p):
        return layer(h, p), None

    h, _ = jax.lax.scan(body, h, params["layers"]["slot0"])
    h = _rms(h, params["final_norm"], cfg["norm_eps"])
    return mm("bld,vd->blv", h, params["embed"])[..., : cfg["vocab_size"]]


# --------------------------------------------------------------------------
# work
# --------------------------------------------------------------------------

def n_params(cfg: dict) -> int:
    layout = param_layout(cfg)
    return sum(math.prod(s) for s, _ in jax.tree.leaves(layout, is_leaf=_is_spec))


def ssd_flops_per_token(cfg: dict, chunk: int = 128) -> float:
    """Forward FLOPs of one layer's SSD per token at the program's chunk
    length: C.B^T within the chunk, the masked product with x*dt, the chunk
    states and their read-out."""
    d = dims(cfg)
    N, H, P = d["N"], d["H"], d["P"]
    return 2 * chunk * N + 2 * chunk * H * P + 4 * H * P * N


def train_flops(cfg: dict, batch: int, seq: int) -> float:
    """FLOPs one train step requires: 6 N per token for the products of the
    weights (the tied head counted once) plus three times the forward SSD;
    recomputation excluded."""
    tokens = batch * seq
    return 6.0 * n_params(cfg) * tokens + 3.0 * cfg["num_layers"] * ssd_flops_per_token(cfg) * tokens
