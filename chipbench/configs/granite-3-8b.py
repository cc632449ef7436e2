"""granite-3-8b: the seeded weights the benchmark serves and the plain
reference (a pre-norm decoder with grouped-query attention, rotary positions
and a SwiGLU MLP, in float32). Imports nothing of the program under test.

Parameters are laid out as the program stores them:

    embed (V_pad, D) bf16, tied with the output head; final_norm (D,)
    layers/slot0/{ln1 (L, D), ln2 (L, D),
                  mixer/{wq (L, D, H, hd), wk (L, D, KV, hd), wv (L, D, KV, hd),
                         wo (L, H, hd, D)},
                  ffn/{w_gate (L, D, F), w_up (L, D, F), w_down (L, F, D)}}

One layer: h += wo(softmax(q k^T / sqrt(hd) + causal) v) with q, k rotated by
position (the two halves of each head as the real and imaginary parts), query
head j reading key/value head j // (H / KV); then h += w_down(silu(w_gate x) *
w_up x). Norms are x / rms(x) * (1 + scale).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> dict[str, int]:
    return {
        "L": cfg["num_layers"], "D": cfg["d_model"], "H": cfg["num_heads"],
        "KV": cfg["num_kv_heads"], "hd": cfg["head_dim"], "F": cfg["d_ff"],
        "V": cfg["vocab_size"], "Vp": -(-cfg["vocab_size"] // 256) * 256,
    }


def param_layout(cfg: dict) -> dict:
    d = dims(cfg)
    L, D, H, KV, hd, F = d["L"], d["D"], d["H"], d["KV"], d["hd"], d["F"]
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "embed": ((d["Vp"], D), bf),
        "final_norm": ((D,), f32),
        "layers": {"slot0": {
            "ln1": ((L, D), f32),
            "mixer": {"wq": ((L, D, H, hd), bf), "wk": ((L, D, KV, hd), bf),
                      "wv": ((L, D, KV, hd), bf), "wo": ((L, H, hd, D), bf)},
            "ln2": ((L, D), f32),
            "ffn": {"w_gate": ((L, D, F), bf), "w_up": ((L, D, F), bf),
                    "w_down": ((L, F, D), bf)},
        }},
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(key: jax.Array, cfg: dict) -> dict:
    """Seeded weights in the type they are served in (call under jit)."""
    d = dims(cfg)
    fan_in = {"wq": d["D"], "wk": d["D"], "wv": d["D"], "wo": d["H"] * d["hd"],
              "w_gate": d["D"], "w_up": d["D"], "w_down": d["F"]}
    paths, treedef = jax.tree_util.tree_flatten_with_path(param_layout(cfg), is_leaf=_is_spec)
    keys = jax.random.split(key, len(paths))
    out = []
    for k, (path, (shape, dtype)) in zip(keys, paths):
        name = jax.tree_util.keystr(path).split("'")[-2]
        n = jax.random.normal(k, shape, jnp.float32)
        if name == "embed":
            x = 0.02 * n
        elif name in ("final_norm", "ln1", "ln2"):
            x = 0.1 * n
        else:
            x = n / math.sqrt(fan_in[name])
        out.append(x.astype(dtype))
    return jax.tree.unflatten(treedef, out)


# --------------------------------------------------------------------------
# plain reference
# --------------------------------------------------------------------------

def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + scale)


def _rope(x, theta: float):
    """x (b, l, heads, hd): rotate pairs (x[i], x[i + hd/2]) by pos * theta^(-2i/hd)."""
    l, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freqs          # (l, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(h, p, cfg: dict, mm):
    d = dims(cfg)
    H, KV, hd = d["H"], d["KV"], d["hd"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    l = h.shape[1]
    x = _rms(h, p["ln1"], eps)
    q = _rope(mm("bld,dhk->blhk", x, p["mixer"]["wq"]), theta)
    k = _rope(mm("bld,dhk->blhk", x, p["mixer"]["wk"]), theta)
    v = mm("bld,dhk->blhk", x, p["mixer"]["wv"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = mm("bqhk,bshk->bhqs", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool))[None, None], s, -jnp.inf)
    o = mm("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v)
    h = h + mm("blhk,hkd->bld", o, p["mixer"]["wo"])
    x = _rms(h, p["ln2"], eps)
    g = mm("bld,df->blf", x, p["ffn"]["w_gate"])
    u = mm("bld,df->blf", x, p["ffn"]["w_up"])
    return h + mm("blf,fd->bld", jax.nn.silu(g) * u, p["ffn"]["w_down"])


def logits(params, tokens, cfg: dict, mm):
    """Full-sequence logits (b, l, V) in float32: tokens (b, l) int32."""
    h = params["embed"].astype(jnp.float32)[tokens]
    layer = jax.checkpoint(partial(_layer, cfg=cfg, mm=mm))

    def body(h, p):
        return layer(h, p), None

    h, _ = jax.lax.scan(body, h, params["layers"]["slot0"])
    h = _rms(h, params["final_norm"], cfg["norm_eps"])
    return mm("bld,vd->blv", h, params["embed"])[..., : cfg["vocab_size"]]
