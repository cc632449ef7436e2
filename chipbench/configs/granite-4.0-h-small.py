"""granite-4.0-h-small: the seeded weights the benchmark serves and the plain
reference (Granite 4.0-H: Mamba-2 and NoPE attention layers, each followed by
a top-k mixture of experts with a shared expert, in float32). Imports nothing
of the program under test. The SSD scan is ``_ssd`` of the mamba2-780m
module beside this file, loaded by path.

Parameters are laid out as the program stores them, one period of layers
(``layer_types``) with a leading dimension of 1 (L = num_layers / period):

    embed (V_pad, D) bf16, tied with the output head; final_norm (D,)
    layers/slot<j>/{ln1 (L, D), ln2 (L, D), mixer/..., ffn/...}
    mamba mixer: z_proj (L, D, E), xBC_proj (L, D, E + 2N), dt_proj (L, D, H),
        conv_w (L, E + 2N, K), conv_b, A_log, dt_bias, D, norm, out_proj (L, E, D)
    attention mixer: wq (L, D, Hq, hd), wk, wv (L, D, KV, hd), wo (L, Hq, hd, D)
    ffn: router (L, D, X) f32 over all X experts; w_gate, w_up (L, n, D, F),
        w_down (L, n, F, D) for the n held experts; shared/{w_gate, w_up (L, D, Fs),
        w_down (L, Fs, D)}

Equations (HF ``granitemoehybrid``): h = embed(tokens) * embedding_multiplier;
per layer h += r * mixer(rms(h)), then h += r * (moe(x) + shared(x)) with
x = rms(h) and r = residual_multiplier; logits = rms(h) embed^T / logits_scaling.
Mamba-2: out_proj(gated_rmsnorm(SSD(x, dt, A, B, C) + D * x, z)) after a causal
depthwise conv with bias and SiLU on (x, B, C). Attention: causal GQA with no
positional encoding, scores q.k * attention_multiplier. MoE: the top-k of the
router's softmax over all X experts, gates normalised over the k chosen; the
held experts e0 .. e0 + n - 1 add gate * w_down(silu(w_gate x) * w_up x), the
others nothing (they are held on other chips).
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp


def _load_mamba2():
    path = Path(__file__).with_name("mamba2-780m.py")
    spec = importlib.util.spec_from_file_location("chipbench_ref_mamba2_780m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_ssd = _load_mamba2()._ssd


def dims(cfg: dict) -> dict:
    D = cfg["d_model"]
    E = cfg["ssm_expand"] * D
    N = cfg["ssm_state"]
    return {
        "L": cfg["num_layers"] // len(kinds(cfg)), "D": D, "Hq": cfg["num_heads"],
        "KV": cfg["num_kv_heads"], "hd": cfg["head_dim"], "F": cfg["d_ff"],
        "Fs": cfg["shared_d_ff"], "X": cfg["num_experts"], "n": cfg["experts_held"],
        "e0": cfg["expert_start"], "k": cfg["experts_per_tok"],
        "E": E, "N": N, "H": E // cfg["ssm_headdim"], "P": cfg["ssm_headdim"],
        "K": cfg["ssm_conv"], "C": E + 2 * N,
        "V": cfg["vocab_size"], "Vp": -(-cfg["vocab_size"] // 256) * 256,
    }


def kinds(cfg: dict) -> list[str]:
    """The period's layer kinds: the first ``num_layers`` of ``layer_types``
    (a whole period of the published pattern)."""
    return cfg["layer_types"][: cfg["num_layers"]]


def param_layout(cfg: dict) -> dict:
    d = dims(cfg)
    L, D, E, H, C, K = d["L"], d["D"], d["E"], d["H"], d["C"], d["K"]
    Hq, KV, hd, F, Fs, n = d["Hq"], d["KV"], d["hd"], d["F"], d["Fs"], d["n"]
    bf, f32 = jnp.dtype(cfg["param_dtype"]), jnp.float32
    ffn = {
        "router": ((L, D, d["X"]), f32),
        "w_gate": ((L, n, D, F), bf), "w_up": ((L, n, D, F), bf), "w_down": ((L, n, F, D), bf),
        "shared": {"w_gate": ((L, D, Fs), bf), "w_up": ((L, D, Fs), bf),
                   "w_down": ((L, Fs, D), bf)},
    }
    mamba = {
        "z_proj": ((L, D, E), bf), "xBC_proj": ((L, D, C), bf),
        "dt_proj": ((L, D, H), bf), "conv_w": ((L, C, K), bf),
        "conv_b": ((L, C), f32), "A_log": ((L, H), f32),
        "dt_bias": ((L, H), f32), "D": ((L, H), f32),
        "norm": ((L, E), f32), "out_proj": ((L, E, D), bf),
    }
    attention = {"wq": ((L, D, Hq, hd), bf), "wk": ((L, D, KV, hd), bf),
                 "wv": ((L, D, KV, hd), bf), "wo": ((L, Hq, hd, D), bf)}
    return {
        "embed": ((d["Vp"], D), bf),
        "final_norm": ((D,), f32),
        "layers": {
            f"slot{j}": {"ln1": ((L, D), f32), "ln2": ((L, D), f32), "ffn": ffn,
                         "mixer": mamba if kind == "mamba" else attention}
            for j, kind in enumerate(kinds(cfg))
        },
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _fan_in(path: str, d: dict) -> int:
    if "shared" in path and path.endswith("w_down']"):
        return d["Fs"]
    name = path.split("'")[-2]
    return {"z_proj": d["D"], "xBC_proj": d["D"], "dt_proj": d["D"], "out_proj": d["E"],
            "conv_w": d["K"], "wq": d["D"], "wk": d["D"], "wv": d["D"],
            "wo": d["Hq"] * d["hd"], "router": d["D"], "w_gate": d["D"], "w_up": d["D"],
            "w_down": d["F"]}[name]


def init_params(key: jax.Array, cfg: dict) -> dict:
    """Seeded weights in the type they are served in (call under jit)."""
    d = dims(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(param_layout(cfg), is_leaf=_is_spec)
    keys = jax.random.split(key, len(paths))
    out = []
    for k, (path, (shape, dtype)) in zip(keys, paths):
        p = jax.tree_util.keystr(path)
        name = p.split("'")[-2]
        n = jax.random.normal(k, shape, jnp.float32)
        if name == "embed":
            # The multiplied embedding enters the residual stream at 0.02, as
            # the other configurations' do. At 0.02 before the multiplier the
            # tied head ranks each position's own token first everywhere, in
            # every precision, and the logit comparison tells nothing apart.
            x = 0.02 / cfg["embedding_multiplier"] * n
        elif name in ("final_norm", "ln1", "ln2", "norm", "conv_b"):
            x = 0.1 * n
        elif name == "A_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
        elif name == "D":
            x = 1.0 + 0.1 * n
        else:
            x = n / math.sqrt(_fan_in(p, d))
        out.append(x.astype(dtype))
    return jax.tree.unflatten(treedef, out)


# --------------------------------------------------------------------------
# plain reference
# --------------------------------------------------------------------------

def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + scale)


def _mamba(x, p, cfg: dict, mm):
    d = dims(cfg)
    E, N, H, P, K = d["E"], d["N"], d["H"], d["P"], d["K"]
    b, l, _ = x.shape
    z = mm("bld,de->ble", x, p["z_proj"])
    xbc = mm("bld,de->ble", x, p["xBC_proj"])
    dt = mm("bld,de->ble", x, p["dt_proj"])
    w = p["conv_w"].astype(jnp.float32)                               # (C, K)
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, i:i + l, :] * w[:, i] for i in range(K)) + p["conv_b"])
    xs = xbc[..., :E].reshape(b, l, H, P)
    Bm, Cm = xbc[..., E:E + N], xbc[..., E + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = _ssd(xs, dt, A, Bm, Cm, mm) + p["D"][None, None, :, None] * xs
    y = _rms(y.reshape(b, l, E) * jax.nn.silu(z), p["norm"], cfg["norm_eps"])
    return mm("ble,ed->bld", y, p["out_proj"])


def _attention(x, p, cfg: dict, mm):
    d = dims(cfg)
    l = x.shape[1]
    q = mm("bld,dhk->blhk", x, p["wq"])
    k = jnp.repeat(mm("bld,dhk->blhk", x, p["wk"]), d["Hq"] // d["KV"], axis=2)
    v = jnp.repeat(mm("bld,dhk->blhk", x, p["wv"]), d["Hq"] // d["KV"], axis=2)
    s = mm("bqhk,bshk->bhqs", q, k) * cfg["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool))[None, None], s, -jnp.inf)
    o = mm("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v)
    return mm("blhk,hkd->bld", o, p["wo"])


def _moe(x, p, cfg: dict, mm):
    """Held experts' gated outputs plus the shared expert, every expert held
    here computed on every token and weighted by its gate (0 where the token
    did not choose it)."""
    d = dims(cfg)
    probs = jax.nn.softmax(mm("bld,dx->blx", x, p["router"]), axis=-1)
    gates, sel = jax.lax.top_k(probs, d["k"])
    gates = gates / gates.sum(-1, keepdims=True)
    held = d["e0"] + jnp.arange(d["n"])
    weight = jnp.sum(gates[..., None] * (sel[..., None] == held), axis=-2)   # (b, l, n)
    g = mm("bld,edf->blef", x, p["w_gate"])
    u = mm("bld,edf->blef", x, p["w_up"])
    routed = mm("blef,efd->bld", jax.nn.silu(g) * u * weight[..., None], p["w_down"])
    s = p["shared"]
    sg = mm("bld,df->blf", x, s["w_gate"])
    su = mm("bld,df->blf", x, s["w_up"])
    return routed + mm("blf,fd->bld", jax.nn.silu(sg) * su, s["w_down"])


def _layer(h, p, kind: str, cfg: dict, mm):
    eps, r = cfg["norm_eps"], cfg["residual_multiplier"]
    mixer = _mamba if kind == "mamba" else _attention
    h = h + r * mixer(_rms(h, p["ln1"], eps), p["mixer"], cfg, mm)
    return h + r * _moe(_rms(h, p["ln2"], eps), p["ffn"], cfg, mm)


def logits(params, tokens, cfg: dict, mm):
    """Full-sequence logits (b, l, V) in float32: tokens (b, l) int32."""
    h = params["embed"].astype(jnp.float32)[tokens] * cfg["embedding_multiplier"]
    for i in range(dims(cfg)["L"]):
        for j, kind in enumerate(kinds(cfg)):
            p = jax.tree.map(lambda a: a[i], params["layers"][f"slot{j}"])
            h = jax.checkpoint(lambda h, p, kind=kind: _layer(h, p, kind, cfg, mm))(h, p)
    h = _rms(h, params["final_norm"], cfg["norm_eps"])
    out = mm("bld,vd->blv", h, params["embed"]) / cfg["logits_scaling"]
    return out[..., : cfg["vocab_size"]]


def n_params(cfg: dict) -> int:
    return sum(math.prod(s) for s, _ in jax.tree.leaves(param_layout(cfg), is_leaf=_is_spec))
