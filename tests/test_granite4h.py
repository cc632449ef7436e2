"""granite-4.0-h-small on the CPU at a small size: the served logits against
the plain reference (``chipbench/configs/granite-4.0-h-small.py``, loaded by
path), the chip's share of an expert layer, dropless routing, NoPE and
Granite's multipliers, and the hybrid session tree through a save, a kill and
``recover()``, with the capture's byte labels."""

import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CONFIGS, get_config
from repro.models import build_model, lm, moe
from repro.models.common import rmsnorm
from repro.obs.trace import tracer
from repro.runtime.failures import FailureInjector
from repro.runtime.server import Server, ServerConfig

BENCH = Path(__file__).resolve().parents[1] / "chipbench"
ARCH = "granite-4.0-h-small"
# Small widths for the published structure: one whole period (9 mamba, 1
# attention), 8 experts of which the block [2, 6) is held, top-3.
SMALL = dict(d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32, shared_d_ff=48,
             vocab_size=256, num_experts=8, experts_per_tok=3, expert_start=2, experts_held=4,
             ssm_state=16, ssm_headdim=16)
# Served logits against the reference: the program in float32 on the CPU
# differs from the reference's float32 at HIGHEST only in the order of its
# sums (about 1e-7 of a logit per product, 1.9e-6 of the largest logit
# measured over ten layers); bfloat16 rounds every weight and activation to
# 8 bits (4e-3), which lands near a third of the largest logit here (the
# logits are small, and a token's routing can flip). The tolerance lies
# between, at 1e-4 of the largest logit.
LOGIT_TOL = 1e-4


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    """The configuration module, its small config dict and its seeded weights."""
    sys.path.insert(0, str(BENCH))
    try:
        mod = _load(BENCH / "configs" / f"{ARCH}.py", "g4h_reference")
        reference = _load(BENCH / "reference.py", "g4h_reference_mm")
    finally:
        sys.path.remove(str(BENCH))
    c = json.loads((BENCH / "configs" / f"{ARCH}.json").read_text())
    c.update(SMALL, param_dtype="float32", compute_dtype="float32")
    params = jax.jit(lambda k: mod.init_params(k, c))(jax.random.PRNGKey(3))
    return mod, c, params, reference.make_mm("f32")


def _program(c: dict, dtype):
    kw = {k: c[k] for k in c["program_keys"]}
    kw["param_dtype"] = kw["compute_dtype"] = dtype
    return get_config(ARCH).with_(**kw)


def _server(cfg, params, batch, max_seq, **kw):
    model = build_model(cfg)
    p = jax.tree.map(lambda a, s: a.astype(s.dtype), params, model.param_shape_dtypes())
    scfg = ServerConfig(batch=batch, max_seq=max_seq, checkpoint_every_tokens=4,
                        checkpoint_mode="async", **kw)
    return Server(model, scfg, params=p)


# --------------------------------------------------------------------------
# (a) served logits against the plain reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype, within", [("float32", True), ("bfloat16", False)])
def test_served_logits_match_reference(ref, dtype, within):
    """Prefill, then decode through the hybrid cache on the Server's own
    path: every served position's logits against the reference's full
    forward over the same tokens. In bfloat16 the same path must fail."""
    mod, c, params, mm = ref
    B, P, G = 2, 16, 12
    s = _server(_program(c, getattr(jnp, dtype)), params, B, P + G + 2)
    got = []
    prefill, decode = s._prefill, s._decode

    def keep(fn):
        def run(*a, **k):
            logits, cache = fn(*a, **k)
            got.append(np.asarray(logits, np.float32))
            return logits, cache
        return run

    s._prefill, s._decode = keep(prefill), keep(decode)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (B, P), 0, c["vocab_size"]))
    tokens = s.prefill_and_decode(prompts, G)
    want = np.asarray(mod.logits(params, jnp.asarray(tokens[:, : P + G]), c, mm))[:, P - 1:]
    served = np.stack(got, 1)[..., : c["vocab_size"]]
    assert served.shape == want.shape == (B, G + 1, c["vocab_size"])
    gap = np.abs(served - want).max() / np.abs(want).max()
    assert (gap < LOGIT_TOL) == within, gap


# --------------------------------------------------------------------------
# (b), (c) the chip's share of an expert layer; dropless routing
# --------------------------------------------------------------------------

def _moe_cfg(**kw):
    return get_config(ARCH).with_(d_model=32, d_ff=16, shared_d_ff=24, num_experts=8,
                                  experts_per_tok=3, param_dtype=jnp.float32,
                                  compute_dtype=jnp.float32, **kw)


def _moe_params(cfg, key):
    from repro.sharding.spec import init_tree

    return init_tree(key, moe.abstract_params(cfg))


def _dense_moe(params, x, cfg, shared=True):
    """Every expert on every token, weighted by its gate (0 where not chosen)."""
    xt = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(xt @ params["router"], axis=-1)
    gates, sel = jax.lax.top_k(probs, cfg.experts_per_tok)
    gates = gates / gates.sum(-1, keepdims=True)
    out = 0.0
    for e in range(cfg.num_experts):
        w = jnp.sum(jnp.where(sel == e, gates, 0.0), axis=-1)
        h = jax.nn.silu(xt @ params["w_gate"][e]) * (xt @ params["w_up"][e])
        out = out + w[:, None] * (h @ params["w_down"][e])
    if shared:
        s = params["shared"]
        out = out + (jax.nn.silu(xt @ s["w_gate"]) * (xt @ s["w_up"])) @ s["w_down"]
    return out.reshape(x.shape)


def test_held_blocks_add_up_to_the_uncut_layer():
    """The layer run once per held block over a partition of all experts,
    the shared expert counted once, gives the uncut layer."""
    from repro.models import mlp

    full_cfg = _moe_cfg()
    full = _moe_params(full_cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 32))
    whole, _ = moe.apply(full, x, full_cfg)
    shared = mlp.apply(full["shared"], x, full_cfg)
    parts = 0.0
    for start, n in ((0, 2), (2, 3), (5, 3)):
        cfg = _moe_cfg(expert_start=start, experts_held=n)
        p = {k: (v[start:start + n] if k.startswith("w_") else v) for k, v in full.items()}
        assert moe.abstract_params(cfg)["w_gate"].shape[0] == n
        out, _ = moe.apply(p, x, cfg)
        parts = parts + (out - shared)
    np.testing.assert_allclose(parts + shared, whole, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(whole, _dense_moe(full, x, full_cfg), atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("tokens", [8, 2 * moe.BLOCK_TOKENS])
def test_dropless_under_skewed_routing(tokens):
    """A router that sends every token to the same experts: the dropless
    layer equals the dense reference, where the capacity path (factor 1.25)
    drops tokens. Also across several token blocks of a long prefill."""
    cfg = _moe_cfg()
    p = _moe_params(cfg, jax.random.PRNGKey(2))
    # Positive inputs and router columns growing with the expert index: all
    # tokens rank the experts alike and pick the last three.
    p["router"] = jnp.tile(jnp.arange(8.0)[None] * 0.1, (32, 1))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (1, tokens, 32))) + 0.1
    out, _ = jax.jit(lambda p, x: moe.apply(p, x, cfg))(p, x)
    dense = _dense_moe(p, x, cfg)
    np.testing.assert_allclose(out, dense, atol=1e-5, rtol=1e-4)
    capped, _ = moe.apply(p, x, cfg.with_(moe_dropless=False, shared_d_ff=0))
    assert not np.allclose(capped, _dense_moe(p, x, cfg, shared=False), atol=1e-3)


# --------------------------------------------------------------------------
# (d) NoPE and Granite's multipliers
# --------------------------------------------------------------------------

def _one_layer(**kw):
    return get_config("llama3.2-1b").with_(
        num_layers=1, layer_pattern=("attn",), d_model=32, num_heads=4, num_kv_heads=2,
        head_dim=8, d_ff=48, vocab_size=256, tie_embeddings=True, num_experts=0,
        param_dtype=jnp.float32, compute_dtype=jnp.float32, scan_layers=False, **kw)


def _by_hand(params, tokens, cfg, rope: bool):
    """The decoder's equations written out: embedding times its multiplier,
    attention with scores q.k * scale (RoPE or none), the residual
    multiplier on both branches, logits over logits_scaling."""
    from repro.models.common import apply_rope

    p = jax.tree.map(lambda a: a[0], params["layers"]["slot0"])
    m = p["mixer"]
    h = params["embed"][tokens] * cfg.embedding_multiplier
    S = tokens.shape[1]
    x = rmsnorm(h, p["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", x, m["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, m["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, m["wv"])
    if rope:
        q = apply_rope(q, jnp.arange(S), cfg.rope_theta)
        k = apply_rope(k, jnp.arange(S), cfg.rope_theta)
    k, v = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    scale = cfg.attention_multiplier if cfg.attention_multiplier is not None else 8 ** -0.5
    s = jnp.einsum("bqhk,bshk->bhqs", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v)
    h = h + cfg.residual_multiplier * jnp.einsum("bqhk,hkd->bqd", o, m["wo"])
    x = rmsnorm(h, p["ln2"], cfg.norm_eps)
    f = p["ffn"]
    h = h + cfg.residual_multiplier * (
        (jax.nn.silu(x @ f["w_gate"]) * (x @ f["w_up"])) @ f["w_down"])
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return (h @ params["embed"].T) / cfg.logits_scaling


@pytest.mark.parametrize("granite", [False, True])
def test_nope_and_multipliers_match_equations(granite):
    kw = dict(use_rope=False, embedding_multiplier=12.0, attention_multiplier=1 / 128,
              residual_multiplier=0.22, logits_scaling=16.0) if granite else {}
    cfg = _one_layer(**kw)
    params = build_model(cfg).init(jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 12), 0, 256)
    h, _, _ = lm.forward(params, cfg, tokens=tokens)
    got = lm.logits_fn(params, h, cfg, None)
    want = _by_hand(params, tokens, cfg, rope=not granite)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)
    # Decoding the last position through the cache reads the same scale.
    model = build_model(cfg)
    _, cache = model.prefill(params, tokens=tokens[:, :-1])
    full = jax.tree.map(lambda fc, pc: fc.at[:, :, : pc.shape[2]].set(pc),
                        model.init_cache(2, 12), cache)
    last, _ = model.decode_step(params, full, tokens[:, -1], jnp.int32(11))
    np.testing.assert_allclose(last, want[:, -1], atol=1e-5, rtol=1e-4)


def test_defaults_are_the_plain_decoder():
    """Every new field defaults to the plain decoder, and the configuration
    the benchmark's granite-serve-kv cell runs keeps them."""
    base = _one_layer()
    assert (base.use_rope, base.embedding_multiplier, base.attention_multiplier,
            base.residual_multiplier, base.logits_scaling) == (True, 1.0, None, 1.0, 1.0)
    assert not base.moe_dropless and base.shared_d_ff == 0 and base.experts_held == 0
    g3 = CONFIGS["granite-3-8b"]
    assert (g3.use_rope, g3.embedding_multiplier, g3.attention_multiplier,
            g3.residual_multiplier, g3.logits_scaling) == (True, 1.0, None, 1.0, 1.0)
    params = build_model(base).init(jax.random.PRNGKey(7))
    tokens = jax.random.randint(jax.random.PRNGKey(8), (1, 10), 0, 256)
    explicit = base.with_(attention_multiplier=8 ** -0.5)
    a = lm.forward(params, base, tokens=tokens)[0]
    b = lm.forward(params, explicit, tokens=tokens)[0]
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_reduced_keeps_routing_and_the_held_block_consistent():
    cfg = get_config(ARCH).with_(expert_start=36, experts_held=18)
    r = cfg.reduced()
    assert r.num_experts == 4 and r.experts_per_tok == 4
    assert (r.expert_start, r.experts_held) == (2, 1)
    assert r.shared_d_ff == 128 and r.moe_dropless
    assert get_config(ARCH).reduced().experts_held == 0  # all held stays all held


# --------------------------------------------------------------------------
# (e) the hybrid session tree through a save, a kill and recover()
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_hybrid():
    cfg = get_config(ARCH).reduced()
    assert set(cfg.layer_pattern) == {"mamba", "attn"}
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(9))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 8), dtype=np.int32)
    return cfg, params, prompts


def _leaf_bits(tree):
    return {jax.tree_util.keystr(k): np.asarray(v).reshape(-1).view(np.uint8).copy()
            for k, v in jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]}


def test_hybrid_sessions_restored_bit_identical(small_hybrid):
    cfg, params, prompts = small_hybrid
    s = _server(cfg, params, 4, 24)
    s.prefill_and_decode(prompts, 8)          # ends on a committed save
    before = _leaf_bits(s.sessions)
    s.cluster.kill(1)
    s.recover()
    after = _leaf_bits(s.sessions)
    kinds = {"['k']", "['v']", "['state']", "['conv']"}
    assert {k for k in kinds if any(p.endswith(k) for p in before)} == kinds
    assert before.keys() == after.keys()
    for path in before:
        assert np.array_equal(before[path], after[path]), path
    assert s.n_recoveries == 1


def test_hybrid_generation_identical_across_a_kill(small_hybrid):
    cfg, params, prompts = small_hybrid
    ref = _server(cfg, params, 4, 24).prefill_and_decode(prompts, 14)
    s = _server(cfg, params, 4, 24)
    s.injector = FailureInjector(4, schedule={7: [2]})
    out = s.prefill_and_decode(prompts, 14)
    assert s.n_recoveries == 1
    assert np.array_equal(ref, out)


# --------------------------------------------------------------------------
# (f) the capture's byte labels
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", [ARCH, "granite-3-8b", "mamba2-780m"])
def test_capture_labels(arch):
    """``kv_bytes + recurrent_bytes == bytes``; ``clean_bytes`` is every
    append-only byte except the positions written since the last save (none
    at the first save, which follows the prefill)."""
    cfg = CONFIGS[arch].reduced()
    model = build_model(cfg)
    B, P, G, max_seq, every = 2, 6, 12, 20, 4
    s = Server(model, ServerConfig(batch=B, max_seq=max_seq, checkpoint_every_tokens=every,
                                   checkpoint_mode="async"),
               params=model.init(jax.random.PRNGKey(1)))
    prompts = np.random.default_rng(1).integers(0, cfg.vocab_size, (B, P), dtype=np.int32)
    tr = tracer()
    tr.reset()
    tr.enable()
    try:
        s.prefill_and_decode(prompts, G)
        d2h = [e["args"] for e in tr.events() if e["name"] == "capture_d2h"]
    finally:
        tr.disable()
        tr.reset()
    state = jax.device_get(s.sessions)
    total = sum(x.nbytes for x in jax.tree.leaves(state))
    kv = state["tokens"].nbytes
    per_pos = state["tokens"].nbytes // max_seq
    for j, kind in enumerate(cfg.layer_pattern):
        if kind != "mamba":
            for leaf in state["cache"][f"slot{j}"].values():
                kv += leaf.nbytes
                per_pos += leaf.nbytes // max_seq
    assert len(d2h) == 1 + G // every
    for i, a in enumerate(d2h):
        assert a["bytes"] == total
        assert a["kv_bytes"] + a["recurrent_bytes"] == a["bytes"]
        assert a["kv_bytes"] == kv
        assert a["clean_bytes"] == (0 if i == 0 else kv - every * per_pos)
