"""Mixture-of-Experts: GShard-style capacity-based einsum dispatch, or
dropless routing over a held block of experts (``cfg.moe_dropless``).

TPU-idiomatic: dispatch/combine are dense one-hot einsums (MXU-friendly, no
gather/scatter), grouped along the token dim so the dispatch matmul cost stays
O(T^2/G) per group rather than O(T^2).

Two sharding modes (see DESIGN.md §6):
  * ``tp`` (baseline): experts replicated across data axes, expert d_ff sharded
    over "model" — collectives look like dense TP.
  * ``ep`` (hillclimb): the expert dim sharded over "data" — GSPMD materializes
    all-to-alls for dispatch/combine, the classic expert-parallel schedule.

The dropless path (:func:`apply_dropless`) is one chip's share of an
expert-parallel layer: the router scores all ``num_experts``, and the layer
computes the weighted outputs of the experts it holds (``cfg.expert_start``,
``cfg.n_experts_held``) for every token routed to them, plus the shared expert.
No capacity, so no token is dropped; nothing stands in for the experts held
elsewhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ShardCtx, constrain
from repro.models import mlp
from repro.models.mlp import _act
from repro.sharding.spec import ParamSpec

GROUP_TOKENS = 512  # target tokens per dispatch group
# Dropless path: tokens routed per pass. A pass gathers up to
# BLOCK_TOKENS * min(k, held) rows of d_model, which bounds a long prefill's
# memory (32k tokens at once would gather 2.4 GB at d_model 4096).
BLOCK_TOKENS = 2048


def abstract_params(cfg: ModelConfig) -> dict[str, ParamSpec]:
    d, f, e, dt = cfg.d_model, cfg.d_ff, cfg.n_experts_held, cfg.param_dtype
    out = {"router": ParamSpec((d, cfg.num_experts), ("embed", None), dtype=jnp.float32)}
    if cfg.mlp_kind in ("swiglu", "geglu"):
        out["w_gate"] = ParamSpec((e, d, f), ("experts", "embed", "mlp"), dtype=dt)
        out["w_up"] = ParamSpec((e, d, f), ("experts", "embed", "mlp"), dtype=dt)
        out["w_down"] = ParamSpec((e, f, d), ("experts", "mlp", "embed"), dtype=dt)
    else:
        out["w_in"] = ParamSpec((e, d, f), ("experts", "embed", "mlp"), dtype=dt)
        out["w_out"] = ParamSpec((e, f, d), ("experts", "mlp", "embed"), dtype=dt)
    if cfg.shared_d_ff:
        fs = cfg.shared_d_ff
        out["shared"] = {
            "w_gate": ParamSpec((d, fs), ("embed", "mlp"), dtype=dt),
            "w_up": ParamSpec((d, fs), ("embed", "mlp"), dtype=dt),
            "w_down": ParamSpec((fs, d), ("mlp", "embed"), dtype=dt),
        }
    return out


def expert_capacity(tokens_per_group: int, num_experts: int, k: int, factor: float = 1.25) -> int:
    cap = int(factor * k * tokens_per_group / num_experts)
    # C == tokens_per_group guarantees droplessness (a token picks each expert
    # at most once), so never allocate beyond it.
    return max(min(cap, tokens_per_group), 1)


def apply(
    params: dict[str, jax.Array],
    x: jax.Array,  # (B, S, d_model)
    cfg: ModelConfig,
    ctx: ShardCtx | None = None,
    num_groups: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (output, aux_loss). Aux loss = load-balancing (Switch style)."""
    if cfg.moe_dropless:
        return apply_dropless(params, x, cfg, ctx)
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_tok
    T = B * S
    # Group along tokens. Group size is THE dispatch-cost knob: the dispatch/
    # combine einsums cost 2·T·(E·C)·D with E·C = k·cf·tg, i.e. linear in the
    # group size — tg=4096 makes dispatch ~10x the expert matmuls (measured:
    # EXPERIMENTS §Perf iter 2), tg<=512 keeps it ~13%. Groups subdivide batch
    # rows so the group dim stays cleanly data-sharded.
    if num_groups is None:
        per_row = max(1, S // GROUP_TOKENS) if S % GROUP_TOKENS == 0 else 1
        G = B * per_row
    else:
        G = num_groups
    assert T % G == 0, (T, G)
    tg = T // G
    C = expert_capacity(tg, E, K, cfg.moe_capacity_factor)

    xt = x.reshape(G, tg, D)
    # Router matmul in compute dtype (its f32 version back-propagates an f32
    # cotangent into the whole residual stream, doubling every TP all-reduce
    # in the backward pass — §Perf iter 3); softmax stays f32.
    logits = jnp.einsum(
        "gtd,de->gte", xt, params["router"].astype(x.dtype)
    ).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)

    # Top-k selection -> per-token (expert, weight) slots.
    weights, sel = jax.lax.top_k(probs, K)  # (G, tg, K)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    # Position of each (token, slot) in its expert's capacity buffer.
    onehot = jax.nn.one_hot(sel, E, dtype=jnp.int32)            # (G, tg, K, E)
    slot_flat = onehot.reshape(G, tg * K, E)
    pos_in_expert = jnp.cumsum(slot_flat, axis=1) * slot_flat - 1  # (G, tg*K, E)
    pos_in_expert = pos_in_expert.reshape(G, tg, K, E)
    within_cap = (pos_in_expert >= 0) & (pos_in_expert < C)

    # dispatch: (G, tg, E, C) one-hot; combine: same with gate weights.
    pos_oh = jax.nn.one_hot(pos_in_expert, C, dtype=x.dtype) * within_cap[..., None]
    dispatch = jnp.einsum("gtke,gtkec->gtec", onehot.astype(x.dtype), pos_oh)
    combine = jnp.einsum("gtk,gtke,gtkec->gtec", weights.astype(x.dtype),
                         onehot.astype(x.dtype), pos_oh)

    ex_in = jnp.einsum("gtec,gtd->gecd", dispatch, xt)  # (G, E, C, D)
    # Keep the group dim batch-sharded: a replicated constraint here makes
    # GSPMD all-gather the dispatch output and compute every expert on every
    # data shard (16x redundant FLOPs — EXPERIMENTS §Perf iter 2).
    ex_in = constrain(ex_in, ctx, ("moe_group", "experts", None, None))

    if "w_gate" in params:
        g = jnp.einsum("gecd,edf->gecf", ex_in, params["w_gate"])
        u = jnp.einsum("gecd,edf->gecf", ex_in, params["w_up"])
        h = _act(cfg.mlp_kind, g) * u
        ex_out = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
    else:
        h = _act("gelu", jnp.einsum("gecd,edf->gecf", ex_in, params["w_in"]))
        ex_out = jnp.einsum("gecf,efd->gecd", h, params["w_out"])
    ex_out = constrain(ex_out, ctx, ("moe_group", "experts", None, None))

    out = jnp.einsum("gtec,gecd->gtd", combine, ex_out).reshape(B, S, D)
    out = constrain(out, ctx, ("batch", "seq", "act_embed"))

    # Switch-transformer load-balance loss: E * sum(frac_tokens * frac_probs).
    frac_tokens = jnp.mean(onehot[..., 0, :].astype(jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return out.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Dropless routing over the held experts
# ---------------------------------------------------------------------------

def route(x: jax.Array, router: jax.Array, k: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing of tokens x (T, D) over all of the router's experts:
    (gates (T, k) normalised over the k chosen, expert ids (T, k), softmax
    probabilities (T, E)). The router runs in float32 at full precision."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router,
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, sel = jax.lax.top_k(probs, k)
    return gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9), sel, probs


def held_expert_outputs(params: dict[str, jax.Array], x: jax.Array, gates: jax.Array,
                 sel: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Sum over each token's chosen experts that this chip holds of gate x
    expert(x): x (T, D), gates and sel (T, k). Returns (T, D) float32.

    The (token, slot) pairs are sorted by held expert (pairs of experts held
    elsewhere sort last) and run as grouped products. A token picks an
    expert at most once, so at most T * min(k, held) pairs are held: the
    first that many sorted rows hold them all, whatever the routing."""
    T, K = sel.shape
    n, e0 = cfg.n_experts_held, cfg.expert_start
    local = sel - e0
    key = jnp.where((local >= 0) & (local < n), local, n).reshape(-1)
    rows = jnp.argsort(key, stable=True)[: T * min(K, n)]
    sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
    tok = rows // K
    xs = jnp.take(x, tok, axis=0)
    g = jax.lax.ragged_dot(xs, params["w_gate"], sizes)
    u = jax.lax.ragged_dot(xs, params["w_up"], sizes)
    h = (_act(cfg.mlp_kind, g) * u).astype(x.dtype)
    y = jax.lax.ragged_dot(h, params["w_down"], sizes, preferred_element_type=jnp.float32)
    held = jnp.arange(rows.shape[0]) < sizes.sum()
    w = jnp.where(held, gates.reshape(-1)[rows], 0.0)
    y = jnp.where(held[:, None], y * w[:, None], 0.0)
    return jnp.zeros((T, x.shape[1]), jnp.float32).at[tok].add(y)


def apply_dropless(
    params: dict[str, jax.Array],
    x: jax.Array,  # (B, S, d_model)
    cfg: ModelConfig,
    ctx: ShardCtx | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The held experts' weighted outputs plus the shared expert, for every
    token: (output, Switch load-balance loss over all experts)."""
    assert cfg.mlp_kind in ("swiglu", "geglu"), cfg.mlp_kind
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_tok
    T = B * S
    nb = T // BLOCK_TOKENS if T % BLOCK_TOKENS == 0 else 1

    def block(xb):
        gates, sel, probs = route(xb, params["router"], K)
        out = held_expert_outputs(params, xb, gates, sel, cfg)
        top1 = jnp.sum(jax.nn.one_hot(sel[:, 0], E, dtype=jnp.float32), axis=0)
        return out, top1, probs.sum(0)

    with jax.named_scope("moe_routed"):
        out, top1, probsum = jax.lax.map(block, x.reshape(nb, T // nb, D))
        out = out.reshape(B, S, D)
    if "shared" in params:
        with jax.named_scope("moe_shared"):
            out = out + mlp.apply(params["shared"], x, cfg, ctx=ctx).astype(jnp.float32)
    out = constrain(out.astype(x.dtype), ctx, ("batch", "seq", "act_embed"))
    aux = E * jnp.sum((top1.sum(0) / T) * (probsum.sum(0) / T))
    return out, aux
