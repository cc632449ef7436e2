"""Pieces the plain references of every configuration share: matrix products
at a stated precision, cross-entropy, AdamW and the comparisons that
decide ``correct``. Nothing here imports the program under test.

Precision ``"f32"`` is float32 at ``Precision.HIGHEST`` (the reference).
``"fp8"`` rounds both operands of every product to float8 e4m3 with one scale
per tensor before the same float32 product: the control, one precision step
below the bfloat16 the configurations state.
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def quantize_fp8(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def make_mm(precision: str) -> Callable[..., jax.Array]:
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown reference precision {precision!r}")

    def mm(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if precision == "fp8":
            a, b = quantize_fp8(a), quantize_fp8(b)
        return jnp.einsum(eq, a, b, precision=HIGHEST)

    return mm


def nll_sum(logits: jax.Array, labels: jax.Array, vocab: int) -> jax.Array:
    """Summed next-token negative log-likelihood over the first ``vocab``
    columns of ``logits`` (B, S, V_any)."""
    logits = logits[..., :vocab]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


def adamw_step(params, grads, m, v, t: int, hp: dict[str, float]):
    """One AdamW step with global-norm clipping and a linear warmup, as the
    configuration's optimizer states it. ``t`` counts from 0."""
    lr = hp["lr"] * min(1.0, (t + 1) / max(hp["warmup_steps"], 1))
    if t >= hp["warmup_steps"]:
        prog = min(max((t - hp["warmup_steps"]) / max(hp["total_steps"] - hp["warmup_steps"], 1), 0.0), 1.0)
        lr = hp["lr"] * (hp["min_lr_ratio"] + (1 - hp["min_lr_ratio"]) * 0.5 * (1 + math.cos(math.pi * prog)))
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    b1, b2, eps, wd = hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"]
    tt = t + 1

    def upd(p, g, m_, v_):
        g = g * clip
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        mhat = m_ / (1 - b1 ** tt)
        vhat = v_ / (1 - b2 ** tt)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p), m_, v_

    out = jax.tree.map(upd, params, grads, m, v)
    istuple = lambda x: isinstance(x, tuple)
    new_p = jax.tree.map(lambda o: o[0], out, is_leaf=istuple)
    new_m = jax.tree.map(lambda o: o[1], out, is_leaf=istuple)
    new_v = jax.tree.map(lambda o: o[2], out, is_leaf=istuple)
    return new_p, new_m, new_v, clip


def leaf_norms(tree: Any) -> list[float]:
    leaves = jax.tree.leaves(tree)
    out = jax.jit(lambda ls: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in ls])(leaves)
    return [float(x) for x in jax.device_get(out)]


def worst_leaf_gap(prog: list[float], ref: list[float], keep: list[bool] | None = None) -> float:
    """max over leaves of |prog - ref| / max(ref, median ref leaf)."""
    ref_a = np.asarray(ref, np.float64)
    prog_a = np.asarray(prog, np.float64)
    floor = float(np.median(ref_a))
    keep_a = np.ones(len(ref_a), bool) if keep is None else np.asarray(keep)
    den = np.maximum(ref_a, floor)
    gaps = np.abs(prog_a - ref_a) / np.where(den > 0, den, 1.0)
    return float(np.max(gaps[keep_a])) if keep_a.any() else 0.0


def worst_logit_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Widest gap by which the logit of a chosen token lies below the
    reference's best, over all positions. ``ref_logits`` (T, V), ``tokens``
    (T,) the token chosen at each position."""
    best = ref_logits.max(axis=-1)
    chosen = np.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return float(np.max(best - chosen))
