"""The granite4h-serve-hybrid cell end to end on the host CPU at a tiny size:
the window, the result line, the per-layer readers and ``correct``, a served
token altered where it is produced, and the control. The cell's tiny widths
and traffic join ``tiny``'s tables for these tests only."""

import json

import pytest

import harness
import test_rehearsal
import tiny
from test_rehearsal import V5E, on_cpu, run_cell

CELL = "granite4h-serve-hybrid"
CONFIG = "granite-4.0-h-small"
TRAFFIC = "serve-32x1024x2048"
# One whole period at tiny widths: 16 experts, the block [4, 8) held, top-4.
# Narrower (d_model 64, 8 experts) a token's routing flips between bfloat16
# and the float32 reference often enough that sound runs read as much as the
# fp8 control.
TINY = {"d_model": 128, "num_heads": 4, "num_kv_heads": 2, "head_dim": 32, "d_ff": 64,
        "shared_d_ff": 96, "vocab_size": 256, "num_experts": 16, "experts_per_tok": 4,
        "expert_start": 4, "experts_held": 4, "ssm_state": 16, "ssm_headdim": 16}
# CPU rehearsals at these widths, seeds 11 to 15, 2**31 + 5 and 2**31 + 77:
# sound runs read up to 4.3e-4, the fp8 control 1.3e-3 and more, an altered
# token 1.5e-3 and more (the logits spread about 2e-3).
TINY_LIMIT = {"logit_gap": 8e-4}


@pytest.fixture
def tiny_cell(monkeypatch):
    monkeypatch.setitem(tiny.TINY_MODEL, CONFIG, TINY)
    monkeypatch.setitem(tiny.TINY_TRAFFIC, TRAFFIC,
                        {"batch": 4, "prompt_len": 16, "decode_budget": 64})
    monkeypatch.setitem(test_rehearsal.TINY_LIMITS, CELL, TINY_LIMIT)


def test_program_keys_are_the_published_sizes():
    """The keys the program reads agree with the catalog's keys beside them,
    but for the depth and the experts held, which ``reduced`` names."""
    c = json.loads((tiny.BENCH / "configs" / f"{CONFIG}.json").read_text())
    same = {"d_model": "hidden_size", "num_heads": "num_attention_heads",
            "num_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
            "shared_d_ff": "shared_intermediate_size", "vocab_size": "vocab_size",
            "experts_per_tok": "num_experts_per_tok", "ssm_state": "mamba_d_state",
            "ssm_expand": "mamba_expand", "ssm_headdim": "mamba_d_head",
            "ssm_conv": "mamba_d_conv", "norm_eps": "rms_norm_eps",
            "embedding_multiplier": "embedding_multiplier",
            "attention_multiplier": "attention_multiplier",
            "residual_multiplier": "residual_multiplier", "logits_scaling": "logits_scaling",
            "tie_embeddings": "tie_word_embeddings", "num_layers": "num_hidden_layers",
            "experts_held": "num_local_experts"}
    for prog, pub in same.items():
        assert c[prog] == c[pub], (prog, pub)
    assert c["num_experts"] == c["published"]["num_local_experts"] == 72
    assert c["d_model"] // c["num_heads"] == c["head_dim"]
    assert c["d_model"] * c["ssm_expand"] // c["ssm_headdim"] == c["mamba_n_heads"]
    assert c["use_rope"] is (c["position_embedding_type"] != "nope")
    assert sorted(c["reduced"]) == ["num_hidden_layers", "num_local_experts"]
    assert c["layer_types"][: c["num_layers"]].count("attention") == 1


@pytest.mark.parametrize("trace", [0, 1])
def test_cell(tiny_cell, tmp_path, monkeypatch, capsys, trace):
    rc, out = run_cell(tmp_path, monkeypatch, capsys, CELL, trace=trace)
    assert rc == 0 and out["correct"] is True, out["checks"]
    spec = harness.load_cell(CELL)
    block = spec.per_layer if trace else spec.end_to_end
    assert set(out["metrics"]) == {m["name"] for m in block}
    if trace:
        # A save changes 16 of the 82 positions of the KV rows and the
        # tokens, and rewrites the SSM states and conv windows whole.
        share = out["metrics"]["capture_clean_share"]["value"]
        assert 0 < share < 100
    assert list(out)[-1] == "checks"


def test_altered_token_is_caught(tiny_cell, tmp_path, monkeypatch, capsys):
    import jax.numpy as jnp

    from repro.models.model import Model

    decode = Model.decode_step

    def altered(self, params, cache, token, pos, ctx=None):
        logits, cache = decode(self, params, cache, token, pos, ctx=ctx)
        other = (jnp.argmax(logits, axis=-1) + 1) % self.cfg.vocab_size
        bumped = logits.at[jnp.arange(logits.shape[0]), other].set(1e4)
        return jnp.where(pos == 16 + 20, bumped, logits), cache

    monkeypatch.setattr(Model, "decode_step", altered)
    rc, out = run_cell(tmp_path, monkeypatch, capsys, CELL, seconds=1)
    assert rc == 0 and out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > out["checks"]["logit_gap"]["limit"]


def test_control_fails(tiny_cell, tmp_path, monkeypatch):
    root, bench = tiny.build(tmp_path, test_rehearsal.TINY_LIMITS)
    monkeypatch.setattr(harness, "peaks", lambda kind: V5E)
    spec = harness.load_cell(CELL, root, bench)
    ctx = harness.Context(spec, 2**31 + 5, 1.0, False, on_cpu(1), harness.CompileClock(),
                          harness.now(), tmp_path / "t", control=True)
    out = spec.driver.run(ctx)
    assert out.checks["logit_gap"][0] <= spec.limits["logit_gap"]
    for fault, reading in out.record["control"].items():
        assert reading > spec.limits["logit_gap"], (fault, reading)
