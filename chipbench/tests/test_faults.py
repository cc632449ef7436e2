"""A run whose timed path is broken underneath must come out not correct:
a train step that leaves its state unchanged, one that leaves half of the
batch out, and a served token altered where it is produced. (One chip: no
exchange between chips to leave out.) The control, the reference in fp8 put
in the program's place, must fail too."""

import jax
import jax.numpy as jnp
import pytest

import harness
from test_rehearsal import TINY_LIMITS, V5E, on_cpu, run_cell
import tiny


def _wrap_step(monkeypatch, fault):
    from repro.runtime.trainer import Trainer

    build = Trainer._build_train_step

    def broken(self):
        step = build(self)

        def run(state, batch):
            if fault == "half_batch":
                half = batch["tokens"].shape[0] // 2
                return step(state, {k: v[:half] for k, v in batch.items()})
            new, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return {**state, "step": new["step"]}, metrics

        return run

    monkeypatch.setattr(Trainer, "_build_train_step", broken)


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_train_fault_is_caught(fault, tmp_path, monkeypatch, capsys):
    _wrap_step(monkeypatch, fault)
    rc, out = run_cell(tmp_path, monkeypatch, capsys, "mamba2-train-save4", seconds=1)
    assert rc == 0
    assert out["correct"] is False
    failed = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert failed & {"grad_gap", "change_gap", "loss_gap"}


@pytest.mark.parametrize("cell", ["granite-serve-kv", "mamba2-serve-ssm"])
def test_altered_token_is_caught(cell, tmp_path, monkeypatch, capsys):
    from repro.models.model import Model

    decode = Model.decode_step
    prompt = tiny.TINY_TRAFFIC[{"granite-serve-kv": "serve-16x1024",
                                "mamba2-serve-ssm": "serve-128x512"}[cell]]["prompt_len"]

    def altered(self, params, cache, token, pos, ctx=None):
        logits, cache = decode(self, params, cache, token, pos, ctx=ctx)
        other = (jnp.argmax(logits, axis=-1) + 1) % self.cfg.vocab_size
        bumped = logits.at[jnp.arange(logits.shape[0]), other].set(1e4)
        return jnp.where(pos == prompt + 20, bumped, logits), cache

    monkeypatch.setattr(Model, "decode_step", altered)
    rc, out = run_cell(tmp_path, monkeypatch, capsys, cell, seconds=1)
    assert rc == 0
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > out["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("cell", ["mamba2-train-save4", "granite-serve-kv", "mamba2-serve-ssm"])
def test_control_fails(cell, tmp_path, monkeypatch):
    """The control and the planted faults read above the limits (as
    ``control.py`` reads them on the chip at the cells' own sizes)."""
    root, bench = tiny.build(tmp_path, TINY_LIMITS)
    monkeypatch.setattr(harness, "peaks", lambda kind: V5E)
    spec = harness.load_cell(cell, root, bench)
    ctx = harness.Context(spec, 2**31 + 5, 1.0, False, on_cpu(1), harness.CompileClock(),
                          harness.now(), tmp_path / "t", control=True)
    out = spec.driver.run(ctx)
    for fault, reading in out.record["control"].items():
        if isinstance(reading, dict):
            assert any(v > lim for v, lim in reading.values()), (fault, reading)
        else:
            assert reading > spec.limits["logit_gap"], (fault, reading)
