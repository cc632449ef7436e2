"""Every cell end to end on the host CPU at a tiny size: the window, the
result line, the per-layer readers and ``correct``. The device check is
steered from here; the trace reduction reads the recorded v5e trace, since a
CPU trace holds no TPU plane."""

import json

import jax
import pytest

import harness
import run
import tiny
import xplane
from test_xplane import TRACE, WINDOW

CELLS = ["mamba2-train-save4", "granite-serve-kv", "mamba2-train-kill", "mamba2-serve-ssm"]
# Limits for the tiny widths, from CPU rehearsals (sound runs read loss 5e-5,
# gradient 4e-3, change 2e-3 and a logit gap of 2e-3; the fp8 control
# reads a logit gap of 0.04 and more).
TINY_LIMITS = {
    "mamba2-train-save4": {"loss_gap": 1e-3, "grad_gap": 0.05, "change_gap": 0.05},
    "mamba2-train-kill": {"loss_gap": 1e-3, "grad_gap": 0.05, "change_gap": 0.05},
    "granite-serve-kv": {"logit_gap": 0.01},
    "mamba2-serve-ssm": {"logit_gap": 0.01},
}
V5E = json.loads((tiny.BENCH / "peaks.json").read_text())["TPU v5 lite"]


def on_cpu(n):
    return jax.devices()[:n]


def run_cell(tmp_path, monkeypatch, capsys, cell, trace=0, seconds=2):
    root, bench = tiny.build(tmp_path, TINY_LIMITS)
    monkeypatch.setattr(harness, "peaks", lambda kind: V5E)
    monkeypatch.setattr(harness.TraceWindow, "reduce",
                        lambda self: xplane.reduce(xplane.load(TRACE), WINDOW))
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 77), "--seconds", str(seconds),
                   "--trace", str(trace)], repo_root=root, bench_dir=bench, device_check=on_cpu)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end(cell, tmp_path, monkeypatch, capsys):
    rc, out = run_cell(tmp_path, monkeypatch, capsys, cell)
    assert rc == 0
    assert out["correct"] is True, out["checks"]
    spec = harness.load_cell(cell)
    assert set(out["metrics"]) == {m["name"] for m in spec.end_to_end}
    # The host's peak RSS is the test process's, which earlier tests may have set.
    assert all(m["value"] > 0 for k, m in out["metrics"].items()
               if k != "host_bytes_per_state_byte"), out["metrics"]
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] == 1 and out["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_cell_traced(cell, tmp_path, monkeypatch, capsys):
    rc, out = run_cell(tmp_path, monkeypatch, capsys, cell, trace=1)
    assert rc == 0 and out["correct"] is True, out["checks"]
    spec = harness.load_cell(cell)
    assert set(out["metrics"]) == {m["name"] for m in spec.per_layer}
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]


def test_no_tpu_no_result(capsys):
    rc = run.main(["--workload", "mamba2-train-save4", "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_fail(tmp_path, capsys):
    import shutil

    root = tmp_path / "bare"
    shutil.copytree(tiny.BENCH, root / "chipbench")
    shutil.copy(tiny.REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    rc = run.main(["--workload", "granite-serve-kv", "--seed", "1", "--seconds", "1"],
                  repo_root=root, bench_dir=root / "chipbench", device_check=on_cpu)
    assert rc != 0
    assert capsys.readouterr().out == ""
