"""A configuration, a traffic mix, a loop kind, a cell and a per-layer
metric are added as new files, with no edit to a file that is there."""

import json
import shutil

import harness
from tiny import BENCH, REPO


def test_new_files_are_found_by_name(tmp_path):
    root, bench = tmp_path, tmp_path / "chipbench"
    for sub in ("configs", "traffic", "drivers", "metrics", "limits"):
        shutil.copytree(BENCH / sub, bench / sub)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    before = {p.relative_to(bench): p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    # New files only: a configuration (sizes + module), a traffic mix naming a
    # new loop kind, that driver, a metric's reader and the cell's limits.
    (bench / "configs" / "toy-1.json").write_text(json.dumps({"name": "toy-1", "layers": 1}))
    (bench / "configs" / "toy-1.py").write_text("WIDTH = 7\n")
    (bench / "traffic" / "stencil-2d.json").write_text(json.dumps({"driver": "stencil", "n": 3}))
    (bench / "drivers" / "stencil.py").write_text("def run(ctx):\n    return 'stencil'\n")
    (bench / "metrics" / "halo_s.stencil.py").write_text(
        "def read(rec):\n    return rec.get('halo_s')\n")
    (bench / "limits" / "toy-1.stencil.json").write_text(json.dumps({"residual": 1e-6}))
    spec["configs"].append({"name": "toy-1", "source": "https://example.org/toy",
                            "file": "chipbench/configs/toy-1.json", "reduced": [],
                            "why": "a test"})
    spec["workloads"].append({"name": "toy-1.stencil", "config": "toy-1",
                              "traffic": "stencil-2d", "chips": 1, "why": "a test"})
    spec["per_layer"].append({"name": "halo_s.stencil", "unit": "s", "better": "lower",
                              "source": "program_span", "layer": "halo",
                              "moves": "setup_s", "workloads": ["toy-1.stencil"]})
    # A metric split by kind with no reader of its own reads with the shared one.
    spec["per_layer"].append({"name": "device_idle.stencil", "unit": "%", "better": "lower",
                              "source": "device_trace", "layer": "device",
                              "moves": "setup_s", "workloads": ["toy-1.stencil"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("toy-1.stencil", root, bench)
    assert cell.config["layers"] == 1 and cell.config_mod.WIDTH == 7
    assert cell.traffic["n"] == 3 and cell.driver.run(None) == "stencil"
    assert cell.limits == {"residual": 1e-6}
    assert [m["name"] for m in cell.per_layer] == ["halo_s.stencil", "device_idle.stencil"]
    assert cell.readers["halo_s.stencil"].read({"halo_s": 0.5}) == 0.5
    assert cell.readers["device_idle.stencil"].read(
        {"trace": {"busy_s": 1.0, "window_s": 4.0}}) == 75.0
    assert {m["name"] for m in cell.end_to_end} == {"host_bytes_per_state_byte", "setup_s"}

    after = {p.relative_to(bench): p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    assert all(after[k] == v for k, v in before.items())

    # The cells that were there load as before, with their own metrics only.
    save = harness.load_cell("mamba2-train-save4", root, bench)
    assert "halo_s.stencil" not in save.readers
    assert {m["name"] for m in save.end_to_end} == {
        "train_tokens_per_s", "host_bytes_per_state_byte", "setup_s"}


def test_unknown_names_are_errors(tmp_path):
    import pytest

    with pytest.raises(harness.BenchError):
        harness.load_cell("no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v0 imaginary")
