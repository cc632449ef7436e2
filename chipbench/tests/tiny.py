"""A copy of the benchmark's tree at CPU size: the same drivers, readers and
configuration modules, with tiny widths, short traffic and its own
BENCHMARK.json, so a test can run every cell end to end on the host."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY_MODEL = {
    "mamba2-780m": {"num_layers": 2, "d_model": 64, "vocab_size": 256, "ssm_state": 16,
                    "ssm_headdim": 16},
    "granite-3-8b": {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
                     "head_dim": 16, "d_ff": 128, "vocab_size": 256},
}
TINY_TRAFFIC = {
    "train-save4": {"batch": 4, "seq": 32},
    "train-kill": {"batch": 4, "seq": 32},
    "serve-16x1024": {"batch": 4, "prompt_len": 16, "decode_budget": 64},
    "serve-128x512": {"batch": 4, "prompt_len": 16, "decode_budget": 64},
}


def build(tmp: Path, limits: dict[str, dict] | None = None) -> tuple[Path, Path]:
    """(repo_root, bench_dir) of a tiny copy under ``tmp``."""
    root = tmp / "repo"
    bench = root / "chipbench"
    (root / "src").mkdir(parents=True)
    (root / "src" / "repro").symlink_to(REPO / "src" / "repro")
    for sub in ("drivers", "metrics", "configs", "traffic", "limits"):
        shutil.copytree(BENCH / sub, bench / sub)
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    for name, over in TINY_MODEL.items():
        p = bench / "configs" / f"{name}.json"
        cfg = json.loads(p.read_text())
        cfg.update(over)
        p.write_text(json.dumps(cfg))
    for name, over in TINY_TRAFFIC.items():
        p = bench / "traffic" / f"{name}.json"
        t = json.loads(p.read_text())
        t.update(over)
        p.write_text(json.dumps(t))
    for cell, lim in (limits or {}).items():
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(lim))
    return root, bench
