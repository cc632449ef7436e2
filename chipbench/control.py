"""Read the control and the planted faults of a cell on its own path, for
the limits of ``limits/<cell>.json``. The benchmark's own runs never do this.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds 11 12 13

For each seed, one process runs the cell as ``run.py`` does and then also:
for a train cell, the reference in fp8 (the control) and the reference with
half of each batch left out; for a serve cell, the reference in fp8 (the gap
of the token fp8 puts first) and one served token altered. A state left
unchanged reads 1 on the change by construction and needs no run. One JSON
line per seed: the program's numbers (``checks``) and the faults'
(``control``).
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.REPO_ROOT / "src"))
    devices = harness.device_check(cell.chips)
    import jax

    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    clock = harness.CompileClock()
    for seed in args.seeds:
        trace_dir = harness.REPO_ROOT / ".chipbench_out" / f"control-{seed}"
        ctx = harness.Context(cell, seed, args.seconds, False, devices, clock,
                              time.perf_counter(), trace_dir, control=True)
        out = cell.driver.run(ctx)
        shutil.rmtree(trace_dir, ignore_errors=True)
        for note in ctx.notes:
            harness.say(note)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "checks": {k: v for k, (v, _) in out.checks.items()},
            "control": {f: ({k: v for k, (v, _) in r.items()} if isinstance(r, dict) else r)
                        for f, r in out.record["control"].items()},
            "e2e": out.e2e,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
