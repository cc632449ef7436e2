"""Run one cell of the chip benchmark and print its result as one JSON line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic, loop kind (driver), per-layer metric
readers and correctness limits are found by name (see ``harness.py``). With
``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` a profiled window of whole cycles gives its per-layer metrics,
the device's busy time and the breakdown. Without the TPU the cell asks for,
the command exits non-zero and prints no result. The last lines on standard
error, and the result's last key, are each number that ``correct`` compared
beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
from harness import BenchError, say  # noqa: E402

OUT_DIR = harness.REPO_ROOT / ".chipbench_out"


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _metric_block(names: list[dict[str, Any]], values: dict[str, float]) -> dict[str, Any]:
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in names if values.get(m["name"]) is not None}


def main(argv: list[str] | None = None, repo_root: Path = harness.REPO_ROOT,
         bench_dir: Path = harness.BENCH_DIR, device_check=None) -> int:
    args = _parse(argv)
    try:
        cell = harness.load_cell(args.workload, repo_root, bench_dir)
        if not (repo_root / "src" / "repro").is_dir():
            raise BenchError(f"no program under {repo_root / 'src'}")
        if str(repo_root / "src") not in sys.path:
            sys.path.insert(0, str(repo_root / "src"))
        devices = (device_check or harness.device_check)(cell.chips)
    except BenchError as e:
        say(f"chipbench: {e}")
        return 1

    import jax

    from repro.utils.compile_cache import use_compile_cache

    say(f"compile cache: {use_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    trace_dir = OUT_DIR / f"trace-{cell.name}-{args.seed}"
    ctx = harness.Context(cell, args.seed, args.seconds, bool(args.trace), devices,
                  harness.CompileClock(), T_START, trace_dir)
    try:
        out: harness.Outcome = cell.driver.run(ctx)
    except BenchError as e:
        say(f"chipbench: {e}")
        return 1
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    if args.trace:
        values = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(out.record)
            if v is not None:
                values[m["name"]] = v
        metrics = _metric_block(cell.per_layer, values)
    else:
        missing = [m["name"] for m in cell.end_to_end if out.e2e.get(m["name"]) is None]
        if missing:
            say(f"chipbench: the run measured no {missing}")
            return 1
        metrics = _metric_block(cell.end_to_end, out.e2e)

    info = harness.device_info(devices)
    info["memory_peak_bytes"] = out.device_peak_bytes
    result: dict[str, Any] = {
        "correct": all(v <= lim for v, lim in out.checks.values()),
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": metrics,
        "device": info,
    }
    if args.trace and out.trace is not None:
        info["busy_s"] = out.trace["busy_s"]
        info["window_s"] = out.trace["window_s"]
        result["breakdown"] = {"device_ops": out.trace["device_ops"],
                               "idle_gaps": out.trace["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in out.checks.items()}
    for note in ctx.notes:
        say(note)
    for k, (v, lim) in out.checks.items():
        say(f"check {k} {v!r} limit {lim!r} {'ok' if v <= lim else 'FAIL'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
