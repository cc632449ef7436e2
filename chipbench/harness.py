"""What every cell of the chip benchmark shares: finding a cell's files by
name, the device check, the peak table, host-memory readings, compile
accounting and on-device bit hashes.

Everything that belongs to one configuration, one traffic mix, one loop kind
or one per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives:

    configs/<file named by the config entry>  + configs/<config>.py
    traffic/<traffic>.json                    (names its driver)
    drivers/<driver>.py                       (exposes ``run(ctx)``)
    metrics/<metric>.py                       (exposes ``read(rec)``; a metric
                                               split by kind, ``<stem>.<kind>``,
                                               falls back to metrics/<stem>.py)
    limits/<cell>.json                        (the limits ``correct`` uses)

so a later change adds a cell, a configuration, a loop kind or a metric by
adding files, with no edit to a file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import resource
import sys
import time
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

BENCH_DIR = Path(__file__).resolve().parent
REPO_ROOT = BENCH_DIR.parent


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, a missing file, a bad cell)."""


# --------------------------------------------------------------------------
# finding a cell's files
# --------------------------------------------------------------------------

def load_module(path: Path, name: str | None = None):
    """Import a Python file by path (names may hold '-' or '.')."""
    spec = importlib.util.spec_from_file_location(
        name or "chipbench_" + path.stem.replace("-", "_").replace(".", "_"), path
    )
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> Any:
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config: dict[str, Any]          # the configuration's JSON file
    config_mod: Any                 # configs/<config>.py: init, reference, FLOPs
    traffic: dict[str, Any]         # traffic/<traffic>.json
    driver: Any                     # drivers/<driver>.py
    limits: dict[str, float]        # limits/<cell>.json
    end_to_end: list[dict[str, Any]]
    per_layer: list[dict[str, Any]]
    readers: dict[str, Any] = field(default_factory=dict)


def _applies(metric: dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, repo_root: Path = REPO_ROOT, bench_dir: Path = BENCH_DIR) -> Cell:
    bench = read_json(repo_root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {name} names unknown config {w['config']!r}")
    centry = configs[w["config"]]
    cfile = repo_root / centry["file"]
    config = read_json(cfile)
    config_mod = load_module(cfile.with_suffix(".py"))
    traffic = read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    driver = load_module(bench_dir / "drivers" / f"{traffic['driver']}.py")
    limits = read_json(bench_dir / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    cell = Cell(name, int(w["chips"]), config, config_mod, traffic, driver,
                limits, e2e, per_layer)
    for m in per_layer:
        cell.readers[m["name"]] = load_module(reader_path(bench_dir, m["name"]))
    return cell


def reader_path(bench_dir: Path, metric: str) -> Path:
    """metrics/<metric>.py, or for ``<stem>.<kind>`` with no file of its own,
    the reader its kinds share, metrics/<stem>.py."""
    own = bench_dir / "metrics" / f"{metric}.py"
    if own.is_file() or "." not in metric:
        return own
    return bench_dir / "metrics" / f"{metric.split('.', 1)[0]}.py"


@dataclass
class Outcome:
    """What a driver hands back."""

    e2e: dict[str, float]                       # end-to-end metrics by name
    record: dict[str, Any]                      # what the per-layer readers read
    checks: dict[str, tuple[float, float]]      # name -> (value, limit)
    attempted: int
    failed: int
    device_peak_bytes: int
    trace: dict[str, Any] | None = None         # xplane.reduce() of the traced window


@dataclass
class Context:
    """What a driver is given."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    clock: "CompileClock"
    t_start: float
    trace_dir: Path
    notes: list[str] = field(default_factory=list)
    control: bool = False       # also read the control and planted faults (control.py only)

    @property
    def key_seed(self) -> int:
        """The seed folded to the 32 bits a PRNG key takes (any --seed works)."""
        return self.seed % 2**32

    def program_config(self):
        """The program's ModelConfig for this cell's configuration file."""
        import jax.numpy as jnp

        from repro.configs import get_config

        c = self.cell.config
        kw = {k: c[k] for k in c["program_keys"]}
        for k in ("param_dtype", "compute_dtype"):
            if k in kw:
                kw[k] = getattr(jnp, kw[k])
        return get_config(c["program_arch"]).with_(**kw)


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------

def device_check(chips: int) -> list:
    """The chips this cell asks for, or BenchError: no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(
            f"no TPU: JAX's default device is {devices[0].platform!r}"
        )
    if len(devices) < chips:
        raise BenchError(f"cell needs {chips} chip(s), JAX reports {len(devices)}")
    return devices[:chips]


def peaks(device_kind: str) -> dict[str, Any]:
    table = read_json(BENCH_DIR / "peaks.json")
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def device_info(devices: list) -> dict[str, Any]:
    d0 = devices[0]
    peak = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices
    )
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


# --------------------------------------------------------------------------
# host memory
# --------------------------------------------------------------------------

def host_rss() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def host_peak_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


# --------------------------------------------------------------------------
# compile accounting (after chip_smoke.py's CompileClock)
# --------------------------------------------------------------------------

class CompileClock:
    """Sums JAX's own compile-duration events (trace, lowering, backend)."""

    def __init__(self) -> None:
        import jax

        self.secs: Counter = Counter()
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_kw) -> None:
        if name.startswith("/jax/core/compile/"):
            self.secs[name.rsplit("/", 1)[-1]] += secs
            if name.endswith("backend_compile_duration"):
                self.events += 1

    def total(self) -> float:
        return sum(self.secs.values())


def seeded_params(cfgmod, config, key, model):
    """The configuration's seeded weights, made on the device in one jitted
    call, after checking that the program lays its parameters out alike."""
    import jax
    import jax.numpy as jnp

    want = jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype)), model.param_shape_dtypes())
    got = jax.eval_shape(lambda k: cfgmod.init_params(k, config), key)
    got = jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype)), got)
    if want != got:
        raise BenchError(f"{config['name']}: the program's parameter layout differs "
                         "from the configuration's")
    return jax.jit(lambda k: cfgmod.init_params(k, config))(key)


# --------------------------------------------------------------------------
# bit-exact comparison on the device
# --------------------------------------------------------------------------

def tree_digest(tree: Any) -> list[tuple[int, int]]:
    """Per leaf, two 32-bit folds of its bits (a position-weighted sum and an
    xor), computed on the device: equal digests for equal bits, and any
    change of a word changes the weighted sum."""
    import jax
    import jax.numpy as jnp

    def one(x):
        x = jnp.asarray(x)
        if x.dtype.itemsize == 1:
            w = jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
        elif x.dtype.itemsize == 2:
            w = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        else:
            w = jax.lax.bitcast_convert_type(x, jnp.uint32)
        w = w.reshape(-1)
        idx = jnp.arange(w.shape[0], dtype=jnp.uint32)
        weighted = jnp.sum(w * (idx * jnp.uint32(2654435761) + jnp.uint32(1)),
                           dtype=jnp.uint32)
        folded = jax.lax.reduce(w, jnp.uint32(0), jax.lax.bitwise_xor, (0,))
        return jnp.stack([weighted, folded])

    leaves = jax.tree.leaves(tree)
    out = jax.jit(lambda ls: [one(x) for x in ls])(leaves)
    return [tuple(int(v) for v in jax.device_get(o)) for o in out]


def now() -> float:
    return time.perf_counter()


def setup_note(ctx, marks: list[tuple[str, float]]) -> str:
    """Where set-up went: process start to the driver, then each mark."""
    parts = [f"to driver {marks[0][1] - ctx.t_start:.3f} s"]
    parts += [f"{name} {t - marks[i][1]:.3f} s" for i, (name, t) in enumerate(marks[1:])]
    return "setup: " + ", ".join(parts) + f"; compile {ctx.clock.total():.3f} s"


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# the traced window
# --------------------------------------------------------------------------

class TraceWindow:
    """Profiles a window of whole cycles with ``jax.profiler`` and the
    program's own span tracer, and reduces it with ``xplane.reduce``."""

    def __init__(self, trace_dir: Path) -> None:
        self.dir = Path(trace_dir)
        self.t0 = self.t1 = self.sync = 0.0
        self.spans: list[tuple[str, float, float]] = []

    def __enter__(self) -> "TraceWindow":
        import jax

        from repro.obs.trace import tracer
        from xplane import SYNC_MARK

        self.dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.dir))
        tracer().reset()
        tracer().enable()
        with jax.profiler.TraceAnnotation(SYNC_MARK):
            self.sync = time.perf_counter()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        import jax

        from repro.obs.trace import tracer

        self.t1 = time.perf_counter()
        tr = tracer()
        tr.disable()
        jax.profiler.stop_trace()
        base = getattr(tr, "_t0", self.t0)
        self.spans += [(e["name"], base + e["t0"], base + e["t0"] + e["dur"])
                       for e in tr.events()]

    def reduce(self) -> dict[str, Any]:
        import xplane

        data = xplane.load(xplane.find_xplane(self.dir))
        off = xplane.sync_offset_ns(data, self.sync)
        ns = lambda t: int(round(t * 1e9)) + off  # noqa: E731
        spans = [(n, ns(a), ns(b)) for n, a, b in self.spans]
        return xplane.reduce(data, (ns(self.t0), ns(self.t1)), spans)
