"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy and idle time,
the device operations that took most time, and the longest idle gaps named
by what the host was doing in them.

Device time is the union of the intervals of the events on each TPU plane's
``XLA Ops`` line. Host spans are given on ``time.perf_counter``'s clock and
brought onto the trace's clock through one marker, a
``jax.profiler.TraceAnnotation`` named ``SYNC_MARK`` whose perf_counter
reading the caller took inside it.
"""

from __future__ import annotations

import bisect
import glob
import re
from pathlib import Path
from typing import Any

SYNC_MARK = "chipbench_sync"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str | Path) -> Path:
    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(paths[-1])


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def _op_name(full: str) -> str:
    """'%fusion.3 = bf16[...] fusion(...)' -> 'fusion.3'."""
    head = full.split(" = ", 1)[0].strip()
    return head.lstrip("%") or full[:64]


def _module_name(full: str) -> str:
    return re.sub(r"\(\d+\)$", "", full)


def load(path: str | Path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def sync_offset_ns(data, sync_perf_s: float) -> int:
    """trace_ns - perf_counter_ns at the sync marker."""
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SYNC_MARK:
                    return int(ev.start_ns) - int(round(sync_perf_s * 1e9))
    raise ValueError(f"no {SYNC_MARK!r} event in the trace's host planes")


def reduce(
    data,
    window_ns: tuple[int, int],
    host_spans_ns: list[tuple[str, int, int]] = (),
    top: int = 10,
) -> dict[str, Any]:
    """Busy and idle seconds per chip over ``window_ns`` (trace clock), the
    ``top`` device operations by total time, and the ``top`` longest idle
    gaps, each named by the host span that covers most of it."""
    lo, hi = window_ns
    busy_per_chip: list[float] = []
    op_time: dict[str, float] = {}
    gaps: list[tuple[int, int]] = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        modules = sorted(
            (int(e.start_ns), int(e.start_ns + e.duration_ns), _module_name(e.name))
            for e in (lines[MODULES_LINE].events if MODULES_LINE in lines else [])
        )
        starts = [m[0] for m in modules]
        ivals = []
        for e in lines[OPS_LINE].events:
            a = int(e.start_ns)
            b = a + int(e.duration_ns)
            if b <= lo or a >= hi:
                continue
            ivals.append((a, b))
            i = bisect.bisect_right(starts, a) - 1
            mod = modules[i][2] if i >= 0 and modules[i][1] >= a else "?"
            key = f"{mod}/{_op_name(e.name)}"
            op_time[key] = op_time.get(key, 0.0) + (min(b, hi) - max(a, lo)) / 1e9
        busy = _union(_clip(ivals, lo, hi))
        busy_per_chip.append(sum(b - a for a, b in busy) / 1e9)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not busy_per_chip:
        raise ValueError("the trace holds no TPU plane with device operations")
    window_s = (hi - lo) / 1e9
    gaps.sort(key=lambda g: g[0] - g[1])
    named_gaps = []
    for a, b in gaps[:top]:
        named_gaps.append([_attribute(a, b, host_spans_ns), (b - a) / 1e9])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy_per_chip) / len(busy_per_chip),
        "window_s": window_s,
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": named_gaps,
    }


def _attribute(a: int, b: int, spans: list[tuple[str, int, int]]) -> str:
    """The innermost (shortest) host span that covers the most of [a, b]."""
    best, best_cover, best_len = "host (no span)", 0, 0
    for name, s0, s1 in spans:
        cover = min(b, s1) - max(a, s0)
        if cover <= 0:
            continue
        length = s1 - s0
        if cover > best_cover or (cover == best_cover and length < best_len):
            best, best_cover, best_len = name, cover, length
    return best
