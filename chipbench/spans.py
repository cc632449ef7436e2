"""The program's own spans of the traced window, for the per-layer readers.

``harness.TraceWindow`` resets the program's tracer (``repro.obs.trace``) on
entry and disables it on exit, so what the tracer holds after the window is
exactly the window's spans. A program that does not emit a span gives
nothing to read, and the reader that wants it returns None.
"""

from __future__ import annotations

import statistics
from typing import Any


def events() -> list[dict[str, Any]]:
    """Every span the window recorded: name, t0 and dur in seconds, tid, args."""
    from repro.obs.trace import tracer

    return tracer().events()


def named(evs: list[dict[str, Any]], name: str) -> list[dict[str, Any]]:
    return [e for e in evs if e["name"] == name]


def inside(child: dict[str, Any], parent: dict[str, Any]) -> bool:
    """``child`` ran on ``parent``'s thread within its interval."""
    return (child["tid"] == parent["tid"] and child["t0"] >= parent["t0"]
            and child["t0"] + child["dur"] <= parent["t0"] + parent["dur"])


def per_parent_s(parent: str, child: str) -> float | None:
    """Seconds of ``child`` spans nested in ``parent`` spans, over the number
    of ``parent`` spans: the child's mean share of one parent."""
    evs = events()
    parents, children = named(evs, parent), named(evs, child)
    if not parents or not children:
        return None
    return sum(c["dur"] for c in children if any(inside(c, p) for p in parents)) / len(parents)


def median_ms(name: str) -> float | None:
    durs = [e["dur"] for e in named(events(), name)]
    return 1e3 * statistics.median(durs) if durs else None
