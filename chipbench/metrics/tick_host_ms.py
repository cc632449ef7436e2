"""Median over the traced window's ticks of the program's ``decode_tick``
span less the ``decode_step`` span inside it: the host's own work in a
serving tick (barriers, the finalize of a pending save, the token update,
and on a save tick the capture)."""

import statistics

import spans


def read(rec):
    evs = spans.events()
    steps = spans.named(evs, "decode_step")
    host = [t["dur"] - sum(s["dur"] for s in steps if spans.inside(s, t))
            for t in spans.named(evs, "decode_tick")]
    return 1e3 * statistics.median(host) if host else None
