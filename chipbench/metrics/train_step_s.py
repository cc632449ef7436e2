"""Median of the program's ``timers("train_step")`` over the traced window:
the step alone, which ends in ``block_until_ready``."""

import statistics


def read(rec):
    steps = rec.get("train_step_s") or []
    return statistics.median(steps) if steps else None
