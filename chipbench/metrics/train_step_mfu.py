"""The FLOPs one train step requires (the configuration's own count: 6 N per
token plus the SSD scan, recomputation excluded) over the median step time
and the chip's bf16 peak, in percent."""

import statistics


def read(rec):
    steps = rec.get("train_step_s") or []
    if not steps or not rec.get("flops_per_step"):
        return None
    return 100.0 * rec["flops_per_step"] / (statistics.median(steps) * rec["peak_flops"])
