"""Share of the bytes packed per save that belong to leaves every rank holds
whole (``ShardPlan.split_dim`` None), counted once per rank copy: the sum of
the ``capture_pack`` span's ``replicated_bytes`` label over the sum of its
``bytes`` label in the traced window, in percent."""

import spans


def read(rec):
    packs = spans.named(spans.events(), "capture_pack")
    total = sum(e["args"].get("bytes", 0) for e in packs)
    if not total:
        return None
    return 100.0 * sum(e["args"].get("replicated_bytes", 0) for e in packs) / total
