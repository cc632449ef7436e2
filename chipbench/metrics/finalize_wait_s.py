"""Mean of ``ckpt_last_finalize_wait_seconds`` per committed save in the
traced window: the time the loop blocked on the previous save's drain."""


def read(rec):
    waits = rec.get("finalize_wait_s") or []
    return sum(waits) / len(waits) if waits else None
