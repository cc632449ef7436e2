"""Mean of the engine's ``restore_last_seconds`` per kill in the traced window."""


def read(rec):
    rs = rec.get("restore_s") or []
    return sum(rs) / len(rs) if rs else None
