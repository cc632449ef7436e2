"""Bytes the engine's host stores hold after the window
(``memory_report()["total_bytes"]``) per byte of checkpointed state."""


def read(rec):
    if not rec.get("state_bytes"):
        return None
    return rec["host_store_bytes"] / rec["state_bytes"]
