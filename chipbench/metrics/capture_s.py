"""Mean seconds per save of the engine's ``ckpt_stage_seconds{phase=capture}``
(device-to-host fetch, split and packing into the arenas) in the traced
window: train state in a training cell, session state in a serving cell."""


def read(rec):
    return rec.get("capture_s")
