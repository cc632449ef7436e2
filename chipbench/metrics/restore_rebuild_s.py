"""Seconds of the program's ``restore_rebuild`` span per restore (its sum over
the number of ``restore`` spans) in the traced window: the engine's rebuild
of every shard from the survivors and the redundancy."""

import spans


def read(rec):
    return spans.per_parent_s("restore", "restore_rebuild")
