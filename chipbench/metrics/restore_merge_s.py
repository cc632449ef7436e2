"""Seconds of the program's ``restore_merge`` span per restore (its sum over
the number of ``restore`` spans) in the traced window: the concatenation of
the recovered shards into the whole state."""

import spans


def read(rec):
    return spans.per_parent_s("restore", "restore_merge")
