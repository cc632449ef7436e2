"""Seconds of the program's ``restore_upload`` span per restore (its sum over
the number of ``restore`` spans) in the traced window: the host-to-device
upload of the merged state, to its end on the device."""

import spans


def read(rec):
    return spans.per_parent_s("restore", "restore_upload")
