"""Seconds of the program's ``capture_checksum`` span per save (its sum over
the number of ``capture`` spans) in the traced window: the validate checksum
of every own payload and the store writes. Train state in a training cell,
session state in a serving cell."""

import spans


def read(rec):
    return spans.per_parent_s("capture", "capture_checksum")
