"""Seconds of the program's ``capture_pack`` span per save (its sum over the
number of ``capture`` spans) in the traced window: the copy of every rank's
own shard and exchange subset into the host arenas. Train state in a
training cell, session state in a serving cell."""

import spans


def read(rec):
    return spans.per_parent_s("capture", "capture_pack")
