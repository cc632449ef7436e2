"""Seconds of the program's ``capture_d2h`` span per save (its sum over the
number of ``capture`` spans) in the traced window: the device-to-host fetch
of the whole state in ``ShardedStateEntity.snapshot_shards``. Train state in
a training cell, session state in a serving cell."""

import spans


def read(rec):
    return spans.per_parent_s("capture", "capture_d2h")
