"""Median of the program's ``decode_step`` span over the traced window's
ticks: the call to the server's jitted decode, which in the benchmark ends
when the tick's tokens reach the host."""

import spans


def read(rec):
    return spans.median_ms("decode_step")
