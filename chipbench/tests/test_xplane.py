"""The trace reduction on a trace recorded on a v5e: five runs of a jitted
pair of 4096^2 bf16 products, 20 ms apart."""

from pathlib import Path

import pytest

import xplane

TRACE = Path(__file__).parent / "data" / "v5e-matmul.xplane.pb"
# Trace-clock bounds that hold all five runs (their ops span 43.06-136.36 ms).
WINDOW = (43_000_000, 160_000_000)


@pytest.fixture(scope="module")
def data():
    return xplane.load(TRACE)


def test_busy_is_the_union_of_device_ops(data):
    r = xplane.reduce(data, WINDOW)
    # The 20 ops of the five runs sum to 7.119346 ms and do not overlap.
    assert r["busy_s"] == pytest.approx(7.119346e-3, rel=1e-9)
    assert r["window_s"] == pytest.approx(0.117)


def test_window_clips_device_time(data):
    half = xplane.reduce(data, (WINDOW[0], 100_000_000))
    full = xplane.reduce(data, WINDOW)
    assert 0 < half["busy_s"] < full["busy_s"]
    # Three of the five runs start before 100 ms.
    assert half["busy_s"] == pytest.approx(3 * full["busy_s"] / 5, rel=1e-3)


def test_top_ops_by_total_time(data):
    ops = xplane.reduce(data, WINDOW)["device_ops"]
    names = [n for n, _ in ops]
    assert names[:2] == ["jit__lambda/convolution_tanh_fusion", "jit__lambda/fusion"]
    secs = [s for _, s in ops]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) == pytest.approx(7.119346e-3, rel=1e-9)


def test_idle_gaps_named_by_host_spans(data):
    spans = [("host_step", 40_000_000, 60_000_000)]
    r = xplane.reduce(data, WINDOW, spans, top=3)
    assert len(r["idle_gaps"]) == 3
    lengths = [s for _, s in r["idle_gaps"]]
    assert lengths == sorted(lengths, reverse=True)
    # The gap after the first run (44.49-66.60 ms) lies mostly in the span.
    assert "host_step" in [n for n, _ in r["idle_gaps"]]
    full = xplane.reduce(data, WINDOW, spans, top=1000)
    assert sum(s for _, s in full["idle_gaps"]) + full["busy_s"] == pytest.approx(full["window_s"])


def test_sync_marker_maps_the_host_clock(data):
    # The marker began at 44.008979 ms on the trace's clock.
    assert xplane.sync_offset_ns(data, 1.0) == 44_008_979 - 1_000_000_000
