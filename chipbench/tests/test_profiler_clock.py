"""The program's spans in the profiler's own trace, on the host CPU: an
enabled tracer puts each span on the trace's host plane under its own name,
and the benchmark's one sync mark maps a span's ``perf_counter`` start onto
its annotation's start on the trace's clock."""

import time

import jax
import jax.numpy as jnp
import pytest

import xplane
from repro.obs.trace import tracer

NAMES = ("capture_d2h", "restore_upload", "decode_step")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A profiled window holding the sync mark and a few program spans
    around device work, as ``harness.TraceWindow`` records one."""
    d = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    tr = tracer()
    jax.profiler.start_trace(str(d))
    tr.reset()
    tr.enable()
    try:
        with jax.profiler.TraceAnnotation(xplane.SYNC_MARK):
            sync = time.perf_counter()
        for name in NAMES:
            with tr.span(name, bytes=x.nbytes):
                f(x).block_until_ready()
            time.sleep(0.002)
        spans = tr.events()
        base = tr._t0
    finally:
        tr.disable()
        jax.profiler.stop_trace()
        tr.reset()
    data = xplane.load(xplane.find_xplane(d))
    return data, sync, [(e["name"], base + e["t0"], e["dur"]) for e in spans]


def _annotations(data):
    out = {}
    for plane in data.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in NAMES:
                        out.setdefault(ev.name, []).append(ev)
    return out


def test_host_plane_holds_the_span_names(traced):
    data, _, spans = traced
    ann = _annotations(data)
    assert [n for n, _, _ in spans] == list(NAMES)
    assert {n: len(evs) for n, evs in ann.items()} == {n: 1 for n in NAMES}


@pytest.mark.parametrize("name", NAMES)
def test_sync_mark_maps_a_span_onto_its_annotation(traced, name):
    data, sync, spans = traced
    off = xplane.sync_offset_ns(data, sync)
    (ev,) = _annotations(data)[name]
    (t0, dur) = [(t, d) for n, t, d in spans if n == name][0]
    assert abs(int(round(t0 * 1e9)) + off - int(ev.start_ns)) < 1_000_000
    assert abs(dur * 1e9 - int(ev.duration_ns)) < 1_000_000
