"""The trace reduction: interval arithmetic, a small trace whose numbers
were worked out by hand, and the loader on a trace the CPU records."""

import json
from pathlib import Path

import pytest

import testlib_chipbench  # noqa: F401  (puts the harness on the path)
from chipbench import tracered

DATA = Path(__file__).resolve().parent / "testdata"


def test_union_merges_and_drops_empty():
    assert tracered.union([(5, 7), (1, 3), (2, 4), (8, 8)]) == [(1, 4), (5, 7)]


def test_minus_cuts_every_overlap():
    a = [(0, 10), (20, 30)]
    b = [(2, 3), (5, 22), (25, 40)]
    assert tracered.minus(a, b) == [(0, 2), (3, 5), (22, 25)]
    assert tracered.measure(tracered.minus(a, [])) == 20


def _events(name):
    raw = json.loads((DATA / name).read_text())
    return [tracered.Event(**e) for e in raw["events"]]


def test_hand_worked_trace():
    """testdata/trace_two_devices.json, two devices in a 10 us window.

    Device 0: kernel 4.0 + 3.5 us, a 0.2 us copy, a 0.4 us fusion that
    overlaps the first collective by 0.1 us, two collectives of 0.2 us,
    gaps of 0.2, 0.9 and 0.5 us, and one op before the window. Device 1:
    kernel 8 us, copy 1 us, one 1 us gap, and a while that holds both.
    """
    r = tracered.reduce_events(_events("trace_two_devices.json"), "stencil_step_fused")
    ns = 1e-9
    assert r.devices == 2
    assert r.window_s == pytest.approx(10000 * ns)
    assert r.busy_s == pytest.approx((8400 + 9000) / 2 * ns)
    assert r.kernel_s == pytest.approx((7500 + 8000) / 2 * ns)
    assert r.collective_s == pytest.approx(400 / 2 * ns)
    assert r.exposed_collective_s == pytest.approx(300 / 2 * ns)
    assert r.other_s == pytest.approx((500 + 1000) / 2 * ns)
    names = [n for n, _ in r.device_ops]
    assert names[:2] == ["stencil_step_fused", "copy.3"]
    assert dict(r.device_ops)["copy.3"] == pytest.approx(600 * ns)
    assert "fusion.1" not in names          # ran before the window
    assert "while" not in names             # holds the ops of device 1
    assert r.idle_gaps == [["wait", pytest.approx(1000 * ns)],
                           ["wait", pytest.approx(900 * ns)],
                           ["wait", pytest.approx(500 * ns)],
                           ["call", pytest.approx(200 * ns)]]


def test_recorded_v5e_trace():
    """testdata/trace_v5e_excerpt.json: the events of one chip in a
    recorded one-chip jacobi window, cut to one call (see its note)."""
    raw = json.loads((DATA / "trace_v5e_excerpt.json").read_text())
    events = [tracered.Event(**e) for e in raw["events"]]
    r = tracered.reduce_events(events, "stencil_step_fused")
    want = raw["hand_worked"]
    assert r.devices == 1
    assert r.window_s == pytest.approx(want["window_s"])
    assert r.kernel_s == pytest.approx(want["kernel_s"])
    assert r.busy_s == pytest.approx(want["busy_s"])
    assert r.other_s == pytest.approx(want["other_s"])
    assert r.collective_s == 0


def test_load_xplane_reads_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: x * 2.0)
    x = jnp.ones((8,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("window"):
        with TraceAnnotation("call"):
            y = f(x)
        with TraceAnnotation("wait"):
            y.block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = sorted(e.name for e in tracered.load_xplane(path))
    assert names == ["call", "wait", "window"]
