"""The reduction from a profiler trace to busy time, idle share, kernel
time and named idle gaps, against values worked out by hand."""

from __future__ import annotations

import pytest

from bench import tracing
from bench.tracing import Event, Trace


def synthetic() -> Trace:
    host = [Event("bench.window", 0, 100), Event("service.tick", 0, 90),
            Event("pull", 18, 35), Event("launch", 50, 52),
            Event("bench.idle", 95, 100)]
    dev0 = [Event("match_swar.1", 0, 10), Event("fusion.3", 5, 20),
            Event("match_swar.1", 30, 40), Event("fusion.3", 60, 70),
            Event("copy", 105, 120)]
    dev1 = [Event("match_swar.1", 10, 50)]
    return Trace(host, {"/device:TPU:1": dev1, "/device:TPU:0": dev0})


def test_busy_union_and_idle_share():
    red = tracing.reduce(synthetic())
    # Device 0: [0, 20] + [30, 40] + [60, 70] = 40 ns of the 100 ns window;
    # device 1: [10, 50] = 40 ns.  The op after the window does not count.
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_by_device == {"/device:TPU:0": pytest.approx(40e-9),
                                  "/device:TPU:1": pytest.approx(40e-9)}
    assert red.busy_s == pytest.approx(40e-9)
    assert red.n_devices == 2


def test_only_the_cells_devices_count():
    # A one-chip cell on a host with more chips: device 1's plane is
    # left out of busy time, op time and the device count.
    red = tracing.reduce(synthetic(), device_ids={0})
    assert red.busy_by_device == {"/device:TPU:0": pytest.approx(40e-9)}
    assert red.busy_s == pytest.approx(40e-9) and red.n_devices == 1
    assert red.seconds_matching(["match_swar"]) == pytest.approx(20e-9)
    red = tracing.reduce(synthetic(), device_ids={1})
    assert red.busy_by_device == {"/device:TPU:1": pytest.approx(40e-9)}
    # Its idle gaps now: [0, 10] and [50, 100], both under service.tick.
    assert red.gaps == [("service.tick", pytest.approx(10e-9)),
                        ("service.tick", pytest.approx(50e-9))]


def test_kernel_time_by_name():
    red = tracing.reduce(synthetic())
    # match_swar: 10 + 10 on device 0 and 40 on device 1.
    assert red.seconds_matching(["match_swar"]) == pytest.approx(60e-9)
    assert red.op_seconds["fusion.3"] == pytest.approx(25e-9)
    assert "copy" not in red.op_seconds
    ops = tracing.breakdown(red)["device_ops"]
    assert ops[0][0] == "match_swar.1"


def test_idle_gaps_are_named_by_the_innermost_span():
    red = tracing.reduce(synthetic())
    # Device 0 idles over [20, 30] (mid 25: pull inside service.tick),
    # [40, 60] (mid 50: launch, 2 ns long), [70, 100] (mid 85:
    # service.tick).  The window's own span never names a gap.
    assert red.gaps == [("pull", pytest.approx(10e-9)),
                        ("launch", pytest.approx(20e-9)),
                        ("service.tick", pytest.approx(30e-9))]
    assert tracing.breakdown(red)["idle_gaps"][0][0] == "service.tick"


def test_a_gap_no_span_covers_is_none():
    tr = Trace([Event("bench.window", 0, 10)], {"/device:TPU:0": []})
    red = tracing.reduce(tr)
    assert red.gaps == [("none", pytest.approx(10e-9))]
    assert red.busy_s == 0.0


def test_union_clips_and_merges():
    assert tracing.union([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 15) == [
        (1, 4), (5, 8), (9, 15)]


def test_missing_window_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        tracing.reduce(Trace([Event("pull", 0, 5)], {}))


# A trace recorded on a TPU v5e: a warmed service over 4,096 rows serves
# two ticks of two top-k queries, with a 5 ms ``bench.idle`` between them,
# all inside ``bench.window``.
RECORDED = "bench/tests/data/small_tick.xplane.pb"
SPANS = {"bench.idle", "bench.window", "launch", "match.run", "merge",
         "pack", "plan", "pull", "service.coalesce", "service.enqueue",
         "service.tick"}


@pytest.fixture(scope="module")
def recorded():
    from bench import spec
    path = str(spec.ROOT / RECORDED)
    return path, tracing.load(path)


def test_recorded_trace_window_and_busy_union(recorded):
    import jax
    import numpy as np
    path, tr = recorded
    red = tracing.reduce(tr, span_names=SPANS)
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(0.021941769, abs=1e-9)
    assert red.busy_s == pytest.approx(0.00036848, abs=1e-9)
    # The same union by brute force: a 1 ns timeline of the window.
    pd = jax.profiler.ProfileData.from_file(path)
    win = [e for p in pd.planes if p.name.startswith("/host:")
           for line in p.lines for e in line.events
           if e.name == "bench.window"][0]
    lo = win.start_ns
    on = np.zeros(int(win.duration_ns) + 1, bool)
    plane = pd.find_plane_with_name("/device:TPU:0")
    for line in plane.lines:
        if line.name == "XLA Ops":
            for e in line.events:
                a = max(0, int(round(e.start_ns - lo)))
                b = min(on.size, int(round(e.start_ns + e.duration_ns - lo)))
                on[a:b] = True
    assert on.sum() * 1e-9 == pytest.approx(red.busy_s, abs=2e-8)
    idle = red.window_s - red.busy_s
    assert sum(s for _, s in red.gaps) == pytest.approx(idle, abs=1e-9)


def test_recorded_trace_kernel_time_by_name(recorded):
    _, tr = recorded
    red = tracing.reduce(tr, span_names=SPANS)
    assert red.seconds_matching(["%match_swar"]) == pytest.approx(
        6.6502e-05, abs=1e-9)
    assert red.op_seconds["jit_upd:%sort.0"] == pytest.approx(9.9504e-05,
                                                             abs=1e-9)
    assert tracing.breakdown(red)["device_ops"][0][0] == "jit_upd:%sort.0"


def test_recorded_trace_gaps_by_span(recorded):
    _, tr = recorded
    red = tracing.reduce(tr, span_names=SPANS)
    totals = dict(red.gap_totals())
    # The sleep between the ticks is the longest idle stretch: no device
    # op runs in it, so its gap holds at least the whole sleep.
    sleep = [e for e in tr.host if e.name == "bench.idle"][0]
    assert red.gap_totals()[0][0] == "bench.idle"
    assert totals["bench.idle"] >= (sleep.end - sleep.start) * 1e-9 >= 0.005
    assert totals["bench.idle"] == pytest.approx(0.009614232, abs=1e-9)
    assert totals["pull"] == pytest.approx(0.005162203, abs=1e-9)
    assert set(totals) <= SPANS | {"none"}
