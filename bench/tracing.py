"""The profiler trace of a window, reduced to numbers.

``capture`` records a JAX profiler trace into a directory; ``load``
reads its ``.xplane.pb`` into plain event lists; ``reduce`` turns those
into what the per-layer readers use:

* the window: the benchmark's ``bench.window`` span on the host plane;
* each device's busy time: the union of its ``XLA Ops`` events inside the
  window, and the mean of that over the cell's devices (planes of other
  devices on the host are left out);
* device time per op (summed durations inside the window), keyed by the
  jitted program and the HLO instruction's name
  (``jit_match_swar:%match_swar.1``: a Pallas kernel's instruction is
  named after its kernel);
* idle gaps: the stretches of the first device's window that no op covers, each
  named by the innermost host span over the gap's midpoint among the
  spans the caller names (the program's ``repro.obs`` spans and the
  benchmark's own ``bench.*`` spans; the runtime's own host events are
  left out), or ``none`` where no such span covers it.

Event times are nanoseconds on the trace's clock; the results are
seconds.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import heapq
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float             # ns
    end: float               # ns


@dataclasses.dataclass
class Trace:
    host: List[Event]
    devices: Dict[str, List[Event]]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                        # mean over devices
    busy_by_device: Dict[str, float]
    op_seconds: Dict[str, float]         # summed over devices
    gaps: List[Tuple[str, float]]        # first device's idle gaps, by span
    n_devices: int

    def seconds_matching(self, patterns) -> float:
        """Device seconds of ops whose instruction name holds a pattern."""
        return sum(s for name, s in self.op_seconds.items()
                   if any(p in name for p in patterns))

    def gap_totals(self) -> List[Tuple[str, float]]:
        tot: Dict[str, float] = {}
        for name, s in self.gaps:
            tot[name] = tot.get(name, 0.0) + s
        return sorted(tot.items(), key=lambda kv: -kv[1])


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the block into ``log_dir``; Python calls are not traced
    (that tracer would slow the host and fill the trace)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(path: str) -> Trace:
    """Host spans and device ops of one ``.xplane.pb`` file (or of the
    newest one under a profiler log directory)."""
    import jax
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    host: List[Event] = []
    devices: Dict[str, List[Event]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append(Event(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
        elif DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.name.split("(", 1)[0])
                          for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in mods]
            evs = devices.setdefault(plane.name, [])
            for e in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][1] + ":" if i >= 0 else ""
                evs.append(Event(mod + e.name.split(" = ", 1)[0], e.start_ns,
                                 e.start_ns + e.duration_ns))
    return Trace(host, devices)


def union(intervals: List[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def innermost(host: List[Event], times: List[float]) -> List[str]:
    """For each time, the name of the shortest host span (the window's
    own excepted) that covers it, or ``none``.

    One sweep in time order: spans that have started sit in a heap keyed
    by length, and a span that has ended is dropped for good once a later
    time reaches the top of the heap.
    """
    spans = sorted((e for e in host if e.name != WINDOW_SPAN),
                   key=lambda e: e.start)
    order = sorted(range(len(times)), key=lambda i: times[i])
    out = ["none"] * len(times)
    heap: list = []
    j = 0
    for i in order:
        t = times[i]
        while j < len(spans) and spans[j].start <= t:
            e = spans[j]
            heapq.heappush(heap, (e.end - e.start, j, e))
            j += 1
        while heap and heap[0][2].end <= t:
            heapq.heappop(heap)
        if heap:
            out[i] = heap[0][2].name
    return out


def reduce(trace: Trace, window: Optional[Tuple[float, float]] = None,
           span_names: Optional[set] = None,
           device_ids: Optional[set] = None) -> Reduced:
    """Busy, per-op and idle-gap numbers of the traced window over the
    devices numbered in ``device_ids`` (all devices if None); idle gaps
    are named by host spans in ``span_names`` (all host events if None)."""
    host = [e for e in trace.host
            if span_names is None or e.name in span_names
            or e.name == WINDOW_SPAN]
    if window is None:
        spans = sorted((e for e in host if e.name == WINDOW_SPAN),
                       key=lambda e: e.start)
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        window = (spans[0].start, spans[0].end)
    lo, hi = window
    busy, ops = {}, {}
    gaps: List[Tuple[str, float]] = []
    planes = sorted((kv for kv in trace.devices.items()
                     if device_ids is None or _dev_no(kv[0]) in device_ids),
                    key=lambda kv: _dev_no(kv[0]))
    for i, (dev, evs) in enumerate(planes):
        merged = union([(e.start, e.end) for e in evs], lo, hi)
        busy[dev] = sum(b - a for a, b in merged) * 1e-9
        for e in evs:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                ops[e.name] = ops.get(e.name, 0.0) + d * 1e-9
        if i == 0:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a]
            names = innermost(host, [(a + b) / 2 for a, b in idle])
            gaps = [(nm, (b - a) * 1e-9) for nm, (a, b) in zip(names, idle)]
    n = len(busy)
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy.values()) / n if n else 0.0,
                   busy_by_device=busy, op_seconds=ops, gaps=gaps,
                   n_devices=n)


def _dev_no(plane: str) -> int:
    return int(plane.rsplit(":", 1)[1])


def breakdown(red: Reduced, n: int = 10) -> dict:
    """The ten device ops that took most time and the ten span names
    under which the first device sat idle longest (seconds summed by name)."""
    ops = sorted(red.op_seconds.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.gap_totals()[:n]]}
