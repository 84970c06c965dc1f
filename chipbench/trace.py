"""A profiler trace of a run's window, and its reduction to metrics.

``Tracer`` records one window with JAX's profiler (``--trace 1`` only) and
puts the harness's host spans into the same trace: ``chipbench.window``
around the whole window and ``chipbench.*`` spans around the harness's own
calls into each layer.  The trace starts just before the window and stops
just after it, with nothing left running on the device, so every device
event in it belongs to the window.  ``reduce`` reads the ``.xplane.pb``:

- device time: the events on the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane.  Busy time is the union of their intervals
  inside the host's ``chipbench.window`` span, per chip, averaged over the
  chips used; the window is that span.  The device's clock is mapped onto
  the host's to within about a millisecond, so up to that much busy time
  at the window's edges may fall outside it and go uncounted.
- the kernel: the device events whose HLO text matches the configuration's
  ``kernel_event`` pattern; their summed durations and count.
- the breakdown: device operations (``<program>/<instruction>``) by summed
  duration, and the longest idle gaps inside the window, each named by the
  innermost harness span that covers its middle; for the same reason, a
  gap shorter than a millisecond may be named after a neighbouring span.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
TOP = 10

Interval = Tuple[float, float]       # start and end, in ns
#: one device operation: its label, its HLO text, start and duration in ns
Op = Tuple[str, str, float, float]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    kernel_s: float
    kernel_events: int
    device_ops: List[List]           # [name, seconds], most time first
    idle_gaps: List[List]            # [host span, seconds], longest first


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge intervals into disjoint ones, in order."""
    merged: List[Interval] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _clip(start: float, end: float, lo: float, hi: float) -> Optional[Interval]:
    start, end = max(start, lo), min(end, hi)
    return (start, end) if end > start else None


def reduce(device_ops: Dict[str, List[Op]],
           spans: List[Tuple[str, float, float]],
           kernel_event: Optional[str]) -> Summary:
    """Reduce device ops (per device plane) and host spans (name, start ns,
    duration ns) to a ``Summary``."""
    windows = [(s, s + d) for name, s, d in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} {WINDOW_SPAN!r} spans")
    lo, hi = windows[0]
    pattern = re.compile(kernel_event) if kernel_event else None
    busy_ns = 0.0
    kernel_ns, kernel_events = 0.0, 0
    by_name: Dict[str, float] = {}
    gaps: List[Interval] = []
    for ops in device_ops.values():
        for label, text, _, dur in ops:
            by_name[label] = by_name.get(label, 0.0) + dur
            if pattern is not None and pattern.search(text):
                kernel_ns += dur
                kernel_events += 1
        merged = union([(start, start + dur) for _, _, start, dur in ops])
        inside = [iv for iv in (_clip(s, e, lo, hi) for s, e in merged) if iv]
        busy_ns += sum(e - s for s, e in inside)
        edges = [lo] + [x for iv in inside for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    chips = max(1, len(device_ops))
    host = [(name, s, s + d) for name, s, d in spans
            if name.startswith(SPAN_PREFIX) and name != WINDOW_SPAN]

    def label(gap: Interval) -> str:
        mid = 0.5 * (gap[0] + gap[1])
        covering = [(e - s, name) for name, s, e in host if s <= mid <= e]
        return min(covering)[1] if covering else "outside the harness's spans"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns / chips * 1e-9,
        kernel_s=kernel_ns * 1e-9,
        kernel_events=kernel_events,
        device_ops=[[name, ns * 1e-9] for name, ns in
                    sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]],
        idle_gaps=[[label(g), (g[1] - g[0]) * 1e-9] for g in longest])


def load(path: str):
    """Device ops per device plane, and the host's ``chipbench.*`` spans,
    from an ``.xplane.pb`` file: ``(device_ops, spans)`` as ``reduce``
    takes them."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Op]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            programs = sorted((e.start_ns, e.name.split("(", 1)[0])
                              for e in lines.get(MODULES_LINE, []))
            starts = [s for s, _ in programs]
            ops = device_ops.setdefault(plane.name, [])
            for e in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                program = programs[i][1] if i >= 0 else "?"
                ops.append((f"{program}/{e.name.split(' = ', 1)[0]}", e.name,
                            e.start_ns, e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return device_ops, spans


def idle_pct(summary: Optional[Summary]) -> Optional[float]:
    """Percent of the traced window in which the device ran nothing."""
    if summary is None or not summary.window_s or not summary.busy_s:
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)


class _Window:
    summary: Optional[Summary] = None


class Tracer:
    """Spans and the traced window of one run; inert unless ``enabled``."""

    def __init__(self, enabled: bool, kernel_event: Optional[str] = None):
        self.enabled = enabled
        self.kernel_event = kernel_event

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        window = _Window()
        if not self.enabled:
            yield window
            return
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1     # the harness's spans, little else
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            jax.profiler.start_trace(tmp, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                    yield window
            finally:
                jax.profiler.stop_trace()
            files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                              recursive=True)
            if len(files) != 1:
                raise RuntimeError(f"expected one trace file, found {files}")
            window.summary = reduce(*load(files[0]), self.kernel_event)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
