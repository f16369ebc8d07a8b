"""From a jax.profiler trace (``.xplane.pb``) to device metrics.

A traced run wraps the traced part of its window in a host annotation
named ``bench.traced_window``, and each call into a layer of the program
in one named ``bench.<what>`` (``bench.engine_step``,
``bench.train_step``, ``bench.input_batch``, ``bench.wait_arrival``).
Device planes are those named ``/device:TPU:<n>``; on each, the line
``XLA Ops`` holds one event per operation that ran and ``XLA Modules``
one per program execution. Host annotations and device events share
the profiler's clock.

- busy: the union of a device's operation intervals inside the window,
  averaged over the devices;
- device ops: total time of each operation over the window, summed over
  the devices and divided by their number. An event's name is the HLO
  instruction's text; it is shortened to ``%name kind`` (a custom call
  adds its target), and control-flow containers (``while``,
  ``conditional``, ``call``), whose time their inner operations already
  count, are left out of the totals but not of busy;
- idle gaps: the stretches inside the window in which no operation ran
  on a device, each named by the innermost ``bench.*`` host annotation
  that covers its middle (``host:idle`` where none does).
"""

from __future__ import annotations

import glob
import os
import re
import warnings
from dataclasses import dataclass, field

WINDOW = "bench.traced_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HLO = re.compile(r"^(%[\w.\-]+) = .*? ([\w\-]+)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
CONTAINERS = {"while", "conditional", "call"}
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass(frozen=True)
class Event:
    name: str
    start: float      # ns on the profiler's clock
    end: float
    step: int | None = None   # a host annotation's ``step`` argument
    args: tuple = ()          # a program span's arguments, (name, value)


@dataclass
class Trace:
    window: tuple[float, float]
    ops: dict[str, list[Event]] = field(default_factory=dict)      # per device
    modules: dict[str, list[Event]] = field(default_factory=dict)  # per device
    host: list[Event] = field(default_factory=list)   # bench.* annotations

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_label(name: str) -> tuple[str, str]:
    """``(label, kind)`` of an ``XLA Ops`` event: ``%fusion.3 fusion``,
    ``%custom-call.7 custom-call:tpu_custom_call``."""
    m = HLO.match(name)
    if not m:
        return name[:80], ""
    label, kind = m.group(1), m.group(2)
    if kind == "custom-call":
        t = TARGET.search(name)
        if t:
            kind = f"custom-call:{t.group(1)}"
    return f"{label} {kind}", kind


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    warnings.filterwarnings("ignore", "builtin type event_stats",
                            DeprecationWarning)
    host: list[Event] = []
    ops: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dest = ops if line.name == OPS_LINE else modules
                    dest[plane.name] = [
                        Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats).get("step"))
                            for e in line.events
                            if e.name.startswith("bench."))
    windows = [e for e in host if e.name == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    w = max(windows, key=lambda e: e.end - e.start)
    host = [e for e in host if e is not w]
    return Trace((w.start, w.end), ops, modules, host)


def merge(events: list[Event], lo: float, hi: float) -> list[tuple]:
    """Union of event intervals clipped to [lo, hi], as sorted pairs."""
    spans = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                   if e.end > lo and e.start < hi)
    out: list[list[float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    lo, hi = trace.window
    per = [sum(b - a for a, b in merge(evs, lo, hi)) * 1e-9
           for evs in trace.ops.values()]
    return sum(per) / len(per) if per else 0.0


def op_seconds(trace: Trace, pattern: str | None = None,
               within: list[tuple] | None = None) -> dict[str, float]:
    """Total seconds per operation label in the window (averaged over
    the devices), containers left out, optionally only labels matching
    ``pattern`` and only events inside one of the ``within`` intervals."""
    lo, hi = trace.window
    rx = re.compile(pattern) if pattern else None
    tot: dict[str, float] = {}
    n = max(len(trace.ops), 1)
    for evs in trace.ops.values():
        for e in evs:
            if e.end <= lo or e.start >= hi:
                continue
            label, kind = op_label(e.name)
            if kind in CONTAINERS or (rx and not rx.search(label)):
                continue
            if within is not None and not any(a <= e.start and e.end <= b
                                              for a, b in within):
                continue
            dur = (min(e.end, hi) - max(e.start, lo)) * 1e-9
            tot[label] = tot.get(label, 0.0) + dur / n
    return tot


def complete_modules(trace: Trace, pattern: str) -> list[tuple]:
    """Intervals of program executions (``XLA Modules`` events) whose
    name matches ``pattern`` and that lie wholly inside the window, on
    the first device."""
    lo, hi = trace.window
    if not trace.modules:
        return []
    dev = sorted(trace.modules)[0]
    rx = re.compile(pattern)
    return [(e.start, e.end) for e in trace.modules[dev]
            if rx.search(e.name) and lo <= e.start and e.end <= hi]


def idle_gaps(trace: Trace, top: int = 10) -> list[list]:
    """The longest stretches with no device operation, named by the host
    annotation that covers each, merged by name: [[name, seconds], ...]."""
    lo, hi = trace.window
    if not trace.ops:
        return []
    dev = sorted(trace.ops)[0]
    busy = merge(trace.ops[dev], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    by_name: dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        cover = [e for e in trace.host if e.start <= mid <= e.end]
        name = (min(cover, key=lambda e: e.end - e.start).name
                if cover else "host:idle")
        by_name[name] = by_name.get(name, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(by_name.items(),
                                      key=lambda kv: -kv[1])[:top]]


def top_ops(trace: Trace, top: int = 10) -> list[list]:
    tot = op_seconds(trace)
    return [[k, v] for k, v in sorted(tot.items(),
                                      key=lambda kv: -kv[1])[:top]]


def reduce(trace: Trace) -> dict:
    """The numbers every traced run reports."""
    return {"busy_s": busy_s(trace), "window_s": trace.window_s,
            "device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace),
            "devices": len(trace.ops)}
