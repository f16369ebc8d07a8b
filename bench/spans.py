"""The program's own host spans in a traced run's profile.

The program enters a ``jax.profiler.TraceAnnotation`` for each of its
spans (``src/repro/obs/trace.py``) whenever a profile is being
captured, so the traced window's ``.xplane.pb`` holds them on the
profiler's clock beside the device's operations. ``trace_reduce.load``
keeps only the harness's ``bench.*`` annotations; this reads the same
file again for the rest. A program without such spans yields none, and
a reader built on them then reports nothing.
"""

from __future__ import annotations

from bench import trace_reduce as TR


def by_thread(path: str, window: tuple[float, float]) -> list[list]:
    """Host events wholly inside ``window`` that are not the harness's
    ``bench.*`` annotations, with their arguments, one list per host
    thread, in start order."""
    from jax.profiler import ProfileData

    lo, hi = window
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [TR.Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                               args=tuple(dict(e.stats).items()))
                      for e in line.events
                      if not e.name.startswith("bench.")
                      and lo <= e.start_ns
                      and e.start_ns + e.duration_ns <= hi]
            if events:
                out.append(sorted(events, key=lambda e: e.start))
    return out


def load(r) -> list[list]:
    """``by_thread`` of a traced run's profile (the harness's log
    directory) over its traced window; empty where nothing was traced."""
    if r.trace is None or r.run.tracer is None:
        return []
    return by_thread(TR.find_xplane(str(r.run.tracer.log_dir)),
                     r.trace.window)


def children(parent, events: list) -> list:
    """The events of ``parent``'s thread that lie inside it."""
    return [e for e in events if e is not parent
            and parent.start <= e.start and e.end <= parent.end]
