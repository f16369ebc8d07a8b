"""Span tracer: nested spans -> Chrome-trace/Perfetto JSON and the
``jax.profiler`` trace.

One global ``tracer`` (module-level, like ``decisions.log``) that the
serving engine wraps around every step phase — admission, planning,
prefix-cache lookup, prefill chunk, decode dispatch and read-back,
draft, verify, rollback — plus whatever launchers want to mark. Spans
nest naturally (``with tracer.span("decode_batch"):``) and go to two
sinks, each on its own switch:

* **the profiler** — whenever a ``jax.profiler`` trace is being
  captured (``jax.profiler.trace``/``start_trace``, ``--profile-dir``
  on the launchers, any harness's traced window), each span also enters
  a ``jax.profiler.TraceAnnotation`` of the same name, with its scalar
  args as the annotation's arguments. The spans then sit on the
  profiler's clock beside the device's operations, so a device idle
  gap can be put down to the host phase that covers it;
* **the Chrome-trace buffer** — after ``enable()``, ``B``/``E`` event
  pairs on ``time.perf_counter`` that chrome://tracing and
  https://ui.perfetto.dev open directly (``write``/``export``).

Near zero overhead when both are off: ``span()`` checks one flag and
asks the profiler whether it is capturing
(``TraceAnnotation.is_enabled()``, tens of ns), then returns a shared
no-op context manager — no event append, no timestamp read, no
allocation. The engine is instrumented unconditionally; only a capture
or an enabled buffer pays.

Span names starting with ``bench.`` are reserved for benchmark
harnesses, which name device idle gaps by their own annotations;
``span()`` refuses them.

Jit-compile detection: pass ``compile_key=<hashable>`` and, in the
buffer, the first span with that key is tagged ``args["compile"] =
true``: the engine keys on dispatch shapes (chunk length, draft k, slot
count), so warmup spans that trigger XLA compilation are visually
separable from steady-state dispatches of the same phase.

Thread-safe: events append under a lock (cross-session replicas or a
metrics HTTP thread may export mid-run), and ``tid`` records the
emitting thread so nesting is judged per thread.

Fleet extensions (PR 9): ``pid`` is stamped at *emit* time with the
real ``os.getpid()`` (a fork after import can no longer alias two
replicas onto one track), ``set_process_name()`` names the replica, and
``export()`` prepends Chrome ``ph:"M"`` ``process_name``/``thread_name``
metadata events so N merged traces render as per-replica tracks.
``perf_counter`` timestamps are per-process, so ``export()`` also
records ``otherData.epoch_offset_us`` — the offset that maps this
process's span timestamps onto the shared unix epoch — and
:func:`merge_traces` applies it, making cross-process timelines
comparable. :func:`iter_spans` / :func:`request_spans` reconstruct
completed spans (B/E pairs + instants) and filter them by the
``request``/``requests`` span args the engine attaches; the
``python -m repro.obs --request <id>`` CLI builds on them.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

import jax.profiler
from jax.profiler import TraceAnnotation

#: Span-name prefix reserved for benchmark harnesses' own annotations.
RESERVED_PREFIX = "bench."


class _NullSpan:
    """Shared do-nothing span for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key, value):
        return self


_NULL = _NullSpan()


#: Arg types a profiler annotation can carry.
_SCALAR = (int, float, str, bool)


def _scalars(args: dict | None) -> dict:
    if not args:
        return {}
    return {k: v for k, v in args.items() if isinstance(v, _SCALAR)}


class Span:
    """One live span; ``set(k, v)`` attaches args visible in the trace.

    ``tracer`` is the buffer to write (None when only the profiler is
    capturing); ``annotation`` the profiler annotation (None when it is
    not)."""

    __slots__ = ("_tracer", "name", "_args", "_ann")

    def __init__(self, tracer: "Tracer | None", name: str,
                 args: dict | None, annotation=None):
        self._tracer = tracer
        self.name = name
        self._args = args
        self._ann = annotation

    def set(self, key, value) -> "Span":
        if self._args is None:
            self._args = {}
        self._args[key] = value
        if self._ann is not None and isinstance(value, _SCALAR):
            self._ann.set_metadata(**{key: value})
        return self

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        if self._tracer is not None:
            self._tracer._emit("B", self.name, self._args)
        # args dict is shared with the B event: set() after enter still
        # lands in the exported trace
        return self

    def __exit__(self, *exc):
        # error goes on the E event: the B event's args dict was already
        # emitted (and may be None when the span opened bare)
        if self._tracer is not None:
            self._tracer._emit(
                "E", self.name,
                {"error": exc[0].__name__} if exc[0] is not None else None)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class Tracer:
    """Thread-safe span/event buffer with Chrome-trace export."""

    def __init__(self):
        self.enabled = False
        self.events: list[dict] = []
        self._lock = threading.Lock()
        self._seen_keys: set = set()
        self.process_name: str | None = None

    # -- control ------------------------------------------------------------

    def set_process_name(self, name: str) -> None:
        """Name this process's track in merged traces (replica/host id);
        lands in the ``process_name`` metadata event and ``otherData``."""
        self.process_name = name

    def enable(self) -> None:
        """Write spans into the Chrome-trace buffer (the profiler gets
        them whenever it captures, enabled or not)."""
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self.events = []
            self._seen_keys = set()

    # -- spans --------------------------------------------------------------

    def span(self, name: str, *, compile_key=None,
             **args) -> Span | _NullSpan:
        """Context manager for one nested span; ``args`` land on both
        sinks (the profiler keeps the scalar ones).

        ``compile_key``: hashable dispatch-shape key; in the buffer the
        first span per key is tagged ``compile=true`` (jit warmup
        detection).
        """
        profiling = TraceAnnotation.is_enabled()
        if not (self.enabled or profiling):
            return _NULL
        if name.startswith(RESERVED_PREFIX):
            raise ValueError(f"span names starting with "
                             f"{RESERVED_PREFIX!r} are reserved: {name!r}")
        a = dict(args) if args else None
        if (self.enabled and compile_key is not None
                and compile_key not in self._seen_keys):
            self._seen_keys.add(compile_key)
            a = a or {}
            a["compile"] = True
        ann = TraceAnnotation(name, **_scalars(a)) if profiling else None
        return Span(self if self.enabled else None, name, a, ann)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker event."""
        if TraceAnnotation.is_enabled():
            with TraceAnnotation(name, **_scalars(args)):
                pass
        if self.enabled:
            self._emit("i", name, dict(args) if args else None)

    def _emit(self, ph: str, name: str, args: dict | None) -> None:
        # pid is read at emit time, not cached at construction: the
        # module-level tracer predates any fork, and a cached pid would
        # alias every worker of a forked replica onto one trace track
        ev = {"name": name, "ph": ph, "ts": time.perf_counter() * 1e6,
              "pid": os.getpid(), "tid": threading.get_ident()}
        if args is not None:
            ev["args"] = args
        if ph == "i":
            ev["s"] = "t"     # instant scope: thread
        # lock-free append: CPython list.append is atomic, and clear()
        # swaps the whole list rather than mutating it — the lock only
        # serializes clear()/export() against each other
        self.events.append(ev)

    # -- export -------------------------------------------------------------

    def export(self) -> dict:
        """Chrome trace event format object (deep-copied args).

        Prepends ``process_name``/``thread_name`` metadata events for
        every (pid, tid) that emitted, and records
        ``otherData.epoch_offset_us`` — ``time.time() -
        time.perf_counter()`` in µs — so :func:`merge_traces` can place
        this process's per-process timestamps on the shared unix epoch.
        """
        with self._lock:
            events = [dict(e, args=dict(e["args"])) if "args" in e
                      else dict(e) for e in self.events]
        pname = self.process_name or f"pid {os.getpid()}"
        main_tid = threading.main_thread().ident
        meta: list[dict] = []
        seen_pids: set = set()
        seen_tids: set = set()
        for e in events:
            pid, tid = e["pid"], e["tid"]
            if pid not in seen_pids:
                seen_pids.add(pid)
                meta.append({"name": "process_name", "ph": "M", "ts": 0.0,
                             "pid": pid, "tid": 0,
                             "args": {"name": pname}})
            if (pid, tid) not in seen_tids:
                seen_tids.add((pid, tid))
                meta.append({"name": "thread_name", "ph": "M", "ts": 0.0,
                             "pid": pid, "tid": tid,
                             "args": {"name": ("MainThread"
                                               if tid == main_tid
                                               else f"thread-{tid}")}})
        offset_us = (time.time() - time.perf_counter()) * 1e6
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "otherData": {"process_name": pname,
                              "epoch_offset_us": offset_us}}

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.export(), f)


#: The process-global tracer every instrumented module shares.
tracer = Tracer()


def profile(log_dir: str | None):
    """Capture a ``jax.profiler`` trace into ``log_dir`` around a block
    (the launchers' ``--profile-dir``); a no-op without a directory.
    The tracer's spans land in it beside the device's operations; the
    Python function tracer stays off, as in a benchmark's traced window,
    so the host timeline holds the spans and not every Python call."""
    if not log_dir:
        return contextlib.nullcontext()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(log_dir, profiler_options=opts)


# ---------------------------------------------------------------------------
# Cross-process merge + span reconstruction (python -m repro.obs)
# ---------------------------------------------------------------------------

def merge_traces(*docs: dict) -> dict:
    """Merge N exported trace documents into one epoch-aligned trace.

    Each document's ``otherData.epoch_offset_us`` shifts its event
    timestamps onto the unix epoch; metadata events come first, the rest
    sort by shifted ts (a constant shift per document, so per-thread
    list order — what the validator checks — is preserved). The merged
    document carries ``epoch_offset_us: 0`` so merging is idempotent
    and associative: merge(merge(a, b), c) == merge(a, b, c).
    """
    meta: list[dict] = []
    seen_meta: set = set()
    events: list[dict] = []
    for doc in docs:
        off = float((doc.get("otherData") or {}).get("epoch_offset_us", 0.0))
        for ev in doc.get("traceEvents", ()):
            if ev.get("ph") == "M":
                key = (ev.get("name"), ev.get("pid"), ev.get("tid"))
                if key not in seen_meta:
                    seen_meta.add(key)
                    meta.append(dict(ev))
            else:
                ev = dict(ev)
                ev["ts"] = ev["ts"] + off
                events.append(ev)
    events.sort(key=lambda e: e["ts"])
    return {"traceEvents": meta + events, "displayTimeUnit": "ms",
            "otherData": {"epoch_offset_us": 0.0, "merged": len(docs)}}


def process_names(doc: dict) -> dict:
    """pid -> process/replica name from the metadata events."""
    names: dict = {}
    for ev in doc.get("traceEvents", ()):
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            names[ev["pid"]] = (ev.get("args") or {}).get("name")
    return names


def iter_spans(doc: dict):
    """Yield completed spans and instants from a trace document.

    Spans come from matched B/E pairs per (pid, tid) stack — args from
    both ends merged — as ``{"name", "ts", "dur", "pid", "tid",
    "args"}``; instants carry ``dur == 0.0``. Unclosed spans are
    dropped (the validator flags those separately).
    """
    stacks: dict[tuple, list[dict]] = {}
    for ev in doc.get("traceEvents", ()):
        ph = ev.get("ph")
        key = (ev.get("pid"), ev.get("tid"))
        if ph == "B":
            stacks.setdefault(key, []).append(ev)
        elif ph == "E":
            stack = stacks.get(key)
            if stack and stack[-1]["name"] == ev["name"]:
                b = stack.pop()
                args = dict(b.get("args") or {})
                args.update(ev.get("args") or {})
                yield {"name": b["name"], "ts": b["ts"],
                       "dur": ev["ts"] - b["ts"], "pid": b["pid"],
                       "tid": b["tid"], "args": args}
        elif ph in ("i", "I"):
            yield {"name": ev["name"], "ts": ev["ts"], "dur": 0.0,
                   "pid": ev["pid"], "tid": ev["tid"],
                   "args": dict(ev.get("args") or {})}


def request_spans(doc: dict, request_id: str) -> list[dict]:
    """Spans/instants belonging to one request, chronological.

    A span belongs when its args carry ``request == request_id`` or
    list ``request_id`` in ``requests`` — the two conventions the
    engine uses for per-sequence and batched phases respectively.
    """
    out = []
    for span in iter_spans(doc):
        a = span["args"]
        if (a.get("request") == request_id
                or request_id in (a.get("requests") or ())):
            out.append(span)
    out.sort(key=lambda s: s["ts"])
    return out
