"""The harness: finds a cell's parts by name, runs it, prints the result.

``BENCHMARK.json`` names each cell's configuration and traffic mix. The
harness loads ``bench/configs/<config>.json`` and
``bench/traffic/<traffic>.json``, hands the run to the driver of the
mix's ``kind`` (``bench/drivers/<kind>.py``), and reads each per-layer
metric whose ``workloads`` list the cell with its own reader,
``bench/metrics/<metric>.py``. Adding any of these is adding a file and
an entry, never editing one.

A run prints, in order: a ``bench-stats`` line (sample counts, how late
the generator ran, compilations and garbage collection inside the
window, the slowest steps), the result as one JSON object on the last
line of standard output, and each number that decides ``correct``
beside its limit as the last lines of standard error.

What a configuration brings (``bench/configs/<config>.json``):

- ``model``: the keyword arguments of the program's ``ModelConfig``,
  with ``taylor``, ``moe`` and ``ssm`` as nested sections of
  ``TaylorConfig``, ``MoEConfig`` and ``SSMConfig``; an unknown key
  raises;
- ``init`` (optional): a rule for each parameter leaf whose name the
  built-in table of ``bench/weights.py`` does not know, keyed by that
  name (``{"A_log": {"rule": "log_linspace", "lo": 1, "hi": 16}}``);
- ``optimizer``, for training cells;
- ``reference``: the name of its plain reference,
  ``bench/configs/<reference>.py``, which imports nothing of the
  program and defines ``logits_at(params, m, tokens, positions, *,
  rounding)`` for serving cells, ``train_steps(params, m, opt, batches,
  *, stored, rounding)`` for training cells, and, where the model has a
  layer kind that ``bench/flops.py`` does not count (anything but
  ``global`` attention with its MLP), ``layer_flops(m, kind, n_past,
  n_new)``: the forward FLOPs of one such layer for ``n_new`` tokens
  after ``n_past`` of one sequence. ``rounding="fp8"`` is the control.

With ``--calibrate 1`` the driver also returns the same numbers read
from the control and from each planted fault, each put in the program's
place. Each goes through the comparison that decides ``correct``, and
its verdict is printed (``control <name> correct <verdict>``): a sound
limit makes every one of them false.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field, fields
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 4.0
COMPILE_EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find(bench: dict, key: str, name: str) -> dict:
    for entry in bench[key]:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {key[:-1]} named {name!r} in BENCHMARK.json")


def load_module(path: Path):
    """Import a file by path (names may hold dots and dashes)."""
    spec = importlib.util.spec_from_file_location(
        "bench_part_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Tracer:
    """Traces the last ``seconds`` of a window with jax.profiler."""

    def __init__(self, enabled: bool, window_s: float, seconds: float,
                 log_dir: Path):
        self.enabled = enabled
        self.start_at = max(0.0, window_s - seconds)
        self.log_dir = log_dir
        self.active = False
        self._ann = None

    def maybe_start(self, elapsed: float, settle=None) -> None:
        """Start tracing once ``elapsed`` reaches the traced part, after
        ``settle()``, a wait for the device, where given."""
        if not self.enabled or self.active or elapsed < self.start_at:
            return
        import jax

        if settle is not None:
            settle()
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.traced_window")
        self._ann.__enter__()
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.enabled = False


NESTED = {"taylor": "TaylorConfig", "moe": "MoEConfig", "ssm": "SSMConfig"}


def _dataclass(cls, values: dict, where: str):
    unknown = set(values) - {f.name for f in fields(cls)}
    if unknown:
        raise ValueError(f"configuration section {where!r} has keys "
                         f"{sorted(unknown)} that {cls.__name__} does not")
    return cls(**values)


@dataclass
class Run:
    """What a driver gets: the cell's parts and the run's settings."""
    bench: dict
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: int
    trace: bool
    calibrate: bool
    t_start: float
    chips: int
    base: Path = BENCH
    tracer: Tracer = None
    setup_s: float | None = None
    memory_peak_bytes: int | None = None
    flops_in_window: int | None = None
    compiles: list = field(default_factory=list)
    compiles_at_setup: int | None = None
    compiles_in_window: int | None = None
    gc_events: list = field(default_factory=list)   # (start, end, gen)
    _gc_start: float | None = None

    @property
    def model(self) -> dict:
        return self.config["model"]

    def program_config(self):
        """The program's ModelConfig with the configuration's sizes; the
        nested ``taylor``, ``moe`` and ``ssm`` sections become their
        dataclasses. A key the program does not have raises."""
        from repro.configs import base

        m = dict(self.model)
        for key, cls in NESTED.items():
            if key in m:
                m[key] = _dataclass(getattr(base, cls), m[key], key)
        if "layer_pattern" in m:
            m["layer_pattern"] = tuple(m["layer_pattern"])
        return _dataclass(base.ModelConfig, dict(m, name=self.config["name"]),
                          "model")

    def reference(self):
        return load_module(self.base / "configs" /
                           f"{self.config['reference']}.py")

    def setup_done(self) -> None:
        self.setup_s = time.time() - self.t_start
        self.compiles_at_setup = len(self.compiles)
        gc.callbacks.append(self._on_gc)

    def window_closed(self) -> None:
        self.tracer.stop()
        self.compiles_in_window = len(self.compiles) - self.compiles_at_setup
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._gc_start = now
        elif self._gc_start is not None:
            self.gc_events.append((self._gc_start, now, info["generation"]))
            self._gc_start = None

    def slowest(self, steps, t0: float, parts: dict | None = None,
                k: int = 3) -> list:
        """The ``k`` longest of ``steps`` ((start, end, thread CPU s)), each
        with its offset in the window, the garbage collection inside it
        and, per name in ``parts``, that part's seconds of the step."""
        order = sorted(range(len(steps)),
                       key=lambda i: steps[i][1] - steps[i][0])[::-1][:k]
        out = []
        for i in order:
            a, b, cpu = steps[i]
            gc_s = sum(min(b, e) - max(a, s) for s, e, _ in self.gc_events
                       if s < b and e > a)
            row = {"at_s": a - t0, "wall_ms": 1e3 * (b - a),
                   "cpu_ms": 1e3 * cpu, "gc_ms": 1e3 * gc_s}
            for name, values in (parts or {}).items():
                row[name] = 1e3 * values[i]
            out.append(row)
        return out

    def gc_stats(self) -> dict:
        full = [e - s for s, e, g in self.gc_events if g == 2]
        return {"gc_in_window": len(self.gc_events),
                "gc_in_window_ms": 1e3 * sum(e - s for s, e, _ in
                                             self.gc_events),
                "gc_full_max_ms": 1e3 * max(full, default=0.0)}

    def memory_peak(self) -> None:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()[: self.chips]]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None


@dataclass
class Reading:
    """What a per-layer metric's reader gets."""
    run: Run
    result: dict
    trace: object          # trace_reduce.Trace or None
    reduced: dict | None
    peaks: dict


def check_limits(run: Run, checks: dict) -> dict:
    """Each compared number beside its limit, from the cell's file
    ``bench/limits/<cell>.json``."""
    limits = load_json(run.base / "limits" / f"{run.cell['name']}.json")
    return {k: {"value": v, "limit": limits[k]} for k, v in checks.items()
            if k in limits}


def within(checks: dict) -> bool:
    """The comparison that decides ``correct``: every number compared,
    and each at or under its limit."""
    return bool(checks) and all(c["value"] <= c["limit"]
                                for c in checks.values())


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")


def setup_jax() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def count_compiles(run: Run) -> None:
    import jax

    def on_event(name, *args, **kw):
        if name in COMPILE_EVENTS:
            run.compiles.append(name)

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_event)


def read_per_layer(run: Run, result: dict, peaks: dict) -> dict:
    """Every per-layer metric this cell reports, from its reader."""
    from bench import trace_reduce as TR

    trace = reduced = None
    if run.trace and run.tracer is not None and TRACE_DIR.exists():
        trace = TR.load(TR.find_xplane(str(TRACE_DIR)))
        kinds = result.get("step_kinds") or {}
        trace.host = [relabel(e, kinds) for e in trace.host]
        reduced = TR.reduce(trace)
    reading = Reading(run, result, trace, reduced, peaks)
    out = {}
    for metric in run.bench["per_layer"]:
        if not cell_reports(metric, run.cell["name"]):
            continue
        reader = load_module(run.base / "metrics" / f"{metric['name']}.py")
        value = reader.read(reading)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out, reduced


def relabel(event, kinds: dict):
    """``bench.engine_step`` of step 3 -> ``bench.engine_step[decode]``."""
    from bench.trace_reduce import Event

    if event.step is None or event.step not in kinds:
        return event
    return Event(f"{event.name}[{kinds[event.step]}]", event.start,
                 event.end, event.step)


def execute(args, t_start: float, *, bench_path: Path = ROOT /
            "BENCHMARK.json", require_chip: bool = True) -> dict:
    """Run one cell and return the printed parts (tests call this with
    ``require_chip=False`` on the CPU)."""
    bench = load_json(bench_path)
    cell = find(bench, "workloads", args.workload)
    if require_chip:
        require_chips(cell["chips"])
    base = bench_path.parent / "bench"
    config = load_json(base / "configs" / f"{cell['config']}.json")
    traffic = load_json(base / "traffic" / f"{cell['traffic']}.json")
    run = Run(bench, cell, config, traffic, args.seed, args.seconds,
              bool(args.trace), bool(getattr(args, "calibrate", 0)),
              t_start, cell["chips"], base)
    run.tracer = Tracer(run.trace, args.seconds, TRACE_SECONDS, TRACE_DIR)
    if require_chip:
        setup_jax()
    count_compiles(run)
    driver = importlib.import_module(f"bench.drivers.{traffic['kind']}")
    result = driver.run(run)
    return finish(run, result, require_chip)


def finish(run: Run, result: dict, on_chip: bool) -> dict:
    from bench.peaks import peaks_for

    dev = device_info()
    peaks = peaks_for(dev["kind"]) if on_chip else None
    checks = check_limits(run, result["checks"])
    correct = bool(result["ok"]) and within(checks)
    controls = {}
    for name, readings in result.get("controls", {}).items():
        c = check_limits(run, readings)
        controls[name] = {"correct": within(c), "checks": c}
    if run.trace:
        metrics, reduced = read_per_layer(run, result, peaks)
    else:
        reduced = None
        metrics = {}
        for m in run.bench["end_to_end"]:
            if not cell_reports(m, run.cell["name"]):
                continue
            value = (run.setup_s if m["name"] == "setup_s"
                     else result["e2e"].get(m["name"]))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev["memory_peak_bytes"] = run.memory_peak_bytes
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
    stats = dict(result["stats"], compiles_in_window=run.compiles_in_window,
                 **run.gc_stats(), setup_s=run.setup_s,
                 readings=dict(result["checks"]))
    if controls:
        stats["controls"] = controls
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": dev}
    if reduced is not None:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = checks
    return {"stats": stats, "line": line, "checks": checks,
            "controls": controls}


def main(args, t_start: float) -> int:
    try:
        out = execute(args, t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print("bench-stats " + json.dumps(out["stats"], default=float),
          flush=True)
    print(json.dumps(out["line"]), flush=True)
    for who, v in out["controls"].items():
        for name, c in v["checks"].items():
            print(f"control {who} {name} {c['value']!r} limit "
                  f"{c['limit']!r}", file=sys.stderr)
        print(f"control {who} correct {v['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0

