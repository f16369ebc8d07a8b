"""Fault tolerance for long-running multi-pod jobs.

  * ``PreemptionHandler`` — converts SIGTERM/SIGINT into a cooperative
    "checkpoint and exit" request (TPU pods get ~30s eviction notice).
  * ``StragglerDetector`` — EWMA step-time monitor; flags steps slower
    than ``threshold×`` the running mean. On a real pod the flag feeds
    the controller that triggers replacement of the slow host; here it
    logs and counts (and the train loop exposes the count as a metric).
  * ``Membership`` — heartbeat-based replica membership: peers
    ``heartbeat()``, ``sweep()`` expires the silent ones, and every
    join/leave bumps the *epoch* (the router invalidation signal the
    ROADMAP's fleet-serving tier keys on). Visible to obs: membership
    size, per-peer heartbeat age, heartbeat and epoch-change counters
    all publish into ``repro.obs.metrics.default_registry`` (override
    with ``registry=``), so fleet snapshots carry replica health.
  * ``run_with_restarts`` — the supervision loop: run → on exception,
    restore from the last checkpoint and continue; gives up after
    ``max_failures`` within one step window (a poison-pill guard).
  * ``elastic_remesh`` — rebuild a smaller/larger mesh after losing or
    gaining hosts and re-place a restored checkpoint onto it (the
    checkpoint format is topology-free; see checkpoint/manager.py).
"""

from __future__ import annotations

import logging
import signal
import time
from dataclasses import dataclass, field

import jax
from jax.sharding import AxisType

from repro.obs import metrics as OM

log = logging.getLogger("repro.ft")


class PreemptionHandler:
    def __init__(self, signals=(signal.SIGTERM, signal.SIGINT)):
        self._requested = False
        self._signals = signals
        self._prev = {}

    def __enter__(self):
        for s in self._signals:
            try:
                self._prev[s] = signal.signal(s, self._handle)
            except ValueError:        # not on main thread (tests)
                pass
        return self

    def _handle(self, signum, frame):
        log.warning("preemption signal %s received — requesting checkpoint",
                    signum)
        self._requested = True

    @property
    def preempted(self) -> bool:
        return self._requested

    def __exit__(self, *exc):
        for s, prev in self._prev.items():
            signal.signal(s, prev)
        return False


@dataclass
class StragglerDetector:
    """EWMA step-time monitor. Pass ``registry=`` (a
    ``MetricsRegistry``) to also publish ``ft_straggler_events_total``
    and ``ft_step_time_ewma_seconds`` — the per-host spread of that
    gauge across merged fleet snapshots is the straggler signal."""

    threshold: float = 2.0       # step slower than 2× EWMA = straggler
    alpha: float = 0.1
    ewma: float | None = None
    stragglers: int = 0
    history: list = field(default_factory=list)
    registry: object | None = None

    def __post_init__(self):
        if self.registry is not None:
            self._straggler_c = self.registry.counter(
                "ft_straggler_events_total",
                "steps flagged slower than threshold x EWMA")
            self._ewma_g = self.registry.gauge(
                "ft_step_time_ewma_seconds",
                "EWMA of step wall time on this host")

    def observe(self, step_time_s: float) -> bool:
        is_straggler = False
        if self.ewma is not None and step_time_s > self.threshold * self.ewma:
            self.stragglers += 1
            is_straggler = True
            log.warning("straggler step: %.3fs vs EWMA %.3fs",
                        step_time_s, self.ewma)
            if self.registry is not None:
                self._straggler_c.inc()
        self.ewma = (step_time_s if self.ewma is None
                     else (1 - self.alpha) * self.ewma
                     + self.alpha * step_time_s)
        if self.registry is not None:
            self._ewma_g.set(self.ewma)
        self.history.append((step_time_s, is_straggler))
        return is_straggler


class Membership:
    """Heartbeat membership over replica/host peers, obs-visible.

    Pure bookkeeping — transport is the caller's problem (a real
    deployment forwards peer pings here; tests drive the clock). Every
    *change* of the member set bumps ``epoch``: the future router
    invalidates its placement on epoch changes rather than diffing
    member lists.

    Published metrics (``registry`` defaults to the process-global
    ``repro.obs.metrics.default_registry``):

      ft_members                    gauge    current live peers
      ft_heartbeat_age_seconds{peer} gauge   seconds since last beat
      ft_heartbeats_total           counter  beats received
      ft_epoch_changes_total        counter  joins + leaves
    """

    def __init__(self, *, timeout_s: float = 10.0,
                 registry: OM.MetricsRegistry | None = None,
                 clock=time.monotonic):
        self.timeout_s = timeout_s
        self._clock = clock
        self._last_beat: dict[str, float] = {}
        self.epoch = 0
        reg = registry if registry is not None else OM.default_registry
        self._members_g = reg.gauge("ft_members",
                                    "live peers in the membership")
        self._age_g = reg.gauge("ft_heartbeat_age_seconds",
                                "seconds since each peer's last beat",
                                labelnames=("peer",))
        self._beats_c = reg.counter("ft_heartbeats_total",
                                    "heartbeats received")
        self._epoch_c = reg.counter("ft_epoch_changes_total",
                                    "membership epoch bumps (join/leave)")

    @property
    def members(self) -> list[str]:
        return sorted(self._last_beat)

    def heartbeat(self, peer: str) -> None:
        """Record one beat; a first beat is a join (epoch bump)."""
        now = self._clock()
        joined = peer not in self._last_beat
        self._last_beat[peer] = now
        self._beats_c.inc()
        if joined:
            self.epoch += 1
            self._epoch_c.inc()
            log.info("peer %s joined (epoch %d, %d members)",
                     peer, self.epoch, len(self._last_beat))
        self.publish()

    def leave(self, peer: str) -> None:
        """Explicit departure (cooperative preemption / drain): drop the
        peer now, without waiting for its heartbeats to time out. A
        leave bumps the epoch exactly like an expiry."""
        if peer not in self._last_beat:
            return
        del self._last_beat[peer]
        self.epoch += 1
        self._epoch_c.inc()
        self._age_g.labels(peer=peer).set(self.timeout_s)
        log.info("peer %s left (epoch %d, %d members)",
                 peer, self.epoch, len(self._last_beat))
        self.publish()

    def sweep(self) -> list[str]:
        """Expire peers silent for ``timeout_s``; each is a leave
        (epoch bump). Returns the expired peers."""
        now = self._clock()
        dead = [p for p, t in self._last_beat.items()
                if now - t > self.timeout_s]
        for p in dead:
            del self._last_beat[p]
            self.epoch += 1
            self._epoch_c.inc()
            # the expired peer's age series freezes at the timeout: a
            # flat-lined series reads as "gone", not "infinitely stale"
            self._age_g.labels(peer=p).set(self.timeout_s)
            log.warning("peer %s expired (epoch %d, %d members)",
                        p, self.epoch, len(self._last_beat))
        self.publish()
        return dead

    def publish(self) -> None:
        """Refresh the gauges (called on every beat/sweep; callers may
        also call it right before snapshotting)."""
        now = self._clock()
        self._members_g.set(len(self._last_beat))
        for p, t in self._last_beat.items():
            self._age_g.labels(peer=p).set(now - t)


def run_with_restarts(make_state, run_fn, *, max_failures: int = 3):
    """Supervision loop.

    make_state() -> state      (fresh or restored-from-checkpoint)
    run_fn(state) -> state     (raises on failure; returns on completion)
    """
    failures = 0
    while True:
        state = make_state()
        try:
            return run_fn(state)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:  # noqa: BLE001 — any worker failure
            failures += 1
            log.error("run failed (%d/%d): %s", failures, max_failures, e)
            if failures >= max_failures:
                raise


def elastic_remesh(n_devices: int | None = None, model_parallel: int = 1):
    """Build the largest (data, model) mesh the surviving devices allow."""
    devs = jax.devices()
    n = n_devices if n_devices is not None else len(devs)
    n = min(n, len(devs))
    data = max(n // model_parallel, 1)
    # Auto axes, as launch/mesh.py: the train step runs under this mesh
    return jax.make_mesh((data, model_parallel), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=devs[:data * model_parallel])
