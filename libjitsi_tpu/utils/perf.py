"""What the media loop counts beside its spans: the tick's host/device
phase split, the transfer bytes, the compile-cache counters and the
device-memory gauges.

The stage tracer says "unprotect_wait took 2 ms"; the phase split says
whose milliseconds those were.  One tick's wall time falls into six
phases:

  idle            socket wait inside the recv batching window
  host_python     everything the host interpreter does (residual)
  dispatch        pack and the jit call, until the launch returns
  h2d_transfer    the `jax.device_put` of the staged arrays
  device_compute  the tick thread's wait for a dispatched program
  d2h_transfer    the copy of the results back to host memory

Nothing here times them: the split is READ OFF the span tree, every
tick, by the one rule in utils/tracing.py (`PHASE_OF_STAGE`,
`phase_split`: each seam's `put` / `dispatch` / block / `d2h` spans,
`host_python` the residual, so the six sum to the wall).  It is taken
where the tracer's ledgers are drained: by the supervisor, of the
whole bridge tick it judges and outside that tick's wall
(`sup.last_phases`: what `ladder_escalate`, /healthz and the capacity
model's `host` resource read), which hands it on to `LoopPerf.take`;
a loop that nobody drains (a `MediaStream`, a harness) takes it
itself, of its own tick.  Either way it lands in `last_phases`,
`phase_totals` and the `tick_phase_seconds{phase=...}` histogram
family.  `classify_bound` and `host_share` say host-bound or
device-bound from it.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional

from libjitsi_tpu.utils.compile_cache import compile_stats
from libjitsi_tpu.utils.metrics import (MetricsRegistry,
                                        exponential_buckets)
from libjitsi_tpu.utils.tracing import PHASES, PipelineTracer, phase_split

#: phases owned by the host interpreter vs the device pipeline — the
#: supervisor's "host-bound vs device-bound" overload classification
HOST_PHASES = ("host_python", "dispatch")
DEVICE_PHASES = ("h2d_transfer", "device_compute", "d2h_transfer")

#: 10 µs .. ~2.6 s per phase per tick
PHASE_BUCKETS = tuple(exponential_buckets(1e-5, 4.0, 10))


def classify_bound(phases: Dict[str, float]) -> str:
    """"host" / "device" / "idle" / "unknown" for one phase split."""
    if not phases:
        return "unknown"
    host = sum(phases.get(p, 0.0) for p in HOST_PHASES)
    device = sum(phases.get(p, 0.0) for p in DEVICE_PHASES)
    idle = phases.get("idle", 0.0)
    total = host + device + idle
    if total <= 0.0:
        return "unknown"
    return max((("host", host), ("device", device), ("idle", idle)),
               key=lambda kv: kv[1])[0]


def host_share(phases: Dict[str, float]) -> float:
    """Fraction of non-idle tick time owned by the host
    (host_python + dispatch over everything but idle)."""
    host = sum(phases.get(p, 0.0) for p in HOST_PHASES)
    busy = host + sum(phases.get(p, 0.0) for p in DEVICE_PHASES)
    return host / busy if busy > 0.0 else 0.0


class LoopPerf:
    """The object at `loop.perf` (see module docstring).

    Wire-up (io/loop.py): `begin_tick()` / `end_tick()` bracket the
    loop's tick and, where nobody else drains `tracer`, read its phase
    split off it; `note_h2d` / `note_d2h` count transfer bytes at the
    loop's staging points."""

    def __init__(self, tracer: PipelineTracer, metrics: MetricsRegistry,
                 inflight_fn: Optional[Callable[[], int]] = None):
        self.tracer = tracer
        #: set by the supervisor that drains `tracer`: it takes the
        #: split at its drain and calls `take`, and the loop's own
        #: hooks cost the tick nothing
        self.drained_by_supervisor = False
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.last_phases: Dict[str, float] = {}
        self.phase_totals: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self._t0 = 0.0
        self._mark: Dict[str, float] = {}
        self.stats = compile_stats()
        hist = metrics.histogram_vec(
            "tick_phase_seconds", PHASE_BUCKETS, "phase",
            help_="per-tick wall time of the media loop split by "
                  "host/device phase")
        # every child made here: the family is complete from the first
        # scrape
        self._observe = [hist.labels(p).observe for p in PHASES]
        self.register_metrics(metrics, inflight_fn=inflight_fn)

    # -------------------------------------------------------- registry
    def register_metrics(self, metrics: MetricsRegistry,
                         inflight_fn: Optional[Callable[[], int]] = None
                         ) -> None:
        metrics.register_scalar(
            "h2d_bytes_total", lambda: self.h2d_bytes,
            help_="bytes staged host->device at the loop's staging "
                  "points", kind="counter")
        metrics.register_scalar(
            "d2h_bytes_total", lambda: self.d2h_bytes,
            help_="bytes materialized device->host at the loop's "
                  "egress points", kind="counter")
        metrics.register_scalar(
            "compile_cache_hits", lambda: self.stats.hits,
            help_="persistent-compilation-cache hits", kind="counter")
        metrics.register_scalar(
            "compile_cache_misses", lambda: self.stats.misses,
            help_="persistent-compilation-cache misses",
            kind="counter")
        metrics.register_scalar(
            "compile_events", lambda: self.stats.compile_events,
            help_="XLA compilations observed (a step here mid-run "
                  "means a recompile landed on the data path)",
            kind="counter")
        metrics.register_scalar(
            "compile_seconds_total",
            lambda: self.stats.compile_seconds,
            help_="total seconds spent compiling", kind="counter")
        metrics.register_scalar(
            "dispatch_inflight_ticks",
            (inflight_fn if inflight_fn is not None else lambda: 0),
            help_="age in ticks of the oldest un-flushed async "
                  "dispatch (pipelined loop depth)")
        metrics.register_scalar(
            "device_live_bytes", lambda: self._device_stat(
                "bytes_in_use"),
            help_="live device buffer bytes (first device)")
        metrics.register_scalar(
            "device_num_buffers", lambda: self._device_stat(
                "num_allocs"),
            help_="live device buffer count (first device)")

    @staticmethod
    def _device_stat(key: str) -> float:
        try:
            from libjitsi_tpu.utils.profiling import device_memory

            return float(device_memory().get(key) or 0)
        except Exception:
            return 0.0

    # ------------------------------------------------------- tick hooks
    def begin_tick(self) -> None:
        if self.drained_by_supervisor:
            return
        # nobody drains this loop's tracer: its tick is what the self
        # ledger gains between here and `end_tick`
        self._mark = dict(self.tracer.self_ledger)
        self._t0 = time.perf_counter()

    def note_h2d(self, nbytes: int) -> None:
        self.h2d_bytes += int(nbytes)

    def note_d2h(self, nbytes: int) -> None:
        self.d2h_bytes += int(nbytes)

    def end_tick(self) -> None:
        if self.drained_by_supervisor:
            return
        wall = time.perf_counter() - self._t0
        mark = self._mark
        self.take(phase_split(
            {stage: seconds - mark.get(stage, 0.0)
             for stage, seconds in self.tracer.self_ledger.items()},
            wall))

    def take(self, phases: Dict[str, float]) -> None:
        """One tick's split into `last_phases`, `phase_totals` and the
        histogram family."""
        self.last_phases = phases
        totals = self.phase_totals
        for p, observe in zip(PHASES, self._observe):
            totals[p] += phases[p]
            observe(phases[p])
