"""Host/device phase attribution for the media-loop tick.

ROADMAP #1's gap — protect plane ~622k pps/chip vs loop echo ~95 pps —
lives *somewhere* between the socket and the kernel launch, and the
stage tracer can't see it: stage spans say "forward_chain took 9 ms"
but not whether those were Python milliseconds, dispatch milliseconds,
or transfer milliseconds.  `PhaseProfiler` splits one tick's wall time
into six phases:

  idle            socket wait inside the recv batching window
  host_python     everything the host interpreter does (residual)
  dispatch        jax call until the launch returns (no materialize)
  h2d_transfer    staging batch arrays host -> device (fenced probe)
  device_compute  fenced wait on dispatched device work
  d2h_transfer    materializing device results back to host memory

Fencing (`jax.block_until_ready` at the phase boundaries) serializes
the pipeline, so it is **sampled**: every `sample_every`-th tick pays
the probes (their cost is itself accounted, `probe_overhead_s`);
steady-state ticks run fence-free and only bump the always-on transfer
byte counters.  On a sampled tick the phases sum to the tick wall time
by construction — `host_python` is the residual — which is the
property test's invariant and what makes shares meaningful.

Results feed three sinks: a `tick_phase_seconds{phase=...}` histogram
family, the `PipelineTracer` phase ledger (drained by the supervisor
so `ladder_escalate` can say *host-bound* vs *device-bound*), and
`last_phases` for debug surfaces.  Compile-cache hit/miss/recompile
counters (utils/compile_cache.py) and live device-memory gauges ride
along on the same registry.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Iterable, Optional

from libjitsi_tpu.utils.compile_cache import compile_stats
from libjitsi_tpu.utils.metrics import (MetricsRegistry,
                                        exponential_buckets)
from libjitsi_tpu.utils.tracing import NULL_SPAN

#: the phase taxonomy; `host_python` is always the residual so the six
#: sum to the sampled tick's wall time exactly
PHASES = ("host_python", "dispatch", "h2d_transfer", "device_compute",
          "d2h_transfer", "idle")

#: phases owned by the host interpreter vs the device pipeline — the
#: supervisor's "host-bound vs device-bound" overload classification
HOST_PHASES = ("host_python", "dispatch")
DEVICE_PHASES = ("h2d_transfer", "device_compute", "d2h_transfer")

#: 10 µs .. ~2.6 s per phase per tick
PHASE_BUCKETS = tuple(exponential_buckets(1e-5, 4.0, 10))

_jax = None                      # lazily imported, cached module ref


def _get_jax():
    global _jax
    if _jax is None:
        import jax

        _jax = jax
    return _jax


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


class _PhaseSpan:
    """Times one phase region into the profiler's current tick."""

    __slots__ = ("_prof", "_phase", "_t0")

    def __init__(self, prof: "PhaseProfiler", phase: str):
        self._prof = prof
        self._phase = phase
        self._t0 = 0.0

    def __enter__(self) -> "_PhaseSpan":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self._prof.add_phase(self._phase,
                             time.perf_counter() - self._t0)


def phase_of(perf: "Optional[PhaseProfiler]", name: str):
    """`perf.phase(name)`, or the shared no-op for a component standing
    alone (`perf` None: the bridge hands its loop's profiler to the
    SRTP table and the translator, tests and the mesh seams hand
    none)."""
    return NULL_SPAN if perf is None else perf.phase(name)


class PhaseProfiler:
    """Per-tick host/device phase splitter (see module docstring).

    Wire-up (io/loop.py): `begin_tick()` / `end_tick()` bracket the
    tick; `phase(name)` context managers mark idle/dispatch/compute/
    d2h regions; `probe_h2d(arrays)` measures staging cost with an
    explicit fenced copy; `note_h2d`/`note_d2h` count transfer bytes
    every tick.  `sample_every=0` disables fencing entirely (byte
    counters stay live)."""

    def __init__(self, metrics: Optional[MetricsRegistry] = None,
                 sample_every: int = 16,
                 tracer=None,
                 inflight_fn: Optional[Callable[[], int]] = None):
        self.metrics = metrics
        self.sample_every = int(sample_every)
        self.tracer = tracer
        self.sampled = False
        self.ticks_seen = 0
        self.sampled_ticks = 0
        self.probe_overhead_s = 0.0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.last_phases: Dict[str, float] = {}
        self.phase_totals: Dict[str, float] = {}
        self._phases: Dict[str, float] = {}
        self._t0: Optional[float] = None
        self.stats = compile_stats()
        self.phase_hist = None
        if metrics is not None:
            self.phase_hist = metrics.histogram_vec(
                "tick_phase_seconds", PHASE_BUCKETS, "phase",
                help_="sampled per-tick wall time split by "
                      "host/device phase")
            for p in PHASES:       # family complete from first scrape
                self.phase_hist.labels(p)
            self.register_metrics(metrics, inflight_fn=inflight_fn)

    # -------------------------------------------------------- registry
    def register_metrics(self, metrics: MetricsRegistry,
                         inflight_fn: Optional[Callable[[], int]] = None
                         ) -> None:
        metrics.register_scalar(
            "phase_sampled_ticks", lambda: self.sampled_ticks,
            help_="ticks that paid the fencing probes", kind="counter")
        metrics.register_scalar(
            "phase_probe_overhead_seconds",
            lambda: self.probe_overhead_s,
            help_="total wall time spent inside fencing probes",
            kind="counter")
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
        self.ticks_seen += 1
        self.sampled = (self.sample_every > 0 and
                        (self.ticks_seen - 1) % self.sample_every == 0)
        self._phases = {}
        self._t0 = time.perf_counter()

    def phase(self, name: str):
        """Context manager attributing the region to `name` on sampled
        ticks; free (a shared no-op) otherwise."""
        if not self.sampled:
            return NULL_SPAN
        return _PhaseSpan(self, name)

    def add_phase(self, name: str, seconds: float) -> None:
        self._phases[name] = self._phases.get(name, 0.0) + \
            float(seconds)

    def probe_h2d(self, arrays: Iterable) -> None:
        """Fenced host->device staging probe: copies `arrays` to the
        device and blocks, attributing the span to `h2d_transfer`.
        The probe's own cost is also accounted in `probe_overhead_s` —
        it is extra work sampled ticks pay for attribution."""
        if not self.sampled:
            return
        t0 = time.perf_counter()
        try:
            jax = _get_jax()
            staged = [jax.numpy.asarray(a) for a in arrays
                      if a is not None]
            jax.block_until_ready(staged)
        except Exception:
            pass                       # attribution must never crash IO
        dt = time.perf_counter() - t0
        self.add_phase("h2d_transfer", dt)
        self.probe_overhead_s += dt

    def fence(self, pending, phase: str = "device_compute") -> None:
        """Block on a dispatched result's device work, attributing the
        wait to `phase` (the launch itself was `dispatch`)."""
        if not self.sampled:
            return
        t0 = time.perf_counter()
        block = getattr(pending, "block_until_ready", None)
        if block is not None:
            try:
                block()
            except Exception:
                pass
        dt = time.perf_counter() - t0
        self.add_phase(phase, dt)
        self.probe_overhead_s += dt

    def note_h2d(self, nbytes: int) -> None:
        self.h2d_bytes += int(nbytes)

    def note_d2h(self, nbytes: int) -> None:
        self.d2h_bytes += int(nbytes)

    def end_tick(self) -> None:
        if self._t0 is None:
            return
        wall = time.perf_counter() - self._t0
        self._t0 = None
        if not self.sampled:
            return
        self.sampled = False
        measured = sum(self._phases.values())
        # residual: whatever the explicit phase regions did not claim
        # is host interpreter time, so the six phases sum to `wall`
        self.add_phase("host_python", max(0.0, wall - measured))
        for p in PHASES:
            self._phases.setdefault(p, 0.0)
        self.last_phases = dict(self._phases)
        for p, secs in self._phases.items():
            self.phase_totals[p] = self.phase_totals.get(p, 0.0) + secs
        self.sampled_ticks += 1
        if self.phase_hist is not None:
            for p in PHASES:
                self.phase_hist.labels(p).observe(self._phases[p])
        if self.tracer is not None:
            merge = getattr(self.tracer, "merge_phases", None)
            if merge is not None:
                merge(self._phases)
