"""Pipeline stage tracing: one span tree per tick.

The supervisor's overload ladder used to see one number — bridge.tick
wall time — so "we're over budget" never said *where* the budget went
(ingress? the route loop? the wait for the fan-out launch?).
`PipelineTracer` wraps each stage of a tick in a span.  Spans nest:
the tracer keeps the innermost open span, every span remembers the one
that was open when it began (its parent) and the tick it belongs to
(`loop.trace_id`, the identifier the journey histogram's exemplars
carry).  A span feeds four sinks at once:

  1. a per-stage `TimingRing` in the `MetricsRegistry` (rendered as a
     Prometheus summary, `stage_<name>_seconds{quantile=...}`), so
     /metrics carries p50/p99 per stage;
  2. the per-tick **inclusive ledger** (stage -> seconds this tick,
     children included) and the **self-time ledger** (the same less
     the time its child spans covered).  Self times of a tick sum to
     the time inside its outermost spans, which is what makes a share
     of them meaningful: the supervisor steers on the self ledger, the
     benchmark's `stage_*` metrics read the inclusive one.  Both are
     drained together by `take_ledger()`;
  3. the per-tick **counts ledger** (`span(stage, rows=...)` or
     `sp.note(rows=...)`: stage -> {count: sum}), drained with them;
  4. a `jax.profiler.TraceAnnotation` named `stage:<name>` carrying
     `tick=<id>` and the counts as stats, so in a captured trace the
     host spans line up with the TPU timeline on the profiler's own
     clock and say which tick they belong to and how much they carried.
     It does nothing unless a profiler session is active.

A span is the tick thread's time of the tick that RUNS it: that tick's
ledgers book it.  The `tick` it carries (and writes into the trace) is
the tick its work BELONGS to, which is another where one tick finishes
what an earlier one began: the SFU's fan-out is dispatched by tick N
and collected by tick N+1, under `tracer.on_behalf_of(N)`, so the
collection's `fanout_wait`, `fanout_d2h`, `nack_cache` and `egress`
say `tick=N` in the trace and a reader that joins spans by `tick` pairs
a dispatch with its own wait (benchmarks/seams.py, xstats.py).

Spans are per tick or per batch, never per packet or per row.  A stage
entered twice in a tick sums.  Spans open and close on the tick thread,
innermost first (`with`); the tree has no lock.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Optional, Tuple

from libjitsi_tpu.utils.metrics import MetricsRegistry

try:                                    # annotation sink is optional:
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:                       # pragma: no cover - jax present
    _TraceAnnotation = None

#: the leaves of an SfuBridge tick, in the order they run: spans with
#: no child (but `gc`, which opens wherever a collection lands).  They
#: tile the tick: what lies between them is in no leaf (a container's
#: own lines, or no span at all), and the benchmark's
#: `tick_unspanned_pct` watches that it stays small.  Each device call
#: of the tick (seam S in `unprotect`, `fanout`) books the same four
#: host phases: `S_put` (the `jax.device_put` alone, core/staging.py),
#: the rest of `S_dispatch` (pack and the jit call), the wait for the
#: program (`unprotect_block`, `fanout_wait`) and `S_d2h` (the copy
#: back and the split)
LEAF_STAGES = ("ingress", "demux", "unprotect_host", "unprotect_put",
               "unprotect_block", "unprotect_d2h", "parse", "recovery",
               "bwe", "abs_send_time", "route", "expand", "fanout_put",
               "fanout_wait", "fanout_d2h", "nack_cache", "egress",
               "supervise", "gc")

#: the spans round them: `unprotect_wait` holds `unprotect_dispatch`,
#: `unprotect_block` and `unprotect_d2h` (its counts are the whole
#: call's, as they were when it was a leaf), `S_dispatch` holds `S_put`
CONTAINER_STAGES = ("reverse_chain", "unprotect", "unprotect_wait",
                    "unprotect_dispatch", "forward_chain",
                    "fanout_dispatch")

#: stages that are NOT the tick thread's time: booked with
#: `PipelineTracer.book` from a duration measured elsewhere.
#: `egress_send` is the egress worker's `sendmmsg` of one fan-out burst
#: (io/udp.py:send_batch_async), booked by the tick that reaps it; the
#: tick's own `egress` leaf is the hand-over alone.  In no leaf sum and
#: not in the self ledger the overload ladder steers on
OFF_TICK_STAGES = ("egress_send",)

#: canonical stage names (a tracer accepts any string; these are the
#: ones the dashboards are generated from): the leaves, the containers
#: round them, what runs beside the tick and the mixer bridge's stages
STAGES = (LEAF_STAGES + CONTAINER_STAGES + OFF_TICK_STAGES
          + ("decode", "mixer"))

#: the phases a tick's wall time splits into (`phase_split`;
#: utils/perf.py says which are the host's and which the device's)
PHASES = ("host_python", "dispatch", "h2d_transfer", "device_compute",
          "d2h_transfer", "idle")

#: THE rule from a span's SELF time to a phase, and the one place that
#: knows it.  Of a device call's four host phases (above) the put is
#: the copy in, the rest of the dispatch the launch, the block the wait
#: for the program and the d2h the copy back; `ingress` is the socket's
#: batching window.  A stage not named here, and what no span covers,
#: is the interpreter's: `host_python`.  The `chain_*` spans are the
#: same seams of a `MediaLoop` that runs a transform chain
#: (io/loop.py): a call that blends launch, wait and copy back is
#: booked as the wait, a `result()` as the copy back.  None opens on
#: an SfuBridge tick
PHASE_OF_STAGE = {
    "ingress": "idle",
    "unprotect_put": "h2d_transfer", "fanout_put": "h2d_transfer",
    "unprotect_dispatch": "dispatch", "fanout_dispatch": "dispatch",
    "chain_dispatch": "dispatch",
    "unprotect_block": "device_compute", "fanout_wait": "device_compute",
    "chain_device": "device_compute",
    "unprotect_d2h": "d2h_transfer", "fanout_d2h": "d2h_transfer",
    "chain_d2h": "d2h_transfer",
}


def phase_split(self_ledger: Dict[str, float],
                wall_s: float) -> Dict[str, float]:
    """One tick's self ledger and its wall seconds -> {phase: seconds}
    over `PHASES`.  `host_python` is the residual (clamped at 0 where
    a caller's clock is not the spans'), so the six sum to the wall:
    what makes a share of them meaningful.  `OFF_TICK_STAGES` enter
    nowhere, as they enter no self ledger."""
    out = dict.fromkeys(PHASES, 0.0)
    for stage, phase in PHASE_OF_STAGE.items():
        seconds = self_ledger.get(stage)
        if seconds:
            out[phase] += seconds
    out["host_python"] = max(0.0, wall_s - sum(out.values()))
    return out


class _NullSpan:
    """What a component with no tracer opens: costs one call."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def note(self, **counts) -> None:
        pass

    set_metadata = note          # standing in for a TraceAnnotation


NULL_SPAN = _NullSpan()


def span_of(tracer: "Optional[PipelineTracer]", stage: str, **counts):
    """`tracer.span(stage, **counts)`, or the shared no-op span for a
    component standing alone (`tracer` None: tests, the mesh seams)."""
    if tracer is None:
        return NULL_SPAN
    return tracer.span(stage, **counts)


class _StageSpan:
    """One entry of one stage: name, start, end (`seconds`), parent,
    tick."""

    __slots__ = ("_tracer", "stage", "counts", "tick", "parent", "t0",
                 "seconds", "_child_s", "_ann")

    def __init__(self, tracer: "PipelineTracer", stage: str, counts):
        self._tracer = tracer
        self.stage = stage
        self.counts = counts
        self.tick = 0
        self.parent: Optional["_StageSpan"] = None
        self.t0 = 0.0
        self.seconds: Optional[float] = None
        self._child_s = 0.0
        self._ann = None

    def __enter__(self) -> "_StageSpan":
        t = self._tracer
        self.tick = t.tick if t._behalf is None else t._behalf
        if t.annotate:
            self._ann = _TraceAnnotation(t._sink(self.stage)[1],
                                         tick=self.tick, **self.counts)
            self._ann.__enter__()
        # clock first, link second: a `gc` span may open anywhere, and
        # must find a parent whose time covers it
        self.t0 = time.perf_counter()
        self.parent, t._open = t._open, self
        return self

    def note(self, **counts) -> None:
        """Counts known only once the work is under way (a padded row
        count, the bytes that came back)."""
        self.counts.update(counts)
        if self._ann is not None:
            self._ann.set_metadata(**counts)

    def __exit__(self, *exc) -> None:
        seconds = self.seconds = time.perf_counter() - self.t0
        t = self._tracer
        t._open = self.parent
        if self._ann is not None:
            self._ann.__exit__(*exc if exc else (None, None, None))
            self._ann = None
        if self.parent is not None:
            self.parent._child_s += seconds
        stage = self.stage
        t._sink(stage)[0].record(seconds)
        led = t._ledger
        led[stage] = led.get(stage, 0.0) + seconds
        led = t._self_ledger
        led[stage] = led.get(stage, 0.0) + seconds - self._child_s
        if self.counts:
            mine = t._counts.setdefault(stage, {})
            for k, v in self.counts.items():
                mine[k] = mine.get(k, 0) + v


class PipelineTracer:
    """Per-stage span timing + the per-tick ledgers.

    One tracer per media loop / bridge; share it across the pieces of
    one pipeline (loop + SRTP table + translator + supervisor) so their
    stages land in one tree.  `annotate=True` (default) also emits
    jax.profiler.TraceAnnotation spans when jax is importable — they
    are no-ops unless a profiler trace is active.
    """

    def __init__(self, metrics: Optional[MetricsRegistry] = None,
                 prefix: str = "stage", annotate: bool = True):
        self.metrics = metrics if metrics is not None else \
            MetricsRegistry()
        self.prefix = prefix
        self.annotate = bool(annotate) and _TraceAnnotation is not None
        #: id of the tick under way; the loop sets it as a tick begins
        self.tick = 0
        # the tick whose work the spans opening now finish, where that
        # is not the tick under way (`on_behalf_of`)
        self._behalf: Optional[int] = None
        self._open: Optional[_StageSpan] = None
        # stage -> (ring, annotation name), resolved once per name
        self._sinks: Dict[str, tuple] = {}
        self._ledger: Dict[str, float] = {}
        self._self_ledger: Dict[str, float] = {}
        self._counts: Dict[str, Dict[str, float]] = {}
        self.last_ledger: Dict[str, float] = {}
        self.last_self_ledger: Dict[str, float] = {}
        self.last_counts: Dict[str, Dict[str, float]] = {}

    def _sink(self, stage: str) -> tuple:
        sink = self._sinks.get(stage)
        if sink is None:
            sink = self._sinks[stage] = (
                self.metrics.timing(f"{self.prefix}_{stage}"),
                f"{self.prefix}:{stage}")
        return sink

    def span(self, stage: str, **counts) -> _StageSpan:
        return _StageSpan(self, stage, counts)

    @property
    def self_ledger(self) -> Dict[str, float]:
        """The self ledger as it stands, not drained: for a reader
        that is not the drainer (utils/perf.py reads what a region
        added to it)."""
        return self._self_ledger

    @contextlib.contextmanager
    def on_behalf_of(self, tick: int):
        """Spans opened inside carry `tick` (the id an EARLIER tick's
        spans carry) and are booked, like any span, in the ledgers of
        the tick under way: for work one tick began and this one
        finishes."""
        prev, self._behalf = self._behalf, tick
        try:
            yield
        finally:
            self._behalf = prev

    def book(self, stage: str, seconds: float, **counts) -> None:
        """Book a duration that was measured elsewhere (a worker
        thread's, by its own stamps) under `stage` in the tick under
        way: the stage's ring, the inclusive ledger and the counts.
        It opens no span and has no parent, so it enters neither the
        self ledger (it is not the tick thread's time: the ladder must
        not steer on it) nor the profiler's trace."""
        self._sink(stage)[0].record(seconds)
        led = self._ledger
        led[stage] = led.get(stage, 0.0) + seconds
        if counts:
            mine = self._counts.setdefault(stage, {})
            for k, v in counts.items():
                mine[k] = mine.get(k, 0) + v

    def tick_root(self, tick: int, **counts):
        """Root of one tick's tree: an annotation `<prefix>:tick` with
        counts only (`set_metadata` adds those known at the end).  It
        is booked in neither ledger — it closes after they are drained,
        and the supervisor's `last_tick_s` already is its time.  Sets
        the id every span of the tick carries."""
        self.tick = tick
        if not self.annotate:
            return NULL_SPAN
        return _TraceAnnotation(f"{self.prefix}:tick", tick=tick,
                                **counts)

    def take_ledger(self, fold: bool = False) -> Dict[str, float]:
        """Drain this tick's ledgers and return the inclusive one
        (stage -> seconds); the supervisor calls this once per bridge
        tick.  All three are retained (`last_ledger`,
        `last_self_ledger`, `last_counts`) for health()/debug
        surfaces.  `fold` drains into the retained three in place
        instead: for what closed after the tick's drain (the
        supervisor's own `supervise`)."""
        if not fold:
            self.last_ledger, self._ledger = self._ledger, {}
            self.last_self_ledger, self._self_ledger = \
                self._self_ledger, {}
            self.last_counts, self._counts = self._counts, {}
            return self.last_ledger
        for last, late in ((self.last_ledger, self._ledger),
                           (self.last_self_ledger, self._self_ledger)):
            for stage, seconds in late.items():
                last[stage] = last.get(stage, 0.0) + seconds
            late.clear()
        for stage, counts in self._counts.items():
            mine = self.last_counts.setdefault(stage, {})
            for k, v in counts.items():
                mine[k] = mine.get(k, 0) + v
        self._counts.clear()
        return self.last_ledger

    @staticmethod
    def dominant(ledger: Dict[str, float]
                 ) -> Tuple[Optional[str], float]:
        """(stage, seconds) of the ledger's costliest stage."""
        if not ledger:
            return None, 0.0
        stage = max(ledger, key=ledger.get)
        return stage, ledger[stage]
