"""Supervised runtime: watchdog, overload shedding, stream quarantine,
and crash-restart recovery from crypto checkpoints.

The reference runs inside a JVM container that supplies process
supervision; this framework is its own server process, so liveness and
recovery are in scope (SURVEY §5 robustness gap).  One
`BridgeSupervisor` wraps a bridge's tick and layers four mechanisms:

1. **Watchdog** — every tick is timed against a deadline (default: the
   ptime budget).  Consecutive overruns drive a health state machine
   (healthy → overloaded → stalled) exported via MetricsRegistry, so an
   external orchestrator can probe liveness without touching media.

2. **Graceful degradation** — sustained overload walks an escalation
   ladder instead of letting the tick fall behind unboundedly:
   level 1 shrinks the recv batching window to 0 (poll, don't wait),
   level 2 sets `bridge.degraded` (skips speaker scoring / egress level
   stamping / RTCP report generation — work whose absence degrades UX,
   not correctness).  On a bridge with a loss-recovery controller
   (`bridge.recovery`, sfu/recovery.py) two more rungs precede stream
   loss: level 3 sheds FEC redundancy, level 4 shrinks the
   retransmission budget; only then (level 5+, or 3+ without a
   controller) are the lowest-priority streams shed deterministically.
   Recovery walks the same ladder back down once ticks meet the
   deadline again, restoring shed streams LIFO.

3. **Stream quarantine** — per-stream sliding windows over the SRTP
   auth-failure and replay-rejection counters.  A stream exceeding the
   threshold (key mismatch, replay attack, or a corrupting middlebox)
   is dropped at ingress — BEFORE the source-address latch, so a
   spoofing sender can't redirect return media — and re-admitted after
   an exponentially-backed-off ban.

4. **Crash-restart recovery** — periodic whole-bridge snapshots into a
   single versioned checkpoint file (atomic rename), and a `recover()`
   path that reopens sockets with bounded retry + backoff and restores
   the bridge with SRTP ROC/replay state intact, proven bit-exact by
   tests/test_chaos_recovery.py.
"""

from __future__ import annotations

import gc
import os
import pickle
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import numpy as np

from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.utils.flight import FlightRecorder
from libjitsi_tpu.utils.health import (ExponentialBackoff, SlidingWindowCounter,
                                       Watchdog, retrying, state_code)
from libjitsi_tpu.utils.tracing import (PipelineTracer, phase_split,
                                        span_of)

CKPT_MAGIC = "ljt-ckpt"
CKPT_VERSION = 1


@dataclass
class SupervisorConfig:
    """Knobs, all per-tick counts unless suffixed otherwise.

    Quarantine thresholds are windowed totals: an SSRC is banned when
    its last `quarantine_window` ticks accumulate that many SRTP auth
    failures / replay rejections.  Replay's threshold is much higher —
    reordering and duplication produce benign replay hits, only a storm
    (attack or broken sender) should convict.
    """

    deadline_ms: float = 20.0
    overload_after: int = 3          # consecutive overruns -> escalate
    stall_after: int = 25            # consecutive overruns -> STALLED
    overload_exit: int = 5           # consecutive good ticks -> de-escalate
    shed_step: int = 4               # streams shed per level-3+ escalation
    # stage attribution: when one stage owns at least this share of the
    # tick's budget ledger, escalation jumps to the rung that targets
    # that stage (forward_chain -> shed FEC, ingress -> shrink the recv
    # window) instead of walking the wall-time ladder in order
    stage_share_threshold: float = 0.6
    quarantine_window: int = 50      # ticks of history per stream
    quarantine_auth_threshold: int = 20
    quarantine_replay_threshold: int = 200
    quarantine_backoff_ticks: int = 50    # first ban length
    quarantine_backoff_cap: int = 1600    # ban length ceiling
    checkpoint_every: int = 0        # ticks between checkpoints; 0 = off
    checkpoint_path: Optional[str] = None


class BridgeSupervisor:
    """Wraps ConferenceBridge / SfuBridge ticks with the four mechanisms
    above.  Call `sup.tick()` wherever you called `bridge.tick()`; the
    bridge result passes through unchanged.
    """

    def __init__(self, bridge, config: Optional[SupervisorConfig] = None,
                 metrics=None, priorities: Optional[Dict[int, int]] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 flight: Optional[FlightRecorder] = None,
                 slo=None):
        self.bridge = bridge
        self.cfg = config or SupervisorConfig()
        self.loop = getattr(bridge, "loop", bridge)
        self.clock = clock
        self.priorities = priorities or {}
        # flight recorder: every destructive action below (quarantine,
        # shed, recover) dumps a post-mortem naming its trigger
        self.flight = flight if flight is not None else FlightRecorder()
        self.postmortems: deque = deque(maxlen=32)
        # optional SloEngine (utils/slo.py): ticked here so its windows
        # advance on the same cadence as the watchdog, and its worst
        # state rides on every ladder_escalate event
        self.slo = slo
        if slo is not None and getattr(slo, "flight", None) is None:
            slo.flight = self.flight
        self._attach_flight()
        # stage-budget ledger drained from the loop's PipelineTracer
        # each tick: overload events name the dominant stage instead of
        # just "the tick was slow"
        self.tracer: Optional[PipelineTracer] = getattr(
            self.loop, "tracer", None)
        self.last_ledger: Dict[str, float] = {}
        # the same tick by SELF time (a stage less its child spans) and
        # its counts: what the ladder steers on — on an SfuBridge the
        # inclusive ledger's largest entry is always the container
        # round everything, `reverse_chain`
        self.last_self_ledger: Dict[str, float] = {}
        self.last_counts: Dict[str, Dict[str, float]] = {}
        # the span tree's owner: `tick` spans `supervise`, roots every
        # tick and books the interpreter's collections as `gc` spans
        # (test stubs carry only take_ledger: no tree)
        self._tree: Optional[PipelineTracer] = (
            self.tracer if hasattr(self.tracer, "tick_root") else None)
        self._tick_thread: Optional[int] = None
        self.gc_pause_s = 0.0
        self._gc_t0 = 0.0
        self._gc_span = None
        self._gc_unhook = self._hook_gc() if self._tree else None
        # the host/device phase split of the tick the ladder judges
        # (`_take_ledgers`): escalations say host-bound vs
        # device-bound, not just which stage.  Taken here, where the
        # ledgers are drained, and handed on to the loop's `perf`
        # (utils/perf.py: its totals and histogram), which then takes
        # none of its own (test stubs carry no `perf`)
        self.last_phases: Dict[str, float] = {}
        self._perf = (getattr(self.loop, "perf", None)
                      if self.tracer is not None else None)
        if self._perf is not None:
            self._perf.drained_by_supervisor = True
        cap = self.loop.registry.capacity
        self.watchdog = Watchdog(self.cfg.deadline_ms / 1000.0,
                                 overload_after=self.cfg.overload_after,
                                 stall_after=self.cfg.stall_after)
        self._auth_win = SlidingWindowCounter(cap, self.cfg.quarantine_window)
        self._replay_win = SlidingWindowCounter(cap,
                                                self.cfg.quarantine_window)
        # baseline the failure counters at ATTACH time: a supervisor
        # adopting a long-running (or just-restored) bridge must judge
        # fresh failures only, not replay history as a sudden burst
        table = getattr(bridge, "rx_table", None)
        if table is not None and hasattr(table, "auth_fail"):
            self._last_auth = np.asarray(table.auth_fail[:cap]).copy()
            self._last_replay = np.asarray(
                table.replay_reject[:cap]).copy()
        else:
            self._last_auth = np.zeros(cap, dtype=np.int64)
            self._last_replay = np.zeros(cap, dtype=np.int64)
        self._ban = ExponentialBackoff(self.cfg.quarantine_backoff_ticks,
                                       cap=self.cfg.quarantine_backoff_cap)
        self.level = 0               # current escalation-ladder rung
        self._rungs: List[str] = []  # actions taken, LIFO unwind order
        self._good = 0               # consecutive on-deadline ticks
        self._shed: List[int] = []   # shed sids, LIFO restore order
        self._shed_set: set = set()
        # sids evicted by the lifecycle plane (stream LEFT, the slot is
        # dead or recycled): distinct from overload sheds, so the LIFO
        # unwind never "restores" a departed stream
        self._evicted: set = set()
        # StreamLifecycleManager attaches itself here; when present its
        # commit barrier + off-tick install stage run between ticks
        self.lifecycle = None
        # optional AdaptiveBatcher (io/batching.py): ticked on this
        # cadence; the recv_window rung clamps its window writes so the
        # ladder and the tuner never fight over the same knob
        self.batcher = None
        # optional CapacityModel (utils/capacity.py): fed each tick,
        # consulted by admission_decision (capacity_forecast) and the
        # lifecycle plane's placement steering / retry-after hints
        self.capacity = None
        self.last_tick_s = 0.0
        self._quarantined: Dict[int, int] = {}  # sid -> release tick
        self._q_strikes: Dict[int, int] = {}    # sid -> conviction count
        self.quarantine_total = 0
        self._saved_window: Optional[float] = None
        self.ticks = 0
        self.checkpoints_written = 0
        if metrics is not None:
            self.register_metrics(metrics)

    def _attach_flight(self) -> None:
        """Hand the recorder to every pipeline piece that can feed it
        (loop header samples, recovery-ladder actions, bridge events).
        Only objects that declare a `flight` slot participate."""
        for obj in (self.loop, self.bridge,
                    getattr(self.bridge, "recovery", None)):
            if obj is not None and hasattr(obj, "flight"):
                obj.flight = self.flight

    # -------------------------------------------------- gc accounting

    def _hook_gc(self):
        """Book every collection of the interpreter: its pause into
        `gc_pause_seconds_total`, and on the tick thread a `gc` span
        wherever in the tree it lands (a full collection stalls the
        tick for half a second at 10k streams).  The hook holds the
        supervisor weakly and goes with it; `close` removes it."""
        ref = weakref.ref(self)

        def on_gc(phase, info):
            sup = ref()
            if sup is not None:
                sup._on_gc(phase, info)

        def unhook():
            if on_gc in gc.callbacks:
                gc.callbacks.remove(on_gc)

        gc.callbacks.append(on_gc)
        return weakref.finalize(self, unhook)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            # the tree belongs to the tick thread: a collection another
            # thread triggers is a pause here all the same, and counted
            if threading.get_ident() == self._tick_thread:
                self._gc_span = self._tree.span(
                    "gc", generation=info["generation"])
                self._gc_span.__enter__()
            return
        self.gc_pause_s += time.perf_counter() - self._gc_t0
        if self._gc_span is not None:
            self._gc_span.__exit__(None, None, None)
            self._gc_span = None

    def close(self) -> None:
        """Take the collector hook out and leave the loop's phase
        split to the loop again (the bridge is the caller's to
        close)."""
        if self._gc_unhook is not None:
            self._gc_unhook()
        if self._perf is not None:
            self._perf.drained_by_supervisor = False

    # ------------------------------------------------------------- tick

    def tick(self, now: Optional[float] = None):
        tree = self._tree
        if tree is None:
            return self._tick(now)
        self._tick_thread = threading.get_ident()
        rx0 = getattr(self.loop, "rx_packets", 0)
        # one anchor per tick between the profiler's clock and the
        # wall clock senders stamp with: a late delivery can be put to
        # a tick, and a tick to what the host did in it
        root = tree.tick_root(getattr(self.loop, "trace_id", self.ticks)
                              + 1, wall_ns=time.time_ns())
        with root:
            result = self._tick(now)
            root.set_metadata(
                rx=getattr(self.loop, "rx_packets", 0) - rx0)
        return result

    def _tick(self, now: Optional[float]):
        lc = self.lifecycle
        if lc is not None:
            # bracket the data path with the compile-cache guard: any
            # compile event landing inside this window is a lifecycle
            # bug (shapes must be warmed off-tick)
            lc.tick_begin()
        t0 = self.clock()
        result = (self.bridge.tick(now=now) if now is not None
                  else self.bridge.tick())
        self.last_tick_s = self.clock() - t0
        with span_of(self._tree, "supervise"):
            over = self.watchdog.observe(self.last_tick_s)
            if lc is not None:
                lc.tick_end()
            self._take_ledgers()
            self.ticks += 1
            if self.slo is not None:
                self.slo.on_tick()
            if self.batcher is not None:
                self.batcher.on_tick()
            if self.capacity is not None:
                self.capacity.on_tick(self)
            self._update_quarantine()
            if over:
                self._good = 0
                # one rung per `overload_after` consecutive overruns:
                # graded pressure, not a free-fall to full shedding
                if (self.watchdog.consecutive
                        % self.cfg.overload_after) == 0:
                    self._escalate()
            else:
                self._good += 1
                if (self.level > 0
                        and self._good >= self.cfg.overload_exit):
                    self._deescalate()
                    self._good = 0
            if (self.cfg.checkpoint_every
                    and self.ticks % self.cfg.checkpoint_every == 0):
                self.save_checkpoint()
            if lc is not None:
                # between-ticks window: flip staged streams live
                # (commit barrier), then stage the next admit/evict
                # wave off-tick
                lc.run_between_ticks(now=now)
        if self._tree is not None:
            # `supervise` closed after the drain above: fold it in, so
            # that the ledgers a caller reads now hold THIS tick's own
            self._tree.take_ledger(fold=True)
        return result

    def _take_ledgers(self) -> None:
        """Drain the tracer: the ladder below judges this tick."""
        if self.tracer is None:
            return
        self.last_ledger = self.tracer.take_ledger()
        # where no span nests (a stub, a MediaLoop alone) the two
        # ledgers are equal
        self.last_self_ledger = getattr(
            self.tracer, "last_self_ledger", self.last_ledger)
        self.last_counts = getattr(self.tracer, "last_counts", {})
        # `supervise` is still open: the ledger and `last_tick_s` both
        # cover `bridge.tick()` and nothing else
        self.last_phases = phase_split(self.last_self_ledger,
                                       self.last_tick_s)
        if self._perf is not None:
            self._perf.take(self.last_phases)

    # ------------------------------------------- overload escalation

    #: wall-time rung order (the PR-2 ladder); recovery-only rungs are
    #: skipped on bridges without a controller, and `shed_streams`
    #: repeats once every named rung is held
    LADDER = ("recv_window", "degrade", "shed_fec", "throttle_rtx")

    def _slo_state(self) -> str:
        return self.slo.state() if self.slo is not None else "none"

    def _pick_rung(self, stage: Optional[str], share: float,
                   rec) -> str:
        """Stage-attributed rung choice: when one stage owns the tick
        budget, act on THAT stage — shed FEC only when forward_chain
        dominates, shrink the recv window only when ingress does.  No
        dominant stage (or its rung already held) falls back to the
        wall-time ladder order."""
        taken = set(self._rungs)
        if share >= self.cfg.stage_share_threshold:
            if (stage == "forward_chain" and rec is not None
                    and "shed_fec" not in taken):
                return "shed_fec"
            if stage == "ingress" and "recv_window" not in taken:
                return "recv_window"
        for rung in self.LADDER:
            if rung in ("shed_fec", "throttle_rtx") and rec is None:
                continue
            if rung not in taken:
                return rung
        return "shed_streams"

    def _apply_rung(self, rung: str) -> None:
        rec = getattr(self.bridge, "recovery", None)
        if rung == "recv_window":
            # stop waiting for packets: the batching window is latency
            # the tick can't afford while behind
            self._saved_window = getattr(self.loop, "recv_window_ms",
                                         None)
            if self._saved_window is not None:
                self.loop.recv_window_ms = 0
            if self.batcher is not None:
                self.batcher.clamp_window(True)
        elif rung == "degrade":
            self.bridge.degraded = True
        elif rung == "shed_fec":
            # loss-recovery coupling: FEC overhead is the first
            # bandwidth/CPU to go — redundancy sheds before media
            rec.shed_fec(True)
        elif rung == "throttle_rtx":
            # then the retransmission budget shrinks...
            rec.throttle_rtx(True)
        else:
            # ...and only then are whole streams dropped
            self._shed_streams(self.cfg.shed_step)

    def _escalate(self) -> None:
        self.level += 1
        rec = getattr(self.bridge, "recovery", None)
        # budget attribution: the ladder acts on WHERE the tick budget
        # went, not just that it overran — the dominant stage, its
        # ledger share, the chosen rung, and the SLO state ride on
        # every escalation event for the post-mortem
        stage, stage_s = PipelineTracer.dominant(self.last_self_ledger)
        total = sum(self.last_self_ledger.values())
        share = (stage_s / total) if total > 0 else 0.0
        rung = self._pick_rung(stage, share, rec)
        phase, _phase_s, phase_share, bound = self._phase_attr()
        self.flight.record(
            "ladder_escalate", tick=self.ticks, level=self.level,
            worst_s=self.watchdog.worst_s,
            stage=stage or "unknown", stage_s=stage_s,
            stage_share=round(share, 4), rung=rung,
            phase=phase, phase_share=round(phase_share, 4),
            bound=bound, slo_state=self._slo_state())
        self._apply_rung(rung)
        self._rungs.append(rung)

    def _deescalate(self) -> None:
        """Pop the most recent rung and reverse it — LIFO, so whatever
        order stage attribution escalated in, recovery unwinds it."""
        rec = getattr(self.bridge, "recovery", None)
        rung = self._rungs.pop() if self._rungs else "shed_streams"
        self.flight.record("ladder_deescalate", tick=self.ticks,
                           level=self.level - 1, rung=rung)
        if rung == "shed_streams":
            if self._shed:
                restored = 0
                while self._shed and restored < self.cfg.shed_step:
                    sid = self._shed.pop()
                    self._shed_set.discard(sid)
                    if sid in self._evicted:
                        # the stream LEFT while shed: its slot is dead
                        # (or already recycled) — restoring it would
                        # resurrect a departed stream into someone
                        # else's row.  Skip without consuming budget.
                        continue
                    self.flight.record("shed_restore", sid=sid,
                                       tick=self.ticks)
                    restored += 1
                self._sync_drop_mask()
        elif rung == "throttle_rtx" and rec is not None:
            rec.throttle_rtx(False)
        elif rung == "shed_fec" and rec is not None:
            rec.shed_fec(False)
        elif rung == "degrade":
            self.bridge.degraded = False
        elif rung == "recv_window" and self._saved_window is not None:
            self.loop.recv_window_ms = self._saved_window
            self._saved_window = None
            if self.batcher is not None:
                self.batcher.clamp_window(False)
        self.level -= 1

    def _active_sids(self) -> List[int]:
        by_ssrc = getattr(self.bridge, "_ssrc_of", None)
        if by_ssrc:
            return sorted(by_ssrc.keys())
        ports = getattr(self.loop, "addr_port", None)
        if ports is None:
            return []
        return [int(s) for s in np.nonzero(np.asarray(ports) > 0)[0]]

    def _shed_streams(self, k: int) -> None:
        """Shed the k lowest-priority active streams, deterministically:
        priority ascending (default 0), then highest sid first — newest
        joins go before long-standing participants.  The dominant
        speaker is never shed."""
        speaker = getattr(self.bridge, "speaker", None)
        dominant = getattr(speaker, "dominant", -1) if speaker else -1
        staged = getattr(self.bridge, "_staged", ())
        cands = [s for s in self._active_sids()
                 if s not in self._shed_set and s not in self._quarantined
                 and s not in staged and s != dominant]
        cands.sort(key=lambda s: (self.priorities.get(s, 0), -s))
        stage, stage_s = PipelineTracer.dominant(self.last_self_ledger)
        for sid in cands[:k]:
            self._shed.append(sid)
            self._shed_set.add(sid)
            ev = self.flight.record(
                "shed", sid=sid, tick=self.ticks, level=self.level,
                priority=self.priorities.get(sid, 0),
                stage=stage or "unknown", stage_s=stage_s)
            self.postmortems.append({
                "trigger": "overload_shed", "sid": sid,
                "tick": self.ticks, "event": ev,
                "dump": self.flight.dump(sid)})
        if cands[:k]:
            self._sync_drop_mask()

    # ------------------------------------------------- lifecycle plane

    def note_evicted(self, sids) -> None:
        """Lifecycle evict bookkeeping: the stream LEFT — this is not an
        overload shed.  Clear every per-sid mechanism (shed membership,
        quarantine, strike history, failure windows) so the departed
        stream can never be restored, and its row's next occupant starts
        with a clean record.  Flight-records `evicted`, distinct from
        `shed`."""
        changed = False
        for sid in sids:
            sid = int(sid)
            self._evicted.add(sid)
            self._shed_set.discard(sid)
            if self._quarantined.pop(sid, None) is not None:
                changed = True
            self._q_strikes.pop(sid, None)
            if sid < len(self._last_auth):
                self._auth_win.reset_rows([sid])
                self._replay_win.reset_rows([sid])
            self.flight.record("evicted", sid=sid, tick=self.ticks)
        if changed or sids:
            self._sync_drop_mask()

    def note_admitted(self, sids) -> None:
        """Lifecycle admit bookkeeping: a row given to a NEW stream is
        no longer 'evicted' — overload shedding may target it again."""
        for sid in sids:
            self._evicted.discard(int(sid))

    def admission_decision(self, shard=None, handshake_backlog=None,
                           handshake_bound=0, trunk=None):
        """Burn-aware admission control for the lifecycle plane:
        `(ok, reason)` where reason is a typed string.  Joins are
        refused while the error budget is burning fast, while the phase
        ledger says the tick is host-bound under overload (installing
        more streams feeds the bottleneck), or while streams are
        actively being shed (admitting during shedding thrashes).

        With conference-affinity sharding, pass the TARGET `shard`: a
        join is also refused (`shard_burn`) when a per-shard sliced SLO
        says that specific shard is burning fast — the other shards
        keep admitting, which is the point of slicing (a fleet-wide
        gate would brown out all 8 chips for one hot one).

        DTLS/ZRTP joins pass the handshake plane's current
        `handshake_backlog` (queued datagrams + pending associations)
        and its `handshake_bound`: past the bound the join is refused
        `handshake_backlog` — the shard_burn-style typed backpressure
        for reconnect storms (the caller attaches a retry-after hint)."""
        if self._slo_state() == "fast_burn":
            return False, "fast_burn"
        if shard is not None and self.slo is not None:
            for spec in getattr(self.slo, "sliced", ()):
                if (spec.label == "shard"
                        and self.slo.slice_state(spec.name, shard)
                        == "fast_burn"):
                    return False, "shard_burn"
        if self.slo is not None:
            for spec in getattr(self.slo, "sliced", ()):
                # per-hop journey burn (cascade tracing): a trunk hop
                # whose journey tail is burning fast means more members
                # would land on a degraded cross-bridge path — refuse
                # typed, like shard_burn, rather than brown out
                if (spec.label == "hop"
                        and self.slo.burning_slices(spec.name)):
                    return False, "hop_burn"
        if (handshake_bound and handshake_backlog is not None
                and handshake_backlog >= handshake_bound):
            return False, "handshake_backlog"
        if trunk is not None:
            # cascade relay admission (mesh/cascade.py): typed
            # trunk_down / trunk_backlog, same surface as the
            # handshake plane's backpressure
            r = trunk.admit_reason()
            if r is not None:
                return False, r
        if self.watchdog.state == "stalled":
            return False, "stalled"
        if self._shed_set:
            return False, "shedding"
        if self.level > 0:
            _phase, _s, share, bound = self._phase_attr()
            if bound == "host" and share >= self.cfg.stage_share_threshold:
                return False, "host_bound"
        if self.capacity is not None and \
                self.capacity.should_refuse(shard=shard):
            # forecast refusal (utils/capacity.py): every hard signal
            # above is still green, but a confident headroom fit says
            # this join won't fit before one of them fires — refuse
            # NOW, typed and with a retry-after hint, instead of
            # admitting into a forecast brown-out
            return False, "capacity_forecast"
        return True, "ok"

    # ------------------------------------------------------ quarantine

    def _update_quarantine(self) -> None:
        table = getattr(self.bridge, "rx_table", None)
        if table is None or not hasattr(table, "auth_fail"):
            return
        cap = len(self._last_auth)
        auth = np.asarray(table.auth_fail[:cap])
        replay = np.asarray(table.replay_reject[:cap])
        d_auth = auth - self._last_auth
        d_replay = replay - self._last_replay
        self._auth_win.push(d_auth)
        self._replay_win.push(d_replay)
        self._last_auth[:] = auth
        self._last_replay[:] = replay
        # per-stream failure deltas feed the flight ring: when a
        # conviction lands, the dump shows the storm that caused it
        for sid in np.nonzero(d_auth > 0)[0]:
            self.flight.record("srtp_auth_fail", sid=int(sid),
                               tick=self.ticks, n=int(d_auth[sid]))
        for sid in np.nonzero(d_replay > 0)[0]:
            self.flight.record("srtp_replay_reject", sid=int(sid),
                               tick=self.ticks, n=int(d_replay[sid]))

        changed = False
        for sid in [s for s, until in self._quarantined.items()
                    if self.ticks >= until]:
            del self._quarantined[sid]
            self._auth_win.reset_rows([sid])
            self._replay_win.reset_rows([sid])
            self.flight.record("quarantine_release", sid=sid,
                               tick=self.ticks)
            changed = True

        auth_sum = self._auth_win.sums()
        replay_sum = self._replay_win.sums()
        bad = np.nonzero(
            (auth_sum >= self.cfg.quarantine_auth_threshold)
            | (replay_sum >= self.cfg.quarantine_replay_threshold))[0]
        for sid in (int(s) for s in bad):
            if sid in self._quarantined or sid in self._shed_set:
                continue
            strikes = self._q_strikes.get(sid, 0)
            self._quarantined[sid] = self.ticks + int(
                self._ban.delay(strikes))
            self._q_strikes[sid] = strikes + 1
            self.quarantine_total += 1
            reason = ("auth_storm"
                      if auth_sum[sid] >= self.cfg.quarantine_auth_threshold
                      else "replay_storm")
            ev = self.flight.record(
                "quarantine", sid=sid, tick=self.ticks, reason=reason,
                auth_window=int(auth_sum[sid]),
                replay_window=int(replay_sum[sid]),
                until=self._quarantined[sid], strikes=strikes + 1)
            self.postmortems.append({
                "trigger": "quarantine", "sid": sid,
                "tick": self.ticks, "event": ev,
                "dump": self.flight.dump(sid)})
            self._auth_win.reset_rows([sid])
            self._replay_win.reset_rows([sid])
            changed = True
        if changed:
            self._sync_drop_mask()

    def _sync_drop_mask(self) -> None:
        self.loop.inbound_drop[:] = False
        banned = self._shed_set | set(self._quarantined)
        if banned:
            self.loop.inbound_drop[list(banned)] = True

    # ------------------------------------------------------ checkpoint

    def save_checkpoint(self, path: Optional[str] = None) -> str:
        """Serialize the whole bridge into one versioned checkpoint
        file.  Write-to-temp + rename: a crash mid-write leaves the
        previous checkpoint intact, never a torn one."""
        path = path or self.cfg.checkpoint_path
        if path is None:
            raise ValueError("no checkpoint path configured")
        # pipeline drain barrier: a deep-pipelined loop may hold
        # dispatched-but-uncommitted ticks (replay state, egress bytes,
        # pinned arenas) — the snapshot must never capture a half tick
        drain = getattr(self.loop, "drain", None)
        if drain is not None:
            drain()
        blob = {"magic": CKPT_MAGIC, "version": CKPT_VERSION,
                "bridge": type(self.bridge).__name__,
                "ticks": self.ticks,
                "snap": self.bridge.snapshot()}
        if self.lifecycle is not None:
            # in-flight admits (queued joins + staged-but-uncommitted
            # installs) ride the checkpoint so recover() can complete
            # or roll them back instead of leaving half-installed rows
            blob["lifecycle"] = self.lifecycle.snapshot()
        # cascade control plane (CascadeSupervisor): trunk peer/rosters
        # and the in-flight adoption queue ride the same atomic file —
        # a crash mid-failover resumes adoption, never a torn trunk
        snap_cascade = getattr(self, "cascade_snapshot", None)
        if snap_cascade is not None:
            blob["cascade"] = snap_cascade()
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump(blob, f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, path)
        self.checkpoints_written += 1
        self.flight.record("checkpoint_saved", tick=self.ticks,
                           path=path)
        return path

    @staticmethod
    def load_checkpoint(path: str) -> dict:
        with open(path, "rb") as f:
            blob = pickle.load(f)
        if (not isinstance(blob, dict)
                or blob.get("magic") != CKPT_MAGIC):
            raise ValueError(f"{path}: not a libjitsi_tpu checkpoint")
        if blob.get("version") != CKPT_VERSION:
            raise ValueError(
                f"{path}: checkpoint version {blob.get('version')} "
                f"(supported: {CKPT_VERSION})")
        return blob

    @classmethod
    def recover(cls, config, path: str, bridge_cls, port: int = 0,
                retries: int = 5, backoff_s: float = 0.05,
                sleep: Callable[[float], None] = time.sleep,
                supervisor_config: Optional[SupervisorConfig] = None,
                metrics=None, **bridge_kwargs) -> "BridgeSupervisor":
        """Crash-restart: load the checkpoint, re-bind the socket with
        bounded retry (a just-killed worker's port can linger), restore
        the bridge (SRTP ROC/replay included), resume supervising."""
        blob = cls.load_checkpoint(path)
        bridge = retrying(
            lambda: bridge_cls.restore(config, blob["snap"], port=port,
                                       **bridge_kwargs),
            retries=retries, backoff_s=backoff_s, sleep=sleep)
        sup = cls(bridge, config=supervisor_config, metrics=metrics)
        sup.ticks = blob["ticks"]
        # lifecycle in-flight state (if any) is held for the next
        # StreamLifecycleManager attached to this supervisor: its
        # constructor reconciles every half-installed stream (complete
        # or roll back — never a half state)
        sup.pending_lifecycle = blob.get("lifecycle")
        # crash-restart is a destructive action like any other: it
        # leaves a post-mortem naming the checkpoint it rose from
        ev = sup.flight.record("recovered", tick=sup.ticks, path=path,
                               bridge=blob["bridge"])
        sup.postmortems.append({
            "trigger": "checkpoint_recover", "tick": sup.ticks,
            "event": ev, "dump": sup.flight.dump_all()})
        return sup

    # --------------------------------------------------- observability

    def register_metrics(self, registry, prefix: str = "supervisor") -> None:
        wd, cfg = self.watchdog, self.cfg
        registry.register_scalar(
            f"{prefix}_ticks_overrun", lambda: wd.overruns,
            help_="ticks that exceeded the deadline", kind="counter")
        registry.register_scalar(
            f"{prefix}_watchdog_state", lambda: state_code(wd.state),
            help_="0 healthy, 1 overloaded, 2 stalled")
        registry.register_scalar(
            f"{prefix}_overload_level", lambda: self.level,
            help_="current escalation-ladder rung")
        registry.register_scalar(
            f"{prefix}_streams_shed", lambda: len(self._shed),
            help_="streams currently shed for overload")
        registry.register_scalar(
            f"{prefix}_streams_quarantined", lambda: len(self._quarantined),
            help_="streams currently quarantined")
        registry.register_scalar(
            f"{prefix}_quarantine_total", lambda: self.quarantine_total,
            help_="quarantine convictions since start", kind="counter")
        registry.register_scalar(
            f"{prefix}_checkpoints_written",
            lambda: self.checkpoints_written, kind="counter")
        registry.register_scalar(
            "gc_pause_seconds_total", lambda: self.gc_pause_s,
            help_="time the interpreter's collections stopped the "
                  "process for, every thread's", kind="counter")
        registry.register_scalar(
            f"{prefix}_inbound_dropped",
            lambda: self.loop.inbound_dropped_total,
            help_="packets dropped by shed/quarantine masks",
            kind="counter")
        # per-stream arrays are registered as CALLABLES resolving
        # through self.bridge/self.loop at render time: a checkpoint
        # restore that rebinds rx_table (or the whole bridge) must not
        # leave the exporter reading the pre-restore arrays
        registry.register_array(
            "inbound_dropped", lambda: self.loop.inbound_dropped,
            help_="per-stream packets dropped at ingress", kind="counter")
        table = getattr(self.bridge, "rx_table", None)
        if table is not None and hasattr(table, "auth_fail"):
            registry.register_array(
                "srtp_auth_fail", lambda: self.bridge.rx_table.auth_fail,
                help_="SRTP authentication failures", kind="counter")
            registry.register_array(
                "srtp_replay_reject",
                lambda: self.bridge.rx_table.replay_reject,
                help_="SRTP replay-window rejections", kind="counter")
        if table is not None and hasattr(table, "shard_rows"):
            registry.register_multi(
                "mesh_rows_per_shard",
                lambda: [({"shard": str(d)}, float(n)) for d, n in
                         enumerate(self.bridge.rx_table.shard_rows)],
                help_="rows of the last tick's unprotect call that "
                      "each mesh shard owned")
        if hasattr(self.bridge, "forwarded"):
            # denominator of the residual-loss SLO: packets the bridge
            # actually forwarded downstream
            registry.register_scalar(
                "bridge_forwarded", lambda: self.bridge.forwarded,
                help_="packets forwarded to receivers", kind="counter")
        if hasattr(getattr(self.bridge, "translator", None),
                   "fanout_launches"):
            registry.register_scalar(
                "fanout_launches_total",
                lambda: self.bridge.translator.fanout_launches,
                help_="device calls the fan-out has made (one in "
                      "most ticks)",
                kind="counter")
            registry.register_scalar(
                "fanout_split_ticks_total",
                lambda: self.bridge.translator.fanout_split_ticks,
                help_="ticks whose fan-out rows went out in several "
                      "launches: they outgrew the largest warmed row "
                      "class, or fitted it and were cut by the classes "
                      "under it (fanout_class_cut_ticks_total)",
                kind="counter")
            registry.register_scalar(
                "fanout_class_cut_ticks_total",
                lambda: self.bridge.translator.fanout_class_cut_ticks,
                help_="ticks whose fan-out rows fitted the largest "
                      "warmed row class and went out as launches of "
                      "smaller classes, which padded less",
                kind="counter")
            registry.register_scalar(
                "fanout_collect_total",
                lambda: self.bridge.translator.fanout_collects,
                help_="fan-out launches collected (a tick dispatches "
                      "its fan-out and the next collects it)",
                kind="counter")
            registry.register_scalar(
                "fanout_collect_ready_total",
                lambda: self.bridge.translator.fanout_collects_ready,
                help_="fan-out launches whose every array was ready "
                      "when they were collected: the host work since "
                      "their dispatch hid the whole launch",
                kind="counter")
            registry.register_multi(
                "fanout_launch_rows_total",
                lambda: [({"rows": str(c)}, float(n)) for c, n in sorted(
                    self.bridge.translator.fanout_launch_rows.items())],
                help_="per-row fan-out device calls by the row class "
                      "their rows were padded to (none on a mesh, "
                      "whose owner plan pads the lanes a chip)",
                kind="counter")
        if hasattr(self.bridge, "_video"):
            # simulcast/SVC forwarders are per-receiver objects; export
            # the fleet-wide sums (drift rule: every bumped counter is
            # scraped somewhere)
            def _fwds():
                return [f for t in set(self.bridge._video.values())
                        for f in t.fwd.values()]
            registry.register_scalar(
                "video_layer_switches",
                lambda: sum(f.switches for f in _fwds()),
                help_="simulcast/SVC layer switches across receivers",
                kind="counter")
            registry.register_scalar(
                "video_svc_dropped",
                lambda: sum(f.dropped for f in _fwds()
                            if hasattr(f, "dropped")),
                help_="SVC packets dropped by layer projection",
                kind="counter")
            registry.register_scalar(
                "video_svc_late_dropped",
                lambda: sum(f.late_dropped for f in _fwds()
                            if hasattr(f, "late_dropped")),
                help_="late SVC packets with no renumber hole left",
                kind="counter")
        rec = getattr(self.bridge, "recovery", None)
        if rec is not None:
            rec.register_metrics(registry)
        if hasattr(getattr(self.bridge, "cache", None), "slabs"):
            registry.register_scalar(
                "recovery_rtx_cache_slabs",
                lambda: self.bridge.cache.slabs,
                help_="fan-out batches live in the retransmission cache")
            registry.register_scalar(
                "recovery_rtx_cache_resident_bytes",
                lambda: self.bridge.cache.resident_bytes,
                help_="memory the retransmission cache's slabs pin")
        if self.slo is not None:
            self.slo.register_metrics(registry)
        bank = getattr(self.bridge, "bank", None)
        if bank is not None and hasattr(bank, "plc_frames"):
            registry.register_array(
                "plc_frames", lambda: self.bridge.bank.plc_frames,
                help_="frames concealed by packet-loss concealment",
                kind="counter")
            if hasattr(bank, "register_metrics"):
                bank.register_metrics(registry)

    def _phase_attr(self):
        """(phase, seconds, share, bound) of the last tick's phase
        split — "which phase owns the tick, and is that host-side or
        device-side?"."""
        from libjitsi_tpu.utils.perf import classify_bound

        phase, phase_s = PipelineTracer.dominant(self.last_phases)
        total = sum(self.last_phases.values())
        share = (phase_s / total) if total > 0 else 0.0
        return (phase or "unknown", phase_s, share,
                classify_bound(self.last_phases))

    def phase_attribution(self) -> dict:
        """Host/device attribution summary for /debug/slo: the phase
        split the escalation ladder is currently judging by, labeled
        with the ingest engine mode and its syscall telemetry — a phase
        share is only comparable against runs of the SAME engine."""
        phase, phase_s, share, bound = self._phase_attr()
        out = {"bound": bound, "phase": phase,
               "phase_share": round(share, 4),
               "phases": dict(self.last_phases)}
        loop = getattr(self.bridge, "loop", None)
        if loop is not None:
            out["engine_mode"] = getattr(loop, "engine_mode", "recvmmsg")
            out["ingest_syscalls"] = int(
                getattr(loop, "ingest_syscalls", 0))
            out["ingest_ring_reaps"] = int(
                getattr(loop, "ingest_ring_reaps", 0))
        caches = [c for name in ("rx_table", "tx_table")
                  for c in (getattr(getattr(self.bridge, name, None),
                                    "_ks_cache", None),)
                  if c is not None]
        if caches:
            # off-tick phases don't appear in the tick's phase split —
            # keystream pregeneration runs at the lifecycle barrier, so
            # its cost is attributed here as a separate ledger line
            served = sum(c.hits for c in caches)
            missed = sum(c.misses for c in caches)
            out["off_tick"] = {
                "keystream_fill_seconds": round(
                    sum(c.fill_seconds for c in caches), 6),
                "keystream_filled_slots": int(
                    sum(c.filled_slots for c in caches)),
                "keystream_hit_rate": round(
                    served / (served + missed), 4)
                if served + missed else None,
            }
        hq = getattr(self.lifecycle, "handshakes", None) \
            if self.lifecycle is not None else None
        if hq is not None:
            # same rule as the keystream ledger: handshake OpenSSL work
            # runs on the between-ticks window, so the tick's phase
            # split never contains it — its wall time is attributed
            # here, and `tick_thread_feeds` must stay 0 (the reconnect
            # soak gates on it)
            out.setdefault("off_tick", {}).update({
                "handshake_drain_seconds": round(hq.off_tick_seconds, 6),
                "handshake_queue_depth": int(hq.depth),
                "handshake_tick_thread_feeds": int(
                    getattr(self.lifecycle,
                            "tick_thread_handshake_feeds", 0)),
            })
        return out

    def health(self) -> dict:
        """Liveness summary for probes / logs."""
        return {"state": self.watchdog.state, "level": self.level,
                "rungs": list(self._rungs),
                "shed": sorted(self._shed_set),
                "evicted": len(self._evicted),
                "quarantined": sorted(self._quarantined),
                "ticks": self.ticks, "overruns": self.watchdog.overruns,
                "last_ledger": dict(self.last_ledger),
                "last_self_ledger": dict(self.last_self_ledger),
                "last_counts": {k: dict(v) for k, v in
                                self.last_counts.items()},
                "last_phases": dict(self.last_phases),
                "bound": self._phase_attr()[3],
                "slo_state": self._slo_state(),
                "postmortems": len(self.postmortems)}


class CascadeSupervisor(BridgeSupervisor):
    """Supervisor for one end of a bridge-to-bridge cascade
    (mesh/cascade.py): everything BridgeSupervisor does, plus the trunk
    control plane and the failover headline — a conference that
    survives the death of its home bridge.

    Division of labour with CascadeTrunk: the trunk owns the wire
    (SRTP-keyed relay, heartbeats, NACK/RTX/FEC under the hop's
    deadline budget, typed `trunk_down`/`trunk_backlog` refusals); this
    class owns POLICY — which conferences ride the trunk, roster sync
    from the bridge's committed keyed rows, and orphan adoption when
    the peer dies:

    * heartbeat loss trips `trunk.on_down` -> `_on_trunk_down`: the
      peer's conferences are promoted (their typed trunk refusals
      lift), the placer's bridge axis is evacuated, and every remote
      roster member is queued for adoption;
    * adoption rides the NORMAL lifecycle commit barrier —
      `request_join` -> staged -> committed between ticks; an orphan
      counts as adopted only once its row resolves committed, and a
      join refused under pressure re-queues on the PR 16 retry-after
      hint with exponential escalation (adopt-or-retry, never torn);
    * the whole adoption queue plus trunk control plane rides the
      checkpoint spine (`cascade_snapshot`), so a crash mid-failover
      resumes adoption on recovery instead of stranding half a
      conference.

    Per-bridge burn: when an SloEngine is attached, a
    `SlicedSloSpec(label="bridge")` tracks this bridge's trunk media
    continuity exactly as PR 10's `label="shard"` slices shard burn.
    """

    #: a queued-but-uncommitted adoption older than this is treated as
    #: rolled back and re-queued (covers recovery from a checkpoint
    #: that captured the join before its commit)
    adopt_commit_timeout_s = 1.0
    #: roster re-derivation cadence (ticks); pushes only on change
    roster_sync_ticks = 5

    def __init__(self, bridge, trunk, config=None, metrics=None,
                 bridge_id: int = 0, peer_bridge_id: int = 1, **kw):
        super().__init__(bridge, config, metrics=None, **kw)
        self.trunk = trunk
        self.bridge_id = int(bridge_id)
        self.peer_bridge_id = int(peer_bridge_id)
        trunk.on_down = self._on_trunk_down
        trunk.on_up = self._on_trunk_up
        trunk.on_roster = self._on_roster
        trunk.on_speakers = self._apply_remote_speakers
        trunk.deliver = self._deliver_remote
        trunk.bridge_id = int(bridge_id)   # stamped on trace extensions
        if hasattr(trunk, "flight"):
            trunk.flight = self.flight
        self.trunk_failovers_total = 0
        self.orphans_adopted = 0
        self.orphans_requeued = 0
        self.remote_delivered = 0
        # cross-bridge journey tracing: hop-labeled children of
        # packet_journey_seconds (register_metrics binds the vec; falls
        # back to the bridge loop's own vec when none is registered),
        # plus the rtt-ring-corrected trunk one-way-delay estimate
        self._journey_vec = None
        self.trunk_owd_s = 0.0
        self.adopting = False            # failover in progress
        self._now = 0.0                  # model clock from tick()
        self._adopt_q: deque = deque()   # entries awaiting request_join
        self._pending_commit: List[dict] = []   # joined, pre-barrier
        self._conf_outstanding: Dict[int, int] = {}
        self._remote_marks: set = set()  # confs homed on the peer
        self._marks_pending = False      # marks awaiting lifecycle
        if self.slo is not None:
            self._register_bridge_slo()
        if metrics is not None:
            self.register_metrics(metrics)

    # ------------------------------------------------------ wiring

    def cascade_conference(self, conference, speakers=None,
                           remote: bool = False) -> None:
        """Put one conference on the trunk.  `remote=False`: homed
        HERE — local speaker-bus media relays to the peer.
        `remote=True`: homed on the PEER — local joins consult the
        trunk's typed admission (the PR 16 refusal surface) and the
        conference is a failover-adoption candidate."""
        conf = int(conference)
        self.bridge.attach_trunk(self.trunk, conf, speakers)
        if remote:
            self._remote_marks.add(conf)
            if self.lifecycle is not None:
                self.lifecycle.mark_remote_conference(conf, self.trunk)
            else:
                self._marks_pending = True

    # -------------------------------------------------------- tick

    def tick(self, now: Optional[float] = None):
        result = super().tick(now=now)
        tnow = float(now) if now is not None else self.clock()
        self._now = tnow
        lc = self.lifecycle
        if lc is not None and self._marks_pending:
            for conf in sorted(self._remote_marks):
                lc.mark_remote_conference(conf, self.trunk)
            self._marks_pending = False
        if self.ticks % self.roster_sync_ticks == 0:
            self._sync_roster()
        self.trunk.pump(tnow)
        if self._adopt_q and lc is not None:
            self._drain_adoptions(tnow)
        if self._pending_commit:
            self._scan_commits(tnow)
        if (self.adopting and not self._adopt_q
                and not self._pending_commit):
            self.adopting = False
        return result

    def _sync_roster(self) -> None:
        """Re-derive the local roster from the bridge's COMMITTED keyed
        rows (staged rows are not yet adoptable) and push on change.
        This is what makes failover possible at all: the survivor can
        only re-key orphans it has a roster for."""
        b = self.bridge
        roster: Dict[int, list] = {}
        for sid, conf in sorted(b._conf_of.items()):
            conf = int(conf)
            if conf not in getattr(b, "_trunks", {}):
                continue
            if sid in b._staged:
                continue
            ssrc = b._ssrc_of.get(sid)
            rx = b._rx_keys.get(sid)
            tx = b._tx_keys.get(sid)
            if ssrc is None or rx is None or tx is None:
                continue
            if int(ssrc) in self.trunk._remote_ssrcs:
                # peer-homed member installed here by roster sync: not
                # ours to advertise (claimed only on failover adoption)
                continue
            roster.setdefault(conf, []).append({
                "ssrc": int(ssrc),
                "rx": [rx[0].hex(), rx[1].hex()],
                "tx": [tx[0].hex(), tx[1].hex()],
            })
        if roster != self.trunk.local_roster:
            self.trunk.set_roster(roster)

    # -------------------------------------------------- trunk hooks

    def _deliver_remote(self, conf: int, inner: bytes,
                        trace=None) -> None:
        """Re-inject a trunk-delivered participant packet into the
        local bridge's primary socket: the remote speaker is a regular
        keyed row here (roster sync installed it), so the inner SRTP
        authenticates and routes through the stock data path — zero
        cascade-specific shapes, zero recompiles.

        When the frame carried a journey trace extension, the hop is
        recorded here (host side, off the jit path): a hop-labeled
        `packet_journey_seconds` observation whose exemplar carries the
        ORIGIN bridge's trace id — the stitch point /debug/fleet and
        `trace_report.py --merge-bridges` join on."""
        if trace is not None:
            self._note_hop(trace)
        self.trunk.engine.send_batch(
            PacketBatch.from_payloads([inner]),
            "127.0.0.1", self.bridge.port)
        self.remote_delivered += 1

    def _note_hop(self, trace) -> None:
        """Observe one cross-bridge journey segment: origin ingress
        stamp -> local trunk ingest, under a `b<origin>-b<me>` hop
        label.  The origin stamp is a FOREIGN monotonic clock; the
        trunk RTT ring corrects it — the wire can't be faster than
        half the measured round trip, so the raw delta is floored at
        owd (and a cross-machine, incomparable-clock delta degrades to
        the rtt-derived estimate instead of garbage)."""
        ring = getattr(self.trunk, "_rtt_ring", None)
        rtt = (ring.percentile(50) if ring is not None and ring.count
               else float(self.trunk.rtt))
        owd = max(rtt / 2.0, 0.0)
        self.trunk_owd_s = owd
        raw = time.perf_counter() - float(trace.t0)
        # plausibility window: floor at the wire delay, and treat a
        # multi-second delta (incomparable clocks) as wire-delay-only
        dt = raw if owd <= raw <= 10.0 else owd
        vec = self._journey_vec
        if vec is None:
            vec = getattr(getattr(self.bridge, "loop", None),
                          "journey_vec", None)
            if vec is None:
                return
        hop = f"b{int(trace.bridge_id)}-b{self.bridge_id}"
        tail = vec.labels(hop).observe(
            dt, exemplar={"trace_id": str(int(trace.trace_id)),
                          "origin": str(int(trace.bridge_id))})
        if tail:
            self.flight.record("hop_tail", tick=self.ticks,
                               hop=hop, trace=int(trace.trace_id),
                               seconds=dt)

    def _apply_remote_speakers(self, conf: int, ssrcs) -> None:
        """Speaker bus crossing the trunk: map the peer's active-speaker
        SSRCs onto local rows and update the broadcast route.  The
        bridge's no-change early-return breaks the echo loop."""
        b = self.bridge
        if conf not in b._bcast_speakers:
            return
        sids = [s for s in (b._sid_of_ssrc(int(x)) for x in ssrcs)
                if s is not None]
        if sids:
            b.set_broadcast_speakers(conf, sids)

    def _on_roster(self, roster: dict) -> None:
        """Peer roster sync: install any not-yet-local member of a
        cascaded conference as a regular keyed row (that is what lets
        its trunk-delivered media authenticate), via the same admission
        queue failover adoption uses — just without the promotion."""
        b = self.bridge
        queued = {(e["conf"], int(e["m"]["ssrc"]))
                  for e in list(self._adopt_q) + self._pending_commit}
        for conf, members in sorted(roster.items()):
            conf = int(conf)
            if (conf not in self.trunk._confs
                    and conf not in self._remote_marks):
                continue
            for m in members:
                ssrc = int(m["ssrc"])
                if b._sid_of_ssrc(ssrc) is not None:
                    continue
                if (conf, ssrc) in queued:
                    continue
                self._adopt_q.append({
                    "conf": conf, "m": dict(m), "n": len(members),
                    "attempts": 0, "retry_at": self._now,
                    "promote": False})

    def _on_trunk_up(self, now: float) -> None:
        self.flight.record("trunk_up", tick=self.ticks,
                           peer=self.peer_bridge_id)

    def _on_trunk_down(self, now: float) -> None:
        """Failover: the peer stopped answering heartbeats.  Promote
        its conferences (typed trunk refusals lift — joins admit HERE
        now), evacuate its placement axis, and queue every remote
        roster member for adoption through the commit barrier."""
        self.trunk_failovers_total += 1
        self.adopting = True
        ev = self.flight.record("trunk_failover", tick=self.ticks,
                                peer=self.peer_bridge_id,
                                inflight=self._journey_inflight())
        # post-mortem at conviction, mirroring quarantine/shed/recover:
        # the in-flight journey set names exactly which trace ids were
        # mid-hop when the trunk died — the per-hop attribution for
        # time-to-media-restored in churn_soak --cascade
        self.postmortems.append({
            "trigger": "trunk_failover", "tick": self.ticks,
            "event": ev, "dump": self.flight.dump_all()})
        lc = self.lifecycle
        placer = getattr(lc, "placer", None) if lc is not None else None
        if placer is not None and getattr(placer, "n_bridges", 0):
            placer.evacuate_bridge(self.peer_bridge_id)
        b = self.bridge
        queued = {(e["conf"], int(e["m"]["ssrc"]))
                  for e in list(self._adopt_q) + self._pending_commit}
        for conf, members in sorted(self.trunk.remote_roster.items()):
            conf = int(conf)
            if lc is not None:
                lc.promote_remote_conference(conf)
            self._remote_marks.discard(conf)
            fresh = [m for m in members
                     if b._sid_of_ssrc(int(m["ssrc"])) is None
                     and (conf, int(m["ssrc"])) not in queued]
            if not fresh:
                continue
            self._conf_outstanding[conf] = (
                self._conf_outstanding.get(conf, 0) + len(fresh))
            for m in fresh:
                self._adopt_q.append({
                    "conf": conf, "m": dict(m), "n": len(members),
                    "attempts": 0, "retry_at": float(now),
                    "promote": True})

    # ----------------------------------------------------- adoption

    def _drain_adoptions(self, now: float) -> None:
        lc = self.lifecycle
        n = len(self._adopt_q)
        for _ in range(n):
            ent = self._adopt_q.popleft()
            if float(ent["retry_at"]) > now:
                self._adopt_q.append(ent)
                continue
            m = ent["m"]
            ssrc = int(m["ssrc"])
            sid = self.bridge._sid_of_ssrc(ssrc)
            if sid is not None:
                self._adopt_done(ent, sid=sid)       # already local
                continue
            rx = tuple(bytes.fromhex(h) for h in m["rx"])
            tx = tuple(bytes.fromhex(h) for h in m["tx"])
            ok, reason = lc.request_join(ssrc, rx, tx,
                                         name=m.get("name"),
                                         conference=ent["conf"])
            if ok or reason == "duplicate":
                ent["commit_deadline"] = now + self.adopt_commit_timeout_s
                self._pending_commit.append(ent)
                continue
            # typed refusal: re-queue on the retry-after hint, with the
            # same exponential escalation a storming client would apply
            ent["attempts"] = int(ent["attempts"]) + 1
            ent["retry_at"] = now + (
                lc.retry_after_hint(reason, conference=ent["conf"])
                * (2 ** min(ent["attempts"], 6)))
            self.orphans_requeued += 1
            self._adopt_q.append(ent)

    def _scan_commits(self, now: float) -> None:
        """An orphan is adopted when its row resolves COMMITTED (past
        the barrier), not when the join queues.  A join that never
        commits (rolled back, or checkpointed pre-commit) re-queues —
        adopt-or-retry, never a torn row."""
        b = self.bridge
        still: List[dict] = []
        for ent in self._pending_commit:
            ssrc = int(ent["m"]["ssrc"])
            sid = b._sid_of_ssrc(ssrc)
            if sid is not None and sid not in b._staged:
                self._adopt_done(ent, sid=sid)
            elif now >= float(ent.get("commit_deadline", 0.0)):
                ent["attempts"] = int(ent["attempts"]) + 1
                ent["retry_at"] = now
                ent.pop("commit_deadline", None)
                self.orphans_requeued += 1
                self._adopt_q.append(ent)
            else:
                still.append(ent)
        self._pending_commit = still

    def _adopt_done(self, ent: dict, sid: Optional[int] = None) -> None:
        conf = int(ent["conf"])
        if ent.get("promote"):
            self.orphans_adopted += 1
            ssrc = int(ent["m"]["ssrc"])
            self.trunk.claim_member(conf, ssrc)
            ev = self.flight.record("orphan_adopted", sid=sid,
                                    tick=self.ticks, conf=conf,
                                    ssrc=ssrc)
            # adoption-commit post-mortem: second half of the failover
            # story (conviction is the first), per adopted stream
            self.postmortems.append({
                "trigger": "trunk_failover", "sid": sid,
                "tick": self.ticks, "event": ev,
                "dump": self.flight.dump(sid) if sid is not None
                else self.flight.dump_all()})
            # an orphan that was on the conference's top-K speaker bus
            # resumes speaking HERE: its fresh row landed as a listener
            # (the broadcast speaker set holds the dead row's sid)
            spk = self.trunk._confs.get(conf)
            cur = self.bridge._bcast_speakers.get(conf)
            if (sid is not None and spk is not None and ssrc in spk
                    and cur is not None and sid not in cur):
                self.bridge.set_broadcast_speakers(
                    conf, sorted(cur | {sid}))
        left = self._conf_outstanding.get(conf, 0) - 1
        if left > 0:
            self._conf_outstanding[conf] = left
        elif conf in self._conf_outstanding:
            del self._conf_outstanding[conf]
            if ent.get("promote"):
                # the whole conference is committed here: re-home it on
                # the placer's bridge axis
                lc = self.lifecycle
                placer = getattr(lc, "placer", None) \
                    if lc is not None else None
                if placer is not None and getattr(placer, "n_bridges", 0):
                    placer.adopt_bridge(conf, self.bridge_id,
                                        int(ent.get("n", 1)))

    # ------------------------------------------------- observability

    def _journey_inflight(self) -> List[int]:
        """Trace ids currently mid-journey on this bridge's loop: the
        live tick's trace plus every pipelined dispatch still holding
        an origin stamp.  Captured into the trunk-down post-mortem —
        these are the packets whose journey the failover cut."""
        lp = getattr(self.bridge, "loop", None)
        if lp is None:
            return []
        ids = {int(getattr(lp, "trace_id", 0))}
        for ent in getattr(lp, "_inflight", ()):
            ids.add(int(ent[2][0]))          # (pend, mask, origin, tick)
        for e in getattr(lp, "_rx_inflight", ()):
            ids.add(int(e["origin"][0]))
        return sorted(ids)

    def _register_bridge_slo(self) -> None:
        from libjitsi_tpu.utils.slo import SlicedSloSpec
        tr = self.trunk
        me = str(self.bridge_id)

        def _read():
            good = tr.relay_frames_total + self.remote_delivered
            bad = (tr.plc_fallthrough_total + tr.unprotect_drops_total
                   + tr.refusals_total)
            yield (me, float(good), float(bad))

        self.slo.add_sliced(SlicedSloSpec(
            name="bridge_media", objective=0.999, label="bridge",
            reader=_read,
            description="per-bridge trunk media continuity: frames "
                        "relayed/delivered vs concealed, dropped or "
                        "refused"))
        self._register_hop_slo()

    def _register_hop_slo(self) -> None:
        """Per-hop journey burn (`label="hop"`): each hop-labeled
        child of packet_journey_seconds is one slice; an observation
        within the trunk's deadline budget is good, past it is bad.
        `admission_decision` refuses `hop_burn` while any hop slice is
        fast-burning — the cross-bridge twin of shard_burn."""
        from libjitsi_tpu.utils.slo import SlicedSloSpec
        budget = float(self.trunk.cfg.deadline_budget_s)

        def _read():
            vec = self._journey_vec
            if vec is None:
                vec = getattr(getattr(self.bridge, "loop", None),
                              "journey_vec", None)
            if vec is None:
                return
            for lv, h in vec.children():
                j = int(np.searchsorted(h.uppers, budget,
                                        side="right")) - 1
                good = float(h.cumulative()[j]) if j >= 0 else 0.0
                yield (lv, good, float(h.count) - good)

        self.slo.add_sliced(SlicedSloSpec(
            name="hop_journey", objective=0.99, label="hop",
            reader=_read,
            description="per-hop packet journey tail vs the trunk "
                        "deadline budget"))

    def register_metrics(self, registry,
                         prefix: str = "supervisor") -> None:
        super().register_metrics(registry, prefix)
        # owner indirection: gauges follow THIS supervisor's current
        # trunk, so a recovery-supplied replacement stays observable
        self.trunk.register_metrics(registry, owner=self)
        registry.register_scalar(
            "trunk_failovers_total",
            lambda: self.trunk_failovers_total,
            help_="trunk down transitions that triggered failover",
            kind="counter")
        registry.register_scalar(
            "cascade_orphans_adopted", lambda: self.orphans_adopted,
            help_="orphaned remote streams committed on this bridge "
                  "after peer death", kind="counter")
        registry.register_scalar(
            "cascade_orphans_requeued", lambda: self.orphans_requeued,
            help_="adoption attempts re-queued on a typed refusal or "
                  "rollback", kind="counter")
        registry.register_scalar(
            "cascade_remote_delivered", lambda: self.remote_delivered,
            help_="trunk-delivered remote packets re-injected locally",
            kind="counter")
        registry.register_scalar(
            "trunk_one_way_delay_seconds", lambda: self.trunk_owd_s,
            help_="rtt-ring-corrected trunk one-way-delay estimate")
        from libjitsi_tpu.io.loop import JOURNEY_BUCKETS
        self._journey_vec = registry.histogram_vec(
            "packet_journey_seconds", JOURNEY_BUCKETS, "hop",
            help_="ingress-arrival to egress-send packet latency",
            exemplars=True)

    # ------------------------------------------------- checkpointing

    def cascade_snapshot(self) -> dict:
        """Picked up by BridgeSupervisor.save_checkpoint: the trunk
        control plane plus every in-flight adoption."""
        return {
            "trunk": self.trunk.snapshot(),
            "adopting": bool(self.adopting),
            "remote_marks": sorted(self._remote_marks),
            "adopt_q": [dict(e) for e in self._adopt_q],
            "pending_commit": [dict(e) for e in self._pending_commit],
            "conf_outstanding": {int(c): int(n) for c, n
                                 in self._conf_outstanding.items()},
            "counters": {
                "trunk_failovers_total": self.trunk_failovers_total,
                "orphans_adopted": self.orphans_adopted,
                "orphans_requeued": self.orphans_requeued,
            },
        }

    def restore_cascade(self, cas: dict, now: float = 0.0) -> None:
        self.trunk.restore(cas.get("trunk", {}), now=now)
        self.adopting = bool(cas.get("adopting", False))
        self._remote_marks = {int(c) for c
                              in cas.get("remote_marks", ())}
        self._marks_pending = bool(self._remote_marks)
        self._adopt_q = deque(dict(e) for e in cas.get("adopt_q", ()))
        # joins checkpointed pre-commit cannot be assumed committed:
        # give them a fresh deadline; _scan_commits either sees the
        # reconciled row (adopted) or times out and re-queues
        self._pending_commit = []
        for e in cas.get("pending_commit", ()):
            ent = dict(e)
            ent["commit_deadline"] = now + self.adopt_commit_timeout_s
            self._pending_commit.append(ent)
        self._conf_outstanding = {
            int(c): int(n)
            for c, n in cas.get("conf_outstanding", {}).items()}
        ctr = cas.get("counters", {})
        self.trunk_failovers_total = int(
            ctr.get("trunk_failovers_total", 0))
        self.orphans_adopted = int(ctr.get("orphans_adopted", 0))
        self.orphans_requeued = int(ctr.get("orphans_requeued", 0))
        # re-attach cascaded conferences to the restored bridge
        for conf, speakers in sorted(self.trunk._confs.items()):
            self.bridge.attach_trunk(
                self.trunk, conf,
                sorted(speakers) if speakers is not None else None)

    @classmethod
    def recover(cls, config, path: str, bridge_cls, trunk=None,
                port: int = 0, retries: int = 5,
                backoff_s: float = 0.05,
                sleep: Callable[[float], None] = time.sleep,
                supervisor_config: Optional[SupervisorConfig] = None,
                metrics=None, bridge_id: int = 0,
                peer_bridge_id: int = 1,
                **bridge_kwargs) -> "CascadeSupervisor":
        """Crash-restart with the cascade control plane restored: the
        caller supplies a fresh CascadeTrunk (sockets don't survive a
        crash any more than the bridge's do); peer, cascaded
        conferences, rosters and the adoption queue come back from the
        checkpoint, so a failover interrupted by the crash RESUMES."""
        if trunk is None:
            raise ValueError("CascadeSupervisor.recover needs a trunk")
        blob = cls.load_checkpoint(path)
        bridge = retrying(
            lambda: bridge_cls.restore(config, blob["snap"], port=port,
                                       **bridge_kwargs),
            retries=retries, backoff_s=backoff_s, sleep=sleep)
        sup = cls(bridge, trunk, config=supervisor_config,
                  metrics=metrics, bridge_id=bridge_id,
                  peer_bridge_id=peer_bridge_id)
        sup.ticks = blob["ticks"]
        sup.pending_lifecycle = blob.get("lifecycle")
        cas = blob.get("cascade")
        if cas is not None:
            sup.restore_cascade(cas)
        ev = sup.flight.record("recovered", tick=sup.ticks, path=path,
                               bridge=blob["bridge"])
        sup.postmortems.append({
            "trigger": "checkpoint_recover", "tick": sup.ticks,
            "event": ev, "dump": sup.flight.dump_all()})
        return sup
