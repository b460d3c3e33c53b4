"""Stream lifecycle plane: churn-proof admit/evict for the whole bridge.

The translator/SFU primitive benchmarks beautifully on a STATIC stream
population, but the north-star traffic is continuous join/leave: every
naive install risks landing a recompile or a multi-hundred-ms table
copy on the data path, departed streams leak recovery/PLC/BWE state,
and overload shedding can "restore" a stream that already left.  One
`StreamLifecycleManager` owns the whole problem:

1. **O(1) slot admit/evict into pre-compiled bucketed shapes** — the
   device only ever sees the size-class shapes of core/packet.py
   (`LENGTH_CLASSES` x `ROW_CLASSES`, and for the one-chip fan-out,
   which has a 512-row class of its own, `FANOUT_ROW_CLASSES`); the
   manager warms each row class, and with it the fan-out's classes
   between it and the class below, OFF-TICK the first time the
   population bucket (power of two) could reach it, so growing from 63
   to 64 streams compiles nothing on the media path.
   `utils/compile_cache.CompileCacheStats` brackets every tick
   (`tick_begin`/`tick_end`, wired by BridgeSupervisor): any
   compile event inside the window increments `datapath_recompiles`,
   and `assert_datapath_clean()` turns the "zero recompiles ever land
   on the data path" claim into a checkable invariant.

2. **Pipelined off-tick key install** — `request_join` only queues; the
   KDF/key-schedule/GHASH work runs between ticks in batches
   (`SfuBridge.stage_endpoints` -> one vectorized `add_streams` per
   table), media racing the install queues on the MediaLoop hold mask,
   and `commit_endpoints` flips the whole batch live atomically between
   ticks (one route rebuild, held media replayed).  In-flight admits
   ride the supervisor checkpoint and are completed or rolled back by
   `_reconcile` after `recover()` — never left half-installed.

3. **Burn-aware admission control** — joins are refused with a TYPED
   reason (`fast_burn`, `host_bound`, `shedding`, `stalled`,
   `capacity`, `backlog`, `duplicate`, `shard_burn`,
   `handshake_backlog`) exported as
   `lifecycle_admit_rejected{reason=...}` and flight-recorded, via
   `BridgeSupervisor.admission_decision()`.  Evictions are bookkept as
   `evicted` (distinct from overload `shed`), so the supervisor's LIFO
   unwind never resurrects a departed stream.

4. **Off-tick handshake pipeline** (`HandshakeQueue`) — DTLS joins
   admit through `request_handshake` (same typed-refusal contract,
   plus a retry-after hint when the handshake plane is saturated),
   their OpenSSL work drains in bounded batches on the between-ticks
   window, and completed keys land via `stage_dtls_keys` -> the same
   commit barrier as direct-keyed joins: a keyed row becomes live
   atomically, never mid-tick, and the media tick thread never
   executes a single OpenSSL call.

Reference: no analog — the reference allocates a MediaStream object
per join and lets the JVM GC departures; a dense-table runtime must
manage stream mortality explicitly.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from libjitsi_tpu.core.packet import FANOUT_ROW_CLASSES, ROW_CLASSES
from libjitsi_tpu.utils.compile_cache import (compile_concurrently,
                                               compile_stats)
from libjitsi_tpu.utils.flight import FlightRecorder
from libjitsi_tpu.utils.logging import get_logger

_log = get_logger("lifecycle")

#: every reason `request_join`/`request_handshake` can refuse with
#: (typed: metrics, flight events and callers all share these strings)
ADMIT_REASONS = ("capacity", "backlog", "duplicate", "fast_burn",
                 "stalled", "shedding", "host_bound", "shard_burn",
                 "hop_burn", "handshake_backlog", "trunk_down",
                 "trunk_backlog", "capacity_forecast", "conference_full")


@dataclass
class LifecycleConfig:
    """Knobs for the admit/evict pipeline."""

    min_bucket: int = 16         # smallest population bucket warmed
    install_batch: int = 64      # joins staged per between-ticks window
    max_pending: int = 512       # queued + staged backlog cap
    warm_payload_len: int = 160  # representative payload for warmups
    # est. packets per stream per tick: sizes the row classes a
    # population bucket can drive (warmup_rtp uses the same figure)
    pkts_per_stream: int = 4
    # ------------------------------------------ handshake plane knobs
    # datagrams the HandshakeQueue drains per between-ticks window
    # (the OpenSSL budget — install_batch's twin for handshakes)
    handshake_batch: int = 64
    # backlog bound (queued datagrams + pending associations) past
    # which request_handshake refuses `handshake_backlog`
    max_handshakes: int = 256
    # flight retransmission jitter: each off-tick pass services only
    # 1/stride of the pending associations' RFC 6347 timers, spreading
    # a storm's flights so retransmissions never fire in lockstep
    handshake_retx_stride: int = 4
    # nominal between-ticks cadence used to turn a backlog depth into
    # the retry-after hint attached to handshake_backlog refusals
    handshake_retry_tick_s: float = 0.02
    # ------------------------------------------------ sharded tables
    # what the deployment states of the bridge it hands the manager:
    # its key tables are row-sharded this many ways (0: not stated).
    # Picks nothing: the manager refuses a bridge whose tables are
    # sharded otherwise, and a placement whose shards are not the
    # tables' (a conference could then straddle a chip unseen)
    table_shards: int = 0
    # --------------------------------------------------- the room cap
    # the most members a conference admits (prosody's
    # `muc_max_occupants`; 0: not stated).  An admission rule: with
    # placement enabled `request_join` refuses the member past it
    # `conference_full`; nothing on the data path reads it.  A declared
    # broadcast conference is not held to it (its listeners are what it
    # is for)
    max_conference_size: int = 0
    # ------------------------------------------------ the visitors rule
    # the most members of a conference who take part (Jicofo's
    # `jicofo.visitors.max-participants`; 0: not stated).  An admission
    # rule over the broadcast plane that is here, read by nothing on
    # the data path: with placement enabled `request_join` declares a
    # conference a broadcast conference when its first member joins
    # under the rule, admits a member as a speaker while the conference
    # holds fewer speakers than this and as a fanout-only listener (a
    # receive-only visitor) from then on; a caller's `role` still
    # wins, and `promote_speaker` stays what it is.  A visitor is never
    # refused `conference_full`: `max_conference_size` holds the rooms
    # of peers alone, so where both are stated it caps the panel and
    # the manager refuses a panel larger than the room
    max_conference_participants: int = 0


class HandshakeQueue:
    """Off-tick DTLS handshake pipeline for one bridge.

    Construction flips the bridge's `DtlsAssociationTable` to deferred
    ingest — `on_dtls` (tick thread) only enqueues datagrams — and
    re-points its install callback at the STAGED landing: completed
    keys go through `stage_dtls_keys` and flip live at the next commit
    barrier, never mid-tick.  `drain()` runs on the between-ticks
    window (wired into `run_between_ticks`): one bounded `process`
    batch of OpenSSL work plus a jittered flight-retransmission pass
    with gather egress (one PacketBatch per peer per pass).

    ZRTP associations share the same endpoint surface (`feed` /
    `complete` / `srtp_keys`), so a ZRTP-keyed bridge plugs into this
    queue unchanged; today's bridges key via DTLS-SRTP.
    """

    def __init__(self, lc: "StreamLifecycleManager"):
        self.lc = lc
        self.bridge = lc.bridge
        self.cfg = lc.cfg
        self.table = lc.bridge._dtls
        self.table.deferred = True
        # generous inbox: refusal happens at ADMISSION (typed, with a
        # retry hint), not by silently dropping datagrams of already
        # admitted associations.  ~2 flights of 6 datagrams per row.
        self.table.inbox_limit = max(self.table.inbox_limit,
                                     12 * self.cfg.max_handshakes)
        self._inline_install = self.table.install
        self.table.install = self._on_complete
        # sid -> admission metadata (ssrc/role/fingerprint/cookie/addr
        # + admit tick): what a checkpoint needs to REQUEUE the
        # association after recover (OpenSSL state cannot serialize)
        self.active: Dict[int, dict] = {}
        self._pass = 0
        self.off_tick_seconds = 0.0
        self.completed = 0
        self.requeued = 0

    @property
    def depth(self) -> int:
        """Admission-facing depth: queued datagrams + pending rows."""
        return self.table.backlog

    def retry_after(self) -> float:
        """Hint for a refused client: model-time until the drain could
        plausibly reach it, from the backlog depth and the per-window
        budget.  Clients honor it with their own exponential backoff
        on repeated refusals."""
        passes = 1 + self.depth // max(1, self.cfg.handshake_batch)
        return round(passes * self.cfg.handshake_retry_tick_s, 4)

    def drain(self) -> int:
        """The between-ticks pass: bounded OpenSSL work + jittered
        flight retransmissions.  Wall time accrues to
        `off_tick_seconds` (the supervisor's phase-attribution ledger
        line — handshake cost is attributed HERE, never to a tick
        phase)."""
        t0 = time.perf_counter()
        n = self.table.process(self.cfg.handshake_batch)
        self._pass += 1
        self.table.tick(stride=max(1, self.cfg.handshake_retx_stride),
                        phase=self._pass)
        if self.active:
            # drop metadata for rows that left the plane sideways
            # (evicted mid-handshake, fingerprint-rejected)
            live = self.bridge._ssrc_of
            self.active = {s: m for s, m in self.active.items()
                           if s in self.table.pending or s in live}
        self.off_tick_seconds += time.perf_counter() - t0
        return n

    def _on_complete(self, sid: int, ep) -> None:
        """Install callback for the deferred table: land the exported
        keys STAGED so the commit barrier flips the row live."""
        meta = self.active.pop(sid, None)
        if hasattr(self.bridge, "stage_dtls_keys"):
            # the committed population grows at the next barrier: warm
            # its bucket NOW (off-tick) so the flip compiles nothing
            self.lc._ensure_warm(len(self.bridge._ssrc_of)
                                 - len(self.lc._listener_sids))
            self.bridge.stage_dtls_keys(sid, ep)
            self.lc._staged.append(sid)
            self.lc.key_installs += 1
        else:
            # bridge without a staged pipeline: inline install (still
            # off-tick — we are on the between-ticks window)
            self._inline_install(sid, ep)
            self.bridge.loop.release_stream(sid)
        self.completed += 1
        self.lc.flight.record(
            "handshake_complete", tick=self.lc.ticks(), sid=sid,
            ssrc=(meta or {}).get("ssrc"),
            profile=ep.selected_profile.name)

    def snapshot(self) -> List[dict]:
        """Mid-handshake associations for the supervisor checkpoint:
        OpenSSL state cannot serialize, so each rides as its admission
        parameters (plus its bound 5-tuple) and REQUEUES as a fresh
        association after recover — the peer's flight timers drive the
        new handshake."""
        out = []
        for sid, ep in self.table.pending.items():
            meta = self.active.get(sid, {})
            out.append({
                "ssrc": meta.get("ssrc", self.bridge._ssrc_of.get(sid)),
                "role": meta.get("role", getattr(ep, "role", "server")),
                "fingerprint": meta.get("fingerprint"),
                "cookie": bool(meta.get("cookie", False)),
                "addr": self.table.sid_addr.get(sid),
            })
        return out


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


class StreamLifecycleManager:
    """Owns admit/evict for one bridge.  Construct after the
    BridgeSupervisor; the manager attaches itself
    (`supervisor.lifecycle = self`) so the supervisor's tick brackets
    the data path with the compile guard and runs the commit barrier +
    install stage between ticks.  Without a supervisor, call
    `run_between_ticks()` manually after each `bridge.tick()`."""

    def __init__(self, bridge, supervisor=None,
                 config: Optional[LifecycleConfig] = None,
                 metrics=None, flight: Optional[FlightRecorder] = None):
        self.bridge = bridge
        self.supervisor = supervisor
        self.cfg = config or LifecycleConfig()
        mesh = getattr(bridge, "_mesh", None)
        shards = 1 if mesh is None else int(mesh.devices.size)
        if self.cfg.table_shards and self.cfg.table_shards != shards:
            raise ValueError(
                f"the configuration states {self.cfg.table_shards} table "
                f"shards, the bridge's tables have {shards}")
        if 0 < self.cfg.max_conference_size \
                < self.cfg.max_conference_participants:
            raise ValueError(
                f"a panel of {self.cfg.max_conference_participants} in "
                f"a room capped at {self.cfg.max_conference_size}")
        if flight is None:
            flight = (supervisor.flight if supervisor is not None
                      else getattr(bridge, "flight", None))
        self.flight = flight if flight is not None else FlightRecorder()
        # join queue: (ssrc, rx_key, tx_key, name, conference, role,
        # shard) — host-side only until poll() stages a batch.  `role`/
        # `shard` are None except for broadcast-conference joins
        # ("speaker"/"listener"; listeners carry their assigned shard,
        # which may differ from the conference's home shard)
        self._join_q: deque = deque()
        self._queued_ssrcs: set = set()
        # conference-affinity placement (mesh/placement.py): None until
        # enable_placement — the single-conference bridge needs none
        self.placer = None
        self._rows_per_shard = 0
        self._move_inflight: Optional[dict] = None
        self.moves_applied = 0
        self._staged: List[int] = []     # staged sids awaiting commit
        self._evict_q: List[int] = []
        # counters (all registered in register_metrics)
        self.admits = 0
        self.evicts = 0
        self.key_installs = 0
        self.datapath_recompiles = 0
        self.admit_rejected: Dict[str, int] = {}
        # joins queued into broadcast conferences, by the role given
        self.admit_roles = {"speaker": 0, "listener": 0}
        # broadcast conferences (mesh/hierarchy.py): conf ->
        # {"speakers": set of sids, "join_good"/"join_bad": cumulative
        # listener-join outcomes feeding the label="conference" burn
        # slice}; listener sids tracked separately for the fanout-only
        # warmup ladder and the bcast_listeners gauge
        self._bcast: Dict[int, dict] = {}
        self._listener_sids: set = set()
        # cascaded conferences homed on a REMOTE bridge (mesh/cascade):
        # conf key -> trunk.  While the trunk is down/backlogged, joins
        # into these refuse with the trunk's typed reason + retry-after
        # hint; failover adoption promotes them local and clears this
        self._remote_conf: Dict[int, object] = {}
        self._role_flips: List[Tuple[int, int, str]] = []
        self.speaker_promotions = 0
        self.speaker_demotions = 0
        # population bucket whose shapes are warm; row classes warmed
        self._warm_bucket = 0
        self._warm_rows: set = set()
        # fanout-only listener rows warm a ladder of their own: no
        # uplink RTP classes, just fan-out legs + RTCP
        self._warm_lbucket = 0
        self._warm_lrows: set = set()
        self._tick_compiles0: Optional[int] = None
        # off-tick handshake pipeline: attaches only when the bridge
        # keys rows via a DTLS association table (SfuBridge /
        # ConferenceBridge); direct-keyed bridges and test fakes get
        # None and the plane behaves exactly as before
        self.handshakes: Optional[HandshakeQueue] = None
        if getattr(bridge, "_dtls", None) is not None:
            self.handshakes = HandshakeQueue(self)
        # OpenSSL feed() calls observed INSIDE tick windows (invariant:
        # 0 once deferred — the reconnect soak gates on it)
        self.tick_thread_handshake_feeds = 0
        self._tick_feeds0: Optional[int] = None
        if supervisor is not None:
            supervisor.lifecycle = self
            pend = getattr(supervisor, "pending_lifecycle", None)
            if pend:
                self._reconcile(pend)
                supervisor.pending_lifecycle = None
        if metrics is not None:
            self.register_metrics(metrics)

    # ------------------------------------------------------- placement

    def enable_placement(self, n_shards: int, placer=None) -> None:
        """Turn on conference-affinity sharding (mesh/placement.py):
        joins carry a `conference` id, whole conferences are assigned
        to shards at join time, rows are drawn from the conference's
        shard range, and rebalance moves run through the commit
        barrier.  `n_shards` must divide the registry capacity (shard
        ranges are contiguous row blocks)."""
        from libjitsi_tpu.mesh.placement import ConferencePlacer
        if self.cfg.table_shards and n_shards != self.cfg.table_shards:
            raise ValueError(f"{n_shards} placement shards over "
                             f"{self.cfg.table_shards} table shards")
        capacity = self.bridge.registry.capacity
        if capacity % n_shards:
            raise ValueError(f"capacity {capacity} not divisible by "
                             f"{n_shards} shards")
        self._rows_per_shard = capacity // n_shards
        if placer is None:
            placer = ConferencePlacer(
                n_shards, rows_per_shard=self._rows_per_shard)
        elif placer.rows_per_shard > self._rows_per_shard:
            raise ValueError("placer rows_per_shard exceeds the "
                             "registry's shard range")
        self.placer = placer
        # shard-major dispatch: contiguous shard sid ranges mean a
        # stable per-batch sort groups each device's rows (io/loop.py)
        loop = getattr(self.bridge, "loop", None)
        if loop is not None and hasattr(loop, "enable_shard_major"):
            loop.enable_shard_major(self._rows_per_shard)

    # ------------------------------------------------------- broadcast

    def declare_broadcast(self, conference, objective: float = 0.999
                          ) -> int:
        """Declare `conference` a BROADCAST conference (webinar shape:
        a handful of speakers, fanout-only listeners).  Requires
        placement: the speaker rows get a home shard (never straddle),
        listener rows spread over every shard (`mesh/hierarchy.py`'s
        two-level tick mixes speakers on the home shard and fans the
        bus out in one sanctioned collective).  Joins then default to
        role="listener"; speakers join with role="speaker" or are
        promoted later (`promote_speaker`, a commit-barrier event).
        Registers the label="conference" listener-join burn slice on
        the supervisor's SLO engine the first time.  Returns the home
        shard."""
        if self.placer is None:
            raise RuntimeError("broadcast conferences need placement "
                               "(enable_placement first)")
        conf = int(conference)
        if conf in self._bcast:
            return self.placer.shard_of(conf)
        home = self.placer.place_broadcast(
            conf, 0, avoid=self._burning_shards())
        if home is None:
            raise RuntimeError("no shard can home the broadcast "
                               "conference")
        self._bcast[conf] = {"speakers": set(),
                             "join_good": 0, "join_bad": 0}
        if hasattr(self.bridge, "set_broadcast_speakers"):
            self.bridge.set_broadcast_speakers(conf, ())
        self._register_conference_slo(objective)
        self.flight.record("broadcast_declared", tick=self.ticks(),
                           conf=conf, home=home)
        _log.info("broadcast_declared", conf=conf, home=home)
        return home

    def _register_conference_slo(self, objective: float) -> None:
        slo = getattr(self.supervisor, "slo", None) \
            if self.supervisor is not None else None
        if slo is None:
            return
        if any(s.name == "bcast_listener_join"
               for s in getattr(slo, "sliced", ())):
            return
        from libjitsi_tpu.utils.slo import SlicedSloSpec

        def _reader():
            for conf, st in self._bcast.items():
                yield (str(conf), float(st["join_good"]),
                       float(st["join_bad"]))

        slo.add_sliced(SlicedSloSpec(
            "bcast_listener_join", objective=objective,
            label="conference", reader=_reader,
            description="broadcast listener joins admitted vs refused, "
                        "per conference"))

    def _place_bcast_join(self, conf: int, role: str
                          ) -> Tuple[Optional[int], Optional[str]]:
        """(shard, reason) for a join into a broadcast conference.
        Speakers grow the home shard (never straddle); listeners land
        on any shard with row headroom, steering around burning ones."""
        home = self.placer.shard_of(conf)
        if role == "speaker":
            if self.supervisor is not None:
                ok, r = self.supervisor.admission_decision(shard=home)
                if not ok and r in ("shard_burn", "capacity_forecast"):
                    return None, r
            if not self.placer.try_grow(conf):
                return None, "capacity"
            return home, None
        shard = self.placer.grow_listeners(
            conf, avoid=self._burning_shards())
        if shard is None:
            return None, "capacity"
        return shard, None

    def promote_speaker(self, conference, sid: int) -> None:
        """Queue a listener→speaker role flip; applied at the next
        commit barrier (routes rebuild, fanout-only mask clears, the
        row migrates to the home shard if it lives elsewhere) — never
        mid-tick."""
        self._role_flips.append((int(conference), int(sid), "speaker"))

    # ------------------------------------------------------- cascade
    def mark_remote_conference(self, conference, trunk) -> None:
        """A cascaded conference homed on the trunk's PEER bridge:
        local joins are admitted while the trunk is up (they become
        local legs of the cascade) but refuse with the trunk's typed
        reason (`trunk_down` / `trunk_backlog`) while it is not."""
        self._remote_conf[self._conf_key(0, conference)] = trunk

    def promote_remote_conference(self, conference) -> None:
        """Failover: the conference is now homed HERE (orphan adoption
        committed) — joins stop consulting the trunk."""
        key = self._conf_key(0, conference)
        if self._remote_conf.pop(key, None) is not None:
            self.flight.record("conf_promoted", tick=self.ticks(),
                               conf=key)

    def retry_after_hint(self, reason: str, conference=None) -> float:
        """Seconds a refused caller should wait before retrying (the
        PR 16 hint surface, extended to trunk refusals): handshake
        refusals ride the queue's drain estimate, trunk refusals the
        trunk's jittered-exponential backoff."""
        if reason == "handshake_backlog" and self.handshakes is not None:
            return self.handshakes.retry_after
        if reason == "capacity_forecast":
            cap = getattr(self.supervisor, "capacity", None) \
                if self.supervisor is not None else None
            if cap is not None:
                return float(cap.retry_after())
        if reason in ("trunk_down", "trunk_backlog"):
            trunk = None
            if conference is not None:
                trunk = self._remote_conf.get(
                    self._conf_key(0, conference))
            if trunk is None and self._remote_conf:
                trunk = next(iter(self._remote_conf.values()))
            if trunk is not None:
                return float(trunk.retry_after())
        return self.cfg.handshake_retry_tick_s

    def demote_speaker(self, conference, sid: int) -> None:
        """Queue a speaker→listener role flip (commit-barrier event)."""
        self._role_flips.append((int(conference), int(sid), "listener"))

    def _conf_key(self, ssrc: int, conference) -> int:
        # a placement-enabled join without a conference id is a
        # singleton conference (keyed off the ssrc, negative so user
        # conference ids can never collide with it)
        return int(conference) if conference is not None \
            else -(int(ssrc) + 2)

    def _free_rows_on(self, shard: int, k: int) -> List[int]:
        """Up to `k` free registry rows inside `shard`'s range.  The
        registry stays the single source of truth for row freedom
        (video tracks and direct add_endpoint also draw from it);
        placement only constrains WHERE a conference's rows may live."""
        lo = shard * self._rows_per_shard
        free = np.fromiter(self.bridge.registry._free, dtype=np.int64)
        free = free[(free >= lo) & (free < lo + self._rows_per_shard)]
        return np.sort(free)[:k].tolist()

    # ------------------------------------------------------- admission

    def ticks(self) -> int:
        return self.supervisor.ticks if self.supervisor is not None else 0

    def _admission_reason(self, ssrc: int) -> Optional[str]:
        if self.bridge.has_ssrc(ssrc) or ssrc in self._queued_ssrcs:
            return "duplicate"
        if len(self._join_q) + len(self._staged) >= self.cfg.max_pending:
            return "backlog"
        # queued joins have slots spoken for; evictions still queued do
        # NOT count as free (they only free up at the barrier)
        if self.bridge.registry.free_slots <= len(self._join_q):
            return "capacity"
        if self.supervisor is not None:
            ok, reason = self.supervisor.admission_decision()
            if not ok:
                return reason
        return None

    def _burning_shards(self) -> set:
        """Shards placement must steer around: fast-burning per-shard
        SLO slices, plus shards the capacity forecast already calls
        exhausted (utils/capacity.py) — same avoidance surface, one
        reactive signal and one predictive."""
        sup = self.supervisor
        out: set = set()
        slo = getattr(sup, "slo", None) if sup is not None else None
        if slo is not None:
            for spec in getattr(slo, "sliced", ()):
                if spec.label == "shard":
                    out |= {int(k)
                            for k in slo.burning_slices(spec.name)}
        cap = getattr(sup, "capacity", None) if sup is not None else None
        if cap is not None:
            out |= {int(s) for s in cap.exhausted_shards()}
        return out

    def _place_join(self, ssrc: int, conference) -> Tuple[Optional[int],
                                                          Optional[str]]:
        """Placement half of admission: returns (conf_key, reason).
        A join into an EXISTING conference targets its shard — refused
        `shard_burn` when that specific shard is burning fast (the
        conference cannot straddle to a healthy one), `capacity` when
        the shard's row range is full.  A NEW conference places
        least-loaded, steering around burning shards."""
        conf = self._conf_key(ssrc, conference)
        shard = self.placer.shard_of(conf)
        if shard is not None:
            cap = self.cfg.max_conference_size
            if cap and self.placer.size_of(conf) >= cap:
                return conf, "conference_full"
            if self.supervisor is not None:
                ok, r = self.supervisor.admission_decision(shard=shard)
                if not ok and r in ("shard_burn", "capacity_forecast"):
                    return conf, r
            if not self.placer.try_grow(conf):
                return conf, "capacity"
            return conf, None
        if self.placer.place(conf, 1,
                             avoid=self._burning_shards()) is None:
            return conf, "capacity"
        return conf, None

    def request_join(self, ssrc: int, rx_key: Tuple[bytes, bytes],
                     tx_key: Tuple[bytes, bytes],
                     name: Optional[str] = None,
                     conference=None,
                     role: Optional[str] = None) -> Tuple[bool, str]:
        """Admission decision + queue.  Returns (accepted, reason):
        (True, "queued") or (False, <typed reason>).  Nothing touches
        the device here — keys install off-tick in poll().

        With placement enabled (`enable_placement`), `conference`
        groups endpoints: the whole conference lives on one shard, its
        rows are drawn from that shard's range, and forwarding is
        scoped to it.  A join without a conference id is a singleton
        conference.  Joins into a declared BROADCAST conference default
        to role="listener" (fanout-only row on any shard); pass
        role="speaker" to join the mixed speaker set on the home
        shard.  Where the configuration states the visitors rule
        (`max_conference_participants`), a conference is declared by
        its first member's join and the default role is "speaker"
        while it holds fewer speakers than the rule says."""
        ssrc = int(ssrc) & 0xFFFFFFFF
        reason = self._admission_reason(ssrc)
        if (reason is None and conference is not None
                and self._remote_conf):
            # cascaded conference homed on the trunk's peer: typed
            # trunk refusal while the trunk is down or backlogged
            # (None while up — the join becomes a local cascade leg)
            trunk = self._remote_conf.get(
                self._conf_key(ssrc, conference))
            if trunk is not None:
                reason = trunk.admit_reason()
        conf = shard = None
        bcast = False
        if reason is None and self.placer is not None:
            conf = self._conf_key(ssrc, conference)
            panel = self.cfg.max_conference_participants
            declared = bool(panel and conference is not None
                            and self.placer.shard_of(conf) is None)
            if declared:
                # the visitors rule: the room's first member declares it
                self.declare_broadcast(conf)
            bcast = conf in self._bcast
            if bcast:
                role = role or (
                    "speaker" if self.placer.size_of(conf) < panel
                    else "listener")
                shard, reason = self._place_bcast_join(conf, role)
                if declared and reason is not None:
                    # refused at the door: the room it would have
                    # opened is not kept
                    self._release_broadcast(conf)
                    bcast = False
            else:
                role = None
                conf, reason = self._place_join(ssrc, conference)
        if reason is not None:
            if bcast and role == "listener":
                self._bcast[conf]["join_bad"] += 1
            self.admit_rejected[reason] = \
                self.admit_rejected.get(reason, 0) + 1
            self.flight.record("admit_reject", tick=self.ticks(),
                               ssrc=ssrc, reason=reason)
            _log.info("admit_reject", ssrc=ssrc, reason=reason)
            return False, reason
        self._join_q.append((ssrc, tuple(rx_key), tuple(tx_key), name,
                             conf, role if bcast else None, shard))
        self._queued_ssrcs.add(ssrc)
        if bcast:
            self.admit_roles[role] = self.admit_roles.get(role, 0) + 1
        self.flight.record("admit_queued", tick=self.ticks(), ssrc=ssrc)
        return True, "queued"

    def request_handshake(self, ssrc: int, role: str = "server",
                          remote_fingerprint: Optional[str] = None,
                          cookie_exchange: bool = False,
                          remote_addr=None,
                          name: Optional[str] = None
                          ) -> Tuple[bool, str, float]:
        """Admission decision + association start for a DTLS-keyed
        join: the handshake plane's twin of `request_join`.  Returns
        `(accepted, reason, retry_after_s)` — `(True, "queued", 0.0)`
        on admit, or a typed refusal; `handshake_backlog` refusals
        (the plane saturated past `max_handshakes`) carry a non-zero
        retry-after hint that clients honor with exponential backoff.

        On admit the row allocates and the association starts
        immediately (`add_endpoint_dtls`): datagrams route to it from
        the next packet on, but ALL OpenSSL work runs on the
        between-ticks drain and the keys land via the staged commit
        barrier — the tick thread never handshakes.  Pass
        `remote_addr` when signaling knows the peer's 5-tuple; under a
        storm (many concurrent unbound rows) unknown-address datagrams
        are dropped rather than guessed onto the wrong row."""
        hq = self.handshakes
        if hq is None:
            raise RuntimeError(
                "bridge has no DTLS association table; use request_join")
        ssrc = int(ssrc) & 0xFFFFFFFF
        reason: Optional[str] = None
        if self.bridge.has_ssrc(ssrc) or ssrc in self._queued_ssrcs:
            reason = "duplicate"
        elif self.bridge.registry.free_slots <= len(self._join_q):
            reason = "capacity"
        elif self.supervisor is not None:
            ok, r = self.supervisor.admission_decision(
                handshake_backlog=hq.depth,
                handshake_bound=self.cfg.max_handshakes)
            if not ok:
                reason = r
        elif hq.depth >= self.cfg.max_handshakes:
            reason = "handshake_backlog"
        if reason is not None:
            retry = hq.retry_after() \
                if reason == "handshake_backlog" else 0.0
            self.admit_rejected[reason] = \
                self.admit_rejected.get(reason, 0) + 1
            self.flight.record("handshake_reject", tick=self.ticks(),
                               ssrc=ssrc, reason=reason,
                               retry_after_s=retry)
            _log.info("handshake_reject", ssrc=ssrc, reason=reason,
                      retry_after_s=retry)
            return False, reason, retry
        sid, _ep = self.bridge.add_endpoint_dtls(
            ssrc, role=role, remote_fingerprint=remote_fingerprint,
            cookie_exchange=cookie_exchange, remote_addr=remote_addr)
        if name is not None:
            self.bridge.loop.metrics.set_stream_name(sid, name)
        hq.active[sid] = {
            "ssrc": ssrc, "role": role,
            "fingerprint": remote_fingerprint,
            "cookie": bool(cookie_exchange), "tick": self.ticks(),
        }
        self.flight.record("handshake_queued", tick=self.ticks(),
                           sid=sid, ssrc=ssrc)
        return True, "queued", 0.0

    def request_leave(self, sid: Optional[int] = None,
                      ssrc: Optional[int] = None) -> bool:
        """Queue an evict (by sid or ssrc).  A join still queued
        host-side is simply cancelled; anything staged or live is torn
        down at the next between-ticks barrier."""
        if sid is None:
            if ssrc is None:
                raise ValueError("need sid or ssrc")
            ssrc = int(ssrc) & 0xFFFFFFFF
            if ssrc in self._queued_ssrcs:          # never installed
                self._queued_ssrcs.discard(ssrc)
                if self.placer is not None:
                    for j in self._join_q:
                        if j[0] != ssrc or j[4] is None:
                            continue
                        if j[5] == "listener":
                            self.placer.shrink_listeners(j[4], j[6])
                        elif j[5] == "speaker":
                            self.placer.resize(
                                j[4], max(self.placer.size_of(j[4]) - 1,
                                          0))
                        else:
                            self.placer.shrink(j[4])
                self._join_q = deque(j for j in self._join_q
                                     if j[0] != ssrc)
                self.flight.record("admit_cancelled",
                                   tick=self.ticks(), ssrc=ssrc)
                return True
            sid = next((s for s, v in self.bridge._ssrc_of.items()
                        if v == ssrc), None)
            if sid is None:
                return False
        self._evict_q.append(int(sid))
        return True

    # ------------------------------------------- between-ticks pipeline

    def run_between_ticks(self, now=None) -> None:
        """The off-tick half of the plane: handshake drain first (its
        completions stage rows that the SAME window's commit flips
        live), then the commit barrier (staged rows flip live, queued
        evicts tear down — both between ticks, never inside one), then
        the next install wave, then any placement rebalance moves
        (also lifecycle events: a conference only ever changes shards
        here, never mid-tick)."""
        if self.handshakes is not None:
            self.handshakes.drain()
        self.commit()
        self.poll()
        self.rebalance()
        self.fill_keystream()

    def _keystream_caches(self):
        for name in ("rx_table", "tx_table"):
            cache = getattr(getattr(self.bridge, name, None),
                            "_ks_cache", None)
            if cache is not None:
                yield cache

    def fill_keystream(self) -> None:
        """Off-tick keystream pregeneration: top up the GCM caches'
        sliding windows AFTER the commit barrier (so a rekey's
        invalidation has already landed and the refill keys are the
        live ones).  All compile shapes here are fixed-chunk, so this
        phase never recompiles the data path."""
        for cache in self._keystream_caches():
            cache.fill()

    def commit(self) -> None:
        """Atomic (w.r.t. the tick) population flip: committed admits
        and processed evicts both land here, between ticks."""
        if self._staged or self._evict_q or self._role_flips:
            # pipeline drain barrier: a deep-pipelined loop may still
            # hold in-flight reverse work referencing rows about to be
            # evicted/recycled — collapse it before the population flips
            loop = getattr(self.bridge, "loop", None)
            drain = getattr(loop, "drain", None)
            if drain is not None:
                drain()
        if self._staged:
            sids, self._staged = self._staged, []
            self.bridge.commit_endpoints(sids)
            self.admits += len(sids)
            if self.supervisor is not None:
                self.supervisor.note_admitted(sids)
            touched: set = set()
            for sid in sids:
                conf = getattr(self.bridge, "_conf_of", {}).get(sid)
                st = self._bcast.get(conf)
                if st is not None:
                    if sid in self._listener_sids:
                        st["join_good"] += 1
                    elif sid in st["speakers"]:
                        touched.add(conf)
                self.flight.record("admit_commit", tick=self.ticks(),
                                   sid=sid)
            # newly committed speakers reshape routing: one
            # set_broadcast_speakers per touched conference rebuilds
            # routes and fanout-only masks at the barrier
            for conf in sorted(touched):
                self._push_speakers(conf)
        if self._evict_q:
            live = dict.fromkeys(self._evict_q)  # de-dup, keep order
            self._evict_q = []
            sids = [s for s in live if s in self.bridge._ssrc_of]
            if sids:
                conf_of = getattr(self.bridge, "_conf_of", {})
                gone_confs = [conf_of.get(s) for s in sids]
                self.bridge.remove_endpoints(sids)
                self.evicts += len(sids)
                if self.supervisor is not None:
                    self.supervisor.note_evicted(sids)
                if self.placer is not None:
                    touched = set()
                    bcast_gone = set()
                    for sid, conf in zip(sids, gone_confs):
                        if conf is None:
                            continue
                        st = self._bcast.get(conf)
                        if st is None:
                            self.placer.shrink(conf)
                            if self.placer.shard_of(conf) is None:
                                self._drop_conference_slices(conf)
                            continue
                        bcast_gone.add(conf)
                        if sid in self._listener_sids:
                            self._listener_sids.discard(sid)
                            self.placer.shrink_listeners(
                                conf, sid // self._rows_per_shard)
                        elif sid in st["speakers"]:
                            st["speakers"].discard(sid)
                            self.placer.resize(
                                conf,
                                max(self.placer.size_of(conf) - 1, 0))
                            touched.add(conf)
                    # a broadcast conference only releases when its last
                    # member leaves (0 speakers with listeners still
                    # attached is a legitimate state)
                    for conf in sorted(bcast_gone):
                        if any(c == conf for s, c in conf_of.items()
                               if s in self.bridge._ssrc_of):
                            continue
                        self._release_broadcast(conf)
                        touched.discard(conf)
                    for conf in sorted(touched):
                        self._push_speakers(conf)
        self._apply_role_flips()

    def _release_broadcast(self, conf: int) -> None:
        """A broadcast conference nobody is in any more: its home-shard
        reservation, burn slice and routing go."""
        self.placer.release(conf)
        self._drop_conference_slices(conf)
        self._bcast.pop(conf, None)
        if hasattr(self.bridge, "clear_broadcast"):
            self.bridge.clear_broadcast(conf)

    def _push_speakers(self, conf: int) -> None:
        if hasattr(self.bridge, "set_broadcast_speakers"):
            self.bridge.set_broadcast_speakers(
                conf, tuple(sorted(self._bcast[conf]["speakers"])))

    def _apply_role_flips(self) -> None:
        """Commit-barrier application of queued promote/demote events:
        routes rebuild, fanout-only masks flip and (for a promotion off
        the home shard) the row migrates home — all between ticks, all
        on pre-warmed shapes, so a role flip compiles nothing."""
        if not self._role_flips:
            return
        flips, self._role_flips = self._role_flips, []
        touched: set = set()
        for conf, sid, role in flips:
            st = self._bcast.get(conf)
            if st is None or sid not in self.bridge._ssrc_of:
                continue
            if role == "speaker":
                if sid in st["speakers"]:
                    continue
                home = self.placer.shard_of(conf)
                cur = sid // self._rows_per_shard
                if cur != home:
                    rows = self._free_rows_on(home, 1)
                    if not rows or not self.placer.try_grow(conf):
                        self.flight.record(
                            "speaker_flip_refused", tick=self.ticks(),
                            conf=conf, sid=sid, reason="capacity")
                        continue
                    self.bridge.migrate_endpoints({sid: rows[0]})
                    self.placer.shrink_listeners(conf, cur)
                    self._listener_sids.discard(sid)
                    sid = rows[0]
                else:
                    if not self.placer.try_grow(conf):
                        self.flight.record(
                            "speaker_flip_refused", tick=self.ticks(),
                            conf=conf, sid=sid, reason="capacity")
                        continue
                    self.placer.shrink_listeners(conf, cur)
                    self._listener_sids.discard(sid)
                st["speakers"].add(sid)
                self.speaker_promotions += 1
            else:
                if sid not in st["speakers"]:
                    continue
                st["speakers"].discard(sid)
                self.placer.resize(
                    conf, max(self.placer.size_of(conf) - 1, 0))
                # the demoted row stays physically put: it re-books as
                # a listener row on its current shard
                self.placer.grow_listeners(
                    conf, shard=sid // self._rows_per_shard)
                self._listener_sids.add(sid)
                self.speaker_demotions += 1
            touched.add(conf)
            self.flight.record("speaker_flip", tick=self.ticks(),
                               conf=conf, sid=sid, role=role)
            _log.info("speaker_flip", conf=conf, sid=sid, role=role)
        for conf in sorted(touched):
            self._push_speakers(conf)

    def poll(self) -> None:
        """Stage the next install wave: batch-limited, slot-limited,
        with the target bucket's shapes warmed BEFORE any new stream
        can contribute traffic.  Under placement, each join's row is
        drawn from its conference's shard range (a spec whose shard has
        no physical row free — out-of-band allocs can fragment a range
        — re-queues for a later wave rather than straddling)."""
        n = min(len(self._join_q), self.cfg.install_batch,
                self.bridge.registry.free_slots)
        if n <= 0:
            return
        popped = [self._join_q.popleft() for _ in range(n)]
        if self.placer is None:
            specs, sids, confs = popped, None, None
        else:
            by_shard: Dict[int, list] = {}
            for spec in popped:
                # broadcast listeners carry their own assigned shard
                # (may straddle off the conference's home shard)
                shard = spec[6] if spec[5] == "listener" \
                    else self.placer.shard_of(spec[4])
                by_shard.setdefault(shard, []).append(spec)
            specs, sids, confs = [], [], []
            requeue: list = []
            for shard in sorted(by_shard):
                group = by_shard[shard]
                rows = self._free_rows_on(shard, len(group))
                for spec, row in zip(group, rows):
                    specs.append(spec)
                    sids.append(row)
                    confs.append(spec[4])
                requeue.extend(group[len(rows):])
            for spec in reversed(requeue):
                self._join_q.appendleft(spec)
            if not specs:
                return
        for spec in specs:
            self._queued_ssrcs.discard(spec[0])
        n_listen = sum(1 for spec in specs if spec[5] == "listener")
        # listeners warm their OWN fanout-only ladder; they never
        # contribute uplink RTP, so they stay out of the RTP-class
        # population estimate entirely
        self._ensure_warm(len(self.bridge._ssrc_of)
                          - len(self._listener_sids)
                          + len(specs) - n_listen)
        if n_listen or self._listener_sids:
            self._ensure_warm_listeners(
                len(self._listener_sids) + n_listen)
        specs4 = [tuple(spec[:4]) for spec in specs]
        if self.placer is None:
            # kwarg-free call: bridge fakes/older bridges keep working
            out_sids = self.bridge.stage_endpoints(specs4)
        else:
            out_sids = self.bridge.stage_endpoints(
                specs4, sids=sids, conferences=confs)
        self.key_installs += len(specs)
        self._staged.extend(out_sids)
        for sid, spec in zip(out_sids, specs):
            if spec[5] == "listener":
                self._listener_sids.add(int(sid))
            elif spec[5] == "speaker":
                self._bcast[spec[4]]["speakers"].add(int(sid))
            self.flight.record("key_install", tick=self.ticks(),
                               sid=sid, ssrc=spec[0])

    @property
    def key_installs_pending(self) -> int:
        return len(self._join_q) + len(self._staged)

    # ------------------------------------------------ placement moves

    def rebalance(self) -> int:
        """Execute the placer's rebalance plan as lifecycle events:
        each move relocates one whole conference's rows to the
        destination shard's range via `migrate_endpoints` (bit-exact
        SRTP/translator state, between ticks, behind the same drain
        barrier commits use).  A conference with members still queued
        or staged skips its move — moving half a conference would
        straddle it, the one invariant this module exists to hold."""
        if self.placer is None:
            return 0
        done = 0
        conf_of = getattr(self.bridge, "_conf_of", {})
        for mv in self.placer.plan_rebalance():
            members = [s for s, c in conf_of.items()
                       if c == mv.conf_id]
            sids = sorted(s for s in members
                          if s in self.bridge._ssrc_of
                          and s not in self.bridge._staged)
            if not sids or len(sids) != len(members):
                continue  # mid-install conference: move next window
            if any(j[4] == mv.conf_id for j in self._join_q):
                continue
            rows = self._free_rows_on(mv.dst, len(sids))
            if len(rows) < len(sids):
                continue  # destination range fragmented; replan later
            mapping = dict(zip(sids, rows))
            self._move_inflight = {"conf": int(mv.conf_id),
                                   "src": mv.src, "dst": mv.dst,
                                   "mapping": dict(mapping)}
            self.flight.record("placement_move_begin",
                               tick=self.ticks(), conf=mv.conf_id,
                               src=mv.src, dst=mv.dst, rows=len(sids))
            self.bridge.migrate_endpoints(mapping)
            self.placer.apply_move(mv)
            self._move_inflight = None
            self.moves_applied += 1
            done += 1
            self.flight.record("placement_move", tick=self.ticks(),
                               conf=mv.conf_id, src=mv.src, dst=mv.dst,
                               rows=len(sids))
            _log.info("placement_move", conf=mv.conf_id, src=mv.src,
                      dst=mv.dst, rows=len(sids))
        return done

    def _drop_conference_slices(self, conf) -> None:
        slo = getattr(self.supervisor, "slo", None) \
            if self.supervisor is not None else None
        if slo is None:
            return
        for spec in getattr(slo, "sliced", ()):
            if spec.label == "conference":
                slo.drop_slice(spec.name, str(conf))

    # ----------------------------------------------- bucketed warmup

    def _ensure_warm(self, population: int) -> None:
        """Grow the warm bucket to the next power of two covering
        `population` and pre-compile (off-tick, throwaway tables) every
        RTP row class that bucket's aggregate traffic can drive.  Shapes
        depend only on the size classes, so within a bucket admits and
        evicts compile NOTHING; crossing a boundary pays compile cost
        here, never inside a tick."""
        bucket = _next_pow2(max(self.cfg.min_bucket, population))
        if bucket <= self._warm_bucket:
            return
        max_rows = min(bucket * self.cfg.pkts_per_stream,
                       ROW_CLASSES[-1])
        # one class of headroom: fan-out rows are packets x receivers,
        # which can cross the class ABOVE the aggregate-traffic estimate
        # while the population is still inside this bucket — that first
        # crossing must not compile inside a tick
        above = [rc for rc in ROW_CLASSES if rc > max_rows]
        cover = above[0] if above else ROW_CLASSES[-1]
        want = [rc for rc in ROW_CLASSES
                if rc <= cover and rc not in self._warm_rows]
        if not want and ROW_CLASSES[0] not in self._warm_rows:
            want = [ROW_CLASSES[0]]
        for rc in want:
            self._warm_class(rc, rtp=True)
            self._warm_rows.add(rc)
        self._bound_fanout()
        self.flight.record("bucket_warm", tick=self.ticks(),
                           bucket=bucket, rows=sorted(self._warm_rows))
        _log.info("bucket_warm", bucket=bucket,
                  row_classes=sorted(self._warm_rows))
        self._warm_bucket = bucket

    def _bound_fanout(self) -> None:
        """The translator cuts a tick's fan-out rows into launches of
        the row classes warmed here, none over the largest of them
        (`launch_rows`; `ROW_CLASSES[-1]` once the ladder is whole): a
        backlog tick of a small population stays inside what its
        ladder compiled too.  Both ladders warm every class up to
        their cover, the fan-out's own classes between them too
        (`_warm_class`), so the classes under `launch_rows` are warm
        and `translator.plan_launches` may cut a tick by them."""
        tr = getattr(self.bridge, "translator", None)
        warmed = self._warm_rows | self._warm_lrows
        if warmed and hasattr(tr, "launch_rows"):
            tr.launch_rows = max(warmed)

    def _warm_class(self, rc: int, rtp: bool) -> None:
        """Compile every program one row class can drive: uplink RTP
        (`rtp`; listener rows have none), the fan-out expansion
        (packets x receivers has its own class-padded shape space) and
        control traffic (NACK/RR/SR ride the same zero-recompile
        discipline as media).  A translator that pads its rows here
        (`_pads_rows`: not the mesh's, whose lanes `_OwnerPlan` pads to
        `ROW_CLASSES`) pads them to `FANOUT_ROW_CLASSES`: the rung
        warms the fan-out at every such class over the rung below, so
        the 512-row fan-out joins the pool of the 1,024-row rung.

        Nothing here is timed (the GCM form is a rule of the shape,
        `context._gcm_form_grid`), so the RTP pair, the fan-out variants
        and the SRTCP pair share no program and compile side by side
        in one pool.  Within a pair rx goes before tx: the second table
        finds the programs warm."""
        rx, tx = self.bridge.rx_table, self.bridge.tx_table
        plen = self.cfg.warm_payload_len
        tr = getattr(self.bridge, "translator", None)
        thunks = []
        if rtp:
            thunks.append(lambda: (rx.warmup_rtp(rc, payload_len=plen),
                                   tx.warmup_rtp(rc, payload_len=plen)))
        if tr is not None and hasattr(tr, "fanout_warmups"):
            under = max((r for r in ROW_CLASSES if r < rc), default=0)
            for frc in (FANOUT_ROW_CLASSES
                        if getattr(tr, "_pads_rows", False) else (rc,)):
                if under < frc <= rc:
                    thunks += tr.fanout_warmups(frc, payload_len=plen)
        if hasattr(rx, "warmup_rtcp"):
            thunks.append(lambda: (rx.warmup_rtcp(rc),
                                   tx.warmup_rtcp(rc)))
        compile_concurrently(thunks)

    def _ensure_warm_listeners(self, population: int) -> None:
        """The fanout-only twin of `_ensure_warm`: listener rows never
        contribute uplink RTP, so their ladder skips the RTP row
        classes entirely and warms only the fan-out expansion (the
        shared bus re-protected once per listener leg) and RTCP shapes.
        A 4096-listener broadcast therefore warms a handful of fanout
        classes instead of dragging the RTP ladder to its ceiling —
        and listener churn inside a bucket still compiles nothing."""
        bucket = _next_pow2(max(self.cfg.min_bucket, population))
        if bucket <= self._warm_lbucket:
            return
        max_rows = min(bucket, ROW_CLASSES[-1])
        above = [rc for rc in ROW_CLASSES if rc > max_rows]
        cover = above[0] if above else ROW_CLASSES[-1]
        want = [rc for rc in ROW_CLASSES
                if rc <= cover and rc not in self._warm_lrows]
        if not want and ROW_CLASSES[0] not in self._warm_lrows:
            want = [ROW_CLASSES[0]]
        for rc in want:
            self._warm_class(rc, rtp=False)
            self._warm_lrows.add(rc)
        self._bound_fanout()
        self.flight.record("listener_bucket_warm", tick=self.ticks(),
                           bucket=bucket,
                           rows=sorted(self._warm_lrows))
        _log.info("listener_bucket_warm", bucket=bucket,
                  row_classes=sorted(self._warm_lrows))
        self._warm_lbucket = bucket

    # --------------------------------------------- data-path compile proof

    def tick_begin(self) -> None:
        self._tick_compiles0 = compile_stats().compile_events
        self._tick_feeds0 = (self.handshakes.table.feeds_total
                             if self.handshakes is not None else None)

    def tick_end(self) -> None:
        if self._tick_feeds0 is not None:
            # the other zero-on-the-tick-thread invariant: with the
            # deferred table no OpenSSL feed may run inside the tick
            d = self.handshakes.table.feeds_total - self._tick_feeds0
            self._tick_feeds0 = None
            if d > 0:
                self.tick_thread_handshake_feeds += d
                self.flight.record("tick_thread_handshake",
                                   tick=self.ticks(), n=d)
                _log.warn("tick_thread_handshake", n=d)
        if self._tick_compiles0 is None:
            return
        delta = compile_stats().compile_events - self._tick_compiles0
        self._tick_compiles0 = None
        if delta > 0:
            self.datapath_recompiles += delta
            self.flight.record("datapath_recompile",
                               tick=self.ticks(), n=delta)
            _log.warn("datapath_recompile", n=delta)

    def assert_datapath_clean(self) -> None:
        """The zero-recompile invariant, as an assertion: call after a
        soak window (once all shapes are warm) — raises if any compile
        event landed inside a tick."""
        if self.datapath_recompiles:
            raise AssertionError(
                f"{self.datapath_recompiles} compile event(s) landed on "
                f"the data path (inside tick windows)")

    # --------------------------------------------------- checkpointing

    def snapshot(self) -> dict:
        """In-flight admit state for the supervisor checkpoint: queued
        joins carry their keys (host-side only so far); staged sids'
        keys already ride the bridge snapshot.  With placement enabled
        the in-flight move (if any) rides too, so recovery can tell a
        completed move from a rolled-back one."""
        snap = {
            "queued": [tuple(j) for j in self._join_q],
            "staged": [(sid, self.bridge._ssrc_of.get(sid))
                       for sid in self._staged],
        }
        if self.handshakes is not None:
            # mid-handshake associations: keyless, so they ride as
            # their admission parameters and requeue after recover
            # (staged handshake rows already carry keys and ride the
            # "staged" list + bridge snapshot like any other admit)
            snap["handshakes"] = self.handshakes.snapshot()
        if self.placer is not None:
            snap["placement"] = {
                "n_shards": self.placer.n_shards,
                "move_inflight": self._move_inflight,
            }
        if self._bcast:
            snap["broadcast"] = {
                str(conf): {"home": self.placer.shard_of(conf),
                            "speakers": sorted(st["speakers"]),
                            "join_good": st["join_good"],
                            "join_bad": st["join_bad"]}
                for conf, st in self._bcast.items()}
            snap["listener_sids"] = sorted(self._listener_sids)
        return snap

    def _reconcile(self, pend: dict) -> None:
        """Post-`recover()` reconciliation: every in-flight admit either
        COMPLETES or ROLLS BACK — never a half state.

        * staged installs: the bridge snapshot captured their keys, SSRC
          mapping and table rows, and `restore()` routed them — the
          admit completes here (counted, flight-recorded).  A staged sid
          whose keys did NOT survive is rolled back: its remnants are
          removed and the slot freed.
        * queued joins: never touched the device; they re-enter the
          queue and install through the normal off-tick pipeline.
        """
        pl = pend.get("placement")
        if pl is not None and self.placer is None:
            self.enable_placement(int(pl["n_shards"]))
        for conf_s, st in pend.get("broadcast", {}).items():
            self._bcast[int(conf_s)] = {
                "speakers": {int(s) for s in st["speakers"]},
                "join_good": int(st["join_good"]),
                "join_bad": int(st["join_bad"]),
            }
        self._bcast_homes = {int(c): int(st["home"])
                             for c, st in
                             pend.get("broadcast", {}).items()
                             if st.get("home") is not None}
        self._listener_sids = {int(s)
                               for s in pend.get("listener_sids", [])}
        if self._bcast:
            self._register_conference_slo(0.999)
        for sid, ssrc in pend.get("staged", []):
            sid = int(sid)
            if (sid in self.bridge._ssrc_of
                    and sid in self.bridge._tx_keys):
                self.admits += 1
                self.flight.record("admit_commit", tick=self.ticks(),
                                   sid=sid, recovered=True)
            else:
                if sid in self.bridge._ssrc_of:
                    self.bridge.remove_endpoints([sid])
                self._listener_sids.discard(sid)
                for st in self._bcast.values():
                    st["speakers"].discard(sid)
                self.flight.record("admit_rollback", tick=self.ticks(),
                                   sid=sid, ssrc=ssrc)
                _log.info("admit_rollback", sid=sid)
        if self.placer is not None:
            self._reconcile_placement(pl or {})
        for spec in pend.get("queued", []):
            ssrc, rx, tx, name = spec[:4]
            conf = spec[4] if len(spec) > 4 else None
            role = spec[5] if len(spec) > 5 else None
            # solo (negative) conference keys re-derive from the ssrc
            self.request_join(ssrc, rx, tx, name=name,
                              conference=conf if (conf is None
                                                  or conf >= 0) else None,
                              role=role)
        for rec in pend.get("handshakes", []):
            # mid-handshake at the kill: the OpenSSL state died with
            # the process, so the association REQUEUES as a fresh row
            # (same ssrc, same bound 5-tuple when known) and the
            # peer's flight timers / signaling re-join drive the new
            # handshake — completed or requeued, never torn
            ssrc = rec.get("ssrc")
            if ssrc is None or self.handshakes is None:
                continue
            addr = rec.get("addr")
            ok, reason, retry = self.request_handshake(
                ssrc, role=rec.get("role", "server"),
                remote_fingerprint=rec.get("fingerprint"),
                cookie_exchange=bool(rec.get("cookie", False)),
                remote_addr=tuple(addr) if addr is not None else None)
            if ok:
                self.handshakes.requeued += 1
            self.flight.record("handshake_requeue", tick=self.ticks(),
                               ssrc=ssrc, accepted=ok,
                               reason=reason, retry_after_s=retry)
            _log.info("handshake_requeue", ssrc=ssrc, accepted=ok,
                      reason=reason)

    def _reconcile_placement(self, pl: dict) -> None:
        """Rebuild placement accounting from the RESTORED rows — the
        bridge's row layout is authoritative, never the placer's
        pre-kill beliefs.  `migrate_endpoints` is host-atomic between
        ticks, so a kill during a placement move restores either the
        fully-pre-move or fully-post-move layout; this proves which one
        landed (completed vs rolled back) and asserts the invariant
        placement exists for: no conference straddles a shard range."""
        members: Dict[int, list] = {}
        for sid, conf in self.bridge._conf_of.items():
            if sid in self.bridge._ssrc_of:
                members.setdefault(int(conf), []).append(int(sid))
        live = set(self.bridge._ssrc_of)
        self._listener_sids &= live
        for st in self._bcast.values():
            st["speakers"] &= live
        homes = getattr(self, "_bcast_homes", {})
        assignments = []
        broadcast = []
        for conf, sids in sorted(members.items()):
            if conf in self._bcast:
                # broadcast conferences legitimately straddle on their
                # LISTENER rows; only the speaker rows are pinned home
                speakers = self._bcast[conf]["speakers"]
                spk = [s for s in sids if s in speakers]
                spk_shards = {s // self._rows_per_shard for s in spk}
                home = homes.get(conf)
                if home is None:
                    home = (spk_shards.pop() if len(spk_shards) == 1
                            else 0)
                elif spk_shards - {home}:
                    raise AssertionError(
                        f"broadcast conference {conf} speaker rows "
                        f"off home shard {home} after recovery — "
                        f"torn placement")
                assignments.append((conf, home, len(spk)))
                per: Dict[int, int] = {}
                for s in sids:
                    if s not in speakers:
                        sh = s // self._rows_per_shard
                        per[sh] = per.get(sh, 0) + 1
                broadcast.append((conf, per))
                continue
            shards = {s // self._rows_per_shard for s in sids}
            if len(shards) != 1:
                raise AssertionError(
                    f"conference {conf} straddles shards {sorted(shards)} "
                    f"after recovery — torn placement")
            assignments.append((conf, shards.pop(), len(sids)))
        # a declared broadcast conference with no live members yet must
        # still hold its home-shard reservation across recovery
        for conf, home in sorted(homes.items()):
            if conf not in members:
                assignments.append((conf, home, 0))
                broadcast.append((conf, {}))
        self.placer.rebuild(assignments, broadcast=broadcast)
        self._bcast_homes = {}
        mv = pl.get("move_inflight")
        if mv:
            conf = int(mv["conf"])
            landed = self.placer.shard_of(conf)
            outcome = ("completed" if landed == int(mv["dst"])
                       else "rolled_back")
            if outcome == "completed":
                self.moves_applied += 1
            self.flight.record("placement_move_recovered",
                               tick=self.ticks(), conf=conf,
                               outcome=outcome, src=mv["src"],
                               dst=mv["dst"])
            _log.info("placement_move_recovered", conf=conf,
                      outcome=outcome)

    # --------------------------------------------------- observability

    def register_metrics(self, registry, prefix: str = "lifecycle") -> None:
        registry.register_counters(self, (
            ("admits", "streams admitted (committed live)"),
            ("evicts", "streams evicted by the lifecycle plane"),
            ("key_installs", "streams whose keys installed off-tick"),
            ("datapath_recompiles",
             "compile events inside tick windows (invariant: 0)"),
            ("moves_applied",
             "placement rebalance moves executed at the barrier"),
        ), prefix=prefix)
        registry.register_scalar(
            f"{prefix}_key_installs_pending",
            lambda: self.key_installs_pending,
            help_="joins queued or staged, not yet committed")
        registry.register_scalar(
            f"{prefix}_warm_bucket", lambda: self._warm_bucket,
            help_="population bucket whose shapes are pre-compiled")
        registry.register_multi(
            f"{prefix}_admit_rejected", self._rejected_samples,
            help_="admissions refused, by typed reason", kind="counter")
        registry.register_multi(
            "admit_roles_total",
            lambda: [({"role": r}, float(c))
                     for r, c in sorted(self.admit_roles.items())],
            help_="joins queued into broadcast conferences, by role",
            kind="counter")
        registry.register_scalar(
            "bcast_conferences", lambda: float(len(self._bcast)),
            help_="broadcast conferences declared (by a caller or by "
                  "the visitors rule)")
        registry.register_scalar(
            "bcast_speakers",
            lambda: float(sum(len(st["speakers"])
                              for st in self._bcast.values())),
            help_="speaker rows staged or live across all broadcast "
                  "conferences")
        registry.register_scalar(
            "bcast_listeners", lambda: float(len(self._listener_sids)),
            help_="fanout-only listener rows live across all "
                  "broadcast conferences")
        registry.register_scalar(
            "speaker_promotions_total",
            lambda: float(self.speaker_promotions),
            help_="listener-to-speaker role flips applied at the "
                  "commit barrier", kind="counter")
        # keystream pregeneration cache (transform/srtp/keystream.py):
        # summed across the rx/tx tables' caches; all zero until
        # enable_keystream_cache is called on a GCM bridge
        registry.register_scalar(
            "srtp_keystream_hits",
            lambda: float(sum(c.hits for c in self._keystream_caches())),
            help_="packets served from the pregenerated keystream "
                  "window (fast-path protect/unprotect)",
            kind="counter")
        registry.register_scalar(
            "srtp_keystream_misses",
            lambda: float(sum(c.misses
                              for c in self._keystream_caches())),
            help_="packets that fell back to the stock GCM path "
                  "(window miss, reorder, rekey, non-uniform batch)",
            kind="counter")
        registry.register_scalar(
            "srtp_keystream_evictions",
            lambda: float(sum(c.evictions
                              for c in self._keystream_caches())),
            help_="pregenerated keystream slots discarded unused "
                  "(window slide, rekey invalidation, SSRC change)",
            kind="counter")
        registry.register_scalar(
            "srtp_keystream_fill_seconds",
            lambda: float(sum(c.fill_seconds
                              for c in self._keystream_caches())),
            help_="cumulative off-tick wall time spent generating "
                  "keystream (the cache-fill phase)", kind="counter")
        # handshake plane (HandshakeQueue + deferred association
        # table): all read through self.handshakes so direct-keyed
        # bridges export zeros instead of raising
        registry.register_scalar(
            "handshake_queue_depth",
            lambda: float(self.handshakes.depth)
            if self.handshakes is not None else 0.0,
            help_="queued handshake datagrams + pending associations "
                  "awaiting the off-tick drain")
        registry.register_scalar(
            "dtls_handshakes_active",
            lambda: float(len(self.handshakes.table.pending))
            if self.handshakes is not None else 0.0,
            help_="DTLS associations mid-handshake (allocated, "
                  "keyless rows)")
        registry.register_scalar(
            "dtls_retransmits_total",
            lambda: float(self.handshakes.table.retransmits_total)
            if self.handshakes is not None else 0.0,
            help_="expired-flight datagrams resent by the batched "
                  "retransmission pass", kind="counter")
        registry.register_scalar(
            "dtls_feeds_total",
            lambda: float(self.handshakes.table.feeds_total)
            if self.handshakes is not None else 0.0,
            help_="handshake datagrams fed to endpoints (all on the "
                  "off-tick drain in deferred mode)", kind="counter")
        registry.register_scalar(
            "dtls_inbox_dropped",
            lambda: float(self.handshakes.table.inbox_dropped)
            if self.handshakes is not None else 0.0,
            help_="handshake datagrams dropped at the deferred "
                  "table's inbox bound (admission refuses first; this "
                  "staying near 0 proves the bound is generous)",
            kind="counter")
        registry.register_scalar(
            "dtls_handshakes_completed",
            lambda: float(self.handshakes.completed)
            if self.handshakes is not None else 0.0,
            help_="handshakes whose keys landed via the staged "
                  "commit barrier", kind="counter")
        registry.register_scalar(
            "handshake_off_tick_seconds",
            lambda: float(self.handshakes.off_tick_seconds)
            if self.handshakes is not None else 0.0,
            help_="cumulative between-ticks wall time in the "
                  "handshake drain (OpenSSL + flight resends)",
            kind="counter")
        registry.register_scalar(
            "handshake_tick_thread_feeds",
            lambda: float(self.tick_thread_handshake_feeds),
            help_="OpenSSL feed() calls observed inside tick windows "
                  "(invariant: 0)", kind="counter")

    def _rejected_samples(self):
        return [({"reason": r}, float(c))
                for r, c in sorted(self.admit_rejected.items())]
