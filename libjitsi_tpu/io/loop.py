"""The host I/O loop: UDP batches in, transform chains, UDP batches out.

This is the glue the reference spreads across
`RTPConnectorInputStream/OutputStream` threads and
`TransformUDPOutputStream` (SURVEY §2.2 "connector-level streams"):
one loop per engine that (1) drains a recvmmsg batching window,
(2) demuxes DTLS from media by first byte, (3) maps SSRCs to stream
rows, (4) runs the shared reverse chain once for the WHOLE batch,
(5) hands decrypted media to a sink (mixer / SFU translator), and
(6) protects + sends whatever the sinks queued — two device launches
per tick regardless of stream count.

Latency budget: the batching window (recv timeout) + one device round
trip; SURVEY §7 step 4 sizes the window ≤500 µs for the 2 ms p99 target.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from libjitsi_tpu.control.dtls import is_dtls
from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.io.pcap import PcapWriter
from libjitsi_tpu.io.udp import UdpEngine
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.utils.flight import FlightRecorder
from libjitsi_tpu.utils.logging import get_logger
from libjitsi_tpu.utils.metrics import MetricsRegistry
from libjitsi_tpu.utils.perf import LoopPerf
from libjitsi_tpu.utils.tracing import PipelineTracer

_log = get_logger("io.loop")

#: wire datagram sizes: 64B keepalives up to jumbo-ish video bursts
PACKET_SIZE_BUCKETS = (64, 128, 256, 512, 768, 1024, 1280, 1500)

#: end-to-end packet journey (ingress arrival -> egress send), seconds;
#: 0.02 is the default tick/ptime budget the journey_p99 SLO keys on.
#: The tail buckets past 0.1 exist for the cross-bridge hop children
#: (PR 19): a trunk hop legitimately spans scheduler + wire time well
#: beyond one tick, and the soak's cross-hop p99 gate needs the tail
#: resolved instead of collapsed into +Inf.
JOURNEY_BUCKETS = (0.0005, 0.001, 0.002, 0.005, 0.01, 0.02, 0.05,
                   0.1, 0.25, 1.0, 5.0)


def _is_rtcp(data: np.ndarray, length: np.ndarray) -> np.ndarray:
    """RFC 5761 demux: full second byte in [192, 223] marks RTCP on a
    muxed port (RTCP PTs 200..207 occupy the M-bit+PT bit positions)."""
    return (length >= 8) & (data[:, 1] >= 192) & (data[:, 1] <= 223)


class MediaLoop:
    """One engine's receive/transmit tick loop.

    Wire-in handlers:
      on_dtls(datagram, addr) -> [reply datagrams]
      on_media(batch, ok_mask) -> optional PacketBatch to send
      on_rtcp(batch, ok_mask) -> optional list[(bytes, addr)]
    Addresses: (ip_u32, port) per row; senders' addresses are learned
    per stream row (latching, like the reference's target discovery).
    """

    def __init__(self, engine: UdpEngine, registry,
                 on_media: Optional[Callable] = None,
                 on_rtcp: Optional[Callable] = None,
                 on_dtls: Optional[Callable] = None,
                 chain=None,
                 pcap_tap: Optional[PcapWriter] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 recv_window_ms: int = 1,
                 pipelined: bool = False,
                 pipeline_depth: int = 1,
                 tracer: Optional[PipelineTracer] = None,
                 flight: Optional[FlightRecorder] = None):
        self.engine = engine
        # drain rings: the primary engine plus any SO_REUSEPORT
        # siblings attached via `add_ring` — each tick drains all of
        # them (primary blocks for the batching window, siblings poll)
        # and runs every non-empty batch through the same ingest body
        self.rings: List[UdpEngine] = [engine]
        # parallel to `rings`: a sink callable per ring, or None for
        # the RTP ingest path.  Sink rings (e.g. a cascade trunk
        # socket) drain with tick cadence in the same ingress span but
        # hand their datagrams to the sink — they are not RTP
        self.ring_sinks: List[Optional[Callable]] = [None]
        self.registry = registry
        self.chain = chain
        # pipeline_depth: how many ticks' reverse-chain work may be in
        # flight at once.  1 = the classic serial tick (recv → decrypt →
        # reply, all within one tick).  Depth D>1 deep-pipelines the
        # receive path: tick N's auth/decrypt is DISPATCHED only and
        # materializes D-1 ticks later, so the device round trip of
        # tick N overlaps the recv windows of ticks N+1..N+D-1 — and
        # ingress lands in zero-copy recv-arena views (io/udp.py) that
        # stay pinned until materialization.  `drain()` is the barrier
        # that collapses the pipeline for checkpoint / lifecycle commit
        # points.  Depth implies pipelined replies.
        self.pipeline_depth = max(1, int(pipeline_depth))
        # pipelined: sink replies are DISPATCHED (device launch only)
        # and their bytes flush at the top of the next tick, so the
        # protect launch overlaps the next recv window instead of
        # serializing with it (SURVEY §7 step 4's budget).  Costs one
        # recv-window of latency on the reply path.
        self.pipelined = pipelined or self.pipeline_depth > 1
        # (pending, mask, journey origin, dispatch tick)
        self._inflight: List[Tuple[object, np.ndarray, tuple, int]] = []
        # in-flight reverse-chain (receive) dispatches, FIFO by tick:
        # dicts of {pend, tick, origin, ats, token, n}
        self._rx_inflight: List[dict] = []
        # kernel arrival stamps ride along when the engine has them;
        # after each tick, `last_rtp_arrival_ns` aligns row-for-row with
        # the batch handed to on_media (BWE wants skb-receive times,
        # not userspace-scheduler-jittered ones)
        self.use_kernel_ts = bool(getattr(engine, "kernel_timestamps",
                                          False))
        self.last_rtp_arrival_ns: Optional[np.ndarray] = None
        self.on_media = on_media
        self.on_rtcp = on_rtcp
        self.on_dtls = on_dtls
        self.pcap = pcap_tap
        self.metrics = metrics or MetricsRegistry()
        # stage spans (ingress/reverse_chain/forward_chain/egress) feed
        # per-stage rings + the supervisor's per-tick budget ledger;
        # bridges share this tracer so their stages land in one ledger
        self.tracer = tracer if tracer is not None else \
            PipelineTracer(self.metrics)
        # optional flight recorder: per-stream header samples + drop
        # events for post-mortems (attached by the supervisor)
        self.flight = flight
        self.pkt_size_hist = self.metrics.histogram(
            "packet_size_bytes", PACKET_SIZE_BUCKETS,
            help_="received datagram sizes")
        # journey tracing: every ingress batch is stamped with a
        # monotonic trace id + arrival time; egress observes the
        # end-to-end latency with an OpenMetrics exemplar carrying the
        # trace id, so a tail-latency bucket links straight to the
        # FlightRecorder `hdr` events recorded under the same trace.
        # One family, labeled by hop: this loop's own egress fills the
        # "local" child; a cascaded peer's ingest fills "b<i>-b<j>"
        # children from the trunk trace extension (mesh/cascade.py),
        # so one histogram tells the whole cross-bridge story
        self.journey_vec = self.metrics.histogram_vec(
            "packet_journey_seconds", JOURNEY_BUCKETS, "hop",
            help_="ingress-arrival to egress-send packet latency",
            exemplars=True)
        self.journey_hist = self.journey_vec.labels("local")
        self.trace_id = 0
        self._trace_t0: Optional[float] = None
        self.recv_window_ms = recv_window_ms
        # learned (ip, port) per stream row (latched from last packet)
        self.addr_ip = np.zeros(registry.capacity, dtype=np.uint32)
        self.addr_port = np.zeros(registry.capacity, dtype=np.uint16)
        # streams on hold (keys not yet installed): their RTP is queued
        # raw, bounded, and replayed through the chain on release —
        # media racing the DTLS Finished flight must not be dropped.
        # Reference: DtlsPacketTransformer's pre-handshake queue.
        self._hold_mask = np.zeros(registry.capacity, dtype=bool)
        self._hold_q: Dict[int, "deque"] = {}
        # supervisor-controlled inbound drop mask (stream quarantine /
        # overload shedding, see service/supervisor.py): rows for masked
        # streams are discarded before any state is touched
        self.inbound_drop = np.zeros(registry.capacity, dtype=bool)
        self.inbound_dropped = np.zeros(registry.capacity, dtype=np.int64)
        self.inbound_dropped_total = 0
        # fanout-only rows (broadcast listeners): uplink RTP is dropped
        # — the row only RECEIVES the shared bus — but RTCP (receiver
        # reports, NACKs) still flows, which is why this is a separate
        # mask from `inbound_drop` (quarantine silences both)
        self.fanout_only = np.zeros(registry.capacity, dtype=bool)
        self._fanout_only_n = 0
        self.fanout_rtp_dropped = 0
        self.metrics.register_scalar(
            "loop_fanout_rtp_dropped",
            lambda: self.fanout_rtp_dropped,
            help_="uplink RTP packets dropped on fanout-only "
                  "(broadcast listener) rows", kind="counter")
        # unknown-SSRC accounting: the warning is interval-suppressed
        # (at most one log line per `unknown_warn_interval` ticks, with
        # the suppressed count carried on the next line) — a flood of
        # unmapped senders must not flood the log
        self.unknown_ssrc_dropped = 0
        self.unknown_warn_interval = 100
        self._unknown_suppressed = 0
        self._unknown_last_warn: Optional[int] = None
        self.metrics.register_scalar(
            "loop_unknown_ssrc_dropped",
            lambda: self.unknown_ssrc_dropped,
            help_="packets dropped for unmapped SSRCs", kind="counter")
        self.metrics.register_scalar(
            "loop_unknown_ssrc_warn_suppressed",
            lambda: self._unknown_suppressed,
            help_="unknown-SSRC warnings suppressed since the last "
                  "logged one")
        # shard-major dispatch (0 = off): when conference-affinity
        # placement is enabled, rows for one shard occupy one
        # contiguous block of stream ids, so a stable sort of the RTP
        # batch by `sid // rows_per_shard` groups each device's rows
        # together — the layout the mesh table's affine owner-plan
        # fast path needs to skip the argsort/scatter permutation
        self.rows_per_shard = 0
        self.shard_major_reorders = 0
        self.metrics.register_scalar(
            "loop_shard_major_reorders",
            lambda: self.shard_major_reorders,
            help_="RTP batches re-sorted into shard-major order before "
                  "dispatch", kind="counter")
        self.ticks = 0
        self.rx_packets = 0
        self.tx_packets = 0
        # syscall-count telemetry: batches that entered the kernel vs
        # completions reaped ring-side, summed across drain rings
        # (delta-accumulated each tick from the engines' own counters,
        # so attaching/closing rings never skews the totals)
        self.ingest_syscalls = 0
        self.ingest_ring_reaps = 0
        self._ingest_enters_seen = 0
        self._ingest_reaps_seen = 0
        self.metrics.register_scalar(
            "loop_ingest_syscalls",
            lambda: self.ingest_syscalls,
            help_="ingest/egress batches that entered the kernel "
                  "(recvmmsg/sendmmsg calls + io_uring_enter syscalls)",
            kind="counter")
        self.metrics.register_scalar(
            "loop_ingest_ring_reaps",
            lambda: self.ingest_ring_reaps,
            help_="io_uring completions reaped ring-side without "
                  "entering the kernel", kind="counter")
        self.metrics.register_scalar(
            "loop_engine_io_uring",
            lambda: 1.0 if self.engine_mode == "io_uring" else 0.0,
            help_="1 when the primary drain ring runs the io_uring "
                  "engine, 0 for recvmmsg — perf numbers must never be "
                  "compared across modes silently")
        self.metrics.register_scalar(
            "loop_ingest_rings", lambda: float(len(self.rings)),
            help_="attached SO_REUSEPORT drain rings")
        # age (in ticks) of the oldest un-flushed async dispatch; >1
        # means protected bytes sat across a full tick — pipeline depth
        self.dispatch_inflight_ticks = 0
        # the tick's host/device phase split (read off the tracer's
        # spans) and the transfer byte counters
        self.perf = LoopPerf(self.tracer, self.metrics,
                             inflight_fn=self._inflight_age)

    # ------------------------------------------------------ drain rings
    @property
    def engine_mode(self) -> str:
        """Primary drain ring's engine mode ("io_uring"/"recvmmsg")."""
        return getattr(self.engine, "engine_mode", "recvmmsg")

    def add_ring(self, engine: UdpEngine,
                 sink: Optional[Callable] = None) -> None:
        """Attach an extra drain ring: an SO_REUSEPORT sibling engine
        on the same port, kernel-sharded by flow hash.  Each tick the
        primary ring blocks for the batching window, then siblings
        drain non-blocking (their packets arrived during that wait).
        When placement makes rings shard-aligned, each ring's batch is
        already shard-major and the `enable_shard_major` sort becomes a
        no-op (its sortedness check sees monotone shard ids).

        With `sink`, the ring is a CONTROL ring (a cascade trunk
        socket): it drains on the same tick cadence but its datagrams
        go to `sink(batch, sip, sport)` — never the RTP ingest body —
        with copy semantics (a sink may hold bytes indefinitely, so
        no arena views)."""
        self.rings.append(engine)
        self.ring_sinks.append(sink)

    def _sync_ingest_counters(self) -> None:
        """Fold the rings' enter/reap counters into the loop's per-tick
        telemetry (delta-accumulation: ring attach/close can't skew)."""
        enters = reaps = 0
        for eng in self.rings:
            enters += int(getattr(eng, "syscall_enters", 0))
            reaps += int(getattr(eng, "ring_reaps", 0))
        self.ingest_syscalls += enters - self._ingest_enters_seen
        self.ingest_ring_reaps += reaps - self._ingest_reaps_seen
        self._ingest_enters_seen = enters
        self._ingest_reaps_seen = reaps

    # ---------------------------------------------------- dispatch order
    def enable_shard_major(self, rows_per_shard: int) -> None:
        """Sort each RTP batch into shard-major row order before the
        reverse chain.  Only meaningful with conference-affinity
        placement (contiguous per-shard sid ranges); packet order
        within a shard is preserved (stable sort), and RTP rows are
        independent, so semantics are unchanged."""
        if rows_per_shard <= 0:
            raise ValueError("rows_per_shard must be positive")
        self.rows_per_shard = int(rows_per_shard)

    def set_fanout_only(self, sid: int, on: bool = True) -> None:
        """Mark/unmark a row fanout-only (broadcast listener / speaker
        role flip).  Flipped only between ticks by the lifecycle commit
        barrier — a promotion takes effect for whole ticks, never mid
        batch."""
        sid = int(sid)
        if bool(self.fanout_only[sid]) != bool(on):
            self.fanout_only[sid] = bool(on)
            self._fanout_only_n += 1 if on else -1

    # ------------------------------------------------------------- holds
    def hold_stream(self, sid: int, max_packets: int = 64) -> None:
        from collections import deque

        self._hold_mask[sid] = True
        self._hold_q[sid] = deque(maxlen=max_packets)

    def discard_stream(self, sid: int) -> None:
        """Drop a held stream's queue without replay (endpoint left)."""
        self._hold_mask[sid] = False
        self._hold_q.pop(sid, None)

    def release_stream(self, sid: int) -> int:
        """Replay a held stream's queued packets through the normal
        receive path (chain + on_media); returns the packet count."""
        self._hold_mask[sid] = False
        q = self._hold_q.pop(sid, None)
        if not q:
            return 0
        self.last_rtp_arrival_ns = None      # no kernel stamps for these
        batch = PacketBatch.from_payloads(list(q), stream=[sid] * len(q))
        if self.chain is not None:
            batch, ok = self.chain.rtp_transformer.reverse_transform(
                batch)
        else:
            ok = np.ones(batch.batch_size, bool)
        if self.on_media is not None:
            reply = self.on_media(batch, ok)
            if reply is not None:
                # a release mid-flood must not serialize the tick: the
                # pipelined loop dispatches the replayed replies like any
                # other and flushes them on the next tick
                if self.pipelined:
                    self.send_media_async(reply)
                else:
                    self.send_media(reply)
        return len(q)

    # -------------------------------------------------------------- tick
    def tick(self) -> int:
        """One batching window; returns packets processed."""
        # every span of this tick carries the id its batch is about to
        # get (`trace_id` steps once per tick, after the recv window)
        self.tracer.tick = self.trace_id + 1
        self.perf.begin_tick()
        try:
            return self._tick_inner()
        finally:
            self._sync_ingest_counters()
            self.perf.end_tick()

    def _recv_ring(self, eng, window_ms, use_view):
        """One ring's batching window -> (batch, sip, sport, ats, token)."""
        if self.use_kernel_ts:
            recv = (eng.recv_batch_ts_view if use_view
                    else eng.recv_batch_ts)
            batch, sip, sport, ats = recv(window_ms)
        else:
            recv = (eng.recv_batch_view if use_view
                    else eng.recv_batch)
            batch, sip, sport = recv(window_ms)
            ats = None
        return batch, sip, sport, ats, getattr(batch, "arena_token", None)

    def _tick_inner(self) -> int:
        # re-established below only when this tick carries RTP rows; a
        # stale previous-tick value must never masquerade as fresh
        self.last_rtp_arrival_ns = None
        deep = (self.pipeline_depth > 1 and self.chain is not None
                and hasattr(self.chain.rtp_transformer,
                            "reverse_transform_async"))
        # deep pipeline: ingress lands in a zero-copy arena view, pinned
        # until the tick's reverse pending materializes; classic depth-1
        # keeps copy semantics (sinks may hold the batch indefinitely)
        use_view = deep and all(hasattr(e, "recv_batch_view")
                                for e in self.rings)
        ring_batches = []
        with self.tracer.span("ingress"):
            for k, eng in enumerate(self.rings):
                if self.ring_sinks[k] is not None:
                    continue             # control ring: drained below
                # primary ring pays the batching window; sibling
                # rings poll — their packets arrived during the wait
                ring_batches.append((eng, self._recv_ring(
                    eng, self.recv_window_ms if k == 0 else 0,
                    use_view)))
            # control rings (cascade trunk sockets): non-blocking copy
            # drain in the same ingress span; frames go to the sink,
            # never the RTP body, and don't count as RTP ingest
            for k, eng in enumerate(self.rings):
                sink = self.ring_sinks[k]
                if sink is None:
                    continue
                cb, csip, csport = eng.recv_batch(0)
                if cb.batch_size:
                    sink(cb, csip, csport)
        # arrival stamp: the batching window just closed — everything
        # this tick sends is measured against this instant (per-batch
        # journey; rows within one batch share the stamp)
        self.trace_id += 1
        self._trace_t0 = time.perf_counter()
        n = sum(rb[1][0].batch_size for rb in ring_batches)
        self.ticks += 1
        self._note_inflight_age()
        # the recv window just elapsed: anything dispatched on EARLIER
        # ticks has had a full socket-wait of device time.  Egress bytes
        # first (lowest journey latency), then reverse pendings that
        # reached their pipeline depth — whose replies dispatch now and
        # flush at the top of the next tick.
        if self._inflight:
            self.flush_sends()
        if self._rx_inflight:
            self._materialize_rx(due_only=True)
        if n == 0:
            # idle window: nothing to overlap with — collapse the
            # pipeline instead of parking bytes for another tick
            if self._rx_inflight or self._inflight:
                self.drain()
            return 0
        self.rx_packets += n
        for eng, (batch, sip, sport, ats, token) in ring_batches:
            if batch.batch_size:
                self._ingest_batch(eng, batch, sip, sport, ats, token,
                                   deep)
        return n

    def _ingest_batch(self, eng, batch, sip, sport, ats, token,
                      deep) -> None:
        """Run ONE ring's non-empty batch through the tick body: DTLS
        split, rtcp-mux demux, holds/fanout/shed masks, shard-major
        reorder, reverse-chain dispatch.  Shared by every drain ring;
        DTLS replies and arena pins stay with the ring they came in on."""
        with self.tracer.span("demux", rows=batch.batch_size) as sp:
            dropped0 = self.fanout_rtp_dropped
            split = self._demux_batch(eng, batch, sip, sport, ats)
            if self._fanout_only_n:
                # a bridge with listener rows says how many uplink rows
                # the mask (not the router) silenced this tick
                sp.note(fanout_only_dropped=self.fanout_rtp_dropped
                        - dropped0)
        if split is None:
            self._release_token(token, eng)
            return
        sub, ats, rtp_rows, rtcp_rows, reordered = split
        self._dispatch_rows(eng, sub, ats, token, deep, rtp_rows,
                            rtcp_rows, reordered)

    def _demux_batch(self, eng, batch, sip, sport, ats):
        """The `demux` stage: everything between the socket and the
        reverse chain.  Returns None for a batch with no media row,
        else (sub, ats, rtp_rows, rtcp_rows, reordered)."""
        n = batch.batch_size
        self.pkt_size_hist.observe_array(np.asarray(batch.length)[:n])
        if self.pcap is not None:
            self.pcap.write_batch(batch)

        # 1. split DTLS (first byte 20..63) from media — host, cheap;
        # the no-DTLS fast path keeps `sub` a view of the recv batch
        first = batch.data[:, 0]
        is_dtls_row = (first >= 20) & (first <= 63)
        if is_dtls_row.any():
            dtls_rows = np.nonzero(is_dtls_row)[0]
            if self.on_dtls is not None:
                # deferred association tables enqueue and reply on the
                # between-ticks drain (replies == []); inline tables'
                # replies gather into ONE batch per peer address
                # instead of one send_batch per datagram
                by_addr: dict = {}
                for i in dtls_rows:
                    addr = (int(sip[i]), int(sport[i]))
                    replies = self.on_dtls(batch.to_bytes(int(i)), addr)
                    if replies:
                        by_addr.setdefault(addr, []).extend(replies)
                for addr, reps in by_addr.items():
                    out = PacketBatch.from_payloads(reps,
                                                    batch.capacity)
                    eng.send_batch(out, addr[0], addr[1])
            media_rows = np.nonzero(~is_dtls_row)[0]
            if len(media_rows) == 0:
                return None
            sub = PacketBatch(batch.data[media_rows],  # jitlint: disable=hotpath-alloc
                              np.asarray(batch.length)[media_rows],
                              batch.stream[media_rows])
            sip, sport = sip[media_rows], sport[media_rows]
            if ats is not None:
                ats = ats[media_rows]
        else:
            sub = batch

        # 2. RTCP vs RTP split (rtcp-mux), then ssrc -> stream row
        # (the SSRC field sits at different offsets in the two formats)
        rtcp_mask = _is_rtcp(sub.data, np.asarray(sub.length))
        any_rtcp = rtcp_mask.any()
        sids = np.full(sub.batch_size, -1, dtype=np.int64)
        if not any_rtcp:
            sids[:] = self.registry.demux(sub)
        else:
            rtp_sel = np.nonzero(~rtcp_mask)[0]
            if len(rtp_sel):
                rtp_sub = PacketBatch(sub.data[rtp_sel],  # jitlint: disable=hotpath-alloc
                                      np.asarray(sub.length)[rtp_sel],
                                      sub.stream[rtp_sel])
                sids[rtp_sel] = self.registry.demux(rtp_sub)
            rtcp_sel = np.nonzero(rtcp_mask)[0]
            if len(rtcp_sel):
                rtcp_sub = PacketBatch(sub.data[rtcp_sel],  # jitlint: disable=hotpath-alloc
                                       np.asarray(sub.length)[rtcp_sel],
                                       sub.stream[rtcp_sel])
                sids[rtcp_sel] = self.registry.demux_rtcp(rtcp_sub)
        sub.stream[:] = sids
        known = sids >= 0
        if not known.all():
            self._warn_unknown_ssrc(int((~known).sum()))
        if self.inbound_drop.any():
            # quarantined / shed streams are dropped BEFORE the address
            # latch below, so a quarantined sender's packets can never
            # redirect the row's return media mid-ban
            shed = known & self.inbound_drop[
                np.clip(sids, 0, len(self.inbound_drop) - 1)]
            if shed.any():
                np.add.at(self.inbound_dropped, sids[shed], 1)
                self.inbound_dropped_total += int(shed.sum())
                known &= ~shed
        self.addr_ip[sids[known]] = sip[known]
        self.addr_port[sids[known]] = sport[known]

        rtp_rows = np.nonzero(~rtcp_mask & known)[0]
        rtcp_rows = np.nonzero(rtcp_mask & known)[0]

        # held streams (pre-handshake): queue raw RTP, drop their RTCP
        if len(rtp_rows) and self._hold_q:
            held = self._hold_mask[sids[rtp_rows]]
            if held.any():
                lens = np.asarray(sub.length)
                for i in rtp_rows[held]:
                    self._hold_q[int(sids[i])].append(
                        sub.data[i, :lens[i]].tobytes())
                rtp_rows = rtp_rows[~held]
        if len(rtcp_rows) and self._hold_q:
            rtcp_rows = rtcp_rows[~self._hold_mask[sids[rtcp_rows]]]

        # fanout-only rows: drop listener uplink RTP (their media never
        # enters the mix); RTCP rows pass untouched so loss recovery on
        # the downlink keeps working
        if len(rtp_rows) and self._fanout_only_n:
            fo = self.fanout_only[sids[rtp_rows]]
            if fo.any():
                self.fanout_rtp_dropped += int(fo.sum())
                rtp_rows = rtp_rows[~fo]

        # shard-major dispatch seam: group the batch by owning shard so
        # the mesh table's affine fast path can place rows with a
        # reshape instead of a gather/scatter permutation
        reordered = False
        if self.rows_per_shard and len(rtp_rows) > 1:
            shard = sids[rtp_rows] // self.rows_per_shard
            if np.any(shard[:-1] > shard[1:]):
                rtp_rows = rtp_rows[np.argsort(shard, kind="stable")]
                self.shard_major_reorders += 1
                reordered = True
        return sub, ats, rtp_rows, rtcp_rows, reordered

    def _dispatch_rows(self, eng, sub, ats, token, deep, rtp_rows,
                       rtcp_rows, reordered) -> None:
        with self.tracer.span("reverse_chain"):
            if len(rtp_rows):
                if len(rtp_rows) == sub.batch_size and not reordered:
                    rtp = sub     # all-RTP fast path: still a view
                    ats_sel = ats
                else:
                    rtp = PacketBatch(sub.data[rtp_rows],  # jitlint: disable=hotpath-alloc
                                      np.asarray(sub.length)[rtp_rows],
                                      sub.stream[rtp_rows])
                    ats_sel = ats[rtp_rows] if ats is not None else None
                if self.flight is not None:
                    # sample RTP headers (seq at bytes 2..3) into the
                    # per-stream flight rings — vectorized field pulls,
                    # bounded rows per stream inside record_headers
                    d = rtp.data
                    seqs = ((d[:, 2].astype(np.int64) << 8) | d[:, 3])
                    self.flight.record_headers(
                        rtp.stream, seqs, np.asarray(rtp.length),
                        tick=self.ticks, trace=self.trace_id)
                if deep:
                    # dispatch-only: auth/decrypt overlaps the NEXT
                    # recv window(s); the arena pin travels with the
                    # pending and is released at materialization
                    self.perf.note_h2d(rtp.data.nbytes +
                                       np.asarray(rtp.length).nbytes)
                    # the serialization barrier (previous window's
                    # replay-state commit) is a wait on already-
                    # dispatched device auth work — run it here so the
                    # dispatch span below measures only the new launch
                    commit = getattr(self.chain.rtp_transformer,
                                     "commit_inflight", None)
                    if commit is not None:
                        with self.tracer.span("chain_device"):
                            commit()
                    with self.tracer.span("chain_dispatch"):
                        pend = (self.chain.rtp_transformer
                                .reverse_transform_async(rtp))
                    self._rx_inflight.append({
                        "pend": pend, "tick": self.ticks,
                        "origin": self.journey_origin(),
                        "ats": ats_sel, "token": token, "eng": eng,
                        "n": rtp.batch_size})
                    token = None          # ownership moved to the entry
                else:
                    self.last_rtp_arrival_ns = ats_sel
                    if self.chain is not None:
                        self.perf.note_h2d(rtp.data.nbytes +
                                           np.asarray(rtp.length).nbytes)
                        # the sync reverse call blends dispatch + compute
                        # + d2h; attributed wholesale to device_compute
                        # (the async seams split them properly)
                        with self.tracer.span("chain_device"):
                            rtp, ok = (self.chain.rtp_transformer
                                       .reverse_transform(rtp))
                        self.perf.note_d2h(rtp.data.nbytes)
                        if not ok.all():
                            _log.warn("reverse_chain_drop",
                                      count=int((~ok).sum()),
                                      tick=self.ticks)
                    else:
                        ok = np.ones(rtp.batch_size, bool)
                    if self.on_media is not None:
                        reply = self.on_media(rtp, ok)
                        if reply is not None:
                            if self.pipelined:
                                self.send_media_async(reply)
                            else:
                                self.send_media(reply)
            if len(rtcp_rows) and self.on_rtcp is not None:
                rb = PacketBatch(sub.data[rtcp_rows],  # jitlint: disable=hotpath-alloc
                                 np.asarray(sub.length)[rtcp_rows],
                                 sub.stream[rtcp_rows])
                if self.chain is not None and \
                        self.chain.rtcp_transformer is not None:
                    rb, okc = self.chain.rtcp_transformer.reverse_transform(
                        rb)
                else:
                    okc = np.ones(rb.batch_size, bool)
                self.on_rtcp(rb, okc)
        self._release_token(token, eng)

    # --------------------------------------------------- deep pipeline
    def _inflight_age(self) -> int:
        """Age (ticks) of the oldest un-materialized dispatch, across
        both the egress (`_inflight`) and reverse (`_rx_inflight`)
        pipelines — computed LIVE so a scrape of a parked loop sees
        the current pipeline depth (e.g. zero after a drain), not the
        value frozen at the last tick."""
        return max(
            max((self.ticks - t for _p, _m, _o, t in self._inflight),
                default=0),
            max((self.ticks - e["tick"] for e in self._rx_inflight),
                default=0))

    def _note_inflight_age(self) -> None:
        """Per-tick snapshot the phase ledger consumers read."""
        self.dispatch_inflight_ticks = self._inflight_age()

    def _release_token(self, token, eng=None) -> None:
        if token is not None:
            (eng if eng is not None else self.engine).release_arena(token)

    def _warn_unknown_ssrc(self, count: int) -> None:
        """Interval-suppressed unknown-SSRC warning: at most one log
        line per `unknown_warn_interval` ticks; skipped occurrences ride
        on the next line's `suppressed` count."""
        self.unknown_ssrc_dropped += count
        last = self._unknown_last_warn
        if last is not None and \
                self.ticks - last < self.unknown_warn_interval:
            self._unknown_suppressed += 1
            return
        _log.warn("unknown_ssrc_drop", count=count, tick=self.ticks,
                  suppressed=self._unknown_suppressed,
                  total=self.unknown_ssrc_dropped)
        self._unknown_last_warn = self.ticks
        self._unknown_suppressed = 0

    def _materialize_rx(self, due_only: bool = True) -> int:
        """Materialize in-flight reverse pendings (FIFO): deliver media
        to the sink, dispatch its reply, release the arena pin.  With
        `due_only`, only entries that have aged `pipeline_depth - 1`
        ticks come due — the depth bound."""
        if not self._rx_inflight:
            return 0
        if due_only:
            horizon = self.pipeline_depth - 1
            due = [e for e in self._rx_inflight
                   if self.ticks - e["tick"] >= horizon]
            if not due:
                return 0
            self._rx_inflight = [e for e in self._rx_inflight
                                 if self.ticks - e["tick"] < horizon]
        else:
            due, self._rx_inflight = self._rx_inflight, []
        done = 0
        for e in due:
            done += self._finish_rx(e)
        return done

    def _finish_rx(self, e: dict) -> int:
        pend = e["pend"]
        with self.tracer.span("reverse_chain"), \
                self.tracer.span("chain_d2h"):
            rtp, ok = pend.result()
        self.perf.note_d2h(rtp.data.nbytes)
        # the original arena bytes were last read inside result() (the
        # failed-row passthrough) — safe to recycle from here on
        self._release_token(e["token"], e.get("eng"))
        if not ok.all():
            _log.warn("reverse_chain_drop", count=int((~ok).sum()),
                      tick=self.ticks)
        self.last_rtp_arrival_ns = e["ats"]
        if self.on_media is not None:
            reply = self.on_media(rtp, ok)
            if reply is not None:
                # replies charge their journey to the ARRIVAL tick: the
                # pipeline delay is real latency those packets paid
                if self.pipelined:
                    self.send_media_async(reply, origin=e["origin"])
                else:
                    self.send_media(reply, origin=e["origin"])
        return e["n"]

    def drain(self) -> int:
        """Pipeline drain barrier: materialize EVERY in-flight reverse
        dispatch (delivering media and committing replay state) and
        flush every dispatched send.  Checkpoint and lifecycle commit
        points run behind this barrier so snapshots never capture — and
        row recycling never races — half-finished ticks."""
        done = self._materialize_rx(due_only=False)
        if self._inflight:
            self.flush_sends()
        self._note_inflight_age()
        return done

    # ----------------------------------------------------------- journey
    def journey_origin(self) -> Tuple[int, Optional[float]]:
        """The current tick's (trace_id, arrival_t0) — captured at
        dispatch time by pipelined senders whose bytes flush on a later
        tick, so the observed journey includes the pipelining delay."""
        return self.trace_id, self._trace_t0

    def note_journey(self, n: int, sids=None) -> Optional[float]:
        return self.note_journey_at(self.journey_origin(), n, sids=sids)

    def note_journey_at(self, origin: Tuple[int, Optional[float]],
                        n: int, sids=None,
                        at: Optional[float] = None) -> Optional[float]:
        """Observe `n` packets leaving now (or at `at`, a
        `time.perf_counter()` instant: the egress worker's end stamp of
        a burst reaped later) against an ingress origin.
        A journey that overflows the top histogram bucket marks the
        shipped streams priority in the flight recorder, so the next
        header sample keeps their burst tail (adaptive hdr sampling)."""
        trace, t0 = origin
        if n <= 0 or t0 is None:
            return None
        dt = (time.perf_counter() if at is None else at) - t0
        tail = self.journey_hist.observe_same(
            dt, int(n), exemplar={"trace_id": str(trace)})
        if tail and self.flight is not None and sids is not None:
            for sid in set(int(s) for s in np.asarray(sids).ravel()):
                if sid >= 0:
                    self.flight.mark_priority(sid)
        return dt

    # -------------------------------------------------------------- send
    def _send_masked(self, out: PacketBatch, mask: np.ndarray) -> int:
        """Transmit the mask-selected rows of a protected batch to each
        stream row's latched address.  All-rows batches go out as-is;
        subsets use the engine's native gather (`send_rows`) so the
        host never materializes a contiguous copy of the egress burst."""
        rows = np.nonzero(mask)[0]
        if len(rows) == 0:
            return 0
        if len(rows) == out.batch_size:
            sids = np.clip(out.stream, 0, self.registry.capacity - 1)
            return self.engine.send_batch(out, self.addr_ip[sids],
                                          self.addr_port[sids])
        sids = np.clip(np.asarray(out.stream)[rows], 0,
                       self.registry.capacity - 1)
        if hasattr(self.engine, "send_rows"):
            return self.engine.send_rows(out, rows, self.addr_ip[sids],
                                         self.addr_port[sids])
        sub = PacketBatch(out.data[rows],  # jitlint: disable=hotpath-alloc
                          np.asarray(out.length)[rows],
                          np.asarray(out.stream)[rows])
        return self.engine.send_batch(sub, self.addr_ip[sids],
                                      self.addr_port[sids])

    def send_media(self, batch: PacketBatch, origin=None) -> int:
        """Protect (forward chain) + send a batch; rows route to each
        stream row's latched address.  `origin` overrides the journey
        origin (pipelined callers charge the arrival tick)."""
        if batch.batch_size == 0:
            return 0
        with self.tracer.span("forward_chain"):
            if self.chain is not None:
                tr = self.chain.rtp_transformer
                self.perf.note_h2d(batch.data.nbytes +
                                   np.asarray(batch.length).nbytes)
                with self.tracer.span("chain_device"):
                    batch, ok = tr.transform(batch)
                self.perf.note_d2h(batch.data.nbytes)
            else:
                ok = np.ones(batch.batch_size, bool)
        with self.tracer.span("egress"):
            sent = self._send_masked(batch, ok)
            streams = np.asarray(batch.stream)[np.nonzero(ok)[0]]
            self.note_journey_at(
                self.journey_origin() if origin is None else origin,
                sent, sids=streams)
        self.tx_packets += sent
        return sent

    def send_media_async(self, batch: PacketBatch, origin=None) -> int:
        """Dispatch the forward chain without materializing; protected
        bytes go out on the next tick's flush (or an explicit
        `flush_sends`)."""
        if batch.batch_size == 0:
            return 0
        if self.chain is None:
            return self.send_media(batch, origin=origin)  # nothing to overlap
        with self.tracer.span("forward_chain"):
            self.perf.note_h2d(batch.data.nbytes +
                               np.asarray(batch.length).nbytes)
            with self.tracer.span("chain_dispatch"):
                pending, mask = (self.chain.rtp_transformer
                                 .transform_async(batch))
        self._inflight.append((
            pending, mask,
            self.journey_origin() if origin is None else origin,
            self.ticks))
        return batch.batch_size

    def flush_sends(self) -> int:
        """Materialize + transmit every in-flight dispatched batch."""
        sent = 0
        inflight, self._inflight = self._inflight, []
        with self.tracer.span("egress"):
            for pending, mask, origin, _tick in inflight:
                with self.tracer.span("chain_d2h"):
                    out = pending.result()
                self.perf.note_d2h(out.data.nbytes)
                k = self._send_masked(out, mask)
                # journey measured from the DISPATCH tick's arrival:
                # the pipelining window is real latency the packet paid
                self.note_journey_at(
                    origin, k,
                    sids=np.asarray(out.stream)[np.nonzero(mask)[0]])
                sent += k
        self.tx_packets += sent
        return sent

    def run(self, duration_s: float) -> None:
        """Drive ticks for a bounded wall-clock interval (tests/tools)."""
        end = time.time() + duration_s
        while time.time() < end:
            self.tick()
