"""SfuBridge — the videobridge-style forwarding conference as one object.

Reference: Jitsi Videobridge builds on the reference's
`RTPTranslatorImpl` + `CachingTransformer` + RTCP termination
(SURVEY §3.4, §2.2, §2.3) with one StreamRTPManager per endpoint and a
per-receiver send chain.  Here the whole SFU tick composes the dense
pieces: one batched MediaLoop (unprotect every sender's packets in one
launch), the `RtpTranslator` (decrypt-once / re-encrypt-per-leg in one
fan-out launch — grouped GCM kernel on AEAD conferences), a
`SlabCache` serving NACK retransmissions per leg, and
`RtcpTermination` (feedback dedupe/aggregation, min-REMB).

Endpoints both send and receive: `add_endpoint(ssrc, rx_key, tx_key)`
installs the sender-side SRTP row (what they send us) and the receiver
leg (what we send them); routing defaults to full mesh (everyone
forwards to everyone else).
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from libjitsi_tpu.bwe.batched import BatchedRemoteBitrateEstimator
from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.io.loop import MediaLoop
from libjitsi_tpu.io.udp import UdpEngine
from libjitsi_tpu.rtp import ext as rtp_ext
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.rtp import rtcp
from libjitsi_tpu.service.media_stream import StreamRegistry
from libjitsi_tpu.sfu import PacketCache, RtpTranslator, SlabCache
from libjitsi_tpu.sfu import rtx as rtx_mod
from libjitsi_tpu.sfu.recovery import RecoveryConfig, RecoveryController
from libjitsi_tpu.sfu.rtcp_termination import RtcpTermination
from libjitsi_tpu.sfu.simulcast import SimulcastForwarder
from libjitsi_tpu.transform.header_ext import AbsSendTimeEngine
from libjitsi_tpu.transform.srtp import SrtpProfile, SrtpStreamTable
from libjitsi_tpu.utils.logging import get_logger

_log = get_logger("service.sfu")


def _layer_for_bw(layer_bps, bw: float) -> int:
    """Highest layer whose nominal rate fits the advertised bandwidth
    (ascending rates; layer 0 always fits)."""
    want = 0
    for layer, bps in enumerate(layer_bps):
        if bps <= bw:
            want = layer
    return want


class _SvcTrack:
    """One sender's VP9 SVC track: every layer in ONE SSRC, each
    receiver gets a `Vp9SvcForwarder` projection (spatial/temporal
    subsetting) instead of a simulcast stream pick.  Shares the
    fan-out/RTX plumbing with `_VideoTrack` via the same duck surface
    (fwd.forward, tx_sid/rtx_sid/rtx_seq, precache, out_ssrc)."""

    def __init__(self, sender_sid: int, ssrc: int, svc_sid: int,
                 layer_bps, rtx_pt: int):
        from libjitsi_tpu.sfu.svc import Vp9SvcForwarder

        self._fwd_cls = Vp9SvcForwarder
        self.sender_sid = sender_sid
        self.out_ssrc = ssrc & 0xFFFFFFFF     # projection keeps the ssrc
        self.rtx_ssrc = (ssrc ^ _VideoTrack.RTX_SSRC_XOR) & 0xFFFFFFFF
        self.layer_sids = [svc_sid]
        self.layer_ssrcs = [self.out_ssrc]    # teardown/feedback key
        self.layer_bps = [float(b) for b in layer_bps]
        self.rtx_pt = rtx_pt
        self.fwd: Dict[int, object] = {}
        self.rtx_seq: Dict[int, int] = {}
        self.tx_sid: Dict[int, int] = {}
        self.rtx_sid: Dict[int, int] = {}
        self.precache = PacketCache()

    def make_forwarder(self):
        return self._fwd_cls(initial_sid=0)

    def select_layer(self, fwd, bw: float):
        """Spatial-layer pick for `bw`; returns the SSRC to PLI when a
        raise awaits a keyframe, else None."""
        want = _layer_for_bw(self.layer_bps, bw)
        if want != fwd.target_sid:
            if fwd.request_layers(sid=want):
                return self.out_ssrc
        elif fwd.awaiting_keyframe:
            return self.out_ssrc
        return None


class _VideoTrack:
    """One sender's simulcast video track inside an SfuBridge.

    Reference: `MediaStreamTrackDesc` + `RTPEncodingDesc` consumed by
    `RTPTranslatorImpl` (SURVEY §2.3): L spatial layers arrive as
    separate SSRCs; each receiver gets exactly one, projected through a
    `SimulcastForwarder` into a single coherent stream.  Retransmissions
    toward receivers ride RFC 4588 RTX streams (own SSRC = out_ssrc ^
    "RTX", own SRTP row), served from a pre-SRTP cache of the rewritten
    per-receiver packets.
    """

    RTX_SSRC_XOR = 0x00525458          # "RTX"

    def __init__(self, sender_sid: int, out_ssrc: int, layer_ssrcs,
                 layer_sids, layer_bps, rtx_pt: int):
        self.sender_sid = sender_sid
        self.out_ssrc = out_ssrc & 0xFFFFFFFF
        self.rtx_ssrc = (out_ssrc ^ self.RTX_SSRC_XOR) & 0xFFFFFFFF
        self.layer_ssrcs = [int(s) & 0xFFFFFFFF for s in layer_ssrcs]
        self.layer_sids = list(layer_sids)
        self.layer_bps = [float(b) for b in layer_bps]
        self.rtx_pt = rtx_pt
        self.fwd: Dict[int, SimulcastForwarder] = {}   # recv sid ->
        self.rtx_seq: Dict[int, int] = {}              # recv sid ->
        # dedicated SRTP tx rows per receiver: the projection and its
        # RTX stream are each their own RTP stream (own SSRC, own seq
        # space), so each gets its own row context — sharing the
        # receiver's audio row would interleave independent seq spaces
        # in one RFC 3711 index estimator
        self.tx_sid: Dict[int, int] = {}               # recv sid ->
        self.rtx_sid: Dict[int, int] = {}              # recv sid ->
        self.precache = PacketCache()                  # pre-SRTP copies

    def make_forwarder(self):
        return SimulcastForwarder(self.layer_ssrcs,
                                  out_ssrc=self.out_ssrc)

    def select_layer(self, fwd, bw: float):
        """Simulcast-layer pick for `bw`; returns the layer SSRC to PLI
        while a switch awaits its keyframe, else None."""
        want = _layer_for_bw(self.layer_bps, bw)
        if want != fwd.target_layer:
            if fwd.request_layer(want):
                return self.layer_ssrcs[want]
        elif fwd.awaiting_keyframe:
            return self.layer_ssrcs[fwd.target_layer]
        return None


class SfuBridge:
    """Secure selective-forwarding bridge on one UDP port."""

    def __init__(self, config, port: int = 0, capacity: int = 256,
                 profile: SrtpProfile =
                 SrtpProfile.AES_CM_128_HMAC_SHA1_80,
                 recv_window_ms: int = 1,
                 kernel_timestamps: bool = False,
                 abs_send_time_ext_id: int = 3,
                 pipeline_depth: int = 1,
                 mesh=None,
                 recovery_config: Optional[RecoveryConfig] = None,
                 engine_mode: str = "auto",
                 ingest_rings: int = 1):
        self.capacity = capacity
        self.profile = profile
        self.ast_ext_id = abs_send_time_ext_id
        self.engine_mode = engine_mode
        self.ingest_rings = max(1, int(ingest_rings))
        # the fan-out a tick has dispatched and the next collects:
        # (PendingTranslate, the journey origin of its packets)
        self._pending_fanout: Optional[tuple] = None
        self._media_ran = False
        self.registry = StreamRegistry(config, capacity=capacity)
        # rx_table: what endpoints SEND us (media + their SRTCP);
        # tx_table: what we send THEM (our SRTCP feedback; media forward
        # crypto is the translator's per-leg fan-out).  Mesh mode
        # (SURVEY §2.7): tables row-partition and the
        # fan-out shards by receiver leg — the assembled SFU tick runs
        # sharded, not just its kernels.
        self._mesh = mesh
        if mesh is not None:
            # the sharded seams leave their outputs on the mesh in lane
            # layout until they are fetched, so the fan-out dispatched
            # by one tick is collected by the next here as on one chip
            from libjitsi_tpu.mesh import (ShardedRtpTranslator,
                                           ShardedSrtpTable)
            self.rx_table = ShardedSrtpTable(capacity, mesh, profile)
            self.tx_table = ShardedSrtpTable(capacity, mesh, profile)
            self.translator = ShardedRtpTranslator(capacity, mesh,
                                                   profile)
        else:
            self.rx_table = SrtpStreamTable(capacity, profile)
            self.tx_table = SrtpStreamTable(capacity, profile)
            self.translator = RtpTranslator(capacity=capacity,
                                            profile=profile)
        self.cache = SlabCache()
        self.rtcp_term = RtcpTermination(bridge_ssrc=0x5F0BFF)
        # end-to-end loss recovery (sfu/recovery.py): uplink gap
        # detection -> upstream NACKs, budgeted NACK service, adaptive
        # FEC on egress legs, and the supervisor's shed-FEC-first /
        # shrink-RTX-second escalation rungs.  Transient (like the
        # caches): a restored bridge re-learns loss state from traffic.
        self.recovery = RecoveryController(recovery_config)
        # resolve uplink SSRCs back to leg sids so nack_queued events
        # land in the stream's flight ring (and mark it priority for
        # tail-biased header sampling)
        self.recovery.sid_of = self._sid_of_ssrc
        # flight recorder slot (attached by BridgeSupervisor; shared
        # with self.loop and self.recovery)
        self.flight = None
        self.loop = MediaLoop(
            UdpEngine(port=port, max_batch=4 * capacity,
                      kernel_timestamps=kernel_timestamps,
                      engine_mode=engine_mode,
                      reuseport=self.ingest_rings > 1),
            self.registry, on_media=self._on_media,
            on_rtcp=self._on_rtcp,
            on_dtls=lambda d, a: self._dtls.on_dtls(d, a), chain=None,
            recv_window_ms=recv_window_ms,
            # the SFU unprotects inside _on_media (chain=None), so deep
            # reverse pipelining doesn't engage here: depth > 1 turns
            # on the loop's dispatched replies (loop.pipelined) and
            # nothing of the fan-out, whose one shape is `_on_media`'s
            pipeline_depth=pipeline_depth)
        self._share_tracer()
        self.port = self.loop.engine.port
        # SO_REUSEPORT multi-queue: sibling drain rings on the SAME
        # port, kernel-sharded by flow hash; each tick drains every
        # ring (io/loop.py) and the AdaptiveBatcher governs their caps
        for _ in range(self.ingest_rings - 1):
            self.loop.add_ring(UdpEngine(
                port=self.port, reuseport=True,
                max_batch=4 * capacity,
                kernel_timestamps=kernel_timestamps,
                engine_mode=engine_mode))
        self._ssrc_of: Dict[int, int] = {}     # sid -> sender ssrc
        # rows keyed by stage_endpoints but not yet committed: demuxed
        # media queues on the hold mask, and the route mesh excludes
        # them until commit_endpoints flips them live between ticks
        self._staged: set = set()
        #: datagrams the fan-out has SENT: counted when a burst's
        #: completion is reaped (the tick after its hand-over), so it
        #: trails the hand-overs until `flush_egress()`
        self.forwarded = 0
        # fan-out bursts handed to the engine's egress worker and not
        # reaped yet: job id -> (journey origin, receiver sids)
        self._egress_jobs: Dict[int, tuple] = {}
        self.retransmitted = 0
        # overload degradation (set by BridgeSupervisor): suppress the
        # RTCP feedback fan-out while media forwarding keeps flowing
        self.degraded = False
        # receive-side GCC over each sender->bridge leg: fed per tick
        # from the abs-send-time ext + (kernel, when enabled) arrival
        # stamps; one transport row per sender sid.  Reference:
        # RemoteBitrateEstimatorAbsSendTime driven from the translator's
        # receive path (SURVEY §2.3).
        self.bwe = BatchedRemoteBitrateEstimator(capacity=capacity)
        self._bwe_fed = np.zeros(capacity, dtype=bool)
        # egress abs-send-time stamping so every receiver can run its
        # own receive-side estimate on the bridge->receiver leg
        # (reference: AbsSendTimeEngine on the SFU's send chain)
        self._ast = AbsSendTimeEngine(abs_send_time_ext_id,
                                      clock=lambda: self._now)
        self._now = time.time()
        # pending DTLS-SRTP associations (shared table: routing,
        # retransmit timers, early-media hold)
        from libjitsi_tpu.control.dtls import DtlsAssociationTable
        self._dtls = DtlsAssociationTable(self.loop, profile,
                                          self._install_dtls)
        # video: layer-row sid -> its track; plus per-endpoint leg keys
        # (kept to derive per-track projection/RTX rows) and receiver
        # downlink REMBs
        self._video: Dict[int, _VideoTrack] = {}
        self._rx_keys: Dict[int, Tuple[bytes, bytes]] = {}
        self._tx_keys: Dict[int, Tuple[bytes, bytes]] = {}
        self._recv_bw: Dict[int, float] = {}   # recv sid -> REMB bps
        # BWE transport row per stream row: GCC estimates per TRANSPORT
        # (5-tuple), so a sender's video layer rows feed its primary row
        self._transport_of = np.arange(capacity, dtype=np.int64)
        # conference scoping (mesh/placement.py): sid -> conference id.
        # Endpoints with a conference id forward only within it; rows
        # without one (direct add_endpoint) form one shared mesh, which
        # keeps the single-conference bridge behavior unchanged.
        self._conf_of: Dict[int, int] = {}
        # the same relation by conference (conference id -> its sids):
        # what a conference-scoped call walks, so a room's admission,
        # panel change or route rebuild costs the room and not the table
        self._members_of: Dict[int, set] = {}
        # broadcast conferences (mesh/hierarchy.py): conference id ->
        # current speaker sids.  Speakers fan out to every member;
        # every other member is a fanout-only listener row (routes to
        # nobody, uplink RTP masked off in the loop).
        self._bcast_speakers: Dict[int, set] = {}
        # cascade trunks (mesh/cascade.py): conference id -> trunk.
        # Accepted uplink media from a cascaded conference's current
        # speaker set is relayed across the trunk (top-K speaker bus,
        # never raw per-participant fan-out)
        self._trunks: Dict[int, object] = {}

    def _share_tracer(self) -> None:
        """Hand the loop's tracer to the pieces that span their own
        parts of the tick (the way the supervisor hands out `flight`);
        again after a restore replaced a table."""
        for obj in (self.rx_table, self.translator):
            obj.tracer = self.loop.tracer

    # ---------------------------------------------------------- endpoints
    def has_ssrc(self, ssrc: int) -> bool:
        """Whether an endpoint has joined under this SSRC: one look in
        the registry's demux map (every join maps its SSRC there), not
        a walk over the live endpoints — admission asks once a join."""
        ssrc &= 0xFFFFFFFF
        sid = self.registry._ssrc_to_sid.get(ssrc)
        return sid is not None and self._ssrc_of.get(sid) == ssrc

    def add_endpoint(self, ssrc: int, rx_key: Tuple[bytes, bytes],
                     tx_key: Tuple[bytes, bytes],
                     name: Optional[str] = None) -> int:
        if self.has_ssrc(ssrc):
            raise ValueError(f"ssrc {ssrc:#x} already joined")
        self._quiesce_fanout()
        sid = self.registry.alloc(self)
        if name is not None:
            # SDES-style display name: hostile input, escaped at
            # metric render time (never trusted raw)
            self.loop.metrics.set_stream_name(sid, name)
        self.rx_table.add_stream(sid, *rx_key)
        self.tx_table.add_stream(sid, *tx_key)
        self.translator.add_receiver(sid, *tx_key)
        self.registry.map_ssrc(ssrc, sid)
        self._ssrc_of[sid] = ssrc & 0xFFFFFFFF
        self._rx_keys[sid] = tuple(rx_key)
        self._tx_keys[sid] = tuple(tx_key)
        self._rebuild_routes()
        for track in set(self._video.values()):
            self._attach_video_receiver(track, sid)
        _log.info("endpoint_join", sid=sid, ssrc=ssrc)
        return sid

    def add_endpoint_dtls(self, ssrc: int, role: str = "server",
                          remote_fingerprint: Optional[str] = None,
                          cookie_exchange: bool = False,
                          remote_addr=None):
        """Join keyed by DTLS-SRTP instead of direct keys: allocates the
        row and starts an association; media arriving before the
        handshake finishes is queued (MediaLoop.hold_stream) and
        replayed once keys install.  Returns (sid, endpoint) — publish
        `endpoint.local_fingerprint` via signaling, and pass
        `remote_addr` when signaling knows the peer's 5-tuple (with
        several concurrent unbound joins, unknown-address handshakes
        are dropped rather than guessed onto the wrong row).
        Reference: DtlsControlImpl started by MediaStream.start
        (SURVEY §3.5)."""
        if self.has_ssrc(ssrc):
            raise ValueError(f"ssrc {ssrc:#x} already joined")
        sid = self.registry.alloc(self)
        self.registry.map_ssrc(ssrc, sid)
        self._ssrc_of[sid] = ssrc & 0xFFFFFFFF
        ep = self._dtls.join(sid, role, remote_fingerprint,
                             cookie_exchange, remote_addr)
        _log.info("endpoint_join_dtls", sid=sid, ssrc=ssrc, role=role)
        return sid, ep

    def _install_dtls(self, sid: int, ep) -> None:
        self._quiesce_fanout()
        profile, tk, tsalt, rk, rsalt = ep.srtp_keys()
        self.rx_table.add_stream(sid, rk, rsalt)
        self.tx_table.add_stream(sid, tk, tsalt)
        self.translator.add_receiver(sid, tk, tsalt)
        self._rx_keys[sid] = (rk, rsalt)
        self._tx_keys[sid] = (tk, tsalt)
        self._rebuild_routes()
        # video tracks created while this endpoint was mid-handshake
        # attach now that its leg keys exist
        for track in set(self._video.values()):
            self._attach_video_receiver(track, sid)
        _log.info("dtls_keys_installed", sid=sid, profile=profile.name)

    def stage_dtls_keys(self, sid: int, ep) -> None:
        """Staged landing for a completed DTLS handshake (the lifecycle
        plane's HandshakeQueue): install the exported keys into both
        SRTP tables + the translator leg for the already-allocated row
        and leave it STAGED — `commit_endpoints` flips it live between
        ticks (one route rebuild for the whole batch, held early media
        replayed atomically).  `_install_dtls` stays as the inline twin
        for bridges running without a lifecycle manager."""
        self._quiesce_fanout()
        profile, tk, tsalt, rk, rsalt = ep.srtp_keys()
        self.rx_table.add_stream(sid, rk, rsalt)
        self.tx_table.add_stream(sid, tk, tsalt)
        self.translator.add_receiver(sid, tk, tsalt)
        self._rx_keys[sid] = (rk, rsalt)
        self._tx_keys[sid] = (tk, tsalt)
        self._staged.add(sid)
        _log.info("dtls_keys_staged", sid=sid, profile=profile.name)

    def remove_endpoint(self, sid: int) -> None:
        self.remove_endpoints([sid])

    def remove_endpoints(self, sids) -> None:
        """Batched evict: `remove_endpoint` for many legs at once — one
        fan-out quiesce, ONE `remove_streams` pass per SRTP table (one
        copy-on-write episode however many streams leave), one route
        rebuild.  The lifecycle plane's leave path; O(evicted), not
        O(evicted * per-call table copies)."""
        sids = [int(s) for s in sids]
        if not sids:
            return
        self._quiesce_fanout()
        rx_rows: list = []
        tx_rows: list = []
        gone_ssrcs: list = []
        for sid in sids:
            ssrc = self._ssrc_of.pop(sid, None)
            if ssrc is not None:
                self.registry.unmap_ssrc(ssrc)
                gone_ssrcs.append(ssrc)
            if self.rx_table.active[sid]:
                rx_rows.append(sid)
            if self.tx_table.active[sid]:
                tx_rows.append(sid)
            self.translator.disconnect(sid)
            self.translator.remove_receiver(sid)
            self.rtcp_term.forget_receiver(sid)
            self._bwe_fed[sid] = False
            self._dtls.forget(sid)
            self._rx_keys.pop(sid, None)
            self._tx_keys.pop(sid, None)
            self._recv_bw.pop(sid, None)
            conf = self._leave_conference(sid)
            if conf is not None and conf in self._bcast_speakers:
                self._bcast_speakers[conf].discard(sid)
                self.loop.set_fanout_only(sid, False)
            # a staged-but-never-committed row: throw its held media
            # away (the endpoint left before its admit flipped live)
            if sid in self._staged:
                self._staged.discard(sid)
                self.loop.discard_stream(sid)
            # as a video sender: tear the track + its layer rows down
            # (the SSRC unmap matters: a recycled row must not demux the
            # old layer SSRCs and latch the departed sender's address)
            for lsid in [k for k, t in self._video.items()
                         if t.sender_sid == sid]:
                track = self._video.pop(lsid)
                li = track.layer_sids.index(lsid)
                self.registry.unmap_ssrc(track.layer_ssrcs[li])
                gone_ssrcs.append(track.layer_ssrcs[li])
                rx_rows.append(lsid)
                self._transport_of[lsid] = lsid
                self.registry.release(lsid)
                for d in (track.tx_sid, track.rtx_sid):
                    for row in d.values():
                        tx_rows.append(row)
                        self.registry.release(row)
            # as a video receiver: drop forwarders + projection/RTX rows
            for track in set(self._video.values()):
                track.fwd.pop(sid, None)
                track.rtx_seq.pop(sid, None)
                for d in (track.tx_sid, track.rtx_sid):
                    row = d.pop(sid, None)
                    if row is not None:
                        tx_rows.append(row)
                        self.registry.release(row)
            self.loop.addr_ip[sid] = 0
            self.loop.addr_port[sid] = 0
            self.loop.metrics.set_stream_name(sid, None)
            self.registry.release(sid)
        self.rx_table.remove_streams(rx_rows)
        self.tx_table.remove_streams(tx_rows)
        self.bwe.reset_rows(sids)
        # recovery state is per departed sender SSRC / receiver leg:
        # recycle it so churn can't grow trackers without bound
        self.recovery.forget_ssrcs(gone_ssrcs)
        self.recovery.forget_legs(sids)
        self._rebuild_routes()
        for sid in sids:
            _log.info("endpoint_leave", sid=sid)

    # ---------------------------------------------------- lifecycle plane
    def stage_endpoints(self, specs, sids=None,
                        conferences=None) -> List[int]:
        """Off-tick half of a batched admit: allocate rows, install BOTH
        SRTP tables and the translator legs in ONE vectorized
        `add_streams` pass each, map the SSRCs (media racing the admit
        queues on the hold mask instead of being dropped), and leave the
        rows STAGED — no route includes them and no held packet replays
        until `commit_endpoints` flips them live between ticks.

        specs: iterable of (ssrc, (rx_mk, rx_ms), (tx_mk, tx_ms), name).
        `sids` pins specific rows (the lifecycle plane's
        conference-affinity path: rows drawn from the conference's
        shard range by `ShardRowAllocator`); `conferences` scopes each
        endpoint's forwarding to its conference id.
        Returns the allocated sids in spec order.
        """
        specs = list(specs)
        if not specs:
            return []
        for ssrc, _rx, _tx, _name in specs:
            if self.has_ssrc(ssrc):
                raise ValueError(f"ssrc {ssrc:#x} already joined")
        self._quiesce_fanout()
        if sids is None:
            sids = [self.registry.alloc(self) for _ in specs]
        else:
            sids = [int(s) for s in sids]
            if len(sids) != len(specs):
                raise ValueError("sids/specs length mismatch")
            self.registry.reserve_many(sids, self)
        if conferences is not None:
            for sid, conf in zip(sids, conferences):
                if conf is not None:
                    self._join_conference(sid, int(conf))
        arr = np.asarray(sids, dtype=np.int64)
        rx_mks = np.stack([np.frombuffer(rx[0], np.uint8)
                           for _, rx, _, _ in specs])
        rx_mss = np.stack([np.frombuffer(rx[1], np.uint8)
                           for _, rx, _, _ in specs])
        tx_mks = np.stack([np.frombuffer(tx[0], np.uint8)
                           for _, _, tx, _ in specs])
        tx_mss = np.stack([np.frombuffer(tx[1], np.uint8)
                           for _, _, tx, _ in specs])
        self.rx_table.add_streams(arr, rx_mks, rx_mss)
        self.tx_table.add_streams(arr, tx_mks, tx_mss)
        # a leg's fan-out keys are its tx keys: derived once a wave
        self.translator.adopt_receivers(arr, self.tx_table)
        for sid, (ssrc, rx, tx, name) in zip(sids, specs):
            self.registry.map_ssrc(ssrc, sid)
            self._ssrc_of[sid] = ssrc & 0xFFFFFFFF
            self._rx_keys[sid] = tuple(rx)
            self._tx_keys[sid] = tuple(tx)
            if name is not None:
                self.loop.metrics.set_stream_name(sid, name)
            self.loop.hold_stream(sid)
            self._staged.add(sid)
            _log.info("endpoint_staged", sid=sid, ssrc=ssrc)
        return sids

    def commit_endpoints(self, sids) -> None:
        """Between-ticks commit barrier: flip staged rows live — one
        route rebuild for the whole batch, of the conferences the batch
        joins and no other (a wave's cost does not grow with the
        bridge), held media replayed through the normal receive path,
        video receivers attached."""
        sids = [int(s) for s in sids if int(s) in self._staged]
        if not sids:
            return
        self._quiesce_fanout()
        touched = {self._conf_of.get(sid, -1) for sid in sids}
        for sid in sids:
            self._staged.discard(sid)
            conf = self._conf_of.get(sid)
            if conf is not None and conf in self._bcast_speakers:
                # joining a broadcast conference: fanout-only unless in
                # the current speaker set (role flips ride the same
                # barrier later)
                self.loop.set_fanout_only(
                    sid, sid not in self._bcast_speakers[conf])
        self._rebuild_routes(touched)
        for sid in sids:
            for track in set(self._video.values()):
                self._attach_video_receiver(track, sid)
            self.loop.release_stream(sid)
            _log.info("endpoint_join", sid=sid,
                      ssrc=self._ssrc_of.get(sid))

    def set_broadcast_speakers(self, conference: int, sids) -> None:
        """Declare/update a broadcast conference's speaker set and
        rebuild its routes: speakers fan out to every member, all other
        members become fanout-only listener rows.  Called by the
        lifecycle plane BETWEEN ticks (a promotion/demotion is a
        commit-barrier event, never a mid-tick one); the fan-out
        quiesce makes the standalone call safe too."""
        conference = int(conference)
        speakers = {int(s) for s in sids}
        if self._bcast_speakers.get(conference) == speakers:
            return
        self._quiesce_fanout()
        self._bcast_speakers[conference] = speakers
        # this conference's members and routes and no other's: a room's
        # panel changing costs nothing that grows with the bridge
        for sid in self._members_of.get(conference, ()):
            self.loop.set_fanout_only(sid, sid not in speakers)
        self._rebuild_routes({conference})
        tr = self._trunks.get(conference)
        if tr is not None:
            # propagate the top-K flip across the trunk: the peer
            # restricts the same legs (speaker bus, not fan-out)
            tr.set_speakers(conference,
                            [self._ssrc_of[s] for s in speakers
                             if s in self._ssrc_of], now=self._now)

    # ------------------------------------------------------------ cascade
    def attach_trunk(self, trunk, conference, speakers=None) -> None:
        """Cascade `conference` over `trunk` (mesh/cascade.py): every
        accepted uplink packet from the conference's speaker set is
        relayed across the trunk, and speaker-set flips propagate to
        the peer bridge.  `speakers` is the initial top-K ssrc set
        (None relays every member — the degenerate bus)."""
        self._trunks[int(conference)] = trunk
        trunk.cascade_conference(int(conference), speakers)

    def detach_trunk(self, conference) -> None:
        tr = self._trunks.pop(int(conference), None)
        if tr is not None:
            tr.uncascade_conference(int(conference))

    def _relay_trunk(self, batch: PacketBatch, rows: np.ndarray,
                     streams, ssrcs) -> None:
        """Relay the ORIGINAL protected wire bytes of accepted rows
        whose (conference, ssrc) rides a trunk's speaker bus.  The
        inner packet stays untouched — the peer bridge authenticates
        it with the participant's own row key."""
        for i, r in enumerate(rows):
            conf = self._conf_of.get(int(streams[i]))
            if conf is None:
                continue
            tr = self._trunks.get(conf)
            if tr is not None and tr.wants(conf, int(ssrcs[i])):
                tr.relay_media(conf, batch.to_bytes(int(r)),
                               now=self._now)

    def clear_broadcast(self, conference: int) -> None:
        """Drop a conference's broadcast routing (back to full mesh)."""
        conference = int(conference)
        if self._bcast_speakers.pop(conference, None) is not None:
            for sid in self._members_of.get(conference, ()):
                self.loop.set_fanout_only(sid, False)
            self._quiesce_fanout()
            self._rebuild_routes({conference})

    def migrate_endpoints(self, mapping: Dict[int, int]) -> None:
        """Move live endpoints to new rows BIT-EXACT — the execution
        half of a placement rebalance (mesh/placement.py): both SRTP
        tables' per-row crypto state (keys, rollover counters, replay
        windows, kdr epochs), the translator leg material, SSRC demux,
        addresses and conference scoping all relocate unchanged, so a
        conference migrating to another shard cannot tear (a packet
        keyed before the move authenticates identically after it).
        Transient learning state (BWE, RTCP reception, recovery
        trackers) resets and re-learns from traffic, same as it does
        across a checkpoint restore.

        Callers run this BETWEEN ticks (the lifecycle plane sequences
        it behind the commit barrier); the pipeline drain + fan-out
        quiesce here make that safe even standalone.  Rows serving
        video tracks or still staged/DTLS-pending refuse to move.
        """
        mapping = {int(s): int(d) for s, d in mapping.items()}
        mapping = {s: d for s, d in mapping.items() if s != d}
        if not mapping:
            return
        src = sorted(mapping)
        dst = [mapping[s] for s in src]
        if len(set(dst)) != len(dst) or set(src) & set(dst):
            raise ValueError("overlapping migration mapping")
        for s in src:
            if s not in self._ssrc_of:
                raise ValueError(f"sid {s} not live")
            if s in self._staged or s in self._dtls.pending:
                raise ValueError(f"sid {s} is mid-install")
            if s in self._video or any(
                    t.sender_sid == s or s in t.fwd
                    for t in set(self._video.values())):
                raise ValueError(f"sid {s} serves a video track")
        drain = getattr(self.loop, "drain", None)
        if drain is not None:
            drain()
        self._quiesce_fanout()
        self.registry.reserve_many(dst, self)
        self.rx_table.move_rows(src, dst)
        self.tx_table.move_rows(src, dst)
        self.translator.move_receivers(src, dst)
        for s, d in zip(src, dst):
            ssrc = self._ssrc_of.pop(s)
            self.registry.unmap_ssrc(ssrc)
            self.registry.map_ssrc(ssrc, d)
            self._ssrc_of[d] = ssrc
            self._rx_keys[d] = self._rx_keys.pop(s)
            self._tx_keys[d] = self._tx_keys.pop(s)
            if s in self._recv_bw:
                self._recv_bw[d] = self._recv_bw.pop(s)
            conf = self._leave_conference(s)
            if conf is not None:
                self._join_conference(d, conf)
                if conf in self._bcast_speakers:
                    spk = self._bcast_speakers[conf]
                    if s in spk:
                        spk.discard(s)
                        spk.add(d)
                    self.loop.set_fanout_only(s, False)
                    self.loop.set_fanout_only(d, d not in spk)
            self.loop.addr_ip[d] = self.loop.addr_ip[s]
            self.loop.addr_port[d] = self.loop.addr_port[s]
            self.loop.addr_ip[s] = 0
            self.loop.addr_port[s] = 0
            name = self.loop.metrics.stream_names.get(s)
            self.loop.metrics.set_stream_name(d, name)
            self.loop.metrics.set_stream_name(s, None)
            self._bwe_fed[s] = False
            self._bwe_fed[d] = False
            self.registry.release(s)
        self.bwe.reset_rows(src)
        self.recovery.forget_legs(src)
        for s in src:
            self.rtcp_term.forget_receiver(s)
        self._rebuild_routes()
        for s, d in zip(src, dst):
            _log.info("endpoint_migrated", src=s, dst=d)

    def _sid_of_ssrc(self, ssrc: int) -> Optional[int]:
        """Reverse of `_ssrc_of` (recovery's sid resolver): uplink
        media SSRC -> sender leg sid, video layers included."""
        ssrc = int(ssrc) & 0xFFFFFFFF
        for sid, s in self._ssrc_of.items():
            if s == ssrc:
                return sid
        for lsid, track in self._video.items():
            li = track.layer_sids.index(lsid)
            if track.layer_ssrcs[li] == ssrc:
                return track.sender_sid
        return None

    # --------------------------------------------------------------- video
    def add_video_track(self, sender_sid: int, layer_ssrcs,
                        layer_bps, rtx_pt: int = 97) -> "_VideoTrack":
        """Declare a joined endpoint's simulcast video track.

        layer_ssrcs: the L spatial layers' SSRCs, low to high;
        layer_bps: nominal bitrate of each layer (ascending) — layer
        selection picks the highest layer whose rate fits the
        receiver's advertised REMB.  Each layer gets its own SRTP row
        (one row per SSRC: RFC 3711 contexts, replay windows and index
        estimation are per-stream).  Reference: RTPEncodingDesc layers
        under MediaStreamTrackDesc (SURVEY §2.3).
        """
        if sender_sid not in self._ssrc_of:
            raise ValueError(f"sid {sender_sid} not joined")
        if len(layer_ssrcs) != len(layer_bps):
            raise ValueError("one nominal bitrate per layer")
        self._quiesce_fanout()
        rx_key = self._rx_keys[sender_sid]
        layer_sids = []
        for ssrc in layer_ssrcs:
            lsid = self.registry.alloc(self)
            self.rx_table.add_stream(lsid, *rx_key)
            self.registry.map_ssrc(ssrc, lsid)
            # GCC is per transport: layer rows feed the sender's row
            self._transport_of[lsid] = sender_sid
            layer_sids.append(lsid)
        track = _VideoTrack(sender_sid, self._ssrc_of[sender_sid],
                            layer_ssrcs, layer_sids, layer_bps, rtx_pt)
        for lsid in layer_sids:
            self._video[lsid] = track
        for r in self._ssrc_of:
            if r != sender_sid:
                self._attach_video_receiver(track, r)
        _log.info("video_track_added", sid=sender_sid,
                  layers=len(layer_sids))
        return track

    def add_svc_track(self, sender_sid: int, ssrc: int, layer_bps,
                      rtx_pt: int = 97) -> "_SvcTrack":
        """Declare a joined endpoint's VP9 SVC track: one SSRC carrying
        every spatial layer; each receiver gets a per-receiver
        `Vp9SvcForwarder` projection (layer subsetting) driven by its
        REMB, with the same RTX/PLI plumbing as simulcast.  layer_bps:
        nominal cumulative rate per spatial layer, ascending."""
        if sender_sid not in self._ssrc_of:
            raise ValueError(f"sid {sender_sid} not joined")
        self._quiesce_fanout()
        svc_sid = self.registry.alloc(self)
        self.rx_table.add_stream(svc_sid, *self._rx_keys[sender_sid])
        self.registry.map_ssrc(ssrc, svc_sid)
        self._transport_of[svc_sid] = sender_sid
        track = _SvcTrack(sender_sid, ssrc, svc_sid, layer_bps, rtx_pt)
        self._video[svc_sid] = track
        for r in self._ssrc_of:
            if r != sender_sid:
                self._attach_video_receiver(track, r)
        _log.info("svc_track_added", sid=sender_sid, ssrc=ssrc,
                  layers=len(track.layer_bps))
        return track

    def _attach_video_receiver(self, track, recv_sid: int) -> None:
        if recv_sid == track.sender_sid or recv_sid in track.fwd:
            return
        if recv_sid not in self._tx_keys:
            # no leg keys yet (mid-DTLS): attach happens at install
            return
        track.fwd[recv_sid] = track.make_forwarder()
        track.rtx_seq[recv_sid] = 0
        # the projection and its RTX stream each get a dedicated row
        # under this receiver's leg keys (RFC 4588: RTX is its own
        # stream; RFC 3711: one index estimator per stream)
        for d in (track.tx_sid, track.rtx_sid):
            row = self.registry.alloc(self)
            self.tx_table.add_stream(row, *self._tx_keys[recv_sid])
            d[recv_sid] = row

    def _forward_video(self, sub: PacketBatch, vrows: np.ndarray
                       ) -> None:
        """Project video rows through each receiver's forwarder, cache
        the pre-SRTP rewrites for RTX, protect all legs in one launch."""
        lens = np.asarray(sub.length)
        rows_of: Dict[int, list] = {}      # id(track) -> batch rows
        tracks: Dict[int, _VideoTrack] = {}
        for i in vrows:
            t = self._video[int(sub.stream[i])]
            rows_of.setdefault(id(t), []).append(int(i))
            tracks[id(t)] = t
        out_payloads: list = []
        out_rows: list = []                # SRTP row per packet
        out_addr: list = []                # receiver sid per packet
        for key_, trows in rows_of.items():
            track = tracks[key_]
            tb = PacketBatch(sub.data[trows], lens[trows],
                             sub.stream[trows])
            for r, fwd in track.fwd.items():
                if self.loop.addr_port[r] == 0:
                    continue
                pkts = fwd.forward(tb)
                key = (r << 32) | track.out_ssrc
                for p in pkts:
                    seq = int.from_bytes(p[2:4], "big")
                    track.precache.insert(key, seq, p, now=self._now)
                out_payloads.extend(pkts)
                out_rows.extend([track.tx_sid[r]] * len(pkts))
                out_addr.extend([r] * len(pkts))
        if not out_payloads:
            return
        wb = PacketBatch.from_payloads(out_payloads, stream=out_rows)
        wire = self.tx_table.protect_rtp(wb)
        addr = np.asarray(out_addr, dtype=np.int64)
        with self.loop.tracer.span("egress"):
            sent = self.loop.engine.send_batch(
                wire, self.loop.addr_ip[addr], self.loop.addr_port[addr])
            self.loop.note_journey(sent, sids=addr)
        self.forwarded += sent

    def _select_video_layers(self) -> None:
        """Keyframe-gated layer selection from receiver REMBs: pick the
        highest layer whose nominal rate fits each receiver's advertised
        bandwidth; a pending switch keeps a PLI request live upstream
        until the target layer's keyframe arrives."""
        for track in set(self._video.values()):
            for r, fwd in track.fwd.items():
                bw = self._recv_bw.get(r)
                if bw is None:
                    continue
                kf_ssrc = track.select_layer(fwd, bw)
                if kf_ssrc is not None:
                    self.rtcp_term.request_keyframe(kf_ssrc)

    def _serve_video_nack(self, sid: int, nack: "rtcp.Nack") -> bool:
        """NACKed video returns as proper RTX encapsulation (not a raw
        replay): pre-SRTP copies from the track's cache, OSN spliced in,
        RTX SSRC/PT/seq space, protected under the receiver's RTX row."""
        for track in set(self._video.values()):
            if sid not in track.fwd or \
                    nack.media_ssrc != track.out_ssrc:
                continue
            rtx_row = track.rtx_sid.get(sid)
            if rtx_row is None:
                return False
            key = (sid << 32) | track.out_ssrc
            copies, missing = track.precache.lookup_nack(
                key, nack.lost_seqs, return_missing=True)
            self.recovery.rtx_cache_miss += len(missing)
            if not copies:
                return True          # ours, but aged out of the cache
            if not self.recovery.allow_rtx(
                    sum(len(c) for c in copies), self._now):
                return True          # over the retransmission budget
            self.recovery.rtx_requests_served += len(copies)
            b = PacketBatch.from_payloads(copies,
                                          stream=[rtx_row] * len(copies))
            out = rtx_mod.encapsulate_batch(b, track.rtx_ssrc,
                                            track.rtx_pt,
                                            track.rtx_seq[sid])
            track.rtx_seq[sid] = (track.rtx_seq[sid]
                                  + out.batch_size) & 0xFFFF
            wire = self.tx_table.protect_rtp(out)
            with self.loop.tracer.span("egress"):
                sent = self.loop.engine.send_batch(
                    wire, self.loop.addr_ip[sid],
                    self.loop.addr_port[sid])
                # NACK-arrival -> RTX-egress is this tick's journey
                self.loop.note_journey(sent, sids=[sid])
            self.retransmitted += sent
            if self.flight is not None:
                self.flight.record("rtx_served", sid=sid,
                                   ssrc=int(track.out_ssrc),
                                   n=len(copies), rtx=True)
            _log.debug("video_nack_rtx", sid=sid, sent=sent)
            return True
        return False

    def _join_conference(self, sid: int, conf: int) -> None:
        self._leave_conference(sid)
        self._conf_of[sid] = conf
        self._members_of.setdefault(conf, set()).add(sid)

    def _leave_conference(self, sid: int):
        """Forget `sid`'s conference; returns it (None: it had none)."""
        conf = self._conf_of.pop(sid, None)
        if conf is not None:
            members = self._members_of[conf]
            members.discard(sid)
            if not members:
                del self._members_of[conf]
        return conf

    def _rebuild_routes(self, conferences=None) -> None:
        """Full mesh: every sender forwards to every OTHER endpoint.
        DTLS-pending rows have no leg keys yet and stay out of the mesh
        until their install completes; staged rows (lifecycle admit in
        flight) stay out until their commit barrier.

        `conferences`: rebuild these conferences' routes alone (ids as
        `_conf_of` has them, -1 the rows without one): a route depends
        on nothing outside its sender's conference, so a commit of a
        few joins reconnects those conferences' senders and not every
        live one.  None: all."""
        conf_of = self._conf_of
        only = conferences if conf_of else None
        if only is None:
            members = self._ssrc_of
        else:
            members = [s for c in only
                       for s in self._members_of.get(c, ())]
            if -1 in only:
                members += [s for s in self._ssrc_of if s not in conf_of]
        sids = sorted(
            s for s in members
            if s in self._ssrc_of and s not in self._dtls.pending
            and s not in self._staged)
        if self._conf_of:
            # conference-scoped mesh: a sender fans out only within its
            # conference (rows without an id share the -1 group)
            groups: Dict[int, list] = {}
            for s in sids:
                groups.setdefault(self._conf_of.get(s, -1), []).append(s)
            for conf, grp in groups.items():
                speakers = self._bcast_speakers.get(conf)
                if speakers is None:
                    for s in grp:
                        self.translator.connect(
                            s, [r for r in grp if r != s])
                else:
                    # broadcast conference: only speakers have legs —
                    # a speaker fans out to every other member; the
                    # listeners are fanout-only rows with no route of
                    # their own (their uplink is masked in the loop)
                    for s in grp:
                        self.translator.connect(
                            s, [r for r in grp if r != s]
                            if s in speakers else [])
        else:
            for s in sids:
                self.translator.connect(s, [r for r in sids if r != s])

    # --------------------------------------------------------------- tick
    def _on_media(self, batch: PacketBatch, _ok) -> None:
        """Decrypt once, fan out; the NEXT tick caches and sends.

        The fan-out's re-encrypt is DISPATCHED here, its copy back
        asked for at once, and the tick returns: the launch, the
        runtime's notice of its end and the copy run under the host
        work that follows (`supervise`, the next tick's reap, `ingress`
        and `demux`) where the tick thread used to sleep beside an idle
        chip.  The next tick collects it first thing here (wait, copy,
        NACK cache, hand-over to the egress worker: `_flush_fanout`),
        before it touches anything the launch read.  A tick that reads
        no media collects at its end (`tick`), `close` /
        `flush_egress` and every mutating entry point collect first
        (`_quiesce_fanout`): a fan-out in flight never waits for
        traffic.  There is no other shape, on one chip or on a mesh."""
        self._media_ran = True
        perf, tracer = self.loop.perf, self.loop.tracer
        self._quiesce_fanout()
        perf.note_h2d(batch.data.nbytes +
                      np.asarray(batch.length).nbytes)
        # the table splits the call into its host part and the device
        # call (`unprotect_host` / `unprotect_wait`, the latter the
        # seam's four host phases)
        with tracer.span("unprotect"):
            dec, ok, idx = self.rx_table.unprotect_rtp(
                batch, return_index=True)
        perf.note_d2h(dec.data.nbytes)
        with tracer.span("parse", rows=batch.batch_size):
            rows = np.nonzero(ok)[0]
            if len(rows) == 0:
                return None
            sub = PacketBatch(dec.data[rows],
                              np.asarray(dec.length)[rows],
                              dec.stream[rows])
            hdr = rtp_header.parse(sub)
        # uplink loss detection: gaps in each sender's seq space queue
        # upstream NACKs (drained toward the sender by emit_feedback)
        with tracer.span("recovery"):
            self.recovery.observe_rx(hdr.ssrc, hdr.seq, self._now)
        with tracer.span("bwe"):
            self._feed_bwe(sub, rows, hdr=hdr)
        if self._trunks:
            # cascade relay taps the PROTECTED ingress rows (the trunk
            # re-wraps them; participant SRTP crosses intact)
            self._relay_trunk(batch, rows, sub.stream, hdr.ssrc)
        # stamp the bridge's own abs-send-time before the fan-out so
        # every receiver leg can run receive-side GCC on its downlink
        with tracer.span("abs_send_time", rows=sub.batch_size) as sp:
            sub, _ = self._ast.rtp_transformer.transform(sub)
            sp.note(groups=self._ast.last_groups)
            idx_sel = idx[rows]
        if self._video:
            vmask = np.isin(sub.stream, list(self._video.keys()))
            if vmask.any():
                self._forward_video(sub, np.nonzero(vmask)[0])
                keep = np.nonzero(~vmask)[0]
                if len(keep) == 0:
                    return None
                sub = PacketBatch(sub.data[keep],
                                  np.asarray(sub.length)[keep],
                                  sub.stream[keep])
                idx_sel = idx_sel[keep]
        # the translator books route / expand / fanout_dispatch inside
        # `translate_async` and fanout_wait / fanout_d2h where a launch
        # is waited for, each with the phase it is (the route loop and
        # the expansion are host_python, the residual)
        with tracer.span("forward_chain"):
            # the pending carries its ingress origin: it is collected
            # on a LATER tick, and the journey charges that wait to the
            # tick the packets arrived on
            self._pending_fanout = (
                self.translator.translate_async(
                    sub, idx_sel).copy_back_async(),
                self.loop.journey_origin())
        return None

    def _quiesce_fanout(self) -> None:
        """Collect the fan-out in flight BEFORE mutating state it may
        still read: SRTP/translator key tensors are rewritten in place
        (a dispatched launch can alias them zero-copy on CPU), and a
        recycled row must not receive a departed endpoint's old-key
        packets.  Every mutating entry point (add/remove endpoint,
        DTLS install, video track/receiver attach) calls this first."""
        if self._pending_fanout is not None:
            self._flush_fanout()

    def _flush_fanout(self) -> None:
        """Collect the fan-out in flight: wait for, cache and hand
        over each of its launches in row order (`_emit_fanout`; the
        egress worker is FIFO, so every socket sees the order one
        launch would have given it, and it sends launch 1 while launch
        2 is awaited).  A tick of one launch comes out of
        `translator.translate`, which finds the pending in flight and
        dispatches nothing (the seam `benchmarks/sut.py:break_fanout`
        wraps); a tick cut into several a launch at a time, its spans
        saying which (`launch`).  The spans are this tick's time and
        carry the dispatching tick's id (`tracer.on_behalf_of`)."""
        (pend, origin), self._pending_fanout = self._pending_fanout, None
        several = pend.launches > 1
        with self.loop.tracer.on_behalf_of(origin[0]):
            parts = pend.each() if several else \
                [self.translator.translate(pend.batch, pend.index)]
            for k, (wire, recv) in enumerate(parts):
                self._emit_fanout(wire, recv, origin,
                                  {"launch": k} if several else None)

    def _emit_fanout(self, wire: PacketBatch, recv: np.ndarray,
                     origin, nth=None) -> None:
        """Cache, then hand over, the rows of ONE fan-out launch whose
        packets arrived at journey `origin` (`nth`: what its spans book
        beside their own counts)."""
        if wire.batch_size == 0:
            return
        nth = nth or {}
        with self.loop.tracer.span("nack_cache", rows=wire.batch_size,
                                   **nth) as sp:
            # a just-joined leg has no latched address yet: sending to
            # 0.0.0.0:0 would EINVAL out of sendmmsg and crash the tick
            ready = self.loop.addr_port[recv] != 0
            copied = not ready.all()
            if copied:
                if not ready.any():
                    return
                rr = np.nonzero(ready)[0]
                wire = PacketBatch(wire.data[rr],
                                   np.asarray(wire.length)[rr],
                                   wire.stream[rr])
                recv = recv[rr]
            # cache each leg's protected copy for NACK service, keyed
            # by (leg sid, SENDER ssrc) + original seq — seq survives
            # the fan-out, and two senders' seq ranges must never
            # collide in one leg's cache.  The cache keeps the tick's
            # wire plane as it came back from the device (or the
            # filtered copy): nothing writes to either afterwards.
            cache = self.cache
            evicted = cache.insert_batch(wire.data, wire.length, recv,
                                         now=self._now)
            sp.note(slabs=cache.slabs, live_rows=len(cache),
                    evicted_rows=evicted, copied=int(copied))
        # the hand-over: the sendmmsg runs on the engine's egress
        # worker while the tick goes on.  The plane is final (the cache
        # above keeps the same one) and the addresses are copies, which
        # is what lets this call site, and no other of the file, send
        # asynchronously.  `_reap_egress` books the outcome next tick
        with self.loop.tracer.span(
                "egress", rows=wire.batch_size, **nth,
                bytes=int(np.asarray(wire.length).sum())) as sp:
            job = self.loop.engine.send_batch_async(
                wire, self.loop.addr_ip[recv], self.loop.addr_port[recv])
            sp.note(queued=1, behind=int(job.behind))
        self._egress_jobs[job.id] = (origin, recv)
        # adaptive FEC over the PROTECTED per-leg copies: XOR of SRTP
        # ciphertexts is opaque, and a recovered packet still passes the
        # receiver's normal SRTP auth — FEC adds redundancy, never an
        # injection surface.  One FEC stream per (leg, sender ssrc).
        if self.recovery.fec_active():
            fec_out, fec_addr = [], []
            hdr = rtp_header.parse(wire)
            for j in range(wire.batch_size):
                fec = self.recovery.fec_protect(int(recv[j]),
                                                int(hdr.ssrc[j]),
                                                wire.to_bytes(j))
                if fec is not None:
                    fec_out.append(fec)
                    fec_addr.append(int(recv[j]))
            if fec_out:
                fa = np.asarray(fec_addr, dtype=np.int64)
                with self.loop.tracer.span("egress"):
                    self.loop.engine.send_batch(
                        PacketBatch.from_payloads(fec_out),
                        self.loop.addr_ip[fa], self.loop.addr_port[fa])
                if self.flight is not None:
                    for fsid in set(fec_addr):
                        self.flight.record(
                            "fec_sent", sid=fsid,
                            n=fec_addr.count(fsid))

    def _feed_bwe(self, sub: PacketBatch, rows: np.ndarray,
                  hdr=None) -> None:
        """Drive the bridge's receive-side GCC from the senders'
        abs-send-time stamps.  Arrival times prefer the engine's kernel
        rx stamps (row-aligned via MediaLoop.last_rtp_arrival_ns);
        without them, the tick's host clock."""
        if hdr is None:
            hdr = rtp_header.parse(sub)
        off, dlen, found = rtp_ext.find_one_byte_ext(sub, hdr,
                                                     self.ast_ext_id)
        f = np.nonzero(found & (dlen == 3))[0]
        if len(f) == 0:
            return
        d = sub.data
        o = off[f]
        ast24 = ((d[f, o].astype(np.int64) << 16)
                 | (d[f, o + 1].astype(np.int64) << 8)
                 | d[f, o + 2].astype(np.int64))
        ats = self.loop.last_rtp_arrival_ns
        if ats is not None:
            arrival_ms = ats[rows][f].astype(np.float64) / 1e6
        else:
            arrival_ms = np.full(len(f), self._now * 1000.0)
        tids = self._transport_of[sub.stream[f].astype(np.int64)]
        self.bwe.incoming_batch(tids, arrival_ms, ast24,
                                np.asarray(sub.length)[f])
        self._bwe_fed[tids] = True

    def own_estimate_bps(self, sid: int) -> Optional[float]:
        """The bridge's current receive-side estimate for a sender leg
        (None until that sender's abs-send-time stamps have fed it)."""
        if not self._bwe_fed[sid]:
            return None
        return float(self.bwe.bitrate[sid])

    def _on_rtcp(self, batch: PacketBatch, _ok) -> None:
        """SRTCP-authenticate, then: NACK -> retransmit from the
        per-leg cache; everything else feeds RTCP termination (REMB
        aggregation, PLI dedupe).  Unauthenticated control packets are
        dropped — a spoofed NACK is a retransmission amplifier and a
        spoofed REMB caps the conference bitrate."""
        dec, ok = self.rx_table.unprotect_rtcp(batch)
        for i in np.nonzero(np.asarray(ok))[0]:
            sid = int(batch.stream[i])
            try:
                pkts = rtcp.parse_compound(dec.to_bytes(int(i)))
            except ValueError:
                continue
            self.rtcp_term.on_receiver_rtcp(sid, pkts)
            for p in pkts:
                if isinstance(p, rtcp.Nack):
                    if not self._serve_video_nack(sid, p):
                        self._serve_nack(sid, p)
                elif isinstance(p, rtcp.Remb):
                    # receiver's downlink estimate drives its simulcast
                    # layer selection
                    self._recv_bw[sid] = float(p.bitrate_bps)
                elif isinstance(p, (rtcp.ReceiverReport,
                                    rtcp.SenderReport)):
                    # reported downlink loss drives the FEC ratio
                    for rb in p.reports:
                        self.recovery.on_receiver_report(
                            rb.fraction_lost)

    def _serve_nack(self, sid: int, nack: "rtcp.Nack") -> None:
        key = (sid << 32) | (nack.media_ssrc & 0xFFFFFFFF)
        copies, missing = self.cache.lookup_nack(key, nack.lost_seqs,
                                                 return_missing=True)
        self.recovery.rtx_cache_miss += len(missing)
        if missing and self.flight is not None:
            self.flight.record("rtx_cache_miss", sid=sid,
                               ssrc=int(nack.media_ssrc),
                               n=len(missing))
        if not copies:
            return
        if not self.recovery.allow_rtx(sum(len(c) for c in copies),
                                       self._now):
            return      # over the retransmission-bandwidth budget
        out = PacketBatch.from_payloads(copies)
        with self.loop.tracer.span("egress"):
            sent = self.loop.engine.send_batch(
                out, self.loop.addr_ip[sid], self.loop.addr_port[sid])
            self.loop.note_journey(sent, sids=[sid])
        self.retransmitted += sent
        self.recovery.rtx_requests_served += len(copies)
        if self.flight is not None:
            self.flight.record("rtx_served", sid=sid,
                               ssrc=int(nack.media_ssrc), n=len(copies))
        _log.debug("nack_served", sid=sid, lost=len(nack.lost_seqs),
                   sent=sent)

    def emit_feedback(self, now: Optional[float] = None) -> int:
        """Drain RTCP termination toward each media sender: aggregated
        RR + min-REMB + merged NACKs + rate-limited PLI, SRTCP-protected
        with the sender leg's keys.  Call periodically (the reference's
        RecurringRunnable cadence); also drains the accumulation so a
        long-lived conference does not grow state unboundedly."""
        if self.degraded:
            # overload: RTCP reports are the first work shed (senders
            # coast on their last estimates; media is untouched)
            return 0
        now = time.time() if now is None else now
        sent = 0
        # periodic GCC tick: every fed sender leg's estimate advances
        # (AIMD increase in normal state, beta-cut on overuse)
        if self._bwe_fed.any():
            self.bwe.update_estimate(now * 1000.0)
        # bridge-detected uplink losses (budgeted, held off, deduped by
        # the NackScheduler) merge into the same termination window as
        # receiver-relayed NACKs
        with self.loop.tracer.span("recovery"):
            upstream = self.recovery.collect_upstream_nacks(now)
        for ssrc, seqs in upstream.items():
            self.rtcp_term.queue_nack(ssrc, seqs)
        if self._video:
            self._select_video_layers()
        for sid, ssrc in list(self._ssrc_of.items()):
            own = self.own_estimate_bps(sid)
            blobs = self.rtcp_term.make_sender_feedback(ssrc, now=now,
                                                        own_bps=own)
            # video senders also get per-layer feedback (the PLIs that
            # gate a pending layer switch are keyed by layer SSRC for
            # simulcast, by the stream SSRC for SVC)
            for track in set(self._video.values()):
                if track.sender_sid == sid:
                    for lssrc in track.layer_ssrcs:
                        blobs += self.rtcp_term.make_sender_feedback(
                            lssrc, now=now)
            # a video-only sender latches addresses on its LAYER rows,
            # not the primary sid — fall back so PLIs still reach it
            arow = sid
            if self.loop.addr_port[arow] == 0:
                for track in set(self._video.values()):
                    if track.sender_sid != sid:
                        continue
                    arow = next((l for l in track.layer_sids
                                 if self.loop.addr_port[l] != 0), sid)
            if self.loop.addr_port[arow] == 0 or not blobs:
                continue
            b = PacketBatch.from_payloads(
                [rtcp.build_compound(blobs)], stream=[sid])
            wire = self.tx_table.protect_rtcp(b)
            sent += self.loop.engine.send_batch(
                wire, self.loop.addr_ip[arow],
                self.loop.addr_port[arow])
        return sent

    def _reap_egress(self) -> None:
        """Book the fan-out bursts the egress worker has completed:
        `forwarded`, the journey (measured to the worker's END stamp,
        not to now) and the send's own duration as `egress_send`.  A
        failed send raises here as the synchronous call raised in its
        own tick; a short one counts what was sent.  Never waits."""
        failed = None
        for done in self.loop.engine.reap():
            origin, recv = self._egress_jobs.pop(done.id)
            if done.sent < 0:
                failed = failed or done
                continue
            self.forwarded += done.sent
            self.loop.note_journey_at(origin, done.sent, sids=recv,
                                      at=done.t1)
            self.loop.tracer.book("egress_send", done.t1 - done.t0,
                                  rows=done.sent)
        if failed is not None:
            raise OSError(-failed.sent, os.strerror(-failed.sent))

    def flush_egress(self) -> None:
        """Collect the fan-out in flight, wait until every burst
        handed over has left, and book it.  For shutdown and for tests
        that read a client socket right after a tick; the tick itself
        never calls it (that would be the serial tick and the
        synchronous send again)."""
        self._quiesce_fanout()
        self.loop.engine.flush()
        self._reap_egress()

    def tick(self, now: Optional[float] = None) -> dict:
        self._now = time.time() if now is None else now
        self._media_ran = False
        if self._egress_jobs:
            self._reap_egress()
        rx = self.loop.tick()
        if not self._media_ran:
            # no media drove _on_media this tick: collect here instead
            # (collecting a fan-out dispatched THIS tick would put the
            # wait back, hence the flag, not an rx check)
            self._quiesce_fanout()
        if self._dtls.pending and not self._dtls.deferred:
            # inline mode only: with a lifecycle manager attached the
            # flight pass runs off-tick (HandshakeQueue.drain)
            self._dtls.tick()
        return {"rx": rx, "forwarded": self.forwarded,
                "retransmitted": self.retransmitted}

    # ----------------------------------------------------------- resume
    def snapshot(self) -> dict:
        """Checkpoint the conference's durable state (SURVEY §5): SRTP
        indices + replay windows (both tables), the per-sender BWE bank,
        endpoint rows/keys/SSRCs, receiver REMBs and latched addresses —
        a restarted bridge resumes mid-conference without re-keying, so
        senders' SRTP counters keep authenticating and nothing glitches.

        Transient state is deliberately excluded and re-established by
        the protocol itself: mid-handshake DTLS endpoints (keyless —
        they rejoin via signaling and fresh flights), video tracks
        (re-attach via add_video_track/add_svc_track; their forwarders
        re-anchor on the next keyframe), and the NACK caches (age out
        in ~1 s anyway).
        """
        self._quiesce_fanout()
        keyed = {sid: ssrc for sid, ssrc in self._ssrc_of.items()
                 if sid in self._tx_keys}
        return {
            "capacity": self.capacity,
            "profile": self.profile.name,
            "sharded": self._mesh is not None,
            "ast_ext_id": self.ast_ext_id,
            # recover must not silently flip I/O engines: a restart in
            # the middle of an A/B perf run would contaminate the run
            "engine_mode": self.engine_mode,
            "ingest_rings": self.ingest_rings,
            "rx_table": self.rx_table.snapshot(),
            "tx_table": self.tx_table.snapshot(),
            "bwe": self.bwe.snapshot(),
            "bwe_fed": self._bwe_fed.copy(),
            "ssrc_of": keyed,
            "rx_keys": dict(self._rx_keys),
            "tx_keys": dict(self._tx_keys),
            "recv_bw": {s: bw for s, bw in self._recv_bw.items()
                        if s in keyed},
            "conf_of": {s: c for s, c in self._conf_of.items()
                        if s in keyed},
            "bcast_speakers": {c: sorted(s) for c, s in
                               self._bcast_speakers.items()},
            "addr_ip": self.loop.addr_ip.copy(),
            "addr_port": self.loop.addr_port.copy(),
        }

    @classmethod
    def restore(cls, config, snap: dict, port: int = 0,
                **kwargs) -> "SfuBridge":
        """Resume a snapshotted conference (fresh socket on `port`).

        Endpoint rows reoccupy their exact old sids (registry.reserve)
        so the restored SRTP tables and SSRC demux line up; the
        translator re-derives its per-leg session keys from the stored
        leg master keys (derivation is deterministic, RFC 3711 KDF).
        """
        from libjitsi_tpu.transform.srtp import SrtpStreamTable as _T

        kwargs.setdefault("engine_mode", snap.get("engine_mode", "auto"))
        kwargs.setdefault("ingest_rings", snap.get("ingest_rings", 1))
        bridge = cls(config, port=port, capacity=snap["capacity"],
                     profile=SrtpProfile[snap["profile"]],
                     abs_send_time_ext_id=snap["ast_ext_id"], **kwargs)
        if snap.get("sharded") and bridge._mesh is None:
            raise ValueError(
                "snapshot came from a MESH bridge; pass mesh=... to "
                "restore (resuming single-chip would silently un-shard "
                "the deployment)")
        if bridge._mesh is not None:
            # a mesh deployment must resume SHARDED, not silently
            # single-chip (same rule as ConferenceBridge.restore)
            from libjitsi_tpu.mesh import ShardedSrtpTable
            bridge.rx_table = ShardedSrtpTable.restore(
                snap["rx_table"], bridge._mesh)
            bridge.tx_table = ShardedSrtpTable.restore(
                snap["tx_table"], bridge._mesh)
        else:
            bridge.rx_table = _T.restore(snap["rx_table"])
            bridge.tx_table = _T.restore(snap["tx_table"])
        bridge._share_tracer()
        bridge.bwe = BatchedRemoteBitrateEstimator.restore(snap["bwe"])
        bridge._bwe_fed = np.asarray(snap["bwe_fed"]).copy()
        bridge._rx_keys = dict(snap["rx_keys"])
        bridge._tx_keys = dict(snap["tx_keys"])
        bridge._recv_bw = dict(snap["recv_bw"])
        for s, c in snap.get("conf_of", {}).items():
            bridge._join_conference(int(s), int(c))
        bridge._bcast_speakers = {
            int(c): {int(s) for s in spk}
            for c, spk in snap.get("bcast_speakers", {}).items()}
        for sid, conf in bridge._conf_of.items():
            if conf in bridge._bcast_speakers:
                bridge.loop.set_fanout_only(
                    sid, sid not in bridge._bcast_speakers[conf])
        sids = sorted(snap["ssrc_of"])
        bridge.registry.reserve_many(sids, bridge)
        for sid in sids:
            ssrc = snap["ssrc_of"][sid]
            bridge.registry.map_ssrc(ssrc, sid)
            bridge._ssrc_of[sid] = ssrc
        bridge.translator.add_receivers(
            sids, [bridge._tx_keys[s][0] for s in sids],
            [bridge._tx_keys[s][1] for s in sids])
        bridge._rebuild_routes()
        # per-row state copies only onto RESERVED rows; anything else
        # (old video layer rows, departed endpoints) must come back
        # zeroed or a later alloc of that row would inherit a stale
        # latched address / BWE estimate
        keep = np.zeros(snap["capacity"], dtype=bool)
        keep[sids] = True
        bridge.loop.addr_ip[:] = np.where(keep, snap["addr_ip"], 0)
        bridge.loop.addr_port[:] = np.where(keep, snap["addr_port"], 0)
        bridge._bwe_fed &= keep
        stale = np.nonzero(~keep)[0]
        if len(stale):
            bridge.bwe.reset_rows(stale)
        return bridge

    def close(self) -> None:
        try:
            self.flush_egress()       # the last tick's media still ships
        finally:
            for eng in self.loop.rings:
                eng.close()
