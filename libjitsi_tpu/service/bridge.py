"""ConferenceBridge — the whole audio-bridge tick as one object.

The reference assembles a conference from many moving parts: an
`AudioMixerMediaDevice` capture device, one `MediaStream` +
FMJ Processor per participant, connector threads, and the SRTP
transformers each stream installs (SURVEY §3.3's receive path feeding
§2.4's mixer, then §3.2's send path per participant).  This class is
that assembly in the dense design: ONE MediaLoop (batched UDP +
reverse chain), ONE ReceiveBank (dense jitter + decode), ONE AudioMixer
row range, and a batched encode→packetize→protect→send tail — a whole
conference tick is a handful of array programs regardless of
participant count.

Tick flow (one ptime, default 20 ms):

    loop.tick()            drain socket -> demux -> batched unprotect
       -> bank.push_decrypted (dense jitter insert)
    bank.tick()            pop due frames -> decode -> mixer deposit
    mixer.mix()            mix-minus + RFC 6465 levels (device)
    encode rows            per-codec (G.711 vectorized; stateful via C)
    loop.send_media()      packetize + batched protect -> sendmmsg

Keying is SDES-style static master keys per participant (rx = what the
participant sends with, tx = what we send to them with); DTLS/ZRTP
controls can feed the same install calls.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from libjitsi_tpu.conference.mixer import AudioMixer
from libjitsi_tpu.conference.speaker import DominantSpeakerIdentification
from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.io.loop import MediaLoop
from libjitsi_tpu.io.udp import UdpEngine
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.service.media_stream import StreamRegistry
from libjitsi_tpu.service.pump import FrameCodec, ReceiveBank, g711_codec
from libjitsi_tpu.transform import (SrtpTransformEngine,
                                    TransformEngineChain)
from libjitsi_tpu.transform.header_ext import CsrcAudioLevelEngine
from libjitsi_tpu.transform.srtp import SrtpProfile, SrtpStreamTable
from libjitsi_tpu.utils.logging import get_logger

_log = get_logger("service.bridge")


class ConferenceBridge:
    """A secure N-party audio bridge on one UDP port."""

    def __init__(self, config, port: int = 0, capacity: int = 256,
                 profile: SrtpProfile =
                 SrtpProfile.AES_CM_128_HMAC_SHA1_80,
                 ptime_ms: int = 20, kernel_timestamps: bool = False,
                 recv_window_ms: int = 1,
                 audio_level_ext_id: int = 1,
                 on_speaker_change=None,
                 recorder=None,
                 pipelined: bool = False,
                 pipeline_depth: int = 1,
                 mesh=None,
                 plc: bool = False):
        self.capacity = capacity
        self.profile = profile
        self.ptime_ms = ptime_ms
        # opt-in packet-loss concealment in the receive bank (the
        # NACK->RTX->FEC->PLC ladder's last rung; see sfu/recovery.py)
        self._plc = plc
        self.registry = StreamRegistry(config, capacity=capacity)
        # mesh mode (SURVEY §2.7): the bridge's SRTP
        # tables row-partition over the device mesh and the mixer's
        # participant axis psums over ICI — the ASSEMBLED bridge tick
        # runs sharded, not just its kernels
        self._mesh = mesh
        if mesh is not None:
            # composes with pipelined=True: the sharded seams defer
            # their wire-order scatter (mesh/table._LazyArray), so the
            # dispatch seam overlaps launches in mesh mode too
            from libjitsi_tpu.mesh import ShardedSrtpTable
            self.rx_table = ShardedSrtpTable(capacity, mesh, profile)
            self.tx_table = ShardedSrtpTable(capacity, mesh, profile)
        else:
            self.rx_table = SrtpStreamTable(capacity, profile)
            self.tx_table = SrtpStreamTable(capacity, profile)
        # egress audio-level stamping (RFC 6465 mixer-to-client, the
        # engine's one-byte element = the loudest contributor heard in
        # that receiver's mix-minus) sits BEFORE SRTP in the forward
        # chain; the reverse chain extracts participants' RFC 6464
        # levels for free.  Reference: .csrc.CsrcTransformEngine.
        self._egress_levels = np.full(capacity, 127, dtype=np.uint8)
        self._level_ext_id = audio_level_ext_id
        self.levels_engine = CsrcAudioLevelEngine(
            audio_level_ext_id, capacity,
            level_of=lambda sids: self._egress_levels[sids])
        self.chain = TransformEngineChain(
            [self.levels_engine,
             SrtpTransformEngine(self.tx_table, self.rx_table)])
        # dominant-speaker detection fed by the mixer's per-tick levels
        # (reference: ActiveSpeakerDetectorImpl on the mixer device)
        self.on_speaker_change = on_speaker_change
        self.recorder = recorder
        self.speaker = DominantSpeakerIdentification(
            capacity, on_change=self._speaker_changed)
        self.speaker_events: List[Tuple[int, int]] = []  # (tick, sid)
        self.loop = MediaLoop(
            UdpEngine(port=port, max_batch=4 * capacity,
                      kernel_timestamps=kernel_timestamps),
            self.registry, on_media=self._on_media, chain=self.chain,
            on_dtls=lambda d, a: self._dtls.on_dtls(d, a),
            recv_window_ms=recv_window_ms, pipelined=pipelined,
            pipeline_depth=pipeline_depth)
        from libjitsi_tpu.control.dtls import DtlsAssociationTable
        self._dtls = DtlsAssociationTable(self.loop, profile,
                                          self._install_dtls)
        self.port = self.loop.engine.port
        # one mixer frame clock per bridge (first codec sets it);
        # other-rate codecs resample to it on both paths
        self._frame_samples: Optional[int] = None
        self._rate: Optional[int] = None
        self.mixer: Optional[AudioMixer] = None
        self.bank: Optional[ReceiveBank] = None
        self._codec: Dict[int, FrameCodec] = {}
        self._ssrc_of: Dict[int, int] = {}      # sid -> mapped rx ssrc
        self._tx_seq = np.zeros(capacity, dtype=np.int64)
        self._tx_ts = np.zeros(capacity, dtype=np.int64)
        self._tx_ssrc = np.zeros(capacity, dtype=np.int64)
        # overload degradation (set by BridgeSupervisor): skip the
        # non-essential tick work — speaker scoring, recorder events,
        # egress level stamping — while media keeps flowing
        self.degraded = False
        # flight recorder slot (attached by BridgeSupervisor; shared
        # with self.loop for packet-header sampling)
        self.flight = None
        self.ticks = 0

    # ------------------------------------------------------- participants
    def add_participant(self, ssrc: int, rx_key: Tuple[bytes, bytes],
                        tx_key: Tuple[bytes, bytes],
                        codec: Optional[FrameCodec] = None) -> int:
        """Join: install keys + codec, map the SSRC, return the row id.

        `rx_key` protects what the participant sends us; `tx_key`
        protects what we send them (SDES-style separate directions).
        """
        sid = self._register_media(ssrc, codec)
        self.rx_table.add_stream(sid, *rx_key)
        self.tx_table.add_stream(sid, *tx_key)
        _log.info("participant_join", sid=sid, ssrc=ssrc)
        return sid

    def has_ssrc(self, ssrc: int) -> bool:
        """Whether a participant has joined under this SSRC (what
        admission asks of any bridge)."""
        return (ssrc & 0xFFFFFFFF) in self._ssrc_of.values()

    def _register_media(self, ssrc: int,
                        codec: Optional[FrameCodec]) -> int:
        """Crypto-independent join half: row, demux, bank/mixer/speaker."""
        codec = codec or g711_codec(ptime_ms=self.ptime_ms)
        if (codec.frame_samples * 1000
                != codec.sample_rate * self.ptime_ms):
            raise ValueError(
                f"codec ptime {codec.frame_samples * 1000.0 / codec.sample_rate:.1f} ms "
                f"!= bridge ptime {self.ptime_ms} ms")
        if self.has_ssrc(ssrc):
            # silently remapping would mute the existing participant
            raise ValueError(f"ssrc {ssrc:#x} already joined")
        sid = self.registry.alloc(self)
        self._attach_media_row(sid, ssrc, codec)
        return sid

    def _attach_media_row(self, sid: int, ssrc: int,
                          codec: FrameCodec) -> None:
        """Join bookkeeping for a CLAIMED row (alloc'd or reserved):
        bridge clock/mixer/bank bootstrap on first attach, demux map,
        bank/mixer/speaker rows, randomized TX counters (checkpoint
        restore overwrites those afterwards).  Shared by live joins and
        `restore` so resumed conferences cannot diverge from live ones."""
        if self._frame_samples is None:
            # the first participant's codec sets the bridge clock; later
            # joins at other rates resample to it (reference: AudioMixer
            # normalizing via the Speex resampler, SURVEY §2.4/§2.5)
            self._bootstrap_clock(codec.frame_samples, codec.sample_rate)
        self.registry.map_ssrc(ssrc, sid)
        self.bank.add_stream(sid, codec)
        self.mixer.add_participant(sid)
        self.speaker.add_participant(sid)
        self._codec[sid] = codec
        self._ssrc_of[sid] = ssrc & 0xFFFFFFFF
        self._tx_seq[sid] = int.from_bytes(np.random.bytes(2), "big")
        self._tx_ts[sid] = int.from_bytes(np.random.bytes(4), "big")
        self._tx_ssrc[sid] = (0x42000000 + sid) & 0xFFFFFFFF

    def warmup(self) -> None:
        """Pre-compile the tick's device programs before going live so
        no 20 ms tick absorbs an XLA compile (reference analog: the
        crypto.Aes startup benchmark).  The mixer warms at construction;
        this warms the SRTP tables — in mesh mode the shard_map lane
        ladder, and for GCM profiles the grouped/per-row measurement."""
        max_batch = 4 * self.capacity
        for table in (self.rx_table, self.tx_table):
            if hasattr(table, "warmup"):          # mesh table ladder
                table.warmup(max_batch)
            else:
                table.warmup_rtp(min(max_batch, 256))

    def _bootstrap_clock(self, frame_samples: int, rate: int) -> None:
        """Fix the bridge clock and build the mixer + receive bank
        (first join live; snapshot restore re-applies the RECORDED
        clock so a mixed-rate conference resumes on the same one)."""
        self._frame_samples = frame_samples
        self._rate = rate
        mix_fn = None
        if self._mesh is not None:
            from libjitsi_tpu.mesh import (sharded_mix_minus,
                                           sharded_mix_minus_2d)
            from libjitsi_tpu.mesh.sharded import DCN_AXIS
            # on the 2-D (dcn, streams) mesh the participant sum must
            # psum over BOTH axes (ICI within a host, DCN across)
            mix_fn = (sharded_mix_minus_2d(self._mesh)
                      if DCN_AXIS in self._mesh.axis_names
                      else sharded_mix_minus(self._mesh))
        self.mixer = AudioMixer(capacity=self.capacity,
                                frame_samples=frame_samples,
                                mix_fn=mix_fn)
        self.bank = ReceiveBank(self.capacity, mixer=self.mixer,
                                payload_cap=max(256, frame_samples),
                                mixer_rate=rate, plc=self._plc)
        # the bank is born AFTER any supervisor registered its metrics
        # (first join builds it), so it exports itself on the loop's
        # registry; name-keyed registration makes a restore's rebuilt
        # bank overwrite the old closures rather than duplicate them
        self.bank.register_metrics(self.loop.metrics)

    def add_participant_dtls(self, ssrc: int,
                             codec: Optional[FrameCodec] = None,
                             role: str = "server",
                             remote_fingerprint: Optional[str] = None,
                             cookie_exchange: bool = False,
                             remote_addr=None):
        """Join keyed by DTLS-SRTP: media registration happens now,
        SRTP keys install when the handshake completes; early media is
        queued and replayed (MediaLoop.hold_stream).  Returns
        (sid, endpoint); pass `remote_addr` when signaling knows the
        peer's 5-tuple.  Reference: DtlsControlImpl under
        MediaStream.start (SURVEY §3.5)."""
        sid = self._register_media(ssrc, codec)
        ep = self._dtls.join(sid, role, remote_fingerprint,
                             cookie_exchange, remote_addr)
        _log.info("participant_join_dtls", sid=sid, ssrc=ssrc,
                  role=role)
        return sid, ep

    def _install_dtls(self, sid: int, ep) -> None:
        profile, tk, tsalt, rk, rsalt = ep.srtp_keys()
        self.rx_table.add_stream(sid, rk, rsalt)
        self.tx_table.add_stream(sid, tk, tsalt)
        _log.info("dtls_keys_installed", sid=sid, profile=profile.name)

    def remove_participant(self, sid: int) -> None:
        """Leave: every per-row residue must go — a recycled sid must
        not demux the old SSRC, keep old keys, or inherit the old
        latched address (late packets would otherwise redirect the NEW
        occupant's media to the OLD participant's socket)."""
        ssrc = self._ssrc_of.pop(sid, None)
        if ssrc is not None:
            self.registry.unmap_ssrc(ssrc)
        self.rx_table.remove_stream(sid)
        self.tx_table.remove_stream(sid)
        self._dtls.forget(sid)
        self.loop.addr_ip[sid] = 0
        self.loop.addr_port[sid] = 0
        self.bank.remove_stream(sid)
        self.mixer.remove_participant(sid)
        self.speaker.remove_participant(sid)
        self._egress_levels[sid] = 127
        self._codec.pop(sid, None)
        self.registry.release(sid)
        _log.info("participant_leave", sid=sid)

    # --------------------------------------------------------------- tick
    def _on_media(self, batch: PacketBatch, ok: np.ndarray):
        self.bank.push_decrypted(batch, ok, now=self._now)
        return None

    def tick(self, now: Optional[float] = None) -> dict:
        """One ptime: returns counters for observability."""
        self._now = time.time() if now is None else now
        rx = self.loop.tick()
        if self._dtls.pending:
            self._dtls.tick()
        if self.bank is None:         # no participants yet
            return {"rx": rx, "mixed": 0, "tx": 0,
                    "trace": self.loop.trace_id,
                    "levels": np.zeros(0, dtype=np.uint8),
                    "dominant": -1}
        with self.loop.tracer.span("decode"):
            sids, _frames = self.bank.tick(now=self._now)
        with self.loop.tracer.span("mixer"):
            out, levels = self.mixer.mix()
            if not self.degraded:
                self.speaker.levels(levels)
                self._update_egress_levels(levels)
        tx = self._send_mixes(out)
        self.ticks += 1
        # trace is the tick's journey id: grep it in flight `hdr`
        # events and in packet_journey_seconds exemplars
        return {"rx": rx, "mixed": len(sids), "tx": tx,
                "trace": self.loop.trace_id,
                "levels": levels, "dominant": self.speaker.dominant}

    def _speaker_changed(self, sid: int) -> None:
        self.speaker_events.append((self.ticks, sid))
        ssrc = self._ssrc_of.get(sid)
        _log.info("speaker_change", sid=sid, ssrc=ssrc)
        if self.recorder is not None and ssrc is not None:
            self.recorder.on_speaker_change(ssrc)
        if self.on_speaker_change is not None:
            self.on_speaker_change(sid, ssrc)

    def _update_egress_levels(self, levels: np.ndarray) -> None:
        """Each receiver's egress level = loudest OTHER contributor
        (min dBov excluding self), i.e. the level of the mix it hears:
        overall min + second-min, one vector pass."""
        act = self.mixer.active
        lv = np.where(act, levels[:len(act)].astype(np.int64), 128)
        order = np.argsort(lv)
        m1, m1_row = int(lv[order[0]]), int(order[0])
        m2 = int(lv[order[1]]) if len(order) > 1 else 128
        outl = np.full(self.capacity, m1, dtype=np.int64)
        outl[m1_row] = m2
        self._egress_levels[:] = np.minimum(outl, 127).astype(np.uint8)

    def _send_mixes(self, out: np.ndarray) -> int:
        """Encode each active participant's mix-minus row and send it
        through the forward chain to their latched address.  G.711 rows
        encode as ONE vectorized kernel call (like ReceiveBank's decode
        grouping); only stateful codecs pay a per-row C call."""
        from libjitsi_tpu.kernels import g711

        # pending-DTLS rows have a latched address (the handshake
        # 5-tuple) but no tx keys yet: sending would emit zero-key
        # "protected" garbage mid-handshake
        active = [sid for sid in self._codec
                  if self.loop.addr_port[sid] != 0
                  and sid not in self._dtls.pending]
        if not active:
            return 0
        payloads: Dict[int, bytes] = {}
        by_kind: Dict[str, List[int]] = {}
        for sid in active:
            by_kind.setdefault(self._codec[sid].name.upper(),
                               []).append(sid)
        for kind, rows in by_kind.items():
            # mix rows are at the bridge clock; off-rate codec legs get
            # one batched resample per kind before encoding
            pcm = self._from_bridge_rate(rows, out[np.asarray(rows)])
            if kind in ("PCMU", "PCMA"):
                fn = g711.ulaw_encode if kind == "PCMU" \
                    else g711.alaw_encode
                enc = np.asarray(fn(pcm), dtype=np.uint8)
                for k, sid in enumerate(rows):
                    payloads[sid] = enc[k].tobytes()
            else:
                for k, sid in enumerate(rows):  # stateful: per-row C
                    payloads[sid] = self._codec[sid].encode(pcm[k])
        sids = np.asarray(active, dtype=np.int64)
        steps = np.asarray([self._codec[s].ts_step for s in active],
                           dtype=np.int64)
        return self._finish_send(active, payloads, sids, steps)

    def _from_bridge_rate(self, rows: List[int], pcm: np.ndarray
                          ) -> np.ndarray:
        """Resample mix rows to a codec leg's clock (same kind => same
        rate); identity when the leg runs at the bridge clock."""
        rate = self._codec[rows[0]].sample_rate
        if rate == self._rate:
            return pcm
        from libjitsi_tpu.kernels.resample import resample_to_frame

        return resample_to_frame(pcm, self._rate, rate,
                                 self._codec[rows[0]].frame_samples)

    def _finish_send(self, active, payloads, sids, steps) -> int:
        batch = rtp_header.build(
            [payloads[s] for s in active], self._tx_seq[sids].tolist(),
            self._tx_ts[sids].tolist(), self._tx_ssrc[sids].tolist(),
            [self._codec[s].pt for s in active],
            stream=sids.tolist())
        self._tx_seq[sids] = (self._tx_seq[sids] + 1) & 0xFFFF
        self._tx_ts[sids] = (self._tx_ts[sids] + steps) & 0xFFFFFFFF
        if self.loop.pipelined:
            # dispatch only: the protect launch overlaps the next recv
            # window; bytes flush at the top of the next tick
            return self.loop.send_media_async(batch)
        return self.loop.send_media(batch)

    # ----------------------------------------------------------- resume
    _STATELESS = ("PCMU", "PCMA")

    def snapshot(self) -> dict:
        """Checkpoint the conference (SURVEY §5 at assembly level):
        SRTP tables (indices + replay windows), the dense jitter rings,
        participant rows/keys/SSRCs, TX counters, speaker-detector
        scores and latched addresses — a restarted bridge resumes the
        playout windows so nothing glitches.

        Codec legs: stateless codecs (G.711) resume bit-exactly.
        Stateful codecs (opus/G.722/GSM/speex — C predictor state that
        cannot be serialized) resume DEGRADED: the codec re-initializes
        on restore (decoder PLC warms up over the first frames, encoder
        restarts with default tuning) while SRTP counters and replay
        windows carry over exactly — streams survive instead of dying
        (SURVEY §5 checkpoint row).  `degraded_rows` in the snapshot
        names the affected legs.  Mid-DTLS participants are excluded
        (they rejoin via signaling), like the SFU snapshot.
        """
        self.loop.flush_sends()      # a pipelined tick's last frame
        keyed = {sid: ssrc for sid, ssrc in self._ssrc_of.items()
                 if sid not in self._dtls.pending}
        return {
            "capacity": self.capacity,
            "profile": self.profile.name,
            "sharded": self._mesh is not None,
            "ptime_ms": self.ptime_ms,
            "level_ext_id": self._level_ext_id,
            "rate": self._rate,
            "frame_samples": self._frame_samples,
            "rx_table": self.rx_table.snapshot(),
            "tx_table": self.tx_table.snapshot(),
            "jb": self.bank.jb.snapshot() if self.bank else None,
            "ssrc_of": keyed,
            "codec_name": {s: self._codec[s].name for s in keyed},
            "degraded_rows": sorted(
                s for s in keyed
                if self._codec[s].name.upper() not in self._STATELESS),
            "tx_seq": self._tx_seq.copy(),
            "tx_ts": self._tx_ts.copy(),
            "tx_ssrc": self._tx_ssrc.copy(),
            "addr_ip": self.loop.addr_ip.copy(),
            "addr_port": self.loop.addr_port.copy(),
            "speaker": {
                "immediate": self.speaker.immediate.copy(),
                "medium": self.speaker.medium.copy(),
                "long": self.speaker.long.copy(),
                "dominant": self.speaker.dominant,
            },
        }

    @classmethod
    def restore(cls, config, snap: dict, port: int = 0,
                **kwargs) -> "ConferenceBridge":
        """Resume a snapshotted conference on a fresh socket."""
        from libjitsi_tpu.rtp.dense_jitter import DenseJitterBank
        from libjitsi_tpu.transform.srtp import SrtpStreamTable as _T

        from libjitsi_tpu.service.pump import codec_from_name

        bridge = cls(config, port=port, capacity=snap["capacity"],
                     profile=SrtpProfile[snap["profile"]],
                     ptime_ms=snap["ptime_ms"],
                     audio_level_ext_id=snap["level_ext_id"], **kwargs)
        sids = sorted(snap["ssrc_of"])
        bridge.registry.reserve_many(sids, bridge)
        if snap.get("rate"):
            # resume on the RECORDED bridge clock (a mixed-rate
            # conference's clock came from its first joiner, who may
            # not be first in row order here)
            bridge._bootstrap_clock(snap["frame_samples"], snap["rate"])
        names = snap.get("codec_name")
        if names is None:      # pre-degraded-resume snapshot format
            names = {s: "PCMU" if snap["codec_ulaw"][s] else "PCMA"
                     for s in sids}
        for sid in sids:
            # stateful codecs come back freshly initialized — the
            # documented degraded-resume semantics (see snapshot)
            bridge._attach_media_row(
                sid, snap["ssrc_of"][sid],
                codec_from_name(names[sid], snap["ptime_ms"]))
        # the crypto, playout and counter state resumes verbatim (jb
        # AFTER add_stream: add_stream resets rows, restore overrides);
        # a mesh bridge must come back with MESH tables — a silent
        # single-chip fallback would un-shard the deployment
        if snap.get("sharded") and bridge._mesh is None:
            raise ValueError(
                "snapshot came from a MESH bridge; pass mesh=... to "
                "restore (resuming single-chip would silently un-shard "
                "the deployment)")
        if bridge._mesh is not None:
            from libjitsi_tpu.mesh import ShardedSrtpTable
            bridge.rx_table = ShardedSrtpTable.restore(snap["rx_table"],
                                                       bridge._mesh)
            bridge.tx_table = ShardedSrtpTable.restore(snap["tx_table"],
                                                       bridge._mesh)
        else:
            bridge.rx_table = _T.restore(snap["rx_table"])
            bridge.tx_table = _T.restore(snap["tx_table"])
        bridge.chain = TransformEngineChain(
            [bridge.levels_engine,
             SrtpTransformEngine(bridge.tx_table, bridge.rx_table)])
        bridge.loop.chain = bridge.chain
        if snap["jb"] is not None and bridge.bank is not None:
            bridge.bank.jb = DenseJitterBank.restore(snap["jb"])
        bridge._tx_seq = np.asarray(snap["tx_seq"]).copy()
        bridge._tx_ts = np.asarray(snap["tx_ts"]).copy()
        bridge._tx_ssrc = np.asarray(snap["tx_ssrc"]).copy()
        keep = np.zeros(snap["capacity"], dtype=bool)
        keep[sids] = True
        bridge.loop.addr_ip[:] = np.where(keep, snap["addr_ip"], 0)
        bridge.loop.addr_port[:] = np.where(keep, snap["addr_port"], 0)
        sp = snap["speaker"]
        bridge.speaker.immediate[:] = sp["immediate"]
        bridge.speaker.medium[:] = sp["medium"]
        bridge.speaker.long[:] = sp["long"]
        bridge.speaker.dominant = sp["dominant"]
        return bridge

    def run(self, duration_s: float) -> None:
        """Drive real-time ticks for a bounded interval."""
        end = time.time() + duration_s
        period = self.ptime_ms / 1000.0
        nxt = time.time()
        while time.time() < end:
            self.tick()
            nxt += period
            delay = nxt - time.time()
            if delay > 0:
                time.sleep(delay)

    def close(self) -> None:
        self.loop.engine.close()
