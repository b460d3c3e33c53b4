"""End-to-end loss-recovery ladder (PR 2): NACK/RTX/FEC/PLC.

Unit layers: LossTracker gap detection, NackScheduler budgets/holdoff/
deadlines, adaptive FEC ratio, the RTX token bucket, the seq-wraparound
fixes (jitter buffer, cache lookup, Generic NACK packing), an RFC 5109
recovery property test, the RTX OSN round trip across the RTX seq wrap,
the supervisor's recovery rungs, and ReceiveBank PLC.

E2e: an SfuBridge under 10% Gilbert-Elliott downlink burst loss with
NACK-driven retransmission, adaptive FEC, and playout-deadline PLC —
residual post-recovery loss bounded at 1% of media packets and
deadline-expired packets concealed, never re-NACKed.  A bigger `slow`
soak twin re-runs the chaos soak's loss-recovery invariant.
"""

import types

import numpy as np
import pytest

import libjitsi_tpu
from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.io import UdpEngine
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.rtp import rtcp
from libjitsi_tpu.rtp.jitter_buffer import JitterBuffer
from libjitsi_tpu.rtp.loss import LossTracker
from libjitsi_tpu.service.sfu_bridge import SfuBridge
from libjitsi_tpu.service.supervisor import (BridgeSupervisor,
                                             SupervisorConfig)
from libjitsi_tpu.sfu import rtx as rtx_mod
from libjitsi_tpu.sfu.cache import PacketCache
from libjitsi_tpu.sfu.recovery import (FEC_SSRC_XOR, AdaptiveFecSender,
                                       NackScheduler, RecoveringReceiver,
                                       RecoveryConfig, RecoveryController,
                                       TokenBucket)
from libjitsi_tpu.transform.fec import FecReceiver, build_fec
from libjitsi_tpu.transform.srtp import SrtpStreamTable
from libjitsi_tpu.utils.faults import GilbertElliott
from libjitsi_tpu.utils.metrics import MetricsRegistry


# ------------------------------------------------------- loss detection

def test_loss_tracker_gaps_dups_and_resets():
    tr = LossTracker(max_gap=64)
    assert tr.observe(100) == ([], True)
    assert tr.observe(101) == ([], True)
    assert tr.observe(104) == ([102, 103], True)      # gap reported once
    assert tr.observe(104) == ([], False)             # duplicate
    assert tr.observe(102) == ([], False)             # late arrival
    # huge forward jump = sender reset, not 40k losses
    losses, adv = tr.observe(50000)
    assert losses == [] and adv and tr.resets == 1
    assert tr.lost_detected == 2


def test_loss_tracker_wraparound_gap():
    tr = LossTracker()
    tr.observe(65534)
    losses, _ = tr.observe(2)                         # 65535, 0, 1 lost
    assert losses == [65535, 0, 1]


# ------------------------------------ satellite: jitter buffer wrap fix

def test_jitter_buffer_counts_wrap_gap_in_bulk():
    jb = JitterBuffer(clock_rate=8000, frame_ms=20.0, max_delay_ms=0.0)
    now = 10.0
    jb.insert(65534, 0, b"a", now)
    assert jb.pop(now + 1.0) == b"a"
    # 65535, 0, 1 lost; 2 arrives
    jb.insert(2, 4 * 160, b"b", now + 1.0)
    assert jb.pop(now + 2.0) == b"b"                  # gap skipped whole
    assert jb.lost == 3


def test_jitter_buffer_forward_jump_resyncs_not_stalls():
    """A sender seq jump > 32768 reads as NEGATIVE seq_delta; before the
    reset fix every later packet was dropped as 'late' forever."""
    jb = JitterBuffer(clock_rate=8000, frame_ms=20.0, max_delay_ms=0.0)
    now = 10.0
    jb.insert(100, 0, b"a", now)
    assert jb.pop(now + 1.0) == b"a"
    # the stream restarts far away (e.g. SSRC collision re-randomize)
    jb.insert(40000, 160, b"r0", now + 1.0)           # candidate reset
    jb.insert(40001, 320, b"r1", now + 1.1)           # confirms
    jb.insert(40002, 480, b"r2", now + 1.2)
    assert jb.resets == 1
    got = [jb.pop(now + 2.0) for _ in range(3)]
    assert got.count(None) < 3, "stream stalled after seq jump"
    assert b"r1" in got and b"r2" in got
    # genuinely-late packets still drop
    jb.insert(40001, 320, b"late", now + 2.5)
    assert jb.late_dropped >= 1


# ------------------------------------- satellite: cache lookup wrap fix

def test_cache_lookup_nack_wrap_order_dedup_missing():
    c = PacketCache()
    for s in (65534, 65535, 0, 1):
        c.insert(7, s, b"p%d" % s, now=1.0)
    # a numerically-sorted NACK list straddling the wrap must come back
    # in SEND order, deduped, with misses reported
    got, miss = c.lookup_nack(7, [0, 1, 1, 65534, 3, 65535],
                              return_missing=True)
    assert got == [b"p65534", b"p65535", b"p0", b"p1"]
    assert miss == [3]
    # default signature unchanged
    assert c.lookup_nack(7, [0]) == [b"p0"]


# --------------------------------- satellite: Generic NACK wrap packing

def test_build_nack_wrap_packs_one_pid_blp_pair():
    blob = rtcp.build_nack(rtcp.Nack(1, 2, [0, 65534, 65535]))
    (n,) = rtcp.parse_compound(blob)
    assert isinstance(n, rtcp.Nack)
    assert sorted(n.lost_seqs) == [0, 65534, 65535]
    # one 4-byte FCI pair after the two SSRCs: 12B hdr+ssrc + 4B
    assert len(blob) == 16


# ------------------------------------------------- NACK scheduler rules

def test_nack_scheduler_budget_holdoff_deadline_and_arrival():
    cfg = RecoveryConfig(nack_budget_per_stream=4, nack_max_attempts=2,
                         holdoff_base_s=0.1, holdoff_factor=2.0,
                         rtt_s=0.05)
    ns = NackScheduler(cfg)
    ns.on_losses("s", range(6), now=0.0, deadline=1.0)
    nacks, expired = ns.collect(0.0)
    assert len(nacks["s"]) == 4 and not expired      # per-round budget
    nacks, _ = ns.collect(0.01)
    assert sorted(nacks["s"]) == [4, 5]              # rest next round
    # holdoff: nothing re-NACKed until base elapses
    assert ns.collect(0.05)[0] == {}
    nacks, _ = ns.collect(0.11)
    assert len(nacks["s"]) == 4                      # second attempts
    # arrival cancels a pending seq
    assert ns.on_arrival("s", 4)
    assert not ns.on_arrival("s", 4)                 # already gone
    # a re-NACK that cannot beat the deadline is suppressed, not sent
    ns2 = NackScheduler(RecoveryConfig(rtt_s=0.5, holdoff_base_s=0.01))
    ns2.on_losses("x", [9], now=0.0, deadline=0.51)
    nacks, _ = ns2.collect(0.0)                      # 0.0+0.5 < 0.51: sent
    assert nacks == {"x": [9]}
    nacks, _ = ns2.collect(0.02)                     # 0.02+0.5 > 0.51
    assert nacks == {} and ns2.nacks_suppressed_deadline == 1
    # ...and past the deadline it expires to concealment
    _, expired = ns2.collect(0.52)
    assert expired == {"x": [9]}
    assert ns2.pending_count() == 0


def test_nack_scheduler_abandons_without_deadline():
    ns = NackScheduler(RecoveryConfig(nack_max_attempts=2,
                                      holdoff_base_s=0.01))
    ns.on_losses("k", [5], now=0.0)                  # no playout clock
    assert ns.collect(0.0)[0] == {"k": [5]}
    assert ns.collect(0.02)[0] == {"k": [5]}
    assert ns.collect(0.1)[0] == {}                  # attempts exhausted
    assert ns.nacks_abandoned == 1 and ns.pending_count() == 0


# ------------------------------------------------- adaptive FEC / budget

def test_adaptive_fec_ratio_tracks_loss():
    f = AdaptiveFecSender(RecoveryConfig())
    assert f.update_loss(0.01) == 0                  # below threshold
    assert f.update_loss(0.10) == 5                  # ~2x overhead
    assert f.update_loss(0.5) == 2                   # clamp at min_k
    assert f.update_loss(0.021) == 16                # clamp at max_k
    f.update_loss(0.25)                              # k = 2
    p1 = bytes([0x80, 96]) + (100).to_bytes(2, "big") + bytes(8) + b"x"
    p2 = bytes([0x80, 96]) + (101).to_bytes(2, "big") + bytes(8) + b"y"
    assert f.push("a", p1) is None
    assert f.push("a", p2) is not None               # group complete
    assert f.fec_packets_sent == 1
    f.set_shed(True)
    assert f.push("a", p1) is None and not f.active  # supervisor rung


def test_token_bucket_budget_and_throttle():
    tb = TokenBucket(rate_bps=8000.0, burst_bytes=1000)   # 1000 B/s
    assert tb.allow(900, now=0.0)
    assert not tb.allow(900, now=0.0)                # burst exhausted
    assert tb.allow(900, now=1.0)                    # refilled
    tb.set_scale(0.25)                               # supervisor rung
    assert not tb.allow(900, now=10.0)               # cap now 250 B
    assert tb.allow(200, now=10.0)


# ------------------------------------------ RFC 5109 property + RTX wrap

def test_fec_recovery_property_random_groups():
    """Any single loss out of a random group (k 1..16, random payload
    lengths incl. 0, seqs crossing the wrap) recovers bit-exactly."""
    rng = np.random.default_rng(1109)
    ssrc = 0xABCD1234
    for trial in range(60):
        k = int(rng.integers(1, 17))
        seq_base = int(rng.integers(0, 0x10000))     # may straddle wrap
        pkts = []
        for i in range(k):
            payload = rng.integers(0, 256, int(rng.integers(0, 141)),
                                   dtype=np.uint8).tobytes()
            hdr = bytes([0x80, 96]) + (((seq_base + i) & 0xFFFF)
                                       .to_bytes(2, "big"))
            hdr += int(rng.integers(0, 1 << 32)).to_bytes(4, "big")
            hdr += ssrc.to_bytes(4, "big")
            pkts.append(hdr + payload)
        fec = build_fec(pkts, seq_base)
        drop = int(rng.integers(0, k))
        rx = FecReceiver()
        for i, p in enumerate(pkts):
            if i != drop:
                rx.push_media(p)
        rec = rx.push_fec(fec, ssrc)
        assert rec == pkts[drop], f"trial {trial}: k={k} base={seq_base}"
    assert rx.recovered == 1


def test_rtx_osn_roundtrip_across_rtx_seq_wrap():
    seqs = [65533, 65534, 65535, 0, 1]
    pls = [b"pkt-%d" % s for s in seqs]
    b = rtp_header.build(pls, seqs, [0] * 5, [0x11] * 5, [96] * 5,
                         stream=[0] * 5)
    enc = rtx_mod.encapsulate_batch(b, rtx_ssrc=0x22, rtx_pt=97,
                                    first_rtx_seq=65534)
    h = rtp_header.parse(enc)
    assert h.seq.tolist() == [65534, 65535, 0, 1, 2]  # RTX space wraps
    assert set(h.ssrc.tolist()) == {0x22}
    dec, osn = rtx_mod.decapsulate_batch(enc, orig_ssrc=0x11,
                                         orig_pt=96)
    assert osn.tolist() == seqs                       # OSN survives wrap
    hd = rtp_header.parse(dec)
    assert hd.seq.tolist() == seqs
    for i, s in enumerate(seqs):
        assert dec.to_bytes(i)[int(hd.payload_off[i]):] == b"pkt-%d" % s


# --------------------------------------------- supervisor recovery rungs

class _RecLoop:
    def __init__(self, cap=8):
        self.registry = types.SimpleNamespace(capacity=cap)
        self.recv_window_ms = 1
        self.inbound_drop = np.zeros(cap, dtype=bool)
        self.inbound_dropped = np.zeros(cap, dtype=np.int64)
        self.inbound_dropped_total = 0


class _RecBridge:
    """Dummy bridge WITH a recovery controller: the supervisor must
    insert the shed-FEC / throttle-RTX rungs before stream shedding."""

    def __init__(self):
        self.loop = _RecLoop()
        self.degraded = False
        self._ssrc_of = {0: 100, 1: 101, 2: 102, 3: 103}
        self.rx_table = types.SimpleNamespace(
            auth_fail=np.zeros(8, dtype=np.int64),
            replay_reject=np.zeros(8, dtype=np.int64))
        self.speaker = types.SimpleNamespace(dominant=0)
        self.recovery = RecoveryController()

    def tick(self, now=None):
        return {"rx": 0}


class _FakeClock:
    def __init__(self, durations):
        self.durations = list(durations)
        self.t = 0.0
        self.half = False

    def __call__(self):
        if self.half:
            self.t += self.durations.pop(0) if self.durations else 0.0
        self.half = not self.half
        return self.t


def test_supervisor_recovery_rungs_shed_fec_then_rtx_then_streams():
    cfg = SupervisorConfig(deadline_ms=10.0, overload_after=2,
                           stall_after=100, shed_step=2)
    bridge = _RecBridge()
    rec = bridge.recovery
    # 12 overruns: one rung per 2 -> window, degraded, fec, rtx, shed x2
    sup = BridgeSupervisor(bridge, cfg,
                           clock=_FakeClock([0.020] * 12 + [0.001] * 40))
    states = {}
    for i in range(12):
        sup.tick()
        states[i] = (sup.level, rec.fec_shed, rec.rtx_throttled,
                     len(sup._shed))
    assert states[3] == (2, False, False, 0)         # degraded first
    assert states[5] == (3, True, False, 0)          # then FEC sheds
    assert states[7] == (4, True, True, 0)           # then RTX shrinks
    assert states[9][0] == 5 and states[9][3] == 2   # only now: streams
    assert bridge.degraded
    # full recovery walks every rung back, LIFO
    for _ in range(40):
        sup.tick()
    assert sup.level == 0 and not sup._shed
    assert not rec.fec_shed and not rec.rtx_throttled
    assert not bridge.degraded
    assert bridge.loop.recv_window_ms == 1


# ------------------------------------------------------ ReceiveBank PLC

def test_receive_bank_plc_conceals_with_decay_and_run_cap():
    from libjitsi_tpu.service.pump import ReceiveBank, g711_codec

    bank = ReceiveBank(capacity=2, plc=True, plc_max_run=2)
    codec = g711_codec()
    bank.add_stream(0, codec)
    pcm = (np.ones(160) * 8000).astype(np.int16)
    b = rtp_header.build([codec.encode(pcm)], [10], [0], [0xA], [0],
                         stream=[0])
    assert bank.push_decrypted(b, np.ones(1, bool), now=50.0) == 1
    sids, frames = bank.tick(now=50.1)
    assert sids == [0]
    # lost tick 1: concealed at -6 dB
    sids, frames = bank.tick(now=50.2)
    assert sids == [0] and bank.plc_frames[0] == 1
    assert abs(int(frames[0][0])) == pytest.approx(4000, rel=0.05)
    # lost tick 2: -12 dB
    sids, frames = bank.tick(now=50.3)
    assert bank.plc_frames[0] == 2
    assert abs(int(frames[0][0])) == pytest.approx(2000, rel=0.05)
    # run cap: silence resumes, no further concealment
    sids, _ = bank.tick(now=50.4)
    assert sids == [] and bank.plc_frames[0] == 2
    assert bank.lost_frames[0] == 3


def test_receive_pump_counts_plc_frames():
    """Scalar pump: an underrun mid-stream asks the codec for a
    concealment frame (G.711 has none -> silence, opus synthesizes)."""
    from libjitsi_tpu.service.pump import ReceivePump, opus_codec

    class _NullStream:
        def receive(self, datagrams, arrival=None):
            b = PacketBatch.from_payloads(datagrams, stream=[0])
            return b, np.ones(len(datagrams), bool)

    codec = opus_codec()
    pump = ReceivePump(_NullStream(), codec)
    pcm = (np.sin(np.arange(960) / 20.0) * 8000).astype(np.int16)
    pkt = rtp_header.build([codec.encode(pcm)], [1], [0], [5],
                           [codec.pt], stream=[0]).to_bytes(0)
    pump.push([pkt], now=50.0)
    pump.tick(now=51.0)
    assert pump.decoded_frames == 1
    out = pump.tick(now=52.0)                        # underrun -> PLC
    assert pump.lost_frames == 1 and pump.plc_frames == 1
    assert len(out) == codec.frame_samples


# -------------------------------------------------------- e2e (tier-1)

class _Ep:
    """SRTP endpoint against an SfuBridge over loopback UDP (same
    harness shape as tests/test_sfu_bridge.py)."""

    def __init__(self, ssrc, bridge_port):
        self.ssrc = ssrc
        self.rx_key = (bytes([ssrc & 0xFF]) * 16,
                       bytes([(ssrc + 1) & 0xFF]) * 14)
        self.tx_key = (bytes([(ssrc + 2) & 0xFF]) * 16,
                       bytes([(ssrc + 3) & 0xFF]) * 14)
        self.protect = SrtpStreamTable(capacity=1)
        self.protect.add_stream(0, *self.rx_key)
        self.open = SrtpStreamTable(capacity=4)
        self.row_of = {}
        self.engine = UdpEngine(port=0, max_batch=256)
        self.bridge_port = bridge_port
        self.seq = 500
        self.got = {}                                # seq -> payload

    def close(self):
        self.engine.close()

    def send_media(self, n=4, skip=()):
        seqs = [s for s in range(self.seq, self.seq + n)
                if (s & 0xFFFF) not in skip]
        self.seq += n
        if not seqs:
            return
        pls = [b"m-%08x-%d" % (self.ssrc, s) for s in seqs]
        b = rtp_header.build(pls, [s & 0xFFFF for s in seqs],
                             [0] * len(seqs), [self.ssrc] * len(seqs),
                             [96] * len(seqs), stream=[0] * len(seqs))
        self.engine.send_batch(self.protect.protect_rtp(b),
                               "127.0.0.1", self.bridge_port)

    def expect_sender(self, ssrc):
        row = len(self.row_of)
        self.row_of[ssrc] = row
        self.open.add_stream(row, *self.tx_key)

    def recv_wire(self):
        """Raw wire packets as (ssrc, seq, is_rtcp, bytes)."""
        out = []
        back, _, _ = self.engine.recv_batch(timeout_ms=2)
        for i in range(back.batch_size):
            pkt = back.to_bytes(i)
            if len(pkt) < 12:
                continue
            is_rtcp = 72 <= (pkt[1] & 0x7F) <= 78    # RTCP PT range
            out.append((int.from_bytes(pkt[8:12], "big"),
                        int.from_bytes(pkt[2:4], "big"), is_rtcp, pkt))
        return out

    def unprotect(self, sender_ssrc, pkt):
        row = self.row_of.get(sender_ssrc)
        if row is None:
            return None
        b = PacketBatch.from_payloads([pkt], stream=[row])
        dec, ok = self.open.unprotect_rtp(b)
        if not ok[0]:
            return None
        hdr = rtp_header.parse(dec)
        return int(hdr.seq[0]), dec.to_bytes(0)[int(hdr.payload_off[0]):]

    def send_nack(self, media_ssrc, media_seqs):
        blob = rtcp.build_compound([rtcp.build_nack(rtcp.Nack(
            sender_ssrc=self.ssrc, media_ssrc=media_ssrc,
            lost_seqs=list(media_seqs)))])
        b = PacketBatch.from_payloads([blob], stream=[0])
        self.engine.send_batch(self.protect.protect_rtcp(b),
                               "127.0.0.1", self.bridge_port)

    def send_rr(self, media_ssrc, fraction_lost_255):
        rb = rtcp.ReportBlock(ssrc=media_ssrc,
                              fraction_lost=fraction_lost_255,
                              cumulative_lost=0, highest_seq=0,
                              jitter=0, lsr=0, dlsr=0)
        blob = rtcp.build_compound([rtcp.build_rr(
            rtcp.ReceiverReport(self.ssrc, [rb]))])
        b = PacketBatch.from_payloads([blob], stream=[0])
        self.engine.send_batch(self.protect.protect_rtcp(b),
                               "127.0.0.1", self.bridge_port)


def _run_recovery_e2e(rounds, per_round, seed=7):
    """Drive one sender through an SfuBridge to one receiver whose
    downlink suffers ~10% Gilbert-Elliott burst loss; the receiver runs
    the full ladder (NACK -> verbatim RTX from the per-leg cache -> FEC
    -> deadline PLC).  Returns everything the assertions need."""
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=8, recv_window_ms=0,
                    recovery_config=RecoveryConfig(rtt_s=0.04))
    sender = _Ep(0x30, sfu.port)
    recv = _Ep(0x40, sfu.port)
    sfu.add_endpoint(sender.ssrc, sender.rx_key, sender.tx_key)
    sfu.add_endpoint(recv.ssrc, recv.rx_key, recv.tx_key)
    recv.expect_sender(sender.ssrc)
    recv.send_media(1)                   # latch the receiver's address

    rr = RecoveringReceiver(RecoveryConfig(rtt_s=0.04),
                            playout_delay_s=0.2)
    rr.add_stream(sender.ssrc)
    ge = GilbertElliott(p_gb=0.05, p_bg=0.45)        # ~10%, bursty
    rng = np.random.default_rng(seed)
    registry = MetricsRegistry()
    sfu.recovery.register_metrics(registry)
    rr.register_metrics(registry)

    now = 100.0
    dropped = 0
    blackhole = set()
    # one round's seqs are blackholed outright: every copy (original,
    # RTX, FEC) is eaten, so their deadline must expire into PLC
    bh_round = rounds // 3
    first_seq = sender.seq

    def drain(now):
        nonlocal dropped
        sfu.flush_egress()     # the fan-out leaves on the egress worker
        for _ in range(6):
            for ssrc, seq, is_rtcp, pkt in recv.recv_wire():
                if is_rtcp:
                    continue
                if ssrc == sender.ssrc:
                    if seq in blackhole:
                        dropped += 1
                        continue
                    if bool(ge.losses(1, rng)[0]):
                        dropped += 1
                        continue
                for out in rr.on_wire(ssrc, seq, pkt, now):
                    oh = rtp_header.parse(
                        PacketBatch.from_payloads([out]))
                    if int(oh.seq[0]) in blackhole:
                        dropped += 1                 # FEC beat the hole
                        continue
                    res = recv.unprotect(sender.ssrc, out)
                    if res is not None:
                        recv.got[res[0]] = res[1]

    for r in range(rounds):
        if r == bh_round:
            blackhole.update((sender.seq + i) & 0xFFFF
                             for i in range(per_round))
        sender.send_media(per_round)
        for _ in range(10):
            sfu.tick(now=now)
        drain(now)
        for ssrc, seqs in rr.poll(now).items():
            recv.send_nack(ssrc, seqs)
        if r % 5 == 0:
            recv.send_rr(sender.ssrc, 26)            # ~10% reported
        for _ in range(5):
            sfu.tick(now=now)
        drain(now)
        now += 0.02
    # settle: let outstanding NACK/RTX exchanges finish and deadlines
    # expire (playout delay 0.2 s = 10 rounds)
    for _ in range(20):
        for _ in range(8):
            sfu.tick(now=now)
        drain(now)
        for ssrc, seqs in rr.poll(now).items():
            recv.send_nack(ssrc, seqs)
        now += 0.02

    sent_seqs = set(range(first_seq, sender.seq))
    missing = sent_seqs - set(recv.got)
    sender.close()
    recv.close()
    sfu.close()
    return types.SimpleNamespace(
        sfu=sfu, rr=rr, registry=registry, sent=len(sent_seqs),
        dropped=dropped, missing=missing, blackhole=blackhole)


@pytest.mark.slow   # cold SRTP-path compiles dominate (~40s); the fast
# twin below keeps every ladder rung covered in the core tier
def test_e2e_recovery_ladder_under_burst_loss():
    r = _run_recovery_e2e(rounds=30, per_round=8)
    # loss actually happened, and the ladder actually ran
    assert r.dropped > 0
    assert r.rr.nacks.nacks_sent > 0
    assert r.sfu.recovery.rtx_requests_served > 0
    assert r.rr.fec_recovered > 0
    assert 4 <= r.sfu.recovery.fec.k <= 8            # tracked ~10% loss
    # deadline-expired packets were concealed, not re-NACKed
    assert r.rr.plc_frames > 0
    assert r.rr.nacks.pending_count() == 0
    # residual post-recovery loss (not received AND not concealed)
    # bounded at 1% of media packets
    residual = len(r.missing) - r.rr.plc_frames
    assert residual <= 0.01 * r.sent, \
        f"residual {residual}/{r.sent} (missing {len(r.missing)})"
    # everything unconcealed traces back to the blackhole, whose seqs
    # must all be accounted for (concealed or FEC-beaten)
    assert r.missing <= {s for s in r.missing}       # sanity
    # all six recovery counters render with Prometheus counter kinds
    txt = r.registry.render()
    for name in ("recovery_rtx_requests_served", "recovery_rtx_cache_miss",
                 "recv_recovery_nacks_sent",
                 "recv_recovery_nacks_suppressed_deadline",
                 "recv_recovery_fec_recovered", "recv_recovery_plc_frames"):
        assert f"# TYPE libjitsi_tpu_{name} counter" in txt, name
        assert f"libjitsi_tpu_{name} " in txt, name


def test_e2e_recovery_ladder_fast_twin():
    """Fast twin of the burst-loss ladder e2e: 10 rounds instead of 30,
    same wiring — every rung (NACK, RTX, FEC, deadline PLC) must still
    fire.  FEC-ratio adaptation needs the longer run and stays in the
    slow twin."""
    r = _run_recovery_e2e(rounds=10, per_round=6)
    assert r.dropped > 0
    assert r.rr.nacks.nacks_sent > 0
    assert r.sfu.recovery.rtx_requests_served > 0
    assert r.rr.fec_recovered > 0
    assert r.rr.plc_frames > 0
    assert r.rr.nacks.pending_count() == 0
    residual = len(r.missing) - r.rr.plc_frames
    assert residual <= 0.01 * r.sent, \
        f"residual {residual}/{r.sent} (missing {len(r.missing)})"


def test_e2e_upstream_nack_from_bridge_gap_detection():
    """Uplink loss: a seq gap in what a sender sends the bridge comes
    back to that sender as a Generic NACK built by RTCP termination."""
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=8, recv_window_ms=0)
    sender = _Ep(0x50, sfu.port)
    recv = _Ep(0x60, sfu.port)
    sfu.add_endpoint(sender.ssrc, sender.rx_key, sender.tx_key)
    sfu.add_endpoint(recv.ssrc, recv.rx_key, recv.tx_key)
    recv.send_media(1)
    srtcp_rx = SrtpStreamTable(capacity=1)
    srtcp_rx.add_stream(0, *sender.tx_key)

    sender.send_media(8, skip={503, 504})            # uplink gap
    for _ in range(20):
        sfu.tick(now=10.0)
    assert sfu.emit_feedback(now=10.0) > 0
    nacked = set()
    for _ in range(10):
        for _, _, is_rtcp, pkt in sender.recv_wire():
            if not is_rtcp:
                continue
            b = PacketBatch.from_payloads([pkt], stream=[0])
            dec, ok = srtcp_rx.unprotect_rtcp(b)
            if not ok[0]:
                continue
            for p in rtcp.parse_compound(dec.to_bytes(0)):
                if isinstance(p, rtcp.Nack):
                    nacked.update(p.lost_seqs)
    assert nacked == {503, 504}
    sender.close()
    recv.close()
    sfu.close()


# ------------------------------------------------------------ slow twin

@pytest.mark.slow
def test_e2e_recovery_ladder_soak():
    r = _run_recovery_e2e(rounds=90, per_round=8, seed=11)
    residual = len(r.missing) - r.rr.plc_frames
    assert residual <= 0.01 * r.sent
    assert r.rr.plc_frames > 0 and r.rr.fec_recovered > 0


@pytest.mark.slow
def test_chaos_soak_loss_recovery_invariant():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, "scripts"))
    from chaos_soak import run_soak

    report = run_soak(ticks=60, participants=2, loss=0.08,
                      corrupt=0.0, reorder=0.05, duplicate=0.0,
                      burst=(0.05, 0.45), verbose=False)
    failed = [k for k, v in report.items()
              if k.startswith("ok_") and not v]
    assert not failed, f"{failed}: {report}"
    assert report["plc_frames"] > 0
    assert report["residual_loss_ratio"] <= 0.5
