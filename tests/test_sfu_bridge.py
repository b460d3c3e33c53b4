"""SfuBridge e2e: decrypt-once fan-out over real loopback UDP + NACK
retransmission from the per-leg cache."""

import numpy as np
import pytest

import libjitsi_tpu
from libjitsi_tpu.io import UdpEngine
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.rtp import rtcp
from libjitsi_tpu.service.sfu_bridge import SfuBridge
from libjitsi_tpu.transform.header_ext import AbsSendTimeEngine
from libjitsi_tpu.transform.srtp import SrtpStreamTable


class _Endpoint:
    def __init__(self, ssrc, bridge_port):
        self.ssrc = ssrc
        self.rx_key = (bytes([ssrc & 0xFF]) * 16,
                       bytes([(ssrc + 1) & 0xFF]) * 14)
        self.tx_key = (bytes([(ssrc + 2) & 0xFF]) * 16,
                       bytes([(ssrc + 3) & 0xFF]) * 14)
        self.protect = SrtpStreamTable(capacity=1)
        self.protect.add_stream(0, *self.rx_key)
        # one rx context PER SENDER SSRC (RFC 3711: contexts are
        # per-SSRC; all legs share this receiver's session keys)
        self.open = SrtpStreamTable(capacity=4)
        self.row_of = {}
        self.engine = UdpEngine(port=0, max_batch=64)
        self.bridge_port = bridge_port
        self.seq = 500
        self.got = {}                     # seq -> payload

    def send_media(self, n=4):
        pls = [b"m-%08x-%d" % (self.ssrc, self.seq + i)
               for i in range(n)]
        b = rtp_header.build(pls, [self.seq + i for i in range(n)],
                             [0] * n, [self.ssrc] * n, [96] * n,
                             stream=[0] * n)
        self.seq += n
        self.engine.send_batch(self.protect.protect_rtp(b),
                               "127.0.0.1", self.bridge_port)

    def expect_sender(self, ssrc):
        row = len(self.row_of)
        self.row_of[ssrc] = row
        self.open.add_stream(row, *self.tx_key)

    def drain(self):
        back, _, _ = self.engine.recv_batch(timeout_ms=2)
        if back.batch_size:
            hdr0 = rtp_header.parse(back)
            back.stream[:] = [self.row_of.get(int(s), -1)
                              for s in hdr0.ssrc]
            dec, ok = self.open.unprotect_rtp(back)
            hdr = rtp_header.parse(dec)
            for i in np.nonzero(ok)[0]:
                i = int(i)
                self.got[(int(hdr.ssrc[i]), int(hdr.seq[i]))] = \
                    dec.to_bytes(i)[int(hdr.payload_off[i]):]

    def send_nack(self, media_ssrc, media_seqs):
        """SRTCP-protected NACK (the bridge drops plaintext control)."""
        blob = rtcp.build_compound([rtcp.build_nack(rtcp.Nack(
            sender_ssrc=self.ssrc, media_ssrc=media_ssrc,
            lost_seqs=list(media_seqs)))])
        from libjitsi_tpu.core.packet import PacketBatch

        b = PacketBatch.from_payloads([blob], stream=[0])
        wire = self.protect.protect_rtcp(b)
        self.engine.send_batch(wire, "127.0.0.1", self.bridge_port)


@pytest.mark.slow
def test_sfu_fanout_and_nack_over_udp():
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=8, recv_window_ms=0)
    eps = [_Endpoint(0x100 + 7 * k, sfu.port) for k in range(3)]
    sids = [sfu.add_endpoint(e.ssrc, e.rx_key, e.tx_key) for e in eps]
    for e in eps:
        for other in eps:
            if other is not e:
                e.expect_sender(other.ssrc)

    # every endpoint sends; everyone must receive the other two's media
    for rnd in range(4):
        for e in eps:
            e.send_media()
        for _ in range(20):
            sfu.tick(now=50.0 + rnd * 0.02)
        sfu.flush_egress()
        for e in eps:
            for _ in range(4):
                e.drain()
    assert sfu.forwarded > 0
    for e in eps:
        payloads = b"".join(e.got.values())
        for other in eps:
            if other is e:
                continue
            assert b"m-%08x" % other.ssrc in payloads, \
                f"{e.ssrc:#x} missing media from {other.ssrc:#x}"
        assert b"m-%08x" % e.ssrc not in payloads, "echoed own media"

    # NACK service: receiver drops a seq, asks again, gets the cached
    # per-leg copy (protected with ITS leg key)
    victim = eps[0]
    missing_seq = 501
    victim.got.clear()
    # fresh contexts for the re-delivery (replay windows already saw
    # these seqs in the live pass)
    for ssrc, row in victim.row_of.items():
        victim.open.add_stream(row, *victim.tx_key)
    victim.send_nack(eps[1].ssrc, [missing_seq])
    for _ in range(20):
        sfu.tick(now=50.5)   # within the cache's 1 s max age
    for _ in range(4):
        victim.drain()
    assert sfu.retransmitted > 0
    assert any(seq == missing_seq for _, seq in victim.got)
    # only the NACKed sender's copy was re-delivered (cache keys carry
    # the sender ssrc)
    assert all(ssrc == eps[1].ssrc for ssrc, _ in victim.got)
    # feedback drain: aggregated NACK/RR toward senders, SRTCP-protected
    sfu.emit_feedback(now=50.6)
    sfu.close()


class _BweSender(_Endpoint):
    """Endpoint whose media carries abs-send-time stamps from a
    controllable clock (lets the test shape queue delay: arrival is the
    bridge tick's `now`, send time is `ast_now`)."""

    def __init__(self, ssrc, bridge_port, ext_id=3):
        super().__init__(ssrc, bridge_port)
        self.ast_now = 0.0
        self._ast = AbsSendTimeEngine(ext_id, clock=lambda: self.ast_now)

    def send_media(self, n=4):
        pls = [b"m-%08x-%d" % (self.ssrc, self.seq + i)
               for i in range(n)]
        b = rtp_header.build(pls, [self.seq + i for i in range(n)],
                             [0] * n, [self.ssrc] * n, [96] * n,
                             stream=[0] * n)
        self.seq += n
        b, _ = self._ast.rtp_transformer.transform(b)
        self.engine.send_batch(self.protect.protect_rtp(b),
                               "127.0.0.1", self.bridge_port)

    def drain_rembs(self):
        """Unprotect bridge SRTCP feedback; return REMB bitrates."""
        out = []
        back, _, _ = self.engine.recv_batch(timeout_ms=2)
        for i in range(back.batch_size):
            back.stream[i] = 0
        if back.batch_size:
            dec, ok = self.srtcp_rx.unprotect_rtcp(back)
            for i in np.nonzero(np.asarray(ok))[0]:
                for p in rtcp.parse_compound(dec.to_bytes(int(i))):
                    if isinstance(p, rtcp.Remb):
                        out.append(p.bitrate_bps)
        return out


@pytest.mark.slow
def test_sfu_bwe_congestion_drives_remb_down_and_back_up():
    """the bridge's OWN receive-side estimate (abs-send-
    time GCC over the sender->bridge leg) governs the REMB it advertises:
    a growing-queue trace cuts it, recovery raises it again."""
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=8, recv_window_ms=0)
    sender = _BweSender(0x700, sfu.port)
    recv = _Endpoint(0x701, sfu.port)
    sid_s = sfu.add_endpoint(sender.ssrc, sender.rx_key, sender.tx_key)
    sfu.add_endpoint(recv.ssrc, recv.rx_key, recv.tx_key)
    recv.expect_sender(sender.ssrc)
    # receiver must latch an address on the bridge (any packet does)
    recv.send_media(1)
    # sender-side SRTCP context for the bridge's feedback (protected
    # with the sender leg's tx key)
    sender.srtcp_rx = SrtpStreamTable(capacity=1)
    sender.srtcp_rx.add_stream(0, *sender.tx_key)

    rembs = []

    def run_phase(rounds, queue_of):
        for r in range(rounds):
            t = run_phase.t0 + r * 0.02
            sender.ast_now = t - queue_of(r)
            sender.send_media(4)
            for _ in range(10):
                sfu.tick(now=t)
            sfu.emit_feedback(now=t)
            sfu.flush_egress()
            got = sender.drain_rembs()
            if got:
                rembs.append(got[-1])
            recv.drain()
        run_phase.t0 += rounds * 0.02

    run_phase.t0 = 50.0
    run_phase(10, lambda r: 0.0)                  # clean network
    assert rembs, "no REMB reached the sender"
    baseline = rembs[-1]
    assert sfu.own_estimate_bps(sid_s) is not None
    run_phase(30, lambda r: r * 0.003)            # queue grows 3 ms/tick
    congested = rembs[-1]
    assert congested < baseline * 0.7, \
        f"REMB did not drop under congestion: {baseline} -> {congested}"
    run_phase(60, lambda r: 0.090)                # constant queue: drained
    recovered = rembs[-1]
    assert recovered > congested * 1.1, \
        f"REMB did not recover: {congested} -> {recovered}"
    sfu.close()


@pytest.mark.slow
def test_sfu_dtls_keyed_endpoint_e2e():
    """a sender joins the SfuBridge keyed by DTLS-SRTP
    over the real UDP port (loop first-byte demux -> on_dtls), media
    sent the instant the client completes flows to a static-keyed
    receiver — any packets racing the install are queued and replayed."""
    from libjitsi_tpu.control.dtls import DtlsSrtpEndpoint
    from libjitsi_tpu.core.packet import PacketBatch
    from libjitsi_tpu.transform.srtp import SrtpProfile

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=8, recv_window_ms=0)
    recv = _Endpoint(0x901, sfu.port)
    sfu.add_endpoint(recv.ssrc, recv.rx_key, recv.tx_key)
    recv.send_media(1)                     # latch receiver address

    ssrc = 0x900
    sid, bridge_ep = sfu.add_endpoint_dtls(ssrc, role="server")
    cli = DtlsSrtpEndpoint(
        "client", remote_fingerprint=bridge_ep.local_fingerprint)
    eng = UdpEngine(port=0, max_batch=16)

    def pump_client(datagrams):
        if datagrams:
            eng.send_batch(PacketBatch.from_payloads(list(datagrams)),
                           "127.0.0.1", sfu.port)
        sfu.tick(now=80.0)
        back, _, _ = eng.recv_batch(timeout_ms=5)
        return [back.to_bytes(i) for i in range(back.batch_size)]

    out = cli.handshake_packets()
    for _ in range(40):
        if cli.complete:
            break
        replies = pump_client(out)
        out = []
        for r in replies:
            out.extend(cli.feed(r))
    assert cli.complete, "client handshake did not complete"

    profile, tk, tsalt, rk, rsalt = cli.srtp_keys()
    assert profile == SrtpProfile.AES_CM_128_HMAC_SHA1_80
    tx = SrtpStreamTable(capacity=1, profile=profile)
    tx.add_stream(0, tk, tsalt)
    # receiver must open the DTLS sender's legs with the BRIDGE leg key
    # it was added with (fan-out re-encrypts per leg as usual)
    recv.expect_sender(ssrc)

    b = rtp_header.build([b"dtls-media-%d" % i for i in range(4)],
                         [700 + i for i in range(4)], [0] * 4,
                         [ssrc] * 4, [96] * 4, stream=[0] * 4)
    eng.send_batch(tx.protect_rtp(b), "127.0.0.1", sfu.port)
    for _ in range(20):
        sfu.tick(now=80.1)
    sfu.flush_egress()
    for _ in range(4):
        recv.drain()
    got = b"".join(recv.got.values())
    assert b"dtls-media-0" in got and b"dtls-media-3" in got
    sfu.close()
    eng.close()


@pytest.mark.slow
def test_sfu_video_simulcast_layer_switch_and_rtx():
    """the assembled video SFU.  A 3-layer VP8 simulcast
    sender (real libvpx bitstreams) feeds the bridge over loopback UDP;
    the receiver's REMB drives keyframe-gated layer selection (PLI goes
    upstream until the target layer's keyframe lands), a NACKed packet
    returns as proper RFC 4588 RTX, and the projected stream stays
    decodable across the switch."""
    from libjitsi_tpu.codecs import vp8 as vp8_mod
    from libjitsi_tpu.codecs.vpx import VpxDecoder, VpxEncoder, \
        vpx_available
    from libjitsi_tpu.core.packet import PacketBatch
    from libjitsi_tpu.sfu import rtx as rtx_mod

    if not vpx_available():
        pytest.skip("libvpx not present")
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=32, recv_window_ms=0)
    send = _Endpoint(0xA0, sfu.port)
    recv = _Endpoint(0xA4, sfu.port)
    sid_s = sfu.add_endpoint(send.ssrc, send.rx_key, send.tx_key)
    sid_r = sfu.add_endpoint(recv.ssrc, recv.rx_key, recv.tx_key)
    recv.send_media(1)                         # latch receiver address
    layer_ssrcs = [0xB00, 0xB01, 0xB02]
    track = sfu.add_video_track(
        sid_s, layer_ssrcs, layer_bps=[100e3, 500e3, 2e6], rtx_pt=97)

    # ---- sender: one SRTP row + encoder per layer
    dims = [(160, 96), (320, 192), (640, 384)]
    tx = SrtpStreamTable(capacity=4)
    for k in range(3):
        tx.add_stream(k, *send.rx_key)
    enc = [VpxEncoder(w, h) for w, h in dims]
    seqs, pids = [1000, 2000, 3000], [10, 20, 30]
    # sender-side SRTCP context for bridge feedback (PLI drain)
    fb = SrtpStreamTable(capacity=1)
    fb.add_stream(0, *send.tx_key)

    def frame_planes(k, t):
        w, h = dims[k]
        yy, xx = np.mgrid[0:h, 0:w].astype(np.float64)
        y = (128 + 60 * np.sin(xx / 17 + t * 0.7)
             + 40 * np.cos(yy / 11 + t)).clip(0, 255).astype(np.uint8)
        c = np.full(((h + 1) // 2, (w + 1) // 2), 128, np.uint8)
        return y, c, c

    def send_video(t):
        for k in range(3):
            for data, _key in enc[k].encode(*frame_planes(k, t)):
                pls = vp8_mod.packetize(data, picture_id=pids[k],
                                        max_payload=1100)
                pids[k] = (pids[k] + 1) & 0x7FFF
                n = len(pls)
                b = rtp_header.build(
                    pls, [(seqs[k] + i) & 0xFFFF for i in range(n)],
                    [t * 3000] * n, [layer_ssrcs[k]] * n, [96] * n,
                    marker=[0] * (n - 1) + [1],
                    stream=[k] * n)
                seqs[k] = (seqs[k] + n) & 0xFFFF
                send.engine.send_batch(tx.protect_rtp(b), "127.0.0.1",
                                       sfu.port)

    def sender_drain_plis():
        back, _, _ = send.engine.recv_batch(timeout_ms=5)
        got = []
        if back.batch_size:
            back.stream[:] = 0
            dec, ok = fb.unprotect_rtcp(back)
            for i in np.nonzero(np.asarray(ok))[0]:
                try:
                    for p in rtcp.parse_compound(dec.to_bytes(int(i))):
                        if isinstance(p, rtcp.Pli):
                            got.append(p.media_ssrc)
                except ValueError:
                    pass
        return got

    # ---- receiver: unprotect rows for the projected stream + RTX
    out_ssrc = send.ssrc
    rxt = SrtpStreamTable(capacity=4)
    rxt.add_stream(0, *recv.tx_key)            # projected video stream
    rxt.add_stream(1, *recv.tx_key)            # RTX stream
    fa = vp8_mod.FrameAssembler()
    seen_seqs = []
    rtx_got = []

    def recv_drain():
        back, _, _ = recv.engine.recv_batch(timeout_ms=2)
        if not back.batch_size:
            return
        hdr0 = rtp_header.parse(back)
        rowmap = {out_ssrc: 0, track.rtx_ssrc: 1}
        back.stream[:] = [rowmap.get(int(s), -1) for s in hdr0.ssrc]
        keep = np.nonzero(np.asarray(back.stream) >= 0)[0]
        if len(keep) == 0:
            return
        sub = PacketBatch(back.data[keep],
                          np.asarray(back.length)[keep],
                          back.stream[keep])
        dec, ok = rxt.unprotect_rtp(sub)
        hdr = rtp_header.parse(dec)
        vid = np.nonzero(ok & (np.asarray(dec.stream) == 0))[0]
        if len(vid):
            vb = PacketBatch(dec.data[vid],
                             np.asarray(dec.length)[vid],
                             dec.stream[vid])
            fa.push_batch(vb)
            seen_seqs.extend(int(s) for s in rtp_header.parse(vb).seq)
        for i in np.nonzero(ok & (np.asarray(dec.stream) == 1))[0]:
            one = PacketBatch(dec.data[i:i+1],
                              np.asarray(dec.length)[i:i+1],
                              dec.stream[i:i+1])
            restored, osn = rtx_mod.decapsulate_batch(one, out_ssrc, 96)
            rtx_got.append(int(osn[0]))

    def run(ticks, t0, remb=None):
        for t in range(ticks):
            send_video(t0 + t)
            if remb is not None:
                blob = rtcp.build_compound([rtcp.build_remb(rtcp.Remb(
                    recv.ssrc, int(remb), [out_ssrc]))])
                b = PacketBatch.from_payloads([blob], stream=[0])
                recv.engine.send_batch(recv.protect.protect_rtcp(b),
                                       "127.0.0.1", sfu.port)
            # 0.1 s rounds: a lost PLI datagram re-fires within the
            # phase (RtcpTermination's PLI limiter is 0.5 s)
            for _ in range(12):
                sfu.tick(now=90.0 + (t0 + t) * 0.1)
            sfu.emit_feedback(now=90.0 + (t0 + t) * 0.1)
            for ssrc in sender_drain_plis():
                if ssrc in layer_ssrcs:        # keyframe request: new
                    k = layer_ssrcs.index(ssrc)  # encoder => keyframe
                    enc[k].close()
                    enc[k] = VpxEncoder(*dims[k])
            recv_drain()

    fwd = track.fwd[sid_r]
    run(10, 0, remb=3_000_000)                 # plenty of bandwidth
    assert fwd.current_layer == 2, \
        f"no upswitch: layer={fwd.current_layer}"
    switches_before = fwd.switches
    run(12, 10, remb=600_000)   # starved to one mid layer (500 kbps)
    assert fwd.current_layer == 1, \
        f"no downswitch: layer={fwd.current_layer}"
    assert fwd.switches > switches_before

    # the projected stream reassembles into decodable VP8 across the
    # switch (keyframe-gated: the decoder survives the resolution jump)
    frames = fa.pop_frames()
    assert len(frames) >= 6
    dec = VpxDecoder()
    decoded = 0
    for _ts, _pid, _key, data in frames:
        try:
            decoded += len(dec.decode(data))
        except RuntimeError:
            pass
    assert decoded >= len(frames) - 2, \
        f"only {decoded}/{len(frames)} frames decodable"

    # NACK -> RTX: ask for a seq we saw; it must come back encapsulated
    assert seen_seqs
    want = seen_seqs[-1]
    recv.send_nack(out_ssrc, [want])
    for _ in range(12):
        sfu.tick(now=90.0 + 22 * 0.1 + 0.05)   # within cache max age
    recv_drain()
    assert want in rtx_got, f"seq {want} not re-delivered as RTX"
    sfu.close()


@pytest.mark.slow
def test_sfu_pipelined_fanout_delivers_everything():
    """The SfuBridge's one tick shape: the fan-out launch dispatched
    in tick N ships at tick N+1 (it runs under the host work in
    between); every endpoint still hears every other endpoint's media,
    and NACK service still works against the flushed cache."""
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=8, recv_window_ms=0)
    eps = [_Endpoint(0x300 + 5 * k, sfu.port) for k in range(3)]
    for e in eps:
        sfu.add_endpoint(e.ssrc, e.rx_key, e.tx_key)
        for other in eps:
            if other is not e:
                e.expect_sender(other.ssrc)

    for rnd in range(4):
        for e in eps:
            e.send_media()
        for _ in range(24):       # extra ticks: flush rides tick N+1
            sfu.tick(now=70.0 + rnd * 0.02)
        sfu.flush_egress()
        for e in eps:
            for _ in range(4):
                e.drain()
    assert sfu.forwarded > 0
    assert sfu._pending_fanout is None, "pending fan-out never flushed"
    for e in eps:
        payloads = b"".join(e.got.values())
        for other in eps:
            if other is e:
                continue
            assert b"m-%08x" % other.ssrc in payloads, \
                f"{e.ssrc:#x} missing media from {other.ssrc:#x}"
        assert b"m-%08x" % e.ssrc not in payloads, "echoed own media"

    # NACK service against the FLUSHED cache: the per-leg copies were
    # inserted at flush time, not dispatch time
    victim = eps[0]
    victim.got.clear()
    for ssrc, row in victim.row_of.items():
        victim.open.add_stream(row, *victim.tx_key)
    victim.send_nack(eps[1].ssrc, [500])
    for _ in range(20):
        sfu.tick(now=70.2)
    for _ in range(4):
        victim.drain()
    assert sfu.retransmitted > 0
    assert any(seq == 500 for _, seq in victim.got)
    sfu.close()


@pytest.mark.slow
def test_sfu_svc_track_projection_e2e():
    """VP9 SVC through the assembled bridge: one SSRC carries two
    spatial layers; the receiver's REMB drives the projection (raise
    gated on a keyframe via PLI, downswitch at a picture boundary), the
    receiver sees a gapless renumbered stream, and a NACKed projected
    seq returns as RTX."""
    from libjitsi_tpu.codecs import vp9
    from libjitsi_tpu.core.packet import PacketBatch
    from libjitsi_tpu.sfu import rtx as rtx_mod

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=16, recv_window_ms=0)
    sender = _Endpoint(0xD0, sfu.port)
    recv = _Endpoint(0xD1, sfu.port)
    sid_s = sfu.add_endpoint(sender.ssrc, sender.rx_key, sender.tx_key)
    sid_r = sfu.add_endpoint(recv.ssrc, recv.rx_key, recv.tx_key)
    recv.send_media(1)
    svc_ssrc = 0xD00
    track = sfu.add_svc_track(sid_s, svc_ssrc,
                              layer_bps=[100e3, 1e6])
    fwd = track.fwd[sid_r]

    tx = SrtpStreamTable(capacity=1)
    tx.add_stream(0, *sender.rx_key)
    fb = SrtpStreamTable(capacity=1)
    fb.add_stream(0, *sender.tx_key)
    rxt = SrtpStreamTable(capacity=2)
    rxt.add_stream(0, *recv.tx_key)            # projected stream
    rxt.add_stream(1, *recv.tx_key)            # RTX stream
    state = {"seq": 100, "pic": 300}

    def send_pic(key=False):
        # every call is a NEW picture (the forwarder's switch logic
        # lands at picture boundaries, keyed by picture id)
        p = state["pic"]
        state["pic"] += 1
        pkts = []
        for s in range(2):
            desc = vp9.build_descriptor(
                begin=True, end=True, picture_id=p & 0x7FFF,
                tid=0, sid=s, tl0picidx=p & 0xFF,
                inter_predicted=not (key and s == 0))
            pkts.append(desc + bytes([0x90 + s]) * 40)
        b = rtp_header.build(pkts, [state["seq"], state["seq"] + 1],
                             [p * 3000] * 2, [svc_ssrc] * 2, [98] * 2,
                             marker=[0, 1], stream=[0, 0])
        state["seq"] += 2
        sender.engine.send_batch(tx.protect_rtp(b), "127.0.0.1",
                                 sfu.port)

    got_seqs, got_sids, rtx_osn = [], [], []

    def drain():
        back, _, _ = recv.engine.recv_batch(timeout_ms=2)
        if not back.batch_size:
            return
        hdr0 = rtp_header.parse(back)
        rowmap = {svc_ssrc: 0, track.rtx_ssrc: 1}
        back.stream[:] = [rowmap.get(int(s), -1) for s in hdr0.ssrc]
        keep = np.nonzero(np.asarray(back.stream) >= 0)[0]
        if len(keep) == 0:
            return
        sub = PacketBatch(back.data[keep],
                          np.asarray(back.length)[keep],
                          back.stream[keep])
        dec, ok = rxt.unprotect_rtp(sub)
        hdr = rtp_header.parse(dec)
        vid = np.nonzero(ok & (np.asarray(dec.stream) == 0))[0]
        if len(vid):
            vb = PacketBatch(dec.data[vid],
                             np.asarray(dec.length)[vid],
                             dec.stream[vid])
            d = vp9.parse_descriptors(vb)
            got_seqs.extend(int(s) for s in rtp_header.parse(vb).seq)
            got_sids.extend(int(s) for s in np.asarray(d.sid))
        for i in np.nonzero(ok & (np.asarray(dec.stream) == 1))[0]:
            one = PacketBatch(dec.data[i:i + 1],
                              np.asarray(dec.length)[i:i + 1],
                              dec.stream[i:i + 1])
            _res, osn = rtx_mod.decapsulate_batch(one, svc_ssrc, 98)
            rtx_osn.append(int(osn[0]))

    def sender_handle_feedback():
        back, _, _ = sender.engine.recv_batch(timeout_ms=3)
        if not back.batch_size:
            return False
        back.stream[:] = 0
        dec, ok = fb.unprotect_rtcp(back)
        saw = False
        for i in np.nonzero(np.asarray(ok))[0]:
            try:
                pkts = rtcp.parse_compound(dec.to_bytes(int(i)))
            except ValueError:
                continue
            saw |= any(isinstance(p, rtcp.Pli)
                       and p.media_ssrc == svc_ssrc for p in pkts)
        return saw

    def run(rounds, t0, remb, key_on_pli=False):
        for t in range(rounds):
            send_pic()
            blob = rtcp.build_compound([rtcp.build_remb(rtcp.Remb(
                recv.ssrc, int(remb), [svc_ssrc]))])
            b = PacketBatch.from_payloads([blob], stream=[0])
            recv.engine.send_batch(recv.protect.protect_rtcp(b),
                                   "127.0.0.1", sfu.port)
            for _ in range(10):
                sfu.tick(now=60.0 + (t0 + t) * 0.1)
            sfu.emit_feedback(now=60.0 + (t0 + t) * 0.1)
            if sender_handle_feedback() and key_on_pli:
                send_pic(key=True)
                for _ in range(10):
                    sfu.tick(now=60.0 + (t0 + t) * 0.1)
            drain()

    run(4, 0, remb=150_000)                 # base layer only
    assert fwd.current_sid == 0
    assert got_sids and max(got_sids) == 0
    run(8, 4, remb=1_500_000, key_on_pli=True)   # raise: needs keyframe
    assert fwd.current_sid == 1, "SVC raise never landed"
    assert 1 in got_sids
    run(4, 12, remb=150_000)                # starve: boundary downswitch
    assert fwd.current_sid == 0
    # gapless output seq space across every projection change
    assert got_seqs == list(range(got_seqs[0],
                                  got_seqs[0] + len(got_seqs)))
    # NACK on a projected seq comes back as RTX with that OSN
    want = got_seqs[-1]
    recv.send_nack(svc_ssrc, [want])
    for _ in range(10):
        sfu.tick(now=60.0 + 16 * 0.1 + 0.05)
    drain()
    assert want in rtx_osn, f"seq {want} not re-delivered as RTX"
    sfu.close()


def test_sfu_video_simulcast_forward_and_switch_core():
    """Core-gate video SFU: tiny-shape simulcast
    forward + REMB-driven layer switch with SYNTHETIC VP8 frames (every
    frame a keyframe, so switches land without a PLI round trip) — no
    libvpx, few packets, seconds not minutes.  The per-change gate now
    fails if SfuBridge video forwarding breaks."""
    from libjitsi_tpu.codecs import vp8 as vp8_mod
    from libjitsi_tpu.core.packet import PacketBatch

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=16, recv_window_ms=0)
    send = _Endpoint(0xE0, sfu.port)
    recv = _Endpoint(0xE4, sfu.port)
    sid_s = sfu.add_endpoint(send.ssrc, send.rx_key, send.tx_key)
    sid_r = sfu.add_endpoint(recv.ssrc, recv.rx_key, recv.tx_key)
    recv.send_media(1)                         # latch receiver address
    layer_ssrcs = [0xE00, 0xE01]
    track = sfu.add_video_track(sid_s, layer_ssrcs,
                                layer_bps=[100e3, 1e6], rtx_pt=97)
    fwd = track.fwd[sid_r]

    tx = SrtpStreamTable(capacity=2)
    for k in range(2):
        tx.add_stream(k, *send.rx_key)
    rxt = SrtpStreamTable(capacity=1)
    rxt.add_stream(0, *recv.tx_key)            # projected stream
    seqs, pids = [1000, 2000], [10, 20]
    got_layers, got_seqs = [], []

    def send_video(t):
        # synthetic VP8: frame tag LSB 0 => keyframe; payload byte
        # encodes the layer so the projection is attributable
        for k in range(2):
            frame = bytes([0x00, 0xE0 + k]) * 20
            pls = vp8_mod.packetize(frame, picture_id=pids[k])
            pids[k] = (pids[k] + 1) & 0x7FFF
            n = len(pls)
            b = rtp_header.build(
                pls, [(seqs[k] + i) & 0xFFFF for i in range(n)],
                [t * 3000] * n, [layer_ssrcs[k]] * n, [96] * n,
                marker=[0] * (n - 1) + [1], stream=[k] * n)
            seqs[k] = (seqs[k] + n) & 0xFFFF
            send.engine.send_batch(tx.protect_rtp(b), "127.0.0.1",
                                   sfu.port)

    def drain():
        back, _, _ = recv.engine.recv_batch(timeout_ms=2)
        if not back.batch_size:
            return
        hdr0 = rtp_header.parse(back)
        back.stream[:] = [0 if int(s) == send.ssrc else -1
                          for s in hdr0.ssrc]
        keep = np.nonzero(np.asarray(back.stream) >= 0)[0]
        if len(keep) == 0:
            return
        sub = PacketBatch(back.data[keep],
                          np.asarray(back.length)[keep],
                          back.stream[keep])
        dec, ok = rxt.unprotect_rtp(sub)
        hdr = rtp_header.parse(dec)
        for i in np.nonzero(ok)[0]:
            i = int(i)
            payload = dec.to_bytes(i)[int(hdr.payload_off[i]):]
            got_layers.append(payload[-1] - 0xE0)
            got_seqs.append(int(hdr.seq[i]))

    def run(rounds, t0, remb):
        for t in range(rounds):
            blob = rtcp.build_compound([rtcp.build_remb(rtcp.Remb(
                recv.ssrc, int(remb), [track.out_ssrc]))])
            b = PacketBatch.from_payloads([blob], stream=[0])
            recv.engine.send_batch(recv.protect.protect_rtcp(b),
                                   "127.0.0.1", sfu.port)
            for _ in range(3):
                sfu.tick(now=95.0 + (t0 + t) * 0.1)
            sfu.emit_feedback(now=95.0 + (t0 + t) * 0.1)
            send_video(t0 + t)
            for _ in range(6):
                sfu.tick(now=95.0 + (t0 + t) * 0.1 + 0.05)
            drain()

    run(3, 0, remb=2_000_000)        # bandwidth for the high layer
    assert fwd.current_layer == 1, f"no upswitch: {fwd.current_layer}"
    assert 1 in got_layers, "high-layer media never projected"
    run(3, 3, remb=150_000)          # starved to the base layer
    assert fwd.current_layer == 0, f"no downswitch: {fwd.current_layer}"
    assert got_layers[-1] == 0, "post-downswitch media not base layer"
    # the projection renumbers into one gapless seq space across the
    # switches
    assert got_seqs == list(range(got_seqs[0],
                                  got_seqs[0] + len(got_seqs)))
    assert sfu.forwarded > 0
    sfu.close()


@pytest.mark.slow
def test_sfu_bridge_snapshot_resume_mid_conference():
    """SURVEY §5 at assembly level: snapshot a live conference, tear
    the bridge down, restore on a NEW port — endpoints keep their SRTP
    counters running and media keeps flowing (replay windows moved with
    the snapshot, so the old packets are rejected and new ones pass)."""
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=8, recv_window_ms=0)
    eps = [_Endpoint(0x500 + 3 * k, sfu.port) for k in range(3)]
    for e in eps:
        sfu.add_endpoint(e.ssrc, e.rx_key, e.tx_key)
        for other in eps:
            if other is not e:
                e.expect_sender(other.ssrc)
    for rnd in range(2):
        for e in eps:
            e.send_media()
        for _ in range(16):
            sfu.tick(now=40.0 + rnd * 0.02)
        sfu.flush_egress()
        for e in eps:
            e.drain()
    assert sfu.forwarded > 0

    snap = sfu.snapshot()
    sfu.close()

    sfu2 = SfuBridge.restore(libjitsi_tpu.configuration_service(),
                             snap, port=0, recv_window_ms=0)
    assert sfu2.port != 0
    for e in eps:
        e.bridge_port = sfu2.port       # "signaling" moves endpoints
        e.got.clear()
    before = sfu2.forwarded
    for rnd in range(3):
        for e in eps:
            e.send_media()              # SRTP counters CONTINUE
        for _ in range(16):
            sfu2.tick(now=41.0 + rnd * 0.02)
        sfu2.flush_egress()
        for e in eps:
            for _ in range(3):
                e.drain()
    assert sfu2.forwarded > before
    for e in eps:
        payloads = b"".join(e.got.values())
        for other in eps:
            if other is e:
                continue
            assert b"m-%08x" % other.ssrc in payloads, \
                f"{e.ssrc:#x} missing post-restore media from " \
                f"{other.ssrc:#x}"
    # replayed pre-snapshot wire must NOT re-enter (windows resumed)
    rx_before = sfu2.forwarded
    replay = rtp_header.build([b"replay"], [500], [0],
                              [eps[0].ssrc], [96], stream=[0])
    old_tab = SrtpStreamTable(capacity=1)
    old_tab.add_stream(0, *eps[0].rx_key)
    eps[0].engine.send_batch(old_tab.protect_rtp(replay), "127.0.0.1",
                             sfu2.port)
    for _ in range(10):
        sfu2.tick(now=41.2)
    sfu2.flush_egress()
    assert sfu2.forwarded == rx_before, "replayed old seq re-forwarded"
    sfu2.close()


# ------------------------------------------------- the egress worker

def _served_rounds(egress_tap, synchronous, pipeline_depth, rounds=4):
    """Three endpoints, `rounds` rounds of four packets each: what
    every endpoint's socket received, raw and in arrival order, with
    the fan-out sent by the egress worker or (`synchronous`) by the
    synchronous call it replaced.  `pipeline_depth` is the loop's (its
    dispatched replies); the fan-out has one shape at every depth:
    dispatched by one tick, collected by the next."""
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=8, recv_window_ms=0,
                    pipeline_depth=pipeline_depth)
    tap = egress_tap(sfu)
    tap.synchronous = synchronous
    eps = [_Endpoint(0x100 + 7 * k, sfu.port) for k in range(3)]
    try:
        for e in eps:
            sfu.add_endpoint(e.ssrc, e.rx_key, e.tx_key)
        got = {e.ssrc: [] for e in eps}
        for rnd in range(rounds):
            for e in eps:
                e.send_media()
            for _ in range(3):
                sfu.tick(now=50.0 + rnd * 0.02)
            sfu.flush_egress()
            for e in eps:
                back, _, _ = e.engine.recv_batch(timeout_ms=0)
                got[e.ssrc] += [back.to_bytes(i)
                                for i in range(back.batch_size)]
        assert not sfu._egress_jobs and not sfu.loop.engine._jobs
        return {"got": got, "forwarded": sfu.forwarded,
                "handed": tap.handed, "jobs": [d.id for d in tap.reaped],
                "ports": {e.ssrc: e.engine.port for e in eps}}
    finally:
        sfu.close()
        for e in eps:
            e.engine.close()


@pytest.mark.parametrize("pipeline_depth", (1, 3),
                         ids=("inline", "pipelined"))
def test_worker_round_delivers_what_the_synchronous_round_delivers(
        egress_tap, pipeline_depth):
    """The same served rounds through the egress worker and through the
    synchronous `send_batch`: every receiver gets the same datagrams,
    byte for byte and in the same order, which is the order they were
    handed over in; `forwarded` after `flush_egress()` is the sends."""
    worker = _served_rounds(egress_tap, False, pipeline_depth)
    sync = _served_rounds(egress_tap, True, pipeline_depth)
    assert worker["jobs"] and all(j > 0 for j in worker["jobs"])
    assert sync["jobs"] and all(j < 0 for j in sync["jobs"])
    # rounds after the address latch forward 2 x 4 packets a receiver
    assert all(len(v) >= 3 * 8 for v in worker["got"].values())
    assert worker["got"] == sync["got"]
    for rec in (worker, sync):
        assert rec["forwarded"] == len(rec["handed"]) \
            == sum(len(v) for v in rec["got"].values())
        for ssrc, port in rec["ports"].items():
            assert rec["got"][ssrc] == [p for to, p in rec["handed"]
                                        if to == port]


def test_journey_is_measured_to_the_workers_end_stamp(sfu_with_traffic,
                                                      egress_tap):
    """The journey histogram takes a burst's latency from its arrival
    to the END of the worker's send, however much later the reap is."""
    import time

    sfu, sup, send = sfu_with_traffic
    send.until_forwarded()
    sfu.flush_egress()
    tap = egress_tap(sfu)
    hist = sfu.loop.journey_hist
    send()
    time.sleep(0.01)
    sum0, count0 = hist.sum, hist.count
    sup.tick(now=50.0)               # the dispatch
    sup.tick(now=50.0)               # no media: collected, handed over
    ((job, (origin, recv)),) = sfu._egress_jobs.items()
    assert (hist.sum, hist.count) == (sum0, count0)   # nothing booked yet
    time.sleep(0.05)                     # the reap comes late ...
    t_reap = time.perf_counter()
    sfu.flush_egress()
    (done,) = tap.reaped
    assert done.id == job and done.sent == len(recv) == 6
    # ... and the journey does not grow with it
    assert hist.count == count0 + 6
    assert hist.sum - sum0 == pytest.approx(6 * (done.t1 - origin[1]))
    assert origin[1] < done.t0 <= done.t1 < t_reap - 0.04


def test_egress_send_is_booked_by_the_reaping_tick_and_is_no_leaf(
        sfu_with_traffic):
    """`egress` is the hand-over (`queued`, `behind`); the send's own
    time is `egress_send`, booked by the tick that reaps the job: in
    the inclusive ledger and the counts, in no leaf, not in the self
    ledger the ladder steers on."""
    import time

    from libjitsi_tpu.utils import tracing

    sfu, sup, send = sfu_with_traffic
    send.until_forwarded()
    sfu.flush_egress()
    sup.tick(now=50.0)               # drains what that flush booked
    send()
    time.sleep(0.01)
    before = sfu.forwarded
    sup.tick(now=50.0)               # the dispatch
    assert "egress" not in sup.last_ledger
    sup.tick(now=50.0)               # no media: collection, hand-over
    led, counts = sup.last_ledger, sup.last_counts
    assert counts["egress"] == {"rows": 6, "bytes": counts["egress"][
        "bytes"], "queued": 1, "behind": 0}
    assert "egress_send" not in led and sfu.forwarded == before
    assert len(sfu._egress_jobs) == 1
    sfu.loop.engine.flush()          # sent, not reaped
    assert sfu.forwarded == before
    sup.tick(now=50.0)               # an idle tick: the reap
    led, counts = sup.last_ledger, sup.last_counts
    assert sfu.forwarded == before + 6 and not sfu._egress_jobs
    assert 0.0 < led["egress_send"] < 0.05
    assert counts["egress_send"] == {"rows": 6}
    assert "egress" not in led       # nothing was handed over here
    assert "egress_send" not in sup.last_self_ledger
    assert "egress_send" in tracing.OFF_TICK_STAGES
    assert "egress_send" not in (tracing.LEAF_STAGES
                                 + tracing.CONTAINER_STAGES)
    assert sfu.loop.metrics.timing("stage_egress_send").count >= 1


@pytest.mark.parametrize("outcome", ("behind", "short", "failed"))
def test_reap_books_what_the_worker_reports(sfu_with_traffic, outcome):
    """`behind` rides on the hand-over's span; a short send counts what
    was sent and a failed one raises `OSError` out of the tick that
    reaps it, as the synchronous call raised out of its own."""
    import errno
    import time

    from libjitsi_tpu.io.udp import SendJob

    sfu, sup, send = sfu_with_traffic
    send.until_forwarded()
    sfu.flush_egress()
    sup.tick(now=50.0)
    eng = sfu.loop.engine
    hand, reap = eng.send_batch_async, eng.reap
    if outcome == "behind":
        eng.send_batch_async = lambda *a: SendJob(
            *hand(*a)[:2], behind=True)
    else:
        sent = 4 if outcome == "short" else -errno.EINVAL
        eng.reap = lambda: [d._replace(sent=sent) for d in reap()]
    try:
        send()
        time.sleep(0.01)
        before = sfu.forwarded
        sup.tick(now=50.0)           # the dispatch
        sup.tick(now=50.0)           # the collection and the hand-over
        assert sup.last_counts["egress"]["behind"] == (
            1 if outcome == "behind" else 0)
        if outcome == "failed":
            with pytest.raises(OSError) as exc:
                sfu.flush_egress()
            assert exc.value.errno == errno.EINVAL
            assert sfu.forwarded == before and not sfu._egress_jobs
        else:
            sfu.flush_egress()
            assert sfu.forwarded == before + (
                4 if outcome == "short" else 6)
    finally:
        vars(eng).pop("reap", None)
        vars(eng).pop("send_batch_async", None)
