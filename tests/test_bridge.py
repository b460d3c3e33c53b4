"""ConferenceBridge: the whole-conference tick as one object, e2e.

Three SRTP clients over real loopback UDP; each must hear the
mix-minus of the OTHERS (their own tone absent), all through the
batched unprotect -> dense bank -> mixer -> encode -> protect tail.
"""

import numpy as np
import pytest

import libjitsi_tpu
from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.io import UdpEngine
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.service.bridge import ConferenceBridge
from libjitsi_tpu.service.pump import g711_codec
from libjitsi_tpu.transform.srtp import SrtpStreamTable


class _Client:
    def __init__(self, ssrc, freq, bridge_port):
        self.ssrc = ssrc
        self.freq = freq
        self.codec = g711_codec()
        self.rx_key = (bytes([ssrc]) * 16, bytes([ssrc + 1]) * 14)
        self.tx_key = (bytes([ssrc + 2]) * 16, bytes([ssrc + 3]) * 14)
        self.protect = SrtpStreamTable(capacity=1)
        self.protect.add_stream(0, *self.rx_key)
        self.unprotect = SrtpStreamTable(capacity=1)
        self.unprotect.add_stream(0, *self.tx_key)
        self.engine = UdpEngine(port=0, max_batch=32)
        self.bridge_port = bridge_port
        self.seq = 100
        self.t = 0
        self.heard = []

    def send_frame(self):
        n = np.arange(160)
        pcm = (8000 * np.sin(2 * np.pi * self.freq *
                             (self.t + n) / 8000)).astype(np.int16)
        self.t += 160
        b = rtp_header.build([self.codec.encode(pcm)], [self.seq],
                             [self.t], [self.ssrc], [0], stream=[0])
        self.seq += 1
        self.engine.send_batch(self.protect.protect_rtp(b),
                               "127.0.0.1", self.bridge_port)

    def drain(self):
        back, _, _ = self.engine.recv_batch(timeout_ms=1)
        if back.batch_size:
            back.stream[:] = 0
            dec, ok = self.unprotect.unprotect_rtp(back)
            hdr = rtp_header.parse(dec)
            for i in np.nonzero(ok)[0]:
                pay = dec.to_bytes(int(i))[int(hdr.payload_off[i]):]
                self.heard.append(self.codec.decode(pay))


@pytest.mark.slow
def test_bridge_three_party_mix_minus():
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    bridge = ConferenceBridge(libjitsi_tpu.configuration_service(),
                              port=0, capacity=16, recv_window_ms=0)
    clients = [_Client(10, 400.0, bridge.port),
               _Client(20, 900.0, bridge.port),
               _Client(30, 1600.0, bridge.port)]
    for c in clients:
        bridge.add_participant(c.ssrc, c.rx_key, c.tx_key)

    now = 100.0
    for tick in range(30):
        for c in clients:
            c.send_frame()
        for _ in range(10):       # let the datagrams land
            stats = bridge.tick(now=now)
            if stats["rx"]:
                break
        bridge.tick(now=now + 0.001)   # decode tick (frames due)
        for c in clients:
            c.drain()
        now += 0.020

    for c in clients:
        assert len(c.heard) >= 10, f"ssrc {c.ssrc} heard too little"
        pcm = np.concatenate(c.heard[5:]).astype(np.float64)
        spec = np.abs(np.fft.rfft(pcm * np.hanning(len(pcm))))
        freqs = np.fft.rfftfreq(len(pcm), 1 / 8000.0)

        def power_at(f):
            return spec[np.argmin(np.abs(freqs - f))]

        own = power_at(c.freq)
        others = [power_at(o.freq) for o in clients if o is not c]
        # mix-minus: both other tones clearly present, own tone absent
        assert min(others) > 10 * own, \
            (c.ssrc, own, others)

    # stats2 / counters sanity through the bridge registry
    assert bridge.bank.decoded_frames[:3].sum() > 30
    bridge.close()


def test_bridge_rejects_mismatched_codec_frame():
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    bridge = ConferenceBridge(libjitsi_tpu.configuration_service(),
                              port=0, capacity=4)
    bridge.add_participant(1, (b"\x01" * 16, b"\x02" * 14),
                           (b"\x03" * 16, b"\x04" * 14))
    with pytest.raises(ValueError):
        bridge.add_participant(
            2, (b"\x05" * 16, b"\x06" * 14),
            (b"\x07" * 16, b"\x08" * 14),
            codec=g711_codec(ptime_ms=30))
    bridge.close()


def test_bridge_participant_churn_clears_row_residue():
    """A leave must clear ssrc demux, SRTP rows, and the latched
    address — the recycled sid must not redirect the new occupant's
    media to the old participant's socket."""
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    bridge = ConferenceBridge(libjitsi_tpu.configuration_service(),
                              port=0, capacity=4, recv_window_ms=0)
    sid = bridge.add_participant(0x10, (b"\x01" * 16, b"\x02" * 14),
                                 (b"\x03" * 16, b"\x04" * 14))
    # simulate a latched address from a received packet
    bridge.loop.addr_ip[sid] = 0x7F000001
    bridge.loop.addr_port[sid] = 55555
    bridge.remove_participant(sid)
    assert bridge.loop.addr_port[sid] == 0
    assert not bridge.rx_table.active[sid]
    assert not bridge.tx_table.active[sid]
    # same ssrc can rejoin; duplicate join is rejected while mapped
    sid2 = bridge.add_participant(0x10, (b"\x05" * 16, b"\x06" * 14),
                                  (b"\x07" * 16, b"\x08" * 14))
    assert sid2 == sid                    # LIFO row recycle
    with pytest.raises(ValueError):
        bridge.add_participant(0x10, (b"\x09" * 16, b"\x0a" * 14),
                               (b"\x0b" * 16, b"\x0c" * 14))
    # empty-tick return shape is stable (levels key always present)
    bridge2 = ConferenceBridge(libjitsi_tpu.configuration_service(),
                               port=0, capacity=4, recv_window_ms=0)
    assert "levels" in bridge2.tick(now=1.0)
    bridge.close()
    bridge2.close()


@pytest.mark.slow
def test_bridge_levels_ext_and_speaker_events():
    """egress packets carry the RFC 6465 audio-level
    extension, and the dominant-speaker detector fires change events
    when the loud tone moves to another participant."""
    from libjitsi_tpu.rtp import ext as rtp_ext

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    events = []
    bridge = ConferenceBridge(
        libjitsi_tpu.configuration_service(), port=0, capacity=16,
        recv_window_ms=0,
        on_speaker_change=lambda sid, ssrc: events.append((sid, ssrc)))
    clients = [_Client(10, 400.0, bridge.port),
               _Client(20, 900.0, bridge.port),
               _Client(30, 1600.0, bridge.port)]
    sids = [bridge.add_participant(c.ssrc, c.rx_key, c.tx_key)
            for c in clients]
    amps = {10: 16000, 20: 0, 30: 0}      # client 10 talks first

    ext_seen = []

    def send_frame(c):
        n = np.arange(160)
        pcm = (amps[c.ssrc] * np.sin(2 * np.pi * c.freq *
                                     (c.t + n) / 8000)).astype(np.int16)
        c.t += 160
        b = rtp_header.build([c.codec.encode(pcm)], [c.seq], [c.t],
                             [c.ssrc], [0], stream=[0])
        c.seq += 1
        c.engine.send_batch(c.protect.protect_rtp(b),
                            "127.0.0.1", c.bridge_port)

    def drain_ext(c):
        back, _, _ = c.engine.recv_batch(timeout_ms=1)
        if back.batch_size:
            back.stream[:] = 0
            dec, ok = c.unprotect.unprotect_rtp(back)
            hdr = rtp_header.parse(dec)
            off, _l, found = rtp_ext.find_one_byte_ext(dec, hdr, 1)
            for i in np.nonzero(ok)[0]:
                if found[i]:
                    lvl = int(dec.data[int(i), int(off[int(i)])]) & 0x7F
                    ext_seen.append((c.ssrc, lvl))

    now = 200.0
    for phase, talker in ((0, 10), (1, 20)):
        amps = {s: (16000 if s == talker else 0) for s in amps}
        for tick in range(45):
            for c in clients:
                send_frame(c)
            for _ in range(10):
                if bridge.tick(now=now)["rx"]:
                    break
            bridge.tick(now=now + 0.001)
            for c in clients:
                drain_ext(c)
            now += 0.020
        want = sids[[c.ssrc for c in clients].index(talker)]
        assert bridge.speaker.dominant == want, (phase, talker)

    # both talkers produced a change event, in order
    assert [e[0] for e in events[:2]] == [sids[0], sids[1]]
    assert events[0][1] == 10 and events[1][1] == 20
    # the audio-level ext rode the wire; listeners of the active talker
    # saw loud (low dBov) levels, the talker itself heard silence-ish
    assert ext_seen, "no audio-level extension seen on egress"
    loud_at_listener = [lv for ssrc, lv in ext_seen if ssrc != 10]
    assert min(loud_at_listener) < 30
    bridge.close()


@pytest.mark.slow
def test_bridge_mixed_rate_g711_and_g722():
    """a G.711 8 kHz phone and a G.722 16 kHz endpoint
    share one conference; each hears the other's tone at its own rate
    (deposit path upsamples to the bridge clock, egress path resamples
    the mix back down/up per leg)."""
    from libjitsi_tpu.service.pump import g722_codec

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    bridge = ConferenceBridge(libjitsi_tpu.configuration_service(),
                              port=0, capacity=16, recv_window_ms=0)

    class _C16(_Client):
        def __init__(self, ssrc, freq, port):
            super().__init__(ssrc, freq, port)
            self.codec = g722_codec()
            self.rate = 16000

        def send_frame(self):
            n = np.arange(320)
            pcm = (8000 * np.sin(2 * np.pi * self.freq *
                                 (self.t + n) / 16000)).astype(np.int16)
            self.t += 320
            b = rtp_header.build([self.codec.encode(pcm)], [self.seq],
                                 [self.t // 2], [self.ssrc], [9],
                                 stream=[0])
            self.seq += 1
            self.engine.send_batch(self.protect.protect_rtp(b),
                                   "127.0.0.1", self.bridge_port)

    wide = _C16(40, 1800.0, bridge.port)     # 16 kHz leg joins FIRST:
    narrow = _Client(50, 400.0, bridge.port)  # bridge clock = 16 kHz
    narrow.rate = 8000
    bridge.add_participant(wide.ssrc, wide.rx_key, wide.tx_key,
                           codec=g722_codec())
    bridge.add_participant(narrow.ssrc, narrow.rx_key, narrow.tx_key)

    now = 300.0
    for tick in range(40):
        wide.send_frame()
        narrow.send_frame()
        for _ in range(10):
            if bridge.tick(now=now)["rx"]:
                break
        bridge.tick(now=now + 0.001)
        for c in (wide, narrow):
            c.drain()
        now += 0.020

    for c, hear_freq, own_freq in ((wide, 400.0, 1800.0),
                                   (narrow, 1800.0, 400.0)):
        assert len(c.heard) >= 10, f"ssrc {c.ssrc} heard too little"
        pcm = np.concatenate(c.heard[5:]).astype(np.float64)
        spec = np.abs(np.fft.rfft(pcm * np.hanning(len(pcm))))
        freqs = np.fft.rfftfreq(len(pcm), 1.0 / c.rate)

        def power_at(f):
            return spec[np.argmin(np.abs(freqs - f))]

        other, own = power_at(hear_freq), power_at(own_freq)
        assert other > 20 * own, \
            (f"ssrc {c.ssrc}: other tone {other:.0f} !>> own "
             f"{own:.0f} (mix-minus across rates)")
    bridge.close()


@pytest.mark.slow
def test_conference_bridge_snapshot_resume_mid_call():
    """A live G.711 conference checkpoints, tears down, and resumes on
    a new port: mix-minus keeps flowing on continuing SRTP counters and
    replayed pre-snapshot wire is rejected (windows resumed)."""
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    bridge = ConferenceBridge(libjitsi_tpu.configuration_service(),
                              port=0, capacity=16, recv_window_ms=0)
    clients = [_Client(60, 400.0, bridge.port),
               _Client(70, 900.0, bridge.port),
               _Client(80, 1600.0, bridge.port)]
    for c in clients:
        bridge.add_participant(c.ssrc, c.rx_key, c.tx_key)
    now = 400.0
    for tick in range(8):
        for c in clients:
            c.send_frame()
        for _ in range(10):
            if bridge.tick(now=now)["rx"]:
                break
        bridge.tick(now=now + 0.001)
        for c in clients:
            c.drain()
        now += 0.020

    snap = bridge.snapshot()
    bridge.close()
    bridge2 = ConferenceBridge.restore(
        libjitsi_tpu.configuration_service(), snap, port=0,
        recv_window_ms=0)
    for c in clients:
        c.bridge_port = bridge2.port
        c.heard.clear()
    for tick in range(20):
        for c in clients:
            c.send_frame()              # SRTP counters CONTINUE
        for _ in range(10):
            if bridge2.tick(now=now)["rx"]:
                break
        bridge2.tick(now=now + 0.001)
        for c in clients:
            c.drain()
        now += 0.020

    for c in clients:
        assert len(c.heard) >= 8, \
            f"ssrc {c.ssrc} heard too little post-restore"
        pcm = np.concatenate(c.heard[4:]).astype(np.float64)
        spec = np.abs(np.fft.rfft(pcm * np.hanning(len(pcm))))
        freqs = np.fft.rfftfreq(len(pcm), 1 / 8000.0)

        def power_at(f):
            return spec[np.argmin(np.abs(freqs - f))]

        own = power_at(c.freq)
        others = [power_at(o.freq) for o in clients if o is not c]
        assert min(others) > 3 * own, \
            f"post-restore mix-minus broken for {c.ssrc}"
    # replayed pre-snapshot wire is rejected: the SRTP replay windows
    # moved with the checkpoint (seq 100 was consumed pre-snapshot)
    drops_before = bridge2.chain.drop_counts.get("SrtpTransformEngine",
                                                 0)
    old_tab = SrtpStreamTable(capacity=1)
    old_tab.add_stream(0, *clients[0].rx_key)
    replay = rtp_header.build([b"replayed"], [100], [160], [60], [0],
                              stream=[0])
    clients[0].engine.send_batch(old_tab.protect_rtp(replay),
                                 "127.0.0.1", bridge2.port)
    for _ in range(10):
        bridge2.tick(now=now)
    assert bridge2.chain.drop_counts.get("SrtpTransformEngine", 0) \
        > drops_before, "pre-snapshot replay was not rejected"

    # stateful-codec legs checkpoint as DEGRADED rows (codec re-inits
    # on restore), no longer a refusal — see the opus resume test
    from libjitsi_tpu.service.pump import g722_codec
    b3 = ConferenceBridge(libjitsi_tpu.configuration_service(), port=0,
                          capacity=4, recv_window_ms=0)
    sid = b3.add_participant(0x91, (b"\x01" * 16, b"\x02" * 14),
                             (b"\x03" * 16, b"\x04" * 14),
                             codec=g722_codec())
    s3 = b3.snapshot()
    assert s3["degraded_rows"] == [sid]
    assert s3["codec_name"][sid] == "G722"
    b3.close()
    bridge2.close()


@pytest.mark.slow
def test_bridge_opus_conference_degraded_resume():
    """an OPUS conference (stateful C codec on every
    leg) snapshots and resumes: SRTP counters/replay windows carry over
    exactly, codec state re-initializes (decoder PLC warms up, encoder
    restarts clean), and after a bounded startup artifact the mix-minus
    audio is correct again."""
    from libjitsi_tpu.service.pump import opus_codec

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    bridge = ConferenceBridge(libjitsi_tpu.configuration_service(),
                              port=0, capacity=16, recv_window_ms=0)

    class _C48(_Client):
        def __init__(self, ssrc, freq, port):
            super().__init__(ssrc, freq, port)
            self.codec = opus_codec()
            self.rate = 48000

        def send_frame(self):
            n = np.arange(960)
            pcm = (8000 * np.sin(2 * np.pi * self.freq *
                                 (self.t + n) / 48000)).astype(np.int16)
            self.t += 960
            b = rtp_header.build([self.codec.encode(pcm)], [self.seq],
                                 [self.t], [self.ssrc], [111],
                                 stream=[0])
            self.seq += 1
            self.engine.send_batch(self.protect.protect_rtp(b),
                                   "127.0.0.1", self.bridge_port)

    clients = [_C48(0xA1, 400.0, bridge.port),
               _C48(0xB1, 900.0, bridge.port),
               _C48(0xC1, 1600.0, bridge.port)]
    for c in clients:
        bridge.add_participant(c.ssrc, c.rx_key, c.tx_key,
                               codec=opus_codec())
    now = 500.0
    for tick in range(8):
        for c in clients:
            c.send_frame()
        for _ in range(10):
            if bridge.tick(now=now)["rx"]:
                break
        bridge.tick(now=now + 0.001)
        for c in clients:
            c.drain()
        now += 0.020

    snap = bridge.snapshot()
    assert sorted(snap["degraded_rows"]) == sorted(snap["ssrc_of"])
    bridge.close()
    bridge2 = ConferenceBridge.restore(
        libjitsi_tpu.configuration_service(), snap, port=0,
        recv_window_ms=0)
    for c in clients:
        c.bridge_port = bridge2.port
        c.heard.clear()
    for tick in range(24):
        for c in clients:
            c.send_frame()              # SRTP counters CONTINUE
        for _ in range(10):
            if bridge2.tick(now=now)["rx"]:
                break
        bridge2.tick(now=now + 0.001)
        for c in clients:
            c.drain()
        now += 0.020

    for c in clients:
        assert len(c.heard) >= 10, \
            f"ssrc {c.ssrc:#x} heard too little post-restore"
        # bounded startup artifact: skip the PLC/encoder warmup frames,
        # then the spectrum must be a clean mix-minus again
        pcm = np.concatenate(c.heard[6:]).astype(np.float64)
        spec = np.abs(np.fft.rfft(pcm * np.hanning(len(pcm))))
        freqs = np.fft.rfftfreq(len(pcm), 1 / 48000.0)

        def power_at(f):
            return spec[np.argmin(np.abs(freqs - f))]

        own = power_at(c.freq)
        others = [power_at(o.freq) for o in clients if o is not c]
        assert min(others) > 3 * own, \
            f"post-restore opus mix-minus broken for {c.ssrc:#x}"
    # pre-snapshot wire must NOT re-enter (replay windows resumed)
    drops_before = bridge2.chain.drop_counts.get("SrtpTransformEngine",
                                                 0)
    old_tab = SrtpStreamTable(capacity=1)
    old_tab.add_stream(0, *clients[0].rx_key)
    replay = rtp_header.build([b"replayed"], [100], [960], [0xA1],
                              [111], stream=[0])
    clients[0].engine.send_batch(old_tab.protect_rtp(replay),
                                 "127.0.0.1", bridge2.port)
    for _ in range(10):
        bridge2.tick(now=now)
    assert bridge2.chain.drop_counts.get("SrtpTransformEngine", 0) \
        > drops_before, "pre-snapshot replay was not rejected"
    bridge2.close()
