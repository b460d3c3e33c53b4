"""Host I/O loop over real loopback sockets: a mini SFU bridge tick.

Exercises the production wiring end to end: client protects RTP ->
UDP -> bridge MediaLoop (recvmmsg batch, SSRC demux, address latching,
batched SRTP reverse chain) -> echo sink -> forward chain -> UDP ->
client decrypts.  Also covers rtcp-mux and DTLS first-byte splitting.
"""

import numpy as np

import libjitsi_tpu
from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.io import UdpEngine
from libjitsi_tpu.io.loop import MediaLoop
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.rtp import rtcp
from libjitsi_tpu.service.media_stream import StreamRegistry
from libjitsi_tpu.transform import SrtpTransformEngine, TransformEngineChain
from libjitsi_tpu.transform.srtp import SrtpStreamTable
import pytest

MK, MS = bytes(range(16)), bytes(range(30, 44))
MK2, MS2 = bytes(range(60, 76)), bytes(range(80, 94))


def _registry():
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    return StreamRegistry(libjitsi_tpu.configuration_service(), capacity=16)


@pytest.mark.slow
def test_bridge_echo_over_udp():
    reg = _registry()
    # bridge rx context (client->bridge key), tx context (bridge->client)
    rx_tab = SrtpStreamTable(capacity=16)
    rx_tab.add_stream(3, MK, MS)
    tx_tab = SrtpStreamTable(capacity=16)
    tx_tab.add_stream(3, MK2, MS2)
    chain = TransformEngineChain([SrtpTransformEngine(tx_tab, rx_tab)])

    got_media = []

    def on_media(batch, ok):
        got_media.append(int(ok.sum()))
        rows = np.nonzero(ok)[0]
        if len(rows) == 0:
            return None
        return PacketBatch(batch.data[rows],
                           np.asarray(batch.length)[rows],
                           batch.stream[rows])  # echo back

    rtcp_seen = []
    bridge = MediaLoop(UdpEngine(port=0, max_batch=64), reg,
                       on_media=on_media,
                       on_rtcp=lambda b, ok: rtcp_seen.append(b.batch_size),
                       chain=chain)
    reg.map_ssrc(0xC11E27, 3)

    # client: protect 8 packets and send them to the bridge
    c_tx = SrtpStreamTable(capacity=1)
    c_tx.add_stream(0, MK, MS)
    c_rx = SrtpStreamTable(capacity=1)
    c_rx.add_stream(0, MK2, MS2)
    payloads = [b"frame-%02d" % i for i in range(8)]
    b = rtp_header.build(payloads, list(range(8)), [0] * 8,
                         [0xC11E27] * 8, [96] * 8, stream=[0] * 8)
    wire = c_tx.protect_rtp(b)
    client = UdpEngine(port=0, max_batch=64)
    client.send_batch(wire, "127.0.0.1", bridge.engine.port)

    # bridge processes one tick (recv batch -> decrypt -> echo -> encrypt)
    for _ in range(50):
        if bridge.tick():
            break
    assert sum(got_media) == 8
    assert bridge.addr_port[3] == client.port  # address latched

    # client receives the re-protected echo and decrypts with MK2
    back, _, _ = client.recv_batch(timeout_ms=500)
    assert back.batch_size == 8
    back.stream[:] = 0
    dec, ok = c_rx.unprotect_rtp(back)
    assert ok.all()
    hdr = rtp_header.parse(dec)
    got = {dec.to_bytes(i)[int(hdr.payload_off[i]):] for i in range(8)}
    assert got == set(payloads)
    client.close()
    bridge.engine.close()


def test_loop_splits_dtls_and_rtcp():
    reg = _registry()
    dtls_in = []

    def on_dtls(pkt, addr):
        dtls_in.append(pkt)
        return [b"\x16\xfe\xfd-reply"]

    rtcp_seen = []
    bridge = MediaLoop(UdpEngine(port=0, max_batch=16), reg,
                       on_rtcp=lambda b, ok: rtcp_seen.append(b.batch_size),
                       on_dtls=on_dtls, chain=None)
    reg.map_ssrc(0xABC, 1)

    client = UdpEngine(port=0, max_batch=16)
    dtls_pkt = b"\x16\xfe\xfd\x00\x00hello"         # handshake record
    rr = rtcp.build_rr(rtcp.ReceiverReport(0xABC, []))
    media = rtp_header.build([b"m"], [1], [0], [0xABC], [96]).to_bytes(0)
    batch = PacketBatch.from_payloads([dtls_pkt, rr, media])
    client.send_batch(batch, "127.0.0.1", bridge.engine.port)

    for _ in range(50):
        if bridge.tick():
            break
    assert dtls_in == [dtls_pkt]
    assert rtcp_seen == [1]
    # the DTLS reply came back to the client
    back, _, _ = client.recv_batch(timeout_ms=500)
    assert back.batch_size == 1 and back.to_bytes(0).startswith(b"\x16")
    # metrics rendered timing quantiles
    assert "reverse_chain_seconds" in bridge.metrics.render()
    client.close()
    bridge.engine.close()


def test_loop_kernel_arrival_ns_aligned_with_media_rows():
    """MediaLoop with a timestamped engine exposes per-row kernel
    arrival times aligned with the batch handed to on_media."""
    reg = _registry()
    rx_tab = SrtpStreamTable(capacity=16)
    rx_tab.add_stream(3, MK, MS)
    tx_tab = SrtpStreamTable(capacity=16)
    tx_tab.add_stream(3, MK2, MS2)
    chain = TransformEngineChain([SrtpTransformEngine(tx_tab, rx_tab)])
    seen = {}

    def on_media(batch, ok):
        seen["n"] = batch.batch_size
        seen["ats"] = bridge.last_rtp_arrival_ns
        return None

    bridge = MediaLoop(
        UdpEngine(port=0, max_batch=64, kernel_timestamps=True), reg,
        on_media=on_media, chain=chain)
    assert bridge.use_kernel_ts
    reg.map_ssrc(0xC11E27, 3)
    c_tx = SrtpStreamTable(capacity=1)
    c_tx.add_stream(0, MK, MS)
    b = rtp_header.build([b"k-%d" % i for i in range(4)],
                         list(range(4)), [0] * 4, [0xC11E27] * 4,
                         [96] * 4, stream=[0] * 4)
    client = UdpEngine(port=0, max_batch=64)
    client.send_batch(c_tx.protect_rtp(b), "127.0.0.1",
                      bridge.engine.port)
    import time as _t
    t0 = _t.time()
    for _ in range(50):
        if bridge.tick():
            break
    assert seen["n"] == 4
    ats = seen["ats"]
    assert ats is not None and len(ats) == 4
    assert np.all(np.abs(ats / 1e9 - t0) < 5.0)


def test_send_media_async_flush_matches_sync():
    """The pipelined seam: dispatch-only protect +
    next-tick flush must emit byte-identical datagrams to the sync
    path, with TX state advancing identically."""
    import libjitsi_tpu
    from libjitsi_tpu.io.loop import MediaLoop
    from libjitsi_tpu.service.media_stream import StreamRegistry
    from libjitsi_tpu.transform import (SrtpTransformEngine,
                                        TransformEngineChain)
    from libjitsi_tpu.transform.srtp import SrtpStreamTable

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    mk, ms = bytes(range(16)), bytes(range(30, 44))

    class _CaptureEngine:
        port = 0

        def __init__(self):
            self.sent = []

        def recv_batch(self, timeout_ms):
            return (PacketBatch.from_payloads([]),
                    np.zeros(0, np.uint32), np.zeros(0, np.uint16))

        def send_batch(self, batch, ip, port):
            for i in range(batch.batch_size):
                self.sent.append(batch.to_bytes(i))
            return batch.batch_size

    def build_loop(pipelined):
        reg = StreamRegistry(libjitsi_tpu.configuration_service(),
                             capacity=4)
        tx = SrtpStreamTable(capacity=4)
        tx.add_stream(2, mk, ms)
        rx = SrtpStreamTable(capacity=4)
        rx.add_stream(2, mk, ms)
        chain = TransformEngineChain([SrtpTransformEngine(tx, rx)])
        eng = _CaptureEngine()
        loop = MediaLoop(eng, reg, chain=chain, pipelined=pipelined)
        loop.addr_ip[2] = 0x7F000001
        loop.addr_port[2] = 4444
        return loop, eng

    batch = rtp_header.build([b"pipelined-%d" % i for i in range(5)],
                             [800 + i for i in range(5)], [0] * 5,
                             [0xF00D] * 5, [96] * 5, stream=[2] * 5)

    sync_loop, sync_eng = build_loop(False)
    sync_loop.send_media(batch)

    pipe_loop, pipe_eng = build_loop(True)
    n = pipe_loop.send_media_async(batch)
    assert n == 5 and pipe_eng.sent == [], "async sent before flush"
    pipe_loop.tick()                 # next tick flushes the in-flight
    assert pipe_eng.sent == sync_eng.sent
    # idempotent: nothing left in flight
    assert pipe_loop.flush_sends() == 0


def test_scrape_sees_live_inflight_age_not_last_tick_note():
    """Staleness regression for the deep pipeline's age gauge: the
    exporter reads `_inflight_age()` LIVE, so a scrape between tick
    boundaries sees the dispatch aging (and sees zero right after a
    drain) instead of the value frozen at the last per-tick note."""
    reg = _registry()
    tx = SrtpStreamTable(capacity=16)
    tx.add_stream(2, MK, MS)
    rx = SrtpStreamTable(capacity=16)
    rx.add_stream(2, MK2, MS2)
    chain = TransformEngineChain([SrtpTransformEngine(tx, rx)])
    loop = MediaLoop(UdpEngine(port=0, max_batch=16), reg,
                     chain=chain, pipelined=True)
    loop.addr_ip[2] = 0x7F000001
    loop.addr_port[2] = 9                # discard; nothing listens
    batch = rtp_header.build([b"inflight-x"], [1], [0], [0xF00D],
                             [96], stream=[2])
    assert loop.send_media_async(batch) == 1
    loop.ticks += 3                      # ticks pass, no flush, no note
    assert loop._inflight_age() == 3
    assert loop.dispatch_inflight_ticks == 0, \
        "per-tick note is only taken at tick boundaries"
    assert "libjitsi_tpu_dispatch_inflight_ticks 3" \
        in loop.metrics.render()
    loop.flush_sends()
    # live again after the drain, still before any tick boundary
    assert "libjitsi_tpu_dispatch_inflight_ticks 0" \
        in loop.metrics.render()
