"""DTLS-SRTP: in-memory handshake, profile negotiation, key export,
fingerprint verification, demux, and keys driving real SRTP tables.

Reference behaviors: DtlsControlImpl/DtlsPacketTransformer (RFC 5764).
"""

import numpy as np
import pytest

from libjitsi_tpu.control.dtls import (
    HAVE_CRYPTOGRAPHY,
    DtlsAssociationTable,
    DtlsSrtpEndpoint,
    StubDtlsEndpoint,
    fingerprint,
    generate_certificate,
    is_dtls,
)
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.transform.srtp import SrtpProfile, SrtpStreamTable


needs_openssl = pytest.mark.skipif(
    not HAVE_CRYPTOGRAPHY,
    reason="gated dependency: the 'cryptography' package is not installed")


class _FakeEng:
    def __init__(self):
        self.out = []

    def send_batch(self, batch, ip, port):
        for i in range(batch.batch_size):
            self.out.append((batch.to_bytes(i), (ip, port)))
        return batch.batch_size


class _FakeLoop:
    def __init__(self, n=8):
        self.addr_ip = np.zeros(n, np.uint32)
        self.addr_port = np.zeros(n, np.uint16)
        self.engine = _FakeEng()
        self.released = []
        self.discarded = []

    def hold_stream(self, sid, max_packets=64):
        pass

    def release_stream(self, sid):
        self.released.append(sid)
        return 0

    def discard_stream(self, sid):
        self.discarded.append(sid)


def _assert_complementary(server_ep, client_ep):
    """The keys that landed are THIS client's keys (never cross-row)."""
    _, stk, sts, srk, srs = server_ep.srtp_keys()
    _, ctk, cts, crk, crs = client_ep.srtp_keys()
    assert (ctk, cts) == (srk, srs)
    assert (crk, crs) == (stk, sts)


def run_handshake(client: DtlsSrtpEndpoint, server: DtlsSrtpEndpoint,
                  drop=lambda i: False):
    """Pump datagrams between the endpoints until both complete."""
    wire = [(0, p) for p in client.handshake_packets()]
    i = 0
    rounds = 0
    while (not client.complete or not server.complete) and rounds < 50:
        rounds += 1
        nxt = []
        for who, pkt in wire:
            i += 1
            if drop(i):
                continue
            ep = server if who == 0 else client
            nxt += [(1 - who, p) for p in ep.feed(pkt)]
        wire = nxt
        if not wire and (not client.complete or not server.complete):
            wire = [(0, p) for p in client.handshake_packets()] + \
                   [(1, p) for p in server.handshake_packets()]
    assert client.complete and server.complete, "handshake did not finish"


@needs_openssl
def test_handshake_and_key_agreement():
    c = DtlsSrtpEndpoint("client")
    s = DtlsSrtpEndpoint("server")
    run_handshake(c, s)
    pc, c_txk, c_txs, c_rxk, c_rxs = c.srtp_keys()
    ps, s_txk, s_txs, s_rxk, s_rxs = s.srtp_keys()
    assert pc is ps
    # client's tx keys are the server's rx keys and vice versa
    assert (c_txk, c_txs) == (s_rxk, s_rxs)
    assert (c_rxk, c_rxs) == (s_txk, s_txs)
    assert len(c_txk) == pc.policy.enc_key_len


@needs_openssl
def test_profile_negotiation_intersection():
    c = DtlsSrtpEndpoint("client",
                         profiles=[SrtpProfile.AEAD_AES_128_GCM])
    s = DtlsSrtpEndpoint("server",
                         profiles=[SrtpProfile.AES_CM_128_HMAC_SHA1_80,
                                   SrtpProfile.AEAD_AES_128_GCM])
    run_handshake(c, s)
    assert c.selected_profile is SrtpProfile.AEAD_AES_128_GCM


@needs_openssl
def test_fingerprint_verification():
    cert, key, fp = generate_certificate()
    c = DtlsSrtpEndpoint("client", cert_der=cert, key_der=key)
    # server pinned to the RIGHT fingerprint: fine
    s = DtlsSrtpEndpoint("server", remote_fingerprint=fp)
    run_handshake(c, s)

    # server pinned to a WRONG fingerprint: handshake completion raises
    wrong = fingerprint(b"not-the-cert")
    c2 = DtlsSrtpEndpoint("client", cert_der=cert, key_der=key)
    s2 = DtlsSrtpEndpoint("server", remote_fingerprint=wrong)
    with pytest.raises((RuntimeError, AssertionError)):
        run_handshake(c2, s2)


def test_demux_first_byte():
    assert is_dtls(bytes([22, 0xfe, 0xfd]))       # handshake record
    assert is_dtls(bytes([20]))                    # ccs
    assert not is_dtls(bytes([0x80, 96]))          # RTP v2
    assert not is_dtls(bytes([0x81, 200]))         # RTCP
    assert not is_dtls(b"")
    assert not is_dtls(bytes([0]))                 # STUN would be 0..3


@needs_openssl
@pytest.mark.slow   # compile-heavy; sibling tests keep core coverage
def test_exported_keys_drive_srtp_tables():
    """End to end: DTLS handshake keys installed into SrtpStreamTables,
    protected media flows client -> server."""
    c = DtlsSrtpEndpoint("client")
    s = DtlsSrtpEndpoint("server")
    run_handshake(c, s)
    prof, c_txk, c_txs, _, _ = c.srtp_keys()
    _, _, _, s_rxk, s_rxs = s.srtp_keys()
    tx = SrtpStreamTable(capacity=2, profile=prof)
    tx.add_stream(0, c_txk, c_txs)
    rx = SrtpStreamTable(capacity=2, profile=prof)
    rx.add_stream(0, s_rxk, s_rxs)
    b = rtp_header.build([b"dtls-keyed-media"], [42], [0], [9], [96],
                         stream=[0])
    dec, ok = rx.unprotect_rtp(tx.protect_rtp(b))
    assert ok.all()
    assert dec.to_bytes(0) == b.to_bytes(0)


@needs_openssl
@pytest.mark.slow
def test_lossy_handshake_completes_via_retransmission():
    """30% datagram loss each way; the RFC 6347 flight
    timers (DtlsSrtpEndpoint.tick) must still complete the handshake.
    Real-time test: OpenSSL's initial flight timer is 1 s."""
    import time as _t

    rng = np.random.default_rng(7)
    c = DtlsSrtpEndpoint("client")
    s = DtlsSrtpEndpoint("server", cookie_exchange=True)

    def deliver(dst, datagrams):
        out = []
        for d in datagrams:
            if rng.random() < 0.30:
                continue                      # lost
            out.extend(dst.feed(d))
        return out

    pend_to_s = c.handshake_packets()
    t0 = _t.time()
    while not (c.complete and s.complete):
        assert _t.time() - t0 < 25, "handshake deadlocked under loss"
        pend_to_c = deliver(s, pend_to_s)
        pend_to_s = deliver(c, pend_to_c)
        pend_to_s += c.tick()
        for d in s.tick():
            pend_to_s.extend(c.feed(d))
        _t.sleep(0.05)
    assert c.retransmits + s.retransmits > 0, \
        "loss seeded but no flight was ever retransmitted"
    pc, ps = c.srtp_keys(), s.srtp_keys()
    assert pc[0] == ps[0]
    assert (pc[1], pc[2]) == (ps[3], ps[4])
    assert (pc[3], pc[4]) == (ps[1], ps[2])


def test_media_loop_hold_queues_and_releases():
    """Early media (racing the DTLS Finished flight) queues raw and
    replays through the chain once keys install."""
    import libjitsi_tpu
    from libjitsi_tpu.core.packet import PacketBatch
    from libjitsi_tpu.io.loop import MediaLoop
    from libjitsi_tpu.service.media_stream import StreamRegistry

    libjitsi_tpu.stop()
    libjitsi_tpu.init()

    class _FakeEngine:
        port = 0

        def recv_batch(self, timeout_ms):
            b = self._next
            self._next = (PacketBatch.from_payloads([]),
                          np.zeros(0, np.uint32), np.zeros(0, np.uint16))
            return b

        def send_batch(self, batch, ip, port):
            return batch.batch_size

    reg = StreamRegistry(libjitsi_tpu.configuration_service(),
                         capacity=4)
    seen = []
    eng = _FakeEngine()
    loop = MediaLoop(eng, reg,
                     on_media=lambda b, ok: seen.append(
                         (b.batch_size, ok.sum())) or None)
    reg.map_ssrc(0xABC, 2)
    loop.hold_stream(2)
    wire = rtp_header.build([b"early-%d" % i for i in range(3)],
                            [10, 11, 12], [0] * 3, [0xABC] * 3,
                            [96] * 3, stream=[0] * 3)
    pkts = [wire.to_bytes(i) for i in range(3)]
    eng._next = (PacketBatch.from_payloads(pkts),
                 np.full(3, 0x7F000001, np.uint32),
                 np.full(3, 5555, np.uint16))
    loop.tick()
    assert seen == [], "held media leaked through"
    n = loop.release_stream(2)
    assert n == 3
    assert seen == [(3, 3)]
    # bounded: queue holds max_packets, oldest evicted
    loop.hold_stream(2, max_packets=2)
    eng._next = (PacketBatch.from_payloads(pkts),
                 np.full(3, 0x7F000001, np.uint32),
                 np.full(3, 5555, np.uint16))
    loop.tick()
    assert loop.release_stream(2) == 2


def test_claim_ambiguity_and_recycled_address():
    """`_claim` under storm: an unknown source facing MULTIPLE unclaimed
    rows is dropped (never guessed onto a row), and a forgotten
    5-tuple's queued datagrams are purged so a rejoin on the recycled
    ip:port never gets the old association's bytes fed into its row."""
    installed = []
    loop = _FakeLoop()
    table = DtlsAssociationTable(
        loop, SrtpProfile.AES_CM_128_HMAC_SHA1_80,
        lambda sid, ep: installed.append((sid, ep)),
        deferred=True, endpoint_factory=StubDtlsEndpoint)

    # two unclaimed pending rows: ambiguous source is dropped
    table.join(1)
    table.join(2)
    stray = StubDtlsEndpoint("client")
    for d in stray.handshake_packets():
        table.on_dtls(d, (0x0A000001, 5000))
    table.process()
    assert (0x0A000001, 5000) not in table.addr_of
    table.forget(1)
    table.forget(2)

    # recycled 5-tuple: old association queues a datagram, the stream
    # leaves (forget), a new association re-binds the same addr
    addr = (0x0A000002, 6000)
    old_client = StubDtlsEndpoint("client")
    table.join(3, remote_addr=addr)
    for d in old_client.handshake_packets():
        table.on_dtls(d, addr)           # queued, NOT yet drained
    assert table._inbox
    table.forget(3)                      # purges the forgotten addr
    assert not table._inbox
    assert 3 in loop.discarded

    new_client = StubDtlsEndpoint("client")
    table.join(4, remote_addr=addr)
    for d in new_client.handshake_packets():
        table.on_dtls(d, addr)
    for _ in range(8):                   # off-tick drain to completion
        table.process()
        for d, a in loop.engine.out:
            if a == addr:
                for r in new_client.feed(d):
                    table.on_dtls(r, a)
        loop.engine.out.clear()
        if installed and new_client.complete:
            break
    assert [s for s, _ in installed] == [4]
    assert table.addr_of[addr] == 4
    _assert_complementary(installed[0][1], new_client)


def test_cookie_spoof_protection_at_queue_depth_two():
    """With queue depth > 1 and cookie exchange on, a spoofed-source
    copy of a victim's ClientHello may bind the fresh row first, but it
    never round-trips the cookie, so the real peer supersedes it — and
    both in-flight handshakes complete on their OWN rows through the
    bounded off-tick drain, keys never crossing."""
    installed = {}
    loop = _FakeLoop()
    table = DtlsAssociationTable(
        loop, SrtpProfile.AES_CM_128_HMAC_SHA1_80,
        lambda sid, ep: installed.__setitem__(sid, ep),
        deferred=True, endpoint_factory=StubDtlsEndpoint)
    r1, r2 = (0x0A000011, 5001), (0x0A000012, 5002)
    spoof = (0x0A999999, 9999)

    table.join(1, cookie_exchange=True)
    c1 = StubDtlsEndpoint("client")
    for d in c1.handshake_packets():
        table.on_dtls(d, r1)
    table.process()                      # c1 claims row 1 -> challenge
    assert table.addr_of[r1] == 1
    for d, a in loop.engine.out:         # c1 answers the cookie
        for r in c1.feed(d):
            table.on_dtls(r, a)
    loop.engine.out.clear()
    table.process()                      # row 1 sends its cert flight
    assert table.pending[1].progressed

    table.join(2, cookie_exchange=True)
    c2 = StubDtlsEndpoint("client")
    c2_hello = c2.handshake_packets()
    # attacker races c2's captured hello bytes from a spoofed source:
    # binds row 2 first, but only ever elicits the cookie challenge
    for d in c2_hello:
        table.on_dtls(d, spoof)
    table.process()
    assert table.addr_of[spoof] == 2
    assert not table.pending[2].progressed

    # the real c2 supersedes the unprogressed binding; both handshakes
    # then interleave through a BOUNDED drain (queue depth > 1)
    for d in c2_hello:
        table.on_dtls(d, r2)
    by_addr = {r1: c1, r2: c2}
    for _ in range(16):
        table.process(budget=2)
        for d, a in loop.engine.out:
            cl = by_addr.get(a)
            if cl is not None:
                for r in cl.feed(d):
                    table.on_dtls(r, a)
        loop.engine.out.clear()
        if len(installed) == 2 and c1.complete and c2.complete:
            break
    assert set(installed) == {1, 2}
    assert table.addr_of[r1] == 1 and table.addr_of[r2] == 2
    assert spoof not in table.addr_of
    _assert_complementary(installed[1], c1)
    _assert_complementary(installed[2], c2)
    # authenticated addresses latched for media return
    assert int(loop.addr_port[1]) == r1[1]
    assert int(loop.addr_port[2]) == r2[1]


def test_storm_interleaving_never_crosses_keys():
    """Property-style: N signaling-bound associations, their datagrams
    drained in randomized interleavings with a bounded budget — every
    install lands its own client's keys, across several seeds."""
    rng = np.random.default_rng(7)
    for _trial in range(4):
        installed = {}
        loop = _FakeLoop(n=16)
        table = DtlsAssociationTable(
            loop, SrtpProfile.AES_CM_128_HMAC_SHA1_80,
            lambda sid, ep: installed.__setitem__(sid, ep),
            deferred=True, endpoint_factory=StubDtlsEndpoint)
        clients = {}
        for k in range(6):
            addr = (0x0A000100 + k, 7000 + k)
            table.join(k, remote_addr=addr)
            clients[addr] = StubDtlsEndpoint("client")
        wire = []
        for addr, cl in clients.items():
            wire += [(d, addr) for d in cl.handshake_packets()]
        for _ in range(40):
            idx = rng.permutation(len(wire))
            for i in idx:
                table.on_dtls(*wire[int(i)])
            wire = []
            table.process(budget=3)
            for d, a in loop.engine.out:
                wire += [(r, a) for r in clients[a].feed(d)]
            loop.engine.out.clear()
            if (len(installed) == len(clients)
                    and all(c.complete for c in clients.values())):
                break
        assert len(installed) == len(clients)
        for k, (addr, cl) in enumerate(sorted(clients.items())):
            assert table.addr_of[addr] == k
            _assert_complementary(installed[k], cl)


@needs_openssl
@pytest.mark.slow      # rides OpenSSL's real flight-timer backoff
def test_association_table_spoofed_hello_cannot_lock_out_peer():
    """A spoofed-source ClientHello may bind the pending row's address
    first, but with cookie_exchange it can never round-trip the cookie,
    so it never 'progresses' — the real peer supersedes the binding
    (via its own flight retransmission) and completes."""
    import time as _t

    from libjitsi_tpu.control.dtls import DtlsAssociationTable

    class _Eng:
        def __init__(self):
            self.out = []

        def send_batch(self, batch, ip, port):
            for i in range(batch.batch_size):
                self.out.append((batch.to_bytes(i), (ip, port)))
            return batch.batch_size

    class _Loop:
        def __init__(self):
            self.addr_ip = np.zeros(8, np.uint32)
            self.addr_port = np.zeros(8, np.uint16)
            self.engine = _Eng()
            self.released = []

        def hold_stream(self, sid, max_packets=64):
            pass

        def release_stream(self, sid):
            self.released.append(sid)
            return 0

        def discard_stream(self, sid):
            pass

    installed = []
    loop = _Loop()
    table = DtlsAssociationTable(
        loop, SrtpProfile.AES_CM_128_HMAC_SHA1_80,
        lambda sid, ep: installed.append(sid))
    server_ep = table.join(3, role="server", cookie_exchange=True)

    client = DtlsSrtpEndpoint("client")
    first_flight = client.handshake_packets()
    spoofed, real = (0x0A090909, 6666), (0x0A000002, 5004)
    # attacker races the ClientHello bytes from a spoofed source: binds
    # the row, receives the HelloVerifyRequest it can never answer
    for d in first_flight:
        table.on_dtls(d, spoofed)
    assert table.addr_of[spoofed] == 3 and not server_ep.progressed

    # the real peer drives from its own address; its retransmission
    # timer re-elicits the HVR after the supersede (real-time: ~1-2 s)
    pend = list(first_flight)
    t0 = _t.time()
    while not (client.complete and installed) and _t.time() - t0 < 40:
        nxt = []
        for d in pend:
            for r in table.on_dtls(d, real):
                nxt.extend(client.feed(r))
        nxt.extend(client.tick())
        table.tick()                     # server-side flight resends
        for d, addr in loop.engine.out:
            if addr == real:
                nxt.extend(client.feed(d))
        loop.engine.out.clear()
        pend = nxt
        _t.sleep(0.05)
    assert installed == [3], "real peer never completed"
    assert table.addr_of.get(real) == 3
    assert loop.released == [3]
    # the authenticated handshake's address latched for media return
    assert int(loop.addr_port[3]) == real[1]
