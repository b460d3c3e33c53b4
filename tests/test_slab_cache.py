"""`SlabCache` (the SFU bridge's per-leg retransmission cache) against
`PacketCache`, the structure it replaced there and the single-packet
callers still run: the same trace of batch inserts and lookups must give
the same hits in the same order, the same `missing`, and the same
`size_bytes` / `len()` wherever no key repeats.  Then the bridge around
it: a NACK answered byte for byte after 100 further ticks (the slab
holds memory no later tick rewrites), FEC unchanged, and an insert that
creates no per-row Python object.
"""

import gc
import sys

import numpy as np
import pytest

import libjitsi_tpu
from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.io import UdpEngine
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.rtp import rtcp
from libjitsi_tpu.service.sfu_bridge import SfuBridge
from libjitsi_tpu.sfu.cache import PacketCache, SlabCache
from libjitsi_tpu.sfu.recovery import FEC_SSRC_XOR, RecoveryConfig
from libjitsi_tpu.transform.fec import build_fec, parse_fec
from libjitsi_tpu.transform.srtp import SrtpStreamTable
from libjitsi_tpu.utils.metrics import MetricsRegistry

WIDTH = 96

# what a profile leans on; every trace has a little of everything else
PROFILES = {
    # legs up to 2**32 - 1: the namespace fills 64 bits and the key
    # array wraps, so a hit has to be checked against the row's own
    "wide_ns": dict(leg_bits=32),
    # every namespace starts just under 65536: NACK lists straddle 0
    "wrap": dict(seq0=65530),
    # the clock jumps past `max_age` between inserts
    "age": dict(dt=(0.2, 0.7), max_bytes=1 << 20),
    # small byte bound, big batches: the low-water row moves inside the
    # oldest slab on nearly every insert
    "bytes": dict(max_bytes=2000, rows=(8, 40)),
    "empty": dict(p_empty=0.4),
    # the bridge's copy path: the slab is a fancy-indexed copy of the
    # plane, not a strided view of it
    "filtered": dict(p_filtered=1.0),
    # the same key inserted again: the newer copy answers (sizes differ
    # by the superseded copy until it ages out, so are not compared)
    "repeats": dict(p_repeat=0.3, max_bytes=1 << 20),
}


def _trace(seed, leg_bits=10, seq0=None, dt=(0.0, 0.25), max_bytes=6000,
           rows=(1, 24), p_empty=0.05, p_filtered=0.3, p_repeat=0.0):
    rng = np.random.default_rng(seed)
    spaces = [(int(rng.integers(0, 1 << leg_bits)),
               int(rng.integers(0, 1 << 32))) for _ in range(6)]
    if leg_bits > 16:
        # two namespaces whose 64-bit keys collide: legs 2**16 apart
        spaces[1] = (spaces[0][0] ^ (1 << 16), spaces[0][1])
        spaces[0] = (spaces[0][0] | (1 << 31), spaces[0][1])
        spaces[1] = (spaces[1][0] | (1 << 31), spaces[1][1])
    nxt = [int(rng.integers(0, 1 << 16)) if seq0 is None else seq0
           for _ in spaces]
    slab, ref = SlabCache(max_bytes, 1.0), PacketCache(max_bytes, 1.0)
    now, seen = 10.0, set()
    saw = dict(partial=False, aged=False, wrapped=False, empty=False,
               filtered=False, hits=0, misses=0)
    for _step in range(60):
        now += float(rng.uniform(*dt))
        n = 0 if rng.random() < p_empty else int(rng.integers(*rows))
        pad = n + int(rng.integers(0, 9))
        plane = rng.integers(0, 256, (pad, WIDTH + 32), dtype=np.uint8)
        which = rng.integers(0, len(spaces), n)
        seqs = np.zeros(n, dtype=np.int64)
        for i, k in enumerate(which):
            if p_repeat and seen and rng.random() < p_repeat:
                which[i], seqs[i] = sorted(seen)[
                    int(rng.integers(0, len(seen)))]
            else:
                seqs[i] = nxt[k]
                nxt[k] = (nxt[k] + 1) & 0xFFFF
            seen.add((int(which[i]), int(seqs[i])))
        legs = np.array([spaces[k][0] for k in which], dtype=np.int64)
        ssrc = np.array([spaces[k][1] for k in which], dtype=np.int64)
        length = rng.integers(12, WIDTH + 1, pad).astype(np.int32)
        plane[:n, 2] = seqs >> 8
        plane[:n, 3] = seqs & 0xFF
        for b in range(4):
            plane[:n, 8 + b] = (ssrc >> (8 * (3 - b))) & 0xFF
        data, length = plane[:, :WIDTH][:n], length[:n]
        if n and rng.random() < p_filtered:
            keep = np.nonzero(rng.random(n) < 0.7)[0]
            data, length, legs = data[keep], length[keep], legs[keep]
            which, seqs, ssrc = which[keep], seqs[keep], ssrc[keep]
            saw["filtered"] = True
        saw["empty"] |= len(legs) == 0
        old = slab.slabs
        slab.insert_batch(data, length, legs, now=now)
        ref.insert_batch([(int(g) << 32) | int(r)
                          for g, r in zip(legs, ssrc)], seqs,
                         [data[i, :length[i]].tobytes()
                          for i in range(len(legs))], now=now)
        saw["aged"] |= slab.slabs < old
        saw["partial"] |= bool(slab.slabs and slab._slabs[0].low)
        if not p_repeat:
            assert slab.size_bytes == ref.size_bytes
            assert len(slab) == len(ref)
        assert slab.size_bytes <= max_bytes
        # a NACK per namespace: the last few seqs, some never sent
        for k, (leg, src) in enumerate(spaces):
            ns = (leg << 32) | src
            lost = [(nxt[k] - int(d)) & 0xFFFF
                    for d in rng.integers(-3, 30, 8)]
            saw["wrapped"] |= max(lost) - min(lost) > 60000
            got = slab.lookup_nack(ns, lost, return_missing=True)
            assert got == ref.lookup_nack(ns, lost, return_missing=True)
            assert got[0] == slab.lookup_nack(ns, lost)
            saw["hits"] += len(got[0])
            saw["misses"] += len(got[1])
            s = int(rng.choice(lost))
            assert slab.get(ns, s) == ref.get(ns, s)
    return saw


@pytest.mark.parametrize("seed", (1, 2 ** 31 + 5))
@pytest.mark.parametrize("profile", sorted(PROFILES))
def test_slab_cache_matches_packet_cache(profile, seed):
    saw = _trace(seed, **PROFILES[profile])
    assert saw["hits"] > 50 and saw["misses"] > 50
    need = {"wide_ns": (), "wrap": ("wrapped",), "age": ("aged",),
            "bytes": ("partial",), "empty": ("empty",),
            "filtered": ("filtered",), "repeats": ()}[profile]
    for flag in need:
        assert saw[flag], f"the {profile} trace never exercised {flag}"


def test_slab_cache_colliding_namespaces_do_not_answer_for_each_other():
    """Legs 2**16 apart share `(ns << 16 | seq) mod 2**64`."""
    c = SlabCache()
    plane = np.zeros((2, 32), dtype=np.uint8)
    plane[:, 3] = 7                                   # seq 7, ssrc 0
    plane[0, 12], plane[1, 12] = 0xAA, 0xBB
    legs = np.array([1, 1 | (1 << 16)], dtype=np.int64)
    c.insert_batch(plane, np.array([13, 13]), legs, now=0.0)
    assert c.get(1 << 32, 7)[12] == 0xAA
    assert c.get((1 | 1 << 16) << 32, 7)[12] == 0xBB
    assert c.get((1 | 1 << 17) << 32, 7) is None
    assert c.get(1 << 80, 7) is None


# ---------------------------------------------------------------- bridge

class _Ep:
    """SRTP endpoint against an SfuBridge over loopback UDP (the
    harness of tests/test_loss_recovery.py, raw wire kept)."""

    def __init__(self, ssrc, bridge_port):
        self.ssrc = ssrc
        self.rx_key = (bytes([ssrc & 0xFF]) * 16,
                       bytes([(ssrc + 1) & 0xFF]) * 14)
        self.tx_key = (bytes([(ssrc + 2) & 0xFF]) * 16,
                       bytes([(ssrc + 3) & 0xFF]) * 14)
        self.protect = SrtpStreamTable(capacity=1)
        self.protect.add_stream(0, *self.rx_key)
        self.engine = UdpEngine(port=0, max_batch=256)
        self.bridge_port = bridge_port
        self.seq = 65530                 # the run crosses 65535 -> 0

    def send_media_in(self, n, per_send, size=40):
        """Protect `n` packets now; each `next()` sends `per_send` of
        them."""
        seqs = [(self.seq + i) & 0xFFFF for i in range(n)]
        self.seq += n
        pls = [(b"m-%08x-%d-" % (self.ssrc, s)).ljust(size, b".")
               for s in seqs]
        b = rtp_header.build(pls, seqs, [0] * n, [self.ssrc] * n,
                             [96] * n, stream=[0] * n)
        wire = self.protect.protect_rtp(b)
        # one protect launch however many sends: on the CPU a launch
        # costs what a hundred packets do
        for lo in range(0, n, per_send or n):
            hi = min(n, lo + (per_send or n))
            yield self.engine.send_batch(
                PacketBatch(wire.data[lo:hi],
                            np.asarray(wire.length)[lo:hi],
                            wire.stream[lo:hi]),
                "127.0.0.1", self.bridge_port)

    def send_media(self, n, size=40):
        list(self.send_media_in(n, None, size))

    def recv_wire(self):
        """{(ssrc, seq): wire bytes} of the RTP that arrived, in a list
        of arrival order."""
        out = []
        for _ in range(4):
            back, _, _ = self.engine.recv_batch(timeout_ms=2)
            for i in range(back.batch_size):
                pkt = back.to_bytes(i)
                if len(pkt) >= 12 and not 72 <= (pkt[1] & 0x7F) <= 78:
                    out.append(((int.from_bytes(pkt[8:12], "big"),
                                 int.from_bytes(pkt[2:4], "big")), pkt))
        return out

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


def _bridge(n_eps=3):
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=8, recv_window_ms=0,
                    recovery_config=RecoveryConfig(rtt_s=0.04))
    eps = [_Ep(0x30 + 0x10 * k, sfu.port) for k in range(n_eps)]
    for e in eps:
        sfu.add_endpoint(e.ssrc, e.rx_key, e.tx_key)
    return sfu, eps


def _ticks(sfu, now, n=6):
    for _ in range(n):
        sfu.tick(now=now)
    # the last fan-out leaves on the egress worker: a client socket is
    # read only once that thread has sent it (never a sleep)
    sfu.flush_egress()


@pytest.mark.parametrize("fec", (False, True), ids=("plain", "fec"))
def test_bridge_nack_after_100_ticks_is_byte_equal(fec):
    """Forward a batch, run 100 further ticks of other traffic (every
    buffer a tick reuses is rewritten, every device output of the first
    tick long released), then NACK the first batch: the retransmission
    is what the receiver got the first time.  With FEC active every FEC
    packet on the leg is `build_fec` over the wire bytes the leg was
    sent, which is what the parent built from its `bytes` per row."""
    sfu, (a, b, c) = _bridge()
    try:
        now = 100.0
        for e in (a, b, c):
            e.send_media(1)                  # latch every address
        _ticks(sfu, now)
        for e in (a, b, c):
            e.recv_wire()
        if fec:
            # ~20% reported loss turns the adaptive FEC on
            for _ in range(3):
                b.send_rr(a.ssrc, 51)
                _ticks(sfu, now)
            assert sfu.recovery.fec_active()
        a.send_media(12)
        _ticks(sfu, now)
        first = b.recv_wire()
        media = {k: p for k, p in first if k[0] == a.ssrc}
        assert len(media) == 12
        sent = [(65531 + i) & 0xFFFF for i in range(12)]   # crosses 0
        assert sorted(s for _, s in media) == sorted(sent)
        if fec:
            fecs = [p for k, p in first
                    if k[0] == (a.ssrc ^ FEC_SSRC_XOR) & 0xFFFFFFFF]
            assert fecs, "FEC active but no FEC packet on the leg"
            for p in fecs:
                f = parse_fec(p[12:])
                prot = [(f["seq_base"] + i) & 0xFFFF for i in range(16)
                        if f["mask"] & (1 << (15 - i))]
                assert p[12:] == build_fec(
                    [media[(a.ssrc, s)] for s in prot], f["seq_base"])
        # 100 further ticks, each forwarding fresh packets of the same
        # row class and width, inside the cache's second
        for _sent in c.send_media_in(300, 3):
            now += 0.005
            _ticks(sfu, now, n=1)
            b.recv_wire(), a.recv_wire()
        assert sfu.cache.slabs > 50
        b.send_nack(a.ssrc, sorted(sent))
        _ticks(sfu, now)
        again = [(k, p) for k, p in b.recv_wire() if k[0] == a.ssrc]
        # circular serve order, every packet, byte for byte
        assert [k[1] for k, _ in again] == sent
        assert all(p == media[k] for k, p in again)
        assert sfu.recovery.rtx_cache_miss == 0
    finally:
        for e in (a, b, c):
            e.engine.close()
        sfu.close()


def test_bridge_unlatched_leg_takes_the_copy_path_and_gauges_read():
    """One leg never sent, so has no address: its rows are filtered out
    of the slab (`copied` 1 on the span) and the others' are served."""
    sfu, (a, b, c) = _bridge()
    try:
        a.send_media(1)
        b.send_media(1)                      # c stays unlatched
        _ticks(sfu, 50.0)
        b.recv_wire()
        a.send_media(4)
        _ticks(sfu, 50.0)
        got = {k: p for k, p in b.recv_wire() if k[0] == a.ssrc}
        assert len(got) == 4
        counts = sfu.loop.tracer._counts["nack_cache"]
        assert counts["copied"] >= 1 and counts["live_rows"] >= 4
        sid_c = 2
        assert sfu.cache.get((sid_c << 32) | a.ssrc, 65531) is None
        sid_b = 1
        assert sfu.cache.get((sid_b << 32) | a.ssrc, 65531) == \
            got[(a.ssrc, 65531)]
        # every leg latched: the plane is kept as it came
        c.send_media(1)
        _ticks(sfu, 50.0)
        before = sfu.loop.tracer._counts["nack_cache"]["copied"]
        a.send_media(4)
        _ticks(sfu, 50.0)
        assert sfu.loop.tracer._counts["nack_cache"]["copied"] == before
        assert sfu.cache.get((sid_c << 32) | a.ssrc, 65535) is not None
        assert sfu.cache.resident_bytes >= sfu.cache.size_bytes > 0
    finally:
        for e in (a, b, c):
            e.engine.close()
        sfu.close()


def test_supervisor_exports_cache_gauges():
    from libjitsi_tpu.service.supervisor import BridgeSupervisor

    sfu, eps = _bridge(2)
    try:
        reg = MetricsRegistry()
        BridgeSupervisor(sfu, metrics=reg)
        text = reg.render()
        assert "recovery_rtx_cache_slabs 0" in text
        assert "recovery_rtx_cache_resident_bytes 0" in text
        assert "recovery_rtx_cache_miss" in text
    finally:
        for e in eps:
            e.engine.close()
        sfu.close()


# ------------------------------------------------------------ allocation

class _Span:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def note(self, **counts):
        pass


class _Tracer:
    def span(self, stage, **counts):
        return _SPAN


_SPAN = _Span()


class _Engine:
    """The hand-over alone: nothing is sent and nothing completes."""

    def send_batch_async(self, batch, ip, port):
        from libjitsi_tpu.io.udp import SendJob

        return SendJob(1, batch.batch_size, False)


@pytest.mark.parametrize("rows", (1024,))
def test_emit_fanout_creates_no_per_row_objects(rows):
    """One `_emit_fanout` of 1,024 rows leaves behind a constant number
    of Python objects (the slab and its arrays), not one per row: the
    parent left 3 x rows (`bytes`, key tuple, value tuple)."""
    import types

    rng = np.random.default_rng(3)
    legs = rng.integers(0, 64, rows).astype(np.int64)
    loop = types.SimpleNamespace(
        tracer=_Tracer(), engine=_Engine(),
        addr_port=np.full(64, 5004, dtype=np.int64),
        addr_ip=np.full(64, 0x7F000001, dtype=np.int64),
        note_journey_at=lambda *a, **k: None)
    me = types.SimpleNamespace(
        loop=loop, cache=SlabCache(), _now=1.0, forwarded=0, flight=None,
        _egress_jobs={},
        recovery=types.SimpleNamespace(fec_active=lambda: False))

    def one():
        plane = rng.integers(0, 256, (rows, 256), dtype=np.uint8)
        wire = PacketBatch(plane[:, :224],
                           np.full(rows, 100, dtype=np.int32),
                           legs.astype(np.int32))
        SfuBridge._emit_fanout(me, wire, legs, (1, 0.0))

    one()                                    # warm caches, lazy imports
    gc.collect()
    gc.disable()
    try:
        blocks, counted = sys.getallocatedblocks(), gc.get_count()[0]
        one()
        grown = sys.getallocatedblocks() - blocks
        tracked = gc.get_count()[0] - counted
    finally:
        gc.enable()
    assert me.cache.slabs == 2 and len(me.cache) == 2 * rows
    assert grown < 64, f"{grown} blocks for {rows} rows"
    assert tracked < 32, f"{tracked} tracked objects for {rows} rows"
