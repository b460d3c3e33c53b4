"""SFU translator fan-out + retransmission cache.

Reference behaviors: RTPTranslatorImpl decrypt-once/re-encrypt-per-
receiver (SURVEY §3.4), CachingTransformer NACK service.
"""

import numpy as np

from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.rtp import rtcp
from libjitsi_tpu.sfu import PacketCache, RtpTranslator
from libjitsi_tpu.transform.srtp import SrtpStreamTable
import pytest

MK_A = bytes(range(16))            # sender A's master key
MS_A = bytes(range(50, 64))
RECV_KEYS = {r: (bytes([r] * 16), bytes([r + 100] * 14)) for r in (1, 2, 3)}


def _sender_batch(n=4, ssrc=0xAAA, sid=0):
    return rtp_header.build(
        [b"media-%d" % i for i in range(n)],
        [1000 + i for i in range(n)], [i * 960 for i in range(n)],
        [ssrc] * n, [96] * n, stream=[sid] * n)


@pytest.mark.slow
def test_fanout_reencrypts_per_receiver():
    # sender -> SFU leg
    tx = SrtpStreamTable(capacity=4)
    tx.add_stream(0, MK_A, MS_A)
    rx = SrtpStreamTable(capacity=4)
    rx.add_stream(0, MK_A, MS_A)
    wire_in = tx.protect_rtp(_sender_batch())
    dec, ok, idx = rx.unprotect_rtp(wire_in, return_index=True)
    assert ok.all()

    # SFU -> receivers
    tr = RtpTranslator(capacity=8)
    for r, (mk, ms) in RECV_KEYS.items():
        tr.add_receiver(r, mk, ms)
    tr.connect(0, [1, 2, 3])
    out, recv = tr.translate(dec, idx)
    assert out.batch_size == 4 * 3
    np.testing.assert_array_equal(np.unique(recv), [1, 2, 3])

    # each receiver decrypts its copies with its own key; payloads match
    for r, (mk, ms) in RECV_KEYS.items():
        leg = SrtpStreamTable(capacity=8)
        leg.add_stream(5, mk, ms)
        rows = np.nonzero(recv == r)[0]
        sub = PacketBatch.from_payloads(
            [out.to_bytes(i) for i in rows], stream=[5] * len(rows))
        dec_r, ok_r = leg.unprotect_rtp(sub)
        assert ok_r.all()
        for j in range(len(rows)):
            assert dec_r.to_bytes(j) == dec.to_bytes(j)
    # different receivers got different ciphertext for the same packet
    c1 = out.to_bytes(int(np.nonzero(recv == 1)[0][0]))
    c2 = out.to_bytes(int(np.nonzero(recv == 2)[0][0]))
    assert c1 != c2


@pytest.mark.slow
def test_fanout_respects_routes_and_removal():
    tr = RtpTranslator(capacity=8)
    for r, (mk, ms) in RECV_KEYS.items():
        tr.add_receiver(r, mk, ms)
    tr.connect(0, [1, 2])
    tr.connect(7, [3])          # other sender, not in this batch
    b = _sender_batch(n=2)
    out, recv = tr.translate(b, np.array([1000, 1001]))
    assert sorted(np.unique(recv)) == [1, 2]
    tr.remove_receiver(2)
    out2, recv2 = tr.translate(b, np.array([1000, 1001]))
    assert sorted(np.unique(recv2)) == [1]
    # unrouted sender: nothing out
    b2 = _sender_batch(sid=9)
    out3, recv3 = tr.translate(b2, np.arange(4))
    assert out3.batch_size == 0


def test_roc_carried_into_fanout():
    """Sender past a seq wrap (index > 2^16): receivers still decrypt."""
    tx = SrtpStreamTable(capacity=2)
    tx.add_stream(0, MK_A, MS_A)
    rx = SrtpStreamTable(capacity=2)
    rx.add_stream(0, MK_A, MS_A)
    seqs = [65534, 65535, 0, 1]  # wraps: ROC increments mid-batch
    b = rtp_header.build([b"wrap-%d" % s for s in seqs], seqs,
                         [0] * 4, [0xAAA] * 4, [96] * 4, stream=[0] * 4)
    dec, ok, idx = rx.unprotect_rtp(tx.protect_rtp(b), return_index=True)
    assert ok.all()
    assert idx[-1] == (1 << 16) + 1

    tr = RtpTranslator(capacity=4)
    mk, ms = RECV_KEYS[1]
    tr.add_receiver(1, mk, ms)
    tr.connect(0, [1])
    out, recv = tr.translate(dec, idx)
    leg = SrtpStreamTable(capacity=4)
    leg.add_stream(0, mk, ms)
    # receiver leg must accept across the wrap too
    sub = PacketBatch.from_payloads(
        [out.to_bytes(i) for i in range(out.batch_size)], stream=[0] * 4)
    dec_r, ok_r = leg.unprotect_rtp(sub)
    assert ok_r.all()


# ------------------------------------------------------------------ cache --

def test_cache_insert_lookup_nack():
    c = PacketCache(max_bytes=10_000, max_age=10.0)
    c.insert_batch([5, 5, 5], [100, 101, 102],
                   [b"p100", b"p101", b"p102"], now=0.0)
    assert c.get(5, 101) == b"p101"
    nack = rtcp.Nack(sender_ssrc=9, media_ssrc=5, lost_seqs=[100, 102, 999])
    got = c.lookup_nack(5, nack.lost_seqs)
    assert got == [b"p100", b"p102"]


def test_cache_eviction_by_bytes_and_age():
    c = PacketCache(max_bytes=250, max_age=0.5)
    for i in range(3):
        c.insert(1, i, bytes(100), now=0.0)
    assert len(c) == 2           # 300B > 250B: oldest evicted
    assert c.get(1, 0) is None
    c.insert(1, 50, bytes(10), now=1.0)   # age evicts the 0.0-era entries
    assert c.get(1, 1) is None and c.get(1, 2) is None
    assert c.get(1, 50) is not None


# -------------------------------------------------------- rtcp termination

def test_rtcp_termination_aggregates_and_throttles():
    from libjitsi_tpu.sfu.rtcp_termination import RtcpTermination

    t = RtcpTermination(bridge_ssrc=0xBEEF, pli_interval_s=1.0)
    media = 0xAAA
    # three receivers report different loss about the forwarded stream
    for rid, (fl, cum, jit) in enumerate([(10, 5, 100), (80, 50, 900),
                                          (0, 0, 10)]):
        rr = rtcp.ReceiverReport(0x100 + rid, [rtcp.ReportBlock(
            media, fl, cum, 5000, jit, 0, 0)])
        t.on_receiver_rtcp(rid, [rr])
    t.on_receiver_rtcp(0, [rtcp.Remb(0x100, 2_000_000, [media])])
    t.on_receiver_rtcp(1, [rtcp.Remb(0x101, 500_000, [media])])
    t.on_receiver_rtcp(0, [rtcp.Nack(0x100, media, [10, 11])])
    t.on_receiver_rtcp(1, [rtcp.Nack(0x101, media, [11, 12])])
    t.on_receiver_rtcp(2, [rtcp.Pli(0x102, media)])
    t.on_receiver_rtcp(1, [rtcp.Pli(0x101, media)])

    out = t.make_sender_feedback(media, now=100.0)
    parsed = [p for blob in out for p in rtcp.parse_compound(blob)]
    rrs = [p for p in parsed if isinstance(p, rtcp.ReceiverReport)]
    assert len(rrs) == 1                       # N receiver RRs -> one
    agg = rrs[0].reports[0]
    assert agg.fraction_lost == 80 and agg.jitter == 900
    rembs = [p for p in parsed if isinstance(p, rtcp.Remb)]
    assert rembs[0].bitrate_bps == 500_000     # bottleneck receiver wins
    nacks = [p for p in parsed if isinstance(p, rtcp.Nack)]
    assert sorted(nacks[0].lost_seqs) == [10, 11, 12]
    plis = [p for p in parsed if isinstance(p, rtcp.Pli)]
    assert len(plis) == 1                      # storm -> one PLI

    # PLI rate limit: another request inside the interval is held
    t.on_receiver_rtcp(0, [rtcp.Pli(0x100, media)])
    out2 = t.make_sender_feedback(media, now=100.2)
    assert not any(isinstance(p, rtcp.Pli) for blob in out2
                   for p in rtcp.parse_compound(blob))
    out3 = t.make_sender_feedback(media, now=101.5)
    assert any(isinstance(p, rtcp.Pli) for blob in out3
               for p in rtcp.parse_compound(blob))

    # a leaving bottleneck receiver releases the REMB cap
    t.forget_receiver(1)
    assert t.min_remb(media) == 2_000_000


# --------------------------------------------------------- GCM fan-out ---

GCM_RECV_KEYS = {r: (bytes([r] * 16), bytes([r + 100] * 12))
                 for r in (1, 2, 3)}


def _gcm_fanout_roundtrip(routes):
    """Protect with a GCM sender, fan out, decrypt each leg, compare."""
    from libjitsi_tpu.transform.srtp import SrtpProfile

    prof = SrtpProfile.AEAD_AES_128_GCM
    mk_a, ms_a = bytes(range(16)), bytes(range(50, 62))
    tx = SrtpStreamTable(capacity=4, profile=prof)
    tx.add_stream(0, mk_a, ms_a)
    rx = SrtpStreamTable(capacity=4, profile=prof)
    rx.add_stream(0, mk_a, ms_a)
    wire_in = tx.protect_rtp(_sender_batch())
    dec, ok, idx = rx.unprotect_rtp(wire_in, return_index=True)
    assert ok.all()

    tr = RtpTranslator(capacity=8, profile=prof)
    for r, (mk, ms) in GCM_RECV_KEYS.items():
        tr.add_receiver(r, mk, ms)
    for sid, rr in routes.items():
        tr.connect(sid, rr)
    out, recv = tr.translate(dec, idx)
    n_legs = len(routes[0])
    assert out.batch_size == 4 * n_legs

    for r in routes[0]:
        mk, ms = GCM_RECV_KEYS[r]
        leg = SrtpStreamTable(capacity=8, profile=prof)
        leg.add_stream(5, mk, ms)
        rows = np.nonzero(recv == r)[0]
        sub = PacketBatch.from_payloads(
            [out.to_bytes(i) for i in rows], stream=[5] * len(rows))
        dec_r, ok_r = leg.unprotect_rtp(sub)
        assert ok_r.all(), f"receiver {r} failed GCM auth"
        for j in range(len(rows)):
            assert dec_r.to_bytes(j) == dec.to_bytes(j)
    c1 = out.to_bytes(int(np.nonzero(recv == routes[0][0])[0][0]))
    c2 = out.to_bytes(int(np.nonzero(recv == routes[0][1])[0][0]))
    assert c1 != c2
    return tr


@pytest.mark.slow
def test_gcm_fanout_full_mesh_grouped_path():
    """Uniform routes (three legs: under `_gcm_leg_major`'s floor, so
    the per-row form; tests/test_gcm_served.py drives the leg-major one
    from 16 legs): every leg must open the AEAD against its own session
    keys."""
    _gcm_fanout_roundtrip({0: [1, 2, 3]})


@pytest.mark.slow
def test_gcm_fanout_general_path_matches_grouped():
    """Two senders with different routes against one sender's uniform
    route: the ciphertext for a shared (packet, receiver) pair must be
    identical (same keys, same IVs => same AEAD output)."""
    from libjitsi_tpu.transform.srtp import SrtpProfile

    prof = SrtpProfile.AEAD_AES_128_GCM
    mk_a, ms_a = bytes(range(16)), bytes(range(50, 62))
    rx = SrtpStreamTable(capacity=4, profile=prof)
    rx.add_stream(0, mk_a, ms_a)
    tx = SrtpStreamTable(capacity=4, profile=prof)
    tx.add_stream(0, mk_a, ms_a)
    wire_in = tx.protect_rtp(_sender_batch())
    dec, ok, idx = rx.unprotect_rtp(wire_in, return_index=True)

    tr = RtpTranslator(capacity=8, profile=prof)
    for r, (mk, ms) in GCM_RECV_KEYS.items():
        tr.add_receiver(r, mk, ms)
    tr.connect(0, [1, 2, 3])
    out_grouped, recv_g = tr.translate(dec, idx)

    # force the general path: batch with two senders, different routes
    tr2 = RtpTranslator(capacity=8, profile=prof)
    for r, (mk, ms) in GCM_RECV_KEYS.items():
        tr2.add_receiver(r, mk, ms)
    tr2.connect(0, [1, 2])
    tr2.connect(9, [3])
    two = rtp_header.build(
        [dec.to_bytes(0)[12:], b"other-sender"],
        [1000, 7], [0, 0], [0xAAA, 0xBBB], [96, 96], stream=[0, 9])
    out_mixed, recv_m = tr2.translate(two, np.array([int(idx[0]), 7]))
    assert sorted(np.unique(recv_m)) == [1, 2, 3]
    # packet 0 to receiver 1: identical bytes via either path
    g_row = int(np.nonzero(recv_g == 1)[0][0])
    m_row = int(np.nonzero(recv_m == 1)[0][0])
    assert out_grouped.to_bytes(g_row) == out_mixed.to_bytes(m_row)


def test_gcm_fanout_forged_ext_header_does_not_crash():
    """A (validly authenticated) packet whose X/ext_words claims a header
    bigger than the packet must not crash translate(): the grouped fast
    path's static-offset gate rejects it and the general path clamps."""
    from libjitsi_tpu.transform.srtp import SrtpProfile

    prof = SrtpProfile.AEAD_AES_128_GCM
    tr = RtpTranslator(capacity=8, profile=prof)
    for r, (mk, ms) in GCM_RECV_KEYS.items():
        tr.add_receiver(r, mk, ms)
    tr.connect(0, [1, 2, 3])
    b = _sender_batch(n=2)
    # forge X=1 + huge ext_words on both rows (same offset -> would take
    # the uniform path if the bound didn't gate it)
    for i in range(2):
        b.data[i, 0] |= 0x10                    # X bit
        b.data[i, 12:14] = (0xBE, 0xDE)         # ext profile
        b.data[i, 14] = 0x03                    # ext_words hi
        b.data[i, 15] = 0xE8                    # 0x3E8 = 1000 words
    out, recv = tr.translate(b, np.array([1000, 1001]))
    assert out.batch_size == 2 * 3              # processed, not crashed


# --------------------------------------------- packed CM fan-out (PR 28) ---

# (packets, legs) one row short of each class, so the launch carries a
# cycled pad row; both warmed widths; payload offset uniform 12,
# uniform 20 and mixed
PACKED_FANOUT_CASES = [
    (3, 5, 100, 12), (9, 7, 100, 20), (51, 5, 1300, "mixed"),
    (341, 3, 100, "mixed"), (585, 7, 100, 12), (21, 3, 1300, 20),
]


@pytest.mark.parametrize("packets,legs,payload_len,off",
                         PACKED_FANOUT_CASES)
def test_packed_cm_fanout_vs_oracle(packets, legs, payload_len, off):
    """Every row of the packed CM fan-out is what the OpenSSL oracle
    seals for that receiver: bytes and lengths, padded rows dropped."""
    from libjitsi_tpu.core.packet import _round_rows
    from test_srtp import _rtp_with_offset, protect_oracle_ext

    rng = np.random.default_rng(packets * legs)
    keys = {r: (bytes(rng.integers(0, 256, 16, dtype=np.uint8)),
                bytes(rng.integers(0, 256, 14, dtype=np.uint8)))
            for r in range(1, legs + 1)}
    tr = RtpTranslator(capacity=16)
    for r, (mk, ms) in keys.items():
        tr.add_receiver(r, mk, ms)
    tr.connect(0, list(keys))
    plain, index = [], []
    for i in range(packets):
        o = off if off != "mixed" else (12, 20)[i % 2]
        pay = bytes(rng.integers(0, 256, payload_len - (i % 7),
                                 dtype=np.uint8))
        # past a sequence wrap: the ROC word is not zero
        index.append((3 << 16) + 100 + i)
        plain.append(_rtp_with_offset(index[-1] & 0xFFFF, 0xAAA, pay, o))
    batch = PacketBatch.from_payloads(plain, stream=[0] * packets)
    out, recv = tr.translate(batch, np.asarray(index))
    assert out.batch_size == packets * legs < _round_rows(packets * legs)
    for j in range(out.batch_size):
        mk, ms = keys[int(recv[j])]
        i = j // legs
        assert out.to_bytes(j) == protect_oracle_ext(
            mk, ms, plain[i], index[i], 10), j
        assert out.length[j] == len(plain[i]) + 10


def test_packed_cm_fanout_one_array_each_way(warmed_launch_guard):
    """With the tracer on, a warmed CM fan-out sends ONE array to the
    device and copies one back, compiles nothing and starts no
    `convert_element_type` program."""
    from libjitsi_tpu.utils.tracing import PipelineTracer

    tr = RtpTranslator(capacity=8)
    for r, (mk, ms) in RECV_KEYS.items():
        tr.add_receiver(r, mk, ms)
    tr.connect(0, [1, 2, 3])
    tr.tracer = tracer = PipelineTracer(annotate=False)
    tr.translate(_sender_batch(), np.arange(1000, 1004))   # warms
    tracer.take_ledger()
    with warmed_launch_guard():
        out, recv = tr.translate(_sender_batch(), np.arange(1004, 1008))
    assert out.batch_size == 12
    tracer.take_ledger()
    counts = tracer.last_counts
    plane = 16 * (192 + 32 + 32)       # 12 rows padded to 16, one class
    assert counts["fanout_dispatch"] == {"h2d_arrays": 1,
                                         "h2d_bytes": plane}
    assert counts["fanout_d2h"] == {"d2h_arrays": 1, "d2h_bytes": plane}
    assert counts["expand"] == {"rows": 12, "rows_padded": 16,
                                "width": 224, "launches": 1,
                                "legs_max": 3, "class_cut": 0,
                                "row_class": 16}
