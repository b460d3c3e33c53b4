"""SRTP/SRTCP tests: RFC 3711 KDF vectors, differential vs an independent
OpenSSL-backed oracle, replay/ROC state machine, SRTCP, checkpoint/restore.

The oracle below reimplements RFC 3711 protection scalar-per-packet straight
from the RFC using the `cryptography` package (OpenSSL) — no shared code
with the device path, so agreement is meaningful (mirrors the reference's
provider cross-check in `.srtp.crypto.Aes`).
"""

import hmac as hmac_mod
import hashlib

import numpy as np
import pytest
from cryptography.hazmat.primitives.ciphers import Cipher as CCipher
from cryptography.hazmat.primitives.ciphers import algorithms, modes

from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.transform.srtp import SrtpProfile, SrtpStreamTable
from libjitsi_tpu.transform.srtp.kdf import derive_session_keys


# ---------------------------------------------------------------- oracle ---

def aes_ctr(key: bytes, iv16: bytes, data: bytes) -> bytes:
    enc = CCipher(algorithms.AES(key), modes.CTR(iv16)).encryptor()
    return enc.update(data) + enc.finalize()


def kdf_oracle(mk: bytes, ms: bytes, label: int, n: int) -> bytes:
    x = int.from_bytes(ms, "big") ^ (label << 48)
    return aes_ctr(mk, (x << 16).to_bytes(16, "big"), b"\x00" * n)


def protect_oracle(mk: bytes, ms: bytes, pkt: bytes, index: int,
                   tag_len: int) -> bytes:
    ke = kdf_oracle(mk, ms, 0, len(mk))
    ka = kdf_oracle(mk, ms, 1, 20)
    ksalt = int.from_bytes(kdf_oracle(mk, ms, 2, 14), "big")
    cc = pkt[0] & 0x0F
    off = 12 + 4 * cc
    ssrc = int.from_bytes(pkt[8:12], "big")
    iv = ((ksalt << 16) ^ (ssrc << 64) ^ (index << 16)).to_bytes(16, "big")
    ct = pkt[:off] + aes_ctr(ke, iv, pkt[off:])
    roc = index >> 16
    tag = hmac_mod.new(ka, ct + roc.to_bytes(4, "big"), hashlib.sha1).digest()
    return ct + tag[:tag_len]


def protect_rtcp_oracle(mk: bytes, ms: bytes, pkt: bytes, index: int,
                        tag_len: int) -> bytes:
    ke = kdf_oracle(mk, ms, 3, len(mk))
    ka = kdf_oracle(mk, ms, 4, 20)
    ksalt = int.from_bytes(kdf_oracle(mk, ms, 5, 14), "big")
    ssrc = int.from_bytes(pkt[4:8], "big")
    iv = ((ksalt << 16) ^ (ssrc << 64) ^ (index << 16)).to_bytes(16, "big")
    ct = pkt[:8] + aes_ctr(ke, iv, pkt[8:])
    word = ((1 << 31) | index).to_bytes(4, "big")
    tag = hmac_mod.new(ka, ct + word, hashlib.sha1).digest()
    return ct + word + tag[:tag_len]


MK = bytes(range(16))
MS = bytes(range(100, 114))


def make_table(profile=SrtpProfile.AES_CM_128_HMAC_SHA1_80, n=8, mk=MK, ms=MS):
    t = SrtpStreamTable(capacity=n, profile=profile)
    for i in range(n):
        t.add_stream(i, mk, ms)
    return t


def rtp_pkt(seq, ssrc=0x1234, payload=b"\xabuvwxyz123", pt=96, ts=3000):
    b = rtp_header.build([payload], [seq], [ts], [ssrc], [pt])
    return b.to_bytes(0)


# ------------------------------------------------------------------- KDF ---

def test_kdf_rfc3711_b3_vectors():
    mk = bytes.fromhex("E1F97A0D3E018BE0D64FA32C06DE4139")
    ms = bytes.fromhex("0EC675AD498AFEEBB6960B3AABE6")
    ks = derive_session_keys(mk, ms)
    assert ks.rtp_enc.hex().upper() == "C61E7A93744F39EE10734AFE3FF7A087"
    assert ks.rtp_salt.hex().upper() == "30CBBC08863D8C85D49DB34A9AE1"
    assert ks.rtp_auth.hex().upper() == (
        "CEBE321F6FF7716B6FD4AB49AF256A156D38BAA4")


def test_kdf_matches_independent_oracle():
    ks = derive_session_keys(MK, MS)
    assert ks.rtp_enc == kdf_oracle(MK, MS, 0, 16)
    assert ks.rtcp_auth == kdf_oracle(MK, MS, 4, 20)
    assert ks.rtcp_salt == kdf_oracle(MK, MS, 5, 14)


# --------------------------------------------------------------- protect ---

@pytest.mark.parametrize("profile,tag_len", [
    (SrtpProfile.AES_CM_128_HMAC_SHA1_80, 10),
    (SrtpProfile.AES_CM_128_HMAC_SHA1_32, 4),
    (SrtpProfile.AES_256_CM_HMAC_SHA1_80, 10),
])
def test_protect_differential_vs_oracle(profile, tag_len):
    mk = bytes(range(profile.policy.enc_key_len))
    t = make_table(profile, n=4, mk=mk)
    rng = np.random.default_rng(7)
    pkts, streams, indices = [], [], []
    per_stream_seq = {0: 100, 1: 65530, 2: 0, 3: 7}
    for i in range(24):
        sid = i % 4
        seq = per_stream_seq[sid]
        per_stream_seq[sid] = (seq + 1) & 0xFFFF
        payload = bytes(rng.integers(0, 256, rng.integers(1, 120), dtype=np.uint8))
        pkts.append(rtp_pkt(seq, ssrc=0x1000 + sid, payload=payload))
        streams.append(sid)
    batch = PacketBatch.from_payloads(pkts, stream=streams)
    out = t.protect_rtp(batch)

    # track expected 48-bit index per stream exactly like a sender would
    ext = {s: None for s in range(4)}
    for i, (p, sid) in enumerate(zip(pkts, streams)):
        seq = int.from_bytes(p[2:4], "big")
        if ext[sid] is None:
            ext[sid] = seq
        else:
            d = (seq - (ext[sid] & 0xFFFF) + 0x8000) % 0x10000 - 0x8000
            ext[sid] = ext[sid] + d
        expected = protect_oracle(mk, MS, p, ext[sid], tag_len)
        assert out.to_bytes(i) == expected, f"packet {i} mismatch"


@pytest.mark.slow
def test_roundtrip_and_auth_failure():
    t_tx = make_table()
    t_rx = make_table()
    pkts = [rtp_pkt(s, payload=bytes([s] * 50)) for s in range(20)]
    batch = PacketBatch.from_payloads(pkts, stream=[0] * 20)
    prot = t_tx.protect_rtp(batch)
    dec, ok = t_rx.unprotect_rtp(prot)
    assert ok.all()
    for i in range(20):
        assert dec.to_bytes(i) == pkts[i]
    # tamper one byte of each: auth must fail for all
    prot2 = t_tx.protect_rtp(PacketBatch.from_payloads(
        [rtp_pkt(s + 100) for s in range(5)], stream=[1] * 5))
    prot2 = prot2.copy()  # device output arrays are read-only views
    prot2.data[:, 20] ^= 0xFF
    _, ok2 = t_rx.unprotect_rtp(prot2)
    assert not ok2.any()


def test_roc_wraparound():
    """Sequence wrap 65535->0 must bump ROC in IV and auth (RFC 3711 App A)."""
    t = make_table(n=1)
    seqs = [65533, 65534, 65535, 0, 1, 2]
    pkts = [rtp_pkt(s) for s in seqs]
    out = t.protect_rtp(PacketBatch.from_payloads(pkts, stream=[0] * 6))
    for i, s in enumerate(seqs):
        index = s if s >= 65533 else (1 << 16) + s
        assert out.to_bytes(i) == protect_oracle(MK, MS, pkts[i], index, 10)
    assert t.tx_ext[0] == (1 << 16) + 2
    # receiver side: unprotect across the wrap works too
    rx = make_table(n=1)
    dec, ok = rx.unprotect_rtp(out)
    assert ok.all()
    assert rx.rx_max[0] == (1 << 16) + 2


def test_replay_rejection():
    t_tx, t_rx = make_table(), make_table()
    pkts = [rtp_pkt(s) for s in range(8)]
    prot = t_tx.protect_rtp(PacketBatch.from_payloads(pkts, stream=[0] * 8))
    _, ok1 = t_rx.unprotect_rtp(prot)
    assert ok1.all()
    # exact replay of the same batch: all rejected
    _, ok2 = t_rx.unprotect_rtp(prot)
    assert not ok2.any()


def test_replay_in_batch_duplicate():
    t_tx, t_rx = make_table(), make_table()
    p = rtp_pkt(500)
    prot = t_tx.protect_rtp(PacketBatch.from_payloads([p], stream=[0]))
    dup = PacketBatch.from_payloads([prot.to_bytes(0)] * 3, stream=[0] * 3)
    _, ok = t_rx.unprotect_rtp(dup)
    assert ok.sum() == 1 and ok[0]


@pytest.mark.slow
def test_replay_window_reorder_and_too_old():
    t_tx, t_rx = make_table(), make_table()
    pkts = {s: rtp_pkt(s) for s in range(0, 200)}
    prot = {}
    batch = PacketBatch.from_payloads([pkts[s] for s in range(200)],
                                      stream=[0] * 200)
    p = t_tx.protect_rtp(batch)
    for s in range(200):
        prot[s] = p.to_bytes(s)
    # deliver 199 first, then reordered 190 (inside window), then 100 (too old)
    _, ok = t_rx.unprotect_rtp(PacketBatch.from_payloads(
        [prot[199]], stream=[0]))
    assert ok.all()
    _, ok = t_rx.unprotect_rtp(PacketBatch.from_payloads(
        [prot[190], prot[100]], stream=[0, 0]))
    assert ok[0] and not ok[1]
    # replay of the reordered one is now rejected
    _, ok = t_rx.unprotect_rtp(PacketBatch.from_payloads(
        [prot[190]], stream=[0]))
    assert not ok.any()


def test_multi_stream_isolation():
    """Streams use independent key rows; wrong-row auth must fail."""
    t_tx = SrtpStreamTable(capacity=2)
    t_tx.add_stream(0, MK, MS)
    t_tx.add_stream(1, bytes(range(50, 66)), bytes(range(14)))
    p = rtp_pkt(10, ssrc=0xAAAA)
    prot0 = t_tx.protect_rtp(PacketBatch.from_payloads([p], stream=[0]))
    rx = SrtpStreamTable(capacity=2)
    rx.add_stream(0, MK, MS)
    rx.add_stream(1, bytes(range(50, 66)), bytes(range(14)))
    # right stream id: ok; wrong stream id: auth failure
    _, ok = rx.unprotect_rtp(PacketBatch(prot0.data.copy(),
                                         prot0.length.copy(),
                                         np.array([1], dtype=np.int32)))
    assert not ok.any()
    _, ok = rx.unprotect_rtp(prot0)
    assert ok.all()


def test_padded_packet_roundtrip():
    """P=1 packets must survive: pad length is ciphertext until decrypt."""
    t_tx, t_rx = make_table(), make_table()
    raw = bytearray(rtp_pkt(42, payload=b"hello" + bytes([0, 0, 3])))
    raw[0] |= 0x20  # set P bit; last payload byte 3 = pad count
    pkts = [bytes(raw)] * 1
    for trial in range(3):
        raw2 = bytearray(raw)
        raw2[2:4] = (42 + trial).to_bytes(2, "big")
        prot = t_tx.protect_rtp(PacketBatch.from_payloads([bytes(raw2)],
                                                          stream=[0]))
        dec, ok = t_rx.unprotect_rtp(prot)
        assert ok.all(), f"padded packet dropped on trial {trial}"
        assert dec.to_bytes(0) == bytes(raw2)


def test_forged_packet_does_not_poison_established_stream():
    """A garbage packet in the same batch must not shift the index estimate
    of a later genuine packet on an established stream."""
    t_tx, t_rx = make_table(), make_table()
    prot = t_tx.protect_rtp(PacketBatch.from_payloads(
        [rtp_pkt(100)], stream=[0]))
    _, ok = t_rx.unprotect_rtp(prot)
    assert ok.all()
    forged = bytearray(rtp_pkt(32868, payload=b"junkjunk"))
    genuine = t_tx.protect_rtp(PacketBatch.from_payloads(
        [rtp_pkt(101)], stream=[0]))
    both = PacketBatch.from_payloads(
        [bytes(forged), genuine.to_bytes(0)], stream=[0, 0])
    _, ok = t_rx.unprotect_rtp(both)
    assert not ok[0] and ok[1]


@pytest.mark.slow
def test_protect_near_capacity_grows_not_truncates():
    """A packet whose tag would overflow the input capacity gets a
    grown output buffer (size-class headroom), never silent truncation
    (it used to raise ValueError before bucketing added headroom)."""
    t = make_table()
    big = rtp_pkt(1, payload=bytes(1500 - 12))
    out = t.protect_rtp(PacketBatch.from_payloads([big], stream=[0]))
    assert out.length[0] == 1500 + 10
    assert out.capacity >= 1510
    assert out.to_bytes(0)[:12] == big[:12]          # header intact
    rx = make_table()
    dec, ok = rx.unprotect_rtp(out)
    assert ok.all() and dec.to_bytes(0) == big       # full roundtrip


# ------------------------------------------------------------------ RTCP ---

def rtcp_sr(ssrc=0x5678, n_extra=40):
    """Minimal RTCP SR: header + sender info (28 bytes) + padding filler."""
    body = bytearray()
    body += bytes([0x80, 200, 0, 6 + n_extra // 4 - 1])
    body += ssrc.to_bytes(4, "big")
    body += bytes(20)  # NTP/RTP ts, counts
    body += bytes(range(n_extra % 256)) * 1
    return bytes(body[: 28 + n_extra])


@pytest.mark.slow
def test_rtcp_differential_and_roundtrip():
    t_tx, t_rx = make_table(), make_table()
    pkts = [rtcp_sr(0x5678, 40), rtcp_sr(0x5678, 40), rtcp_sr(0x9999, 12)]
    batch = PacketBatch.from_payloads(pkts, stream=[0, 0, 1])
    prot = t_tx.protect_rtcp(batch)
    # index assignment: stream 0 gets 0,1; stream 1 gets 0
    assert prot.to_bytes(0) == protect_rtcp_oracle(MK, MS, pkts[0], 0, 10)
    assert prot.to_bytes(1) == protect_rtcp_oracle(MK, MS, pkts[1], 1, 10)
    assert prot.to_bytes(2) == protect_rtcp_oracle(MK, MS, pkts[2], 0, 10)
    dec, ok = t_rx.unprotect_rtcp(prot)
    assert ok.all()
    for i in range(3):
        assert dec.to_bytes(i) == pkts[i]
    # replay
    _, ok2 = t_rx.unprotect_rtcp(prot)
    assert not ok2.any()


# ------------------------------------------------------------ checkpoint ---

def test_snapshot_restore_preserves_replay_and_roc():
    t_tx, t_rx = make_table(), make_table()
    pkts = [rtp_pkt(s) for s in range(5)]
    prot = t_tx.protect_rtp(PacketBatch.from_payloads(pkts, stream=[0] * 5))
    _, ok = t_rx.unprotect_rtp(prot)
    assert ok.all()
    t_rx2 = SrtpStreamTable.restore(t_rx.snapshot())
    # replays still rejected after restore; fresh packets still accepted
    _, ok = t_rx2.unprotect_rtp(prot)
    assert not ok.any()
    p6 = t_tx.protect_rtp(PacketBatch.from_payloads([rtp_pkt(5)], stream=[0]))
    _, ok = t_rx2.unprotect_rtp(p6)
    assert ok.all()


def test_forged_frontrunner_does_not_block_genuine_duplicate_index():
    """A forged copy of a packet arriving EARLIER in the same batch must not
    knock out the authentic one (post-auth dedup, not pre-auth)."""
    t_tx, t_rx = make_table(), make_table()
    p = rtp_pkt(700)
    prot = t_tx.protect_rtp(PacketBatch.from_payloads([p], stream=[0]))
    genuine = prot.to_bytes(0)
    forged = bytearray(genuine)
    forged[14] ^= 0xFF  # corrupt payload -> auth fails, same seq/ssrc
    batch = PacketBatch.from_payloads([bytes(forged), genuine], stream=[0, 0])
    dec, ok = t_rx.unprotect_rtp(batch)
    assert not ok[0] and ok[1]
    assert dec.to_bytes(1) == p


def test_protect_rejects_unmapped_stream():
    """Protect must raise on stream=-1 / inactive rows instead of silently
    corrupting another row's tx state via negative indexing."""
    t = make_table(n=4)
    t.remove_stream(3)
    before = t.tx_ext.copy()
    p = rtp_pkt(1)
    with pytest.raises(KeyError):
        t.protect_rtp(PacketBatch.from_payloads([p], stream=[-1]))
    with pytest.raises(KeyError):
        t.protect_rtp(PacketBatch.from_payloads([p], stream=[3]))  # inactive
    with pytest.raises(KeyError):
        t.protect_rtp(PacketBatch.from_payloads([p], stream=[99]))  # range
    np.testing.assert_array_equal(t.tx_ext, before)


# ----------------------------------------------------- batch install ---

def test_add_streams_matches_scalar_install_all_profiles():
    """The vectorized install plane (bulk joins / restore / bootstrap)
    must produce bit-identical tables and state to per-stream
    add_stream, for CM, GCM and F8 profiles, incl. kdr streams."""
    rng = np.random.default_rng(11)
    for prof in (SrtpProfile.AES_CM_128_HMAC_SHA1_80,
                 SrtpProfile.AES_256_CM_HMAC_SHA1_80,
                 SrtpProfile.AEAD_AES_128_GCM,
                 SrtpProfile.F8_128_HMAC_SHA1_80):
        n = 6
        mks = rng.integers(0, 256, (n, prof.policy.enc_key_len),
                           dtype=np.uint8)
        mss = rng.integers(0, 256, (n, prof.policy.salt_len),
                           dtype=np.uint8)
        kdrs = np.array([0, 0, 16, 0, 256, 0])
        t1 = SrtpStreamTable(capacity=n, profile=prof)
        for i in range(n):
            t1.add_stream(i, mks[i].tobytes(), mss[i].tobytes(),
                          kdr=int(kdrs[i]))
        t2 = SrtpStreamTable(capacity=n, profile=prof)
        t2.add_streams(np.arange(n), mks, mss, kdr=kdrs)
        for attr in ('_rk_rtp', '_rk_rtcp', '_mid_rtp', '_mid_rtcp',
                     '_salt_rtp', '_salt_rtcp', 'tx_ext', 'rx_max',
                     'rx_mask', 'kdr', 'active'):
            assert np.array_equal(getattr(t1, attr), getattr(t2, attr)), \
                (prof, attr)
        if t1._gcm:
            assert np.array_equal(t1._gm_rtp, t2._gm_rtp)
            assert np.array_equal(t1._gm_rtcp, t2._gm_rtcp)
        if t1._f8:
            assert np.array_equal(t1._rk_f8_rtp, t2._rk_f8_rtp)
            assert np.array_equal(t1._rk_f8_rtcp, t2._rk_f8_rtcp)
        assert t1._masters == t2._masters


def test_kdf_batch_matches_scalar_with_epochs():
    from libjitsi_tpu.transform.srtp.kdf import derive_session_keys_batch

    rng = np.random.default_rng(12)
    for ekl in (16, 32):
        mks = rng.integers(0, 256, (5, ekl), dtype=np.uint8)
        mss = rng.integers(0, 256, (5, 14), dtype=np.uint8)
        r = np.array([0, 1, 5, 1000, 2**40], dtype=np.int64)
        rc = np.array([0, 2, 9, 0, 77], dtype=np.int64)
        ksb = derive_session_keys_batch(mks, mss, enc_key_len=ekl,
                                        r=r, rc=rc)
        for i in range(5):
            want = derive_session_keys(
                mks[i].tobytes(), mss[i].tobytes(), enc_key_len=ekl,
                kdr=1, index=int(r[i]), srtcp_index=int(rc[i]))
            got = ksb.row(i)
            for f in ('rtp_enc', 'rtp_auth', 'rtp_salt', 'rtcp_enc',
                      'rtcp_auth', 'rtcp_salt'):
                assert getattr(got, f) == getattr(want, f), (ekl, i, f)


def test_protect_rtp_async_matches_sync():
    """Double-buffered dispatch: N in-flight protects materialize to
    exactly what the sync path produces, with identical TX state."""
    rng = np.random.default_rng(21)
    t_sync = make_table(n=4)
    t_async = make_table(n=4)
    pendings = []
    batches = []
    for k in range(3):                      # three batches in flight
        pkts, sids = [], []
        for i in range(12):
            payload = bytes(rng.integers(0, 256, 30 + 40 * (i % 3),
                                         dtype=np.uint8))
            pkts.append(rtp_pkt(200 + 3 * k + i // 4,
                                ssrc=0x2000 + i % 4, payload=payload))
            sids.append(i % 4)
        b = PacketBatch.from_payloads(pkts, stream=sids)
        batches.append(b)
        pendings.append(t_async.protect_rtp_async(b))
    for k, (b, p) in enumerate(zip(batches, pendings)):
        want = t_sync.protect_rtp(b)
        got = p.result()
        for i in range(b.batch_size):
            assert got.to_bytes(i) == want.to_bytes(i), (k, i)
        assert p.result() is got            # single-shot cache
    assert np.array_equal(t_sync.tx_ext, t_async.tx_ext)


def test_key_mutation_while_protect_pending_is_safe():
    """CPU-backend jnp.asarray can alias host buffers: installing or
    removing keys while async protects are in flight must not corrupt
    the dispatched batches (copy-on-write in the mutators)."""
    rng = np.random.default_rng(30)
    t = make_table(n=4)
    ref = make_table(n=4)
    pkts = [rtp_pkt(700 + i, ssrc=0x3000 + i % 4,
                    payload=bytes(rng.integers(0, 256, 60, dtype=np.uint8)))
            for i in range(8)]
    b = PacketBatch.from_payloads(pkts, stream=[i % 4 for i in range(8)])
    want = ref.protect_rtp(b)
    pend = t.protect_rtp_async(b)
    # mutate the tables while the batch is (potentially) in flight
    t.add_stream(2, bytes(range(50, 66)), bytes(range(70, 84)))
    t.remove_stream(3)
    got = pend.result()
    for i in range(8):
        assert got.to_bytes(i) == want.to_bytes(i), i


# ------------------------------------------- packed CM unprotect (PR 28) ---

def _rtp_with_offset(seq, ssrc, payload, off):
    """An RTP packet whose payload starts at byte `off`: 12 (bare
    header) or 20 (X bit, a 0xBEDE block of one word)."""
    p = rtp_pkt(seq, ssrc=ssrc, payload=payload)
    if off == 12:
        return p
    assert off == 20
    ext = b"\xbe\xde\x00\x01" + b"\x32\xaa\xbb\xcc"
    return bytes([p[0] | 0x10]) + p[1:12] + ext + p[12:]


def protect_oracle_ext(mk, ms, pkt, index, tag_len):
    """`protect_oracle` for a header that may carry an extension."""
    off = 12 + 4 * (pkt[0] & 0x0F)
    if pkt[0] & 0x10:
        off += 4 + 4 * int.from_bytes(pkt[off + 2:off + 4], "big")
    ke = kdf_oracle(mk, ms, 0, len(mk))
    ka = kdf_oracle(mk, ms, 1, 20)
    ksalt = int.from_bytes(kdf_oracle(mk, ms, 2, 14), "big")
    ssrc = int.from_bytes(pkt[8:12], "big")
    iv = ((ksalt << 16) ^ (ssrc << 64) ^ (index << 16)).to_bytes(16, "big")
    ct = pkt[:off] + aes_ctr(ke, iv, pkt[off:])
    tag = hmac_mod.new(ka, ct + (index >> 16).to_bytes(4, "big"),
                       hashlib.sha1).digest()
    return ct + tag[:tag_len]


# every row class, both warmed widths (the audio class 192 + 32 and the
# full MTU 1504 + 32), payload offset uniform 12, uniform 20 and mixed.
# Three rows short of the class, so the launch carries cycled pad rows.
PACKED_CASES = [
    (16, 1300, "mixed"), (64, 100, 20), (256, 1300, 12),
    (1024, 100, "mixed"), (4096, 100, 12), (16, 100, 20),
]


@pytest.mark.parametrize("rows,payload_len,off", PACKED_CASES)
def test_packed_cm_unprotect_vs_oracle(rows, payload_len, off):
    """The packed CM unprotect opens what the OpenSSL oracle sealed:
    bytes, lengths and verdicts, with a forged tag, an in-batch
    duplicate, padded (cycled) rows and, in a second call, a replay."""
    n = rows - 3
    streams = 8
    t = make_table(n=streams)
    rng = np.random.default_rng(rows + payload_len)
    plain, wire, sids = [], [], []
    for i in range(n):
        sid = i % streams
        seq = 1000 + i // streams
        o = off if off != "mixed" else (12, 20)[i % 2]
        pay = bytes(rng.integers(0, 256, payload_len - (i % 5),
                                 dtype=np.uint8))
        p = _rtp_with_offset(seq, 0x1000 + sid, pay, o)
        plain.append(p)
        wire.append(protect_oracle_ext(MK, MS, p, seq, 10))
        sids.append(sid)
    forged, dup = 5, n - 1         # neither is a row the pads cycle
    wire[forged] = wire[forged][:-1] + bytes([wire[forged][-1] ^ 1])
    wire[dup], plain[dup], sids[dup] = wire[4], plain[4], sids[4]
    batch = PacketBatch.from_payloads(wire, stream=sids)
    out, ok, idx = t.unprotect_rtp(batch, return_index=True)
    want_ok = np.ones(n, dtype=bool)
    want_ok[[forged, dup]] = False
    np.testing.assert_array_equal(ok, want_ok)
    for i in range(n):
        if want_ok[i]:
            assert out.to_bytes(i) == plain[i], i
            assert idx[i] == int.from_bytes(plain[i][2:4], "big")
        else:                      # failed rows keep their bytes
            assert out.to_bytes(i) == wire[i], i
    assert t.auth_fail[sids[forged]] == 1
    # a replayed index in a later call dies in the replay window
    again = PacketBatch.from_payloads([wire[0], wire[7]],
                                      stream=[sids[0], sids[7]])
    _, ok2 = t.unprotect_rtp(again)
    assert not ok2.any()
    assert t.replay_reject[sids[0]] >= 1


def test_packed_cm_unprotect_async_matches_sync():
    """`unprotect_rtp_async` goes through the same packed seam: one
    copy back serves both the commit and the result."""
    t_sync, t_async, tx = make_table(n=4), make_table(n=4), make_table(n=4)
    pkts = [rtp_pkt(300 + i // 4, ssrc=0x1000 + i % 4,
                    payload=bytes([i]) * (20 + 30 * (i % 3)))
            for i in range(12)]
    wire = tx.protect_rtp(PacketBatch.from_payloads(
        pkts, stream=[i % 4 for i in range(12)]))
    bad = wire.copy()
    bad.data[5, 14] ^= 0x40
    want, want_ok = t_sync.unprotect_rtp(bad)
    pend = t_async.unprotect_rtp_async(bad).block_until_ready()
    got, ok = pend.result()
    np.testing.assert_array_equal(ok, want_ok)
    assert not ok[5] and ok.sum() == 11
    for i in range(12):
        assert got.to_bytes(i) == want.to_bytes(i), i
    np.testing.assert_array_equal(t_async.rx_max, t_sync.rx_max)


def test_packed_cm_unprotect_one_array_each_way(warmed_launch_guard):
    """With the tracer on, a warmed CM unprotect sends ONE array to
    the device and copies one back per launch, compiles nothing and
    starts no `convert_element_type` program."""
    from libjitsi_tpu.utils.tracing import PipelineTracer

    t, tx = make_table(n=4), make_table(n=4)
    t.tracer = tracer = PipelineTracer(annotate=False)

    def wire(seq0):
        pkts = [rtp_pkt(seq0 + i // 4, ssrc=0x1000 + i % 4,
                        payload=bytes([i]) * 40) for i in range(12)]
        return tx.protect_rtp(PacketBatch.from_payloads(
            pkts, stream=[i % 4 for i in range(12)]))

    first, second = wire(10), wire(20)
    _, ok = t.unprotect_rtp(first)            # warms the program
    assert ok.all()
    tracer.take_ledger()
    with warmed_launch_guard():
        _, ok = t.unprotect_rtp(second)
    assert ok.all()
    tracer.take_ledger()
    c = tracer.last_counts["unprotect_wait"]
    assert c["h2d_arrays"] == 1 and c["d2h_arrays"] == 1
    assert c["rows"] == 12 and c["rows_padded"] == 16
    assert c["h2d_bytes"] == c["d2h_bytes"] == 16 * (192 + 32 + 32)
