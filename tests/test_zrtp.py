"""ZRTP (RFC 6189): in-memory agreement, SAS, commitment/chain checks,
retroactive message-MAC checks, robustness against malformed/out-of-order
packets, keys driving SRTP tables.
"""

import struct

from libjitsi_tpu.control.zrtp import ZrtpEndpoint, crc32c, is_zrtp, sas_b32
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.transform.srtp import SrtpStreamTable


def _reseal(pkt: bytes) -> bytes:
    """Recompute the CRC-32C trailer after tampering with the body."""
    body = pkt[:-4]
    return body + struct.pack("!I", crc32c(body))


def run_zrtp(a: ZrtpEndpoint, b: ZrtpEndpoint):
    """a initiates after the Hello exchange."""
    wire = [(0, p) for p in a.hello_packets()] + \
           [(1, p) for p in b.hello_packets()]
    started = False
    rounds = 0
    while (not a.complete or not b.complete) and rounds < 30:
        rounds += 1
        nxt = []
        for who, pkt in wire:
            ep = b if who == 0 else a
            nxt += [(1 - who, p) for p in ep.feed(pkt)]
        wire = nxt
        if not started and b"Hello   " in a._peer:
            wire += [(0, p) for p in a.initiate()]
            started = True
    assert a.complete and b.complete, "zrtp did not complete"


def test_crc32c_kat():
    # the canonical CRC-32C check value (RFC 3720 §B.4)
    assert crc32c(b"123456789") == 0xE3069283


def test_zrtp_agreement_sas_and_keys():
    a, b = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    run_zrtp(a, b)
    assert a.role == "initiator" and b.role == "responder"
    assert a.sas == b.sas and len(a.sas) == 4
    pa, a_txk, a_txs, a_rxk, a_rxs = a.srtp_keys()
    pb, b_txk, b_txs, b_rxk, b_rxs = b.srtp_keys()
    assert (a_txk, a_txs) == (b_rxk, b_rxs)
    assert (a_rxk, a_rxs) == (b_txk, b_txs)

    # keys drive real SRTP tables end to end
    tx = SrtpStreamTable(capacity=1, profile=pa)
    tx.add_stream(0, a_txk, a_txs)
    rx = SrtpStreamTable(capacity=1, profile=pb)
    rx.add_stream(0, b_rxk, b_rxs)
    pkt = rtp_header.build([b"zrtp-keyed"], [1], [0], [5], [96], stream=[0])
    dec, ok = rx.unprotect_rtp(tx.protect_rtp(pkt))
    assert ok.all() and dec.to_bytes(0) == pkt.to_bytes(0)


def test_zrtp_demux_and_crc():
    a = ZrtpEndpoint()
    pkt = a.hello_packets()[0]
    assert is_zrtp(pkt)
    assert not is_zrtp(b"\x80\x60" + bytes(20))      # RTP
    assert not is_zrtp(bytes([22, 254, 253]))        # DTLS
    # corrupted CRC: silently dropped
    bad = pkt[:-1] + bytes([pkt[-1] ^ 1])
    b = ZrtpEndpoint()
    assert b.feed(bad) == []


def test_zrtp_commitment_binds_dhpart2():
    """A MITM swapping DHPart2 after Commit is caught by the hvi check."""
    a, b = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    for p in a.hello_packets():
        b.feed(p)
    for p in b.hello_packets():
        a.feed(p)
    commit = a.initiate()[0]
    dh1 = b.feed(commit)[0]
    dh2 = a.feed(dh1)[0]
    # attacker substitutes a different DHPart2 (new key pair)
    evil = ZrtpEndpoint(ssrc=1)
    evil_dh2_msg = evil._make_dhpart(b"DHPart2 ")
    forged = _reseal(dh2[:12] + evil_dh2_msg + dh2[12 + len(evil_dh2_msg):])
    assert b.feed(forged) == []
    assert any("hvi" in a_ or "MITM" in a_ for a_ in b.alerts)
    assert not b.complete


def test_zrtp_commit_must_chain_to_hello():
    """A Commit whose H2 does not hash to the Hello's H3 is rejected."""
    a, b = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    for p in a.hello_packets():
        b.feed(p)
    for p in b.hello_packets():
        a.feed(p)
    commit = bytearray(a.initiate()[0])
    commit[12 + 12 + 5] ^= 0xFF  # corrupt H2 inside the commit message
    assert b.feed(_reseal(bytes(commit))) == []
    assert any("chain" in a_ for a_ in b.alerts)
    assert b.role is None


def test_zrtp_tampered_hello_caught_retroactively():
    """Flipping a MAC-covered Hello field (the client-id) is detected when
    H2 is later revealed by the Commit (RFC 6189 §8.1.1)."""
    a, b = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    hello = bytearray(a.hello_packets()[0])
    hello[12 + 12 + 4 + 2] ^= 0xFF   # client-id byte: not in H3/ZID/algos
    b.feed(_reseal(bytes(hello)))
    for p in b.hello_packets():
        a.feed(p)
    assert b.feed(a.initiate()[0]) == []
    assert any("MAC" in a_ for a_ in b.alerts)


def test_zrtp_out_of_order_and_garbage_dropped():
    """Commit before Hello, unknown message types, and truncated or
    non-UTF-8 types are dropped, not crashes."""
    a, b = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    for p in b.hello_packets():
        a.feed(p)
    commit = a.initiate()[0]
    fresh = ZrtpEndpoint()
    assert fresh.feed(commit) == []           # Commit before Hello: dropped
    # unknown/binary message type: dropped
    from libjitsi_tpu.control import zrtp as z
    junk = z._wrap(z._msg(b"\xff" * 8, b"pay"), 1, 0)
    assert fresh.feed(junk) == []
    # reflected Confirm2 at the initiator: dropped (wrong role)
    aa, bb = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    run_zrtp(aa, bb)
    conf2 = aa._send(aa._make_confirm(b"Confirm2"))
    assert aa.feed(conf2) == []


def test_zrtp_duplicate_commit_is_idempotent():
    """A duplicated Commit must re-elicit the SAME DHPart1 (a regenerated
    one would fork total_hash between the sides) and still converge."""
    a, b = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    for p in a.hello_packets():
        b.feed(p)
    for p in b.hello_packets():
        a.feed(p)
    commit = a.initiate()[0]
    dh1_first = b.feed(commit)[0]
    dh1_dup = b.feed(commit)[0]
    assert dh1_first[12:-4] == dh1_dup[12:-4]   # same message, new seq
    dh2 = a.feed(dh1_first)[0]
    conf1 = b.feed(dh2)[0]
    conf2 = a.feed(conf1)[0]
    b.feed(conf2)
    assert a.complete and b.complete and a.sas == b.sas


def test_zrtp_midhandshake_hello_replacement_ignored():
    """A forged Hello injected after the exchange must not replace the
    pinned first Hello that feeds the key derivation."""
    a, b = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    for p in a.hello_packets():
        b.feed(p)
    for p in b.hello_packets():
        a.feed(p)
    pinned = b._peer[b"Hello   "]
    forged_src = ZrtpEndpoint(ssrc=1)
    b.feed(forged_src.hello_packets()[0])
    assert b._peer[b"Hello   "] == pinned
    # handshake still completes with the pinned Hello
    commit = a.initiate()[0]
    dh1 = b.feed(commit)[0]
    dh2 = a.feed(dh1)[0]
    conf1 = b.feed(dh2)[0]
    conf2 = a.feed(conf1)[0]
    b.feed(conf2)
    assert a.complete and b.complete and a.sas == b.sas


def test_sas_encoding():
    assert len(sas_b32(bytes(32))) == 4
    assert sas_b32(bytes.fromhex("ffffffff" + "00" * 28)) != \
        sas_b32(bytes(32))


def test_zrtp_initiate_is_idempotent():
    """Retrying initiate() resends the SAME Commit (a regenerated one
    would fork the hvi commitment the peer pinned)."""
    a, b = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    for p in a.hello_packets():
        b.feed(p)
    for p in b.hello_packets():
        a.feed(p)
    c1 = a.initiate()[0]
    c2 = a.initiate()[0]
    assert c1[12:-4] == c2[12:-4]       # same message, new seq/CRC
    dh1 = b.feed(c1)[0]
    dh2 = a.feed(dh1)[0]
    conf1 = b.feed(dh2)[0]
    conf2 = a.feed(conf1)[0]
    b.feed(conf2)
    assert a.complete and b.complete and a.sas == b.sas


def test_zrtp_forged_confirm_after_complete_dropped():
    """A spoofed Confirm2 (valid CRC, random MAC) after completion is
    dropped with an alert — it must not raise into the I/O loop."""
    from libjitsi_tpu.control import zrtp as z
    a, b = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    run_zrtp(a, b)
    forged = z._wrap(z._msg(b"Confirm2", bytes(40)), 9, 2)
    assert b.feed(forged) == []
    assert any("Confirm MAC" in a_ for a_ in b.alerts)
    assert b.complete                   # session state untouched

def test_zrtp_invalid_ec_point_dropped():
    """A DHPart with a non-curve or truncated public value is dropped
    with an alert, not a ValueError into the I/O loop."""
    a, b = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    for p in a.hello_packets():
        b.feed(p)
    for p in b.hello_packets():
        a.feed(p)
    commit = a.initiate()[0]
    dh1 = bytearray(b.feed(commit)[0])
    # corrupt the x coordinate of the EC point (offset: 12B pkt hdr +
    # 12B msg hdr + 32B H1 + 32B rs)
    for i in range(64):
        dh1[12 + 12 + 64 + i] = 0xFF
    assert a.feed(_reseal(bytes(dh1))) == []
    assert any("EC point" in x or "MAC" in x for x in a.alerts)


def test_zrtp_commit_contention_resolves():
    """Both sides commit (glare): the higher hvi wins, the lower backs
    down to responder (RFC 6189 §4.2) and the handshake completes."""
    a, b = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    for p in a.hello_packets():
        b.feed(p)
    for p in b.hello_packets():
        a.feed(p)
    ca = a.initiate()[0]
    cb = b.initiate()[0]
    outs_a = a.feed(cb)       # each side sees the other's Commit
    outs_b = b.feed(ca)
    # exactly one side backed down and answered with DHPart1
    roles = sorted([a.role, b.role])
    assert roles == ["initiator", "responder"], roles
    wire = [(a if x is b else b, pkt)
            for x, outs in ((a, outs_a), (b, outs_b)) for pkt in outs]
    # drive to completion
    for _ in range(20):
        nxt = []
        for dst, pkt in wire:
            for out in dst.feed(pkt):
                nxt.append((a if dst is b else b, out))
        wire = nxt
        if a.complete and b.complete:
            break
    assert a.complete and b.complete and a.sas == b.sas
    # loser cannot re-initiate
    loser = a if a.role == "responder" else b
    import pytest
    with pytest.raises(RuntimeError, match="responder"):
        loser.initiate()


def test_zrtp_alerts_bounded():
    from libjitsi_tpu.control import zrtp as z
    a, b = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    run_zrtp(a, b)
    forged = z._wrap(z._msg(b"Confirm2", bytes(40)), 9, 2)
    for _ in range(300):
        b.feed(forged)
    assert len(b.alerts) <= 64


def test_zrtp_retained_secret_continuity_across_sessions():
    """a second session between the
    same endpoints mixes the cached retained secret into s0 — key
    continuity holds and the caches rotate in lockstep."""
    from libjitsi_tpu.control.zrtp import ZidCache

    ca, cb = ZidCache(), ZidCache()
    zid_a, zid_b = b"A" * 12, b"B" * 12
    a1 = ZrtpEndpoint(zid=zid_a, ssrc=1, cache=ca)
    b1 = ZrtpEndpoint(zid=zid_b, ssrc=2, cache=cb)
    run_zrtp(a1, b1)
    # first contact: nothing cached yet
    assert not a1.secret_continuity and not b1.secret_continuity
    rs1_a, rs2_a = ca.lookup(zid_b)
    assert rs1_a is not None and rs2_a is None
    assert ca.lookup(zid_b) == cb.lookup(zid_a), "caches must rotate in sync"

    a2 = ZrtpEndpoint(zid=zid_a, ssrc=1, cache=ca)
    b2 = ZrtpEndpoint(zid=zid_b, ssrc=2, cache=cb)
    run_zrtp(a2, b2)
    assert a2.secret_continuity and b2.secret_continuity
    assert a2.srtp_keys()[1] != a1.srtp_keys()[1], "sessions must re-key"
    # rotation: old rs1 shifted to rs2
    assert ca.lookup(zid_b) == (ca.lookup(zid_b)[0], rs1_a)

    # one-generation drift: A lost its newest secret (restored old
    # cache) -> rs2 cross-match still gives continuity
    ca2 = ZidCache.restore({zid_b: (rs1_a, None)})
    a3 = ZrtpEndpoint(zid=zid_a, ssrc=1, cache=ca2)
    b3 = ZrtpEndpoint(zid=zid_b, ssrc=2, cache=cb)
    run_zrtp(a3, b3)
    assert a3.secret_continuity and b3.secret_continuity


def test_zrtp_cache_mismatch_still_completes():
    """A peer with no (or a wrong) cache falls back to a null s1: the
    handshake completes, continuity just reads False on both sides."""
    from libjitsi_tpu.control.zrtp import ZidCache

    ca, cb = ZidCache(), ZidCache()
    zid_a, zid_b = b"C" * 12, b"D" * 12
    run_zrtp(ZrtpEndpoint(zid=zid_a, ssrc=1, cache=ca),
             ZrtpEndpoint(zid=zid_b, ssrc=2, cache=cb))
    a = ZrtpEndpoint(zid=zid_a, ssrc=1, cache=ZidCache())  # lost cache
    b = ZrtpEndpoint(zid=zid_b, ssrc=2, cache=cb)
    run_zrtp(a, b)
    assert not a.secret_continuity and not b.secret_continuity
    pa, atk, ats, ark, ars = a.srtp_keys()
    pb, btk, bts, brk, brs = b.srtp_keys()
    assert (atk, ats) == (brk, brs), "mismatch must not fork the keys"


def test_zrtp_multistream_keys_second_stream_without_dh():
    """RFC 6189 §4.4.3: a second media stream keys off the first
    association's ZRTPSess — Commit(Mult, nonce) -> Confirm, no DH
    round, per-stream keys distinct from the parent's."""
    a1, b1 = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    run_zrtp(a1, b1)
    assert a1.session_key == b1.session_key is not None

    a2 = ZrtpEndpoint(ssrc=3, multistream_from=a1)
    b2 = ZrtpEndpoint(ssrc=4, multistream_from=b1)
    run_zrtp(a2, b2)
    # no DH messages crossed the wire for the second stream
    assert b"DHPart1 " not in a2._peer and b"DHPart2 " not in b2._peer
    pa, atk, ats, ark, ars = a2.srtp_keys()
    pb, btk, bts, brk, brs = b2.srtp_keys()
    assert (atk, ats) == (brk, brs) and (ark, ars) == (btk, bts)
    assert atk != a1.srtp_keys()[1], "per-stream keys must differ"

    # keys drive real SRTP both streams
    tx = SrtpStreamTable(capacity=1, profile=pa)
    tx.add_stream(0, atk, ats)
    rx = SrtpStreamTable(capacity=1, profile=pb)
    rx.add_stream(0, brk, brs)
    pkt = rtp_header.build([b"mult-keyed"], [1], [0], [9], [96],
                           stream=[0])
    dec, ok = rx.unprotect_rtp(tx.protect_rtp(pkt))
    assert ok.all() and dec.to_bytes(0) == pkt.to_bytes(0)

    # a non-multistream endpoint refuses a Mult commit (alert, drop)
    c = ZrtpEndpoint(ssrc=5)
    a3 = ZrtpEndpoint(ssrc=6, multistream_from=a1)
    wire = [(0, p) for p in a3.hello_packets()] + \
           [(1, p) for p in c.hello_packets()]
    for _ in range(4):
        nxt = []
        for who, pkt in wire:
            ep = c if who == 0 else a3
            nxt += [(1 - who, p) for p in ep.feed(pkt)]
        wire = nxt
        if b"Hello   " in a3._peer and a3.role is None:
            wire += [(0, p) for p in a3.initiate()]
    assert not c.complete
    assert any("session key" in s for s in c.alerts)


def test_zrtp_duplicate_confirm_does_not_double_rotate():
    """Retransmitted Confirms must not rotate the retained-secret cache
    twice (a double rotation overwrites both generations with the same
    value, losing the one-generation drift tolerance)."""
    from libjitsi_tpu.control.zrtp import ZidCache

    ca, cb = ZidCache(), ZidCache()
    zid_a, zid_b = b"E" * 12, b"F" * 12
    run_zrtp(ZrtpEndpoint(zid=zid_a, ssrc=1, cache=ca),
             ZrtpEndpoint(zid=zid_b, ssrc=2, cache=cb))
    gen1 = ca.lookup(zid_b)

    a = ZrtpEndpoint(zid=zid_a, ssrc=1, cache=ca)
    b = ZrtpEndpoint(zid=zid_b, ssrc=2, cache=cb)
    # capture + replay every packet once (lossy-path retransmit shape)
    wire = [(0, p) for p in a.hello_packets()] + \
           [(1, p) for p in b.hello_packets()]
    started = False
    for _ in range(30):
        nxt = []
        for who, pkt in wire:
            ep = b if who == 0 else a
            nxt += [(1 - who, p) for p in ep.feed(pkt)]
            nxt += [(1 - who, p) for p in ep.feed(pkt)]   # duplicate
        wire = nxt
        if not started and b"Hello   " in a._peer:
            wire += [(0, p) for p in a.initiate()]
            started = True
        if a.complete and b.complete:
            break
    assert a.complete and b.complete
    rs1, rs2 = ca.lookup(zid_b)
    assert rs2 == gen1[0], "old generation must survive one rotation"
    assert rs1 != rs2
    assert ca.lookup(zid_b) == cb.lookup(zid_a)


def test_zrtp_mult_capable_endpoint_follows_peer_dh_commit():
    """A multistream-capable responder whose peer commits in DH mode
    must key via DH (the negotiated mode, not the constructor flag)."""
    a1, b1 = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    run_zrtp(a1, b1)
    dh_init = ZrtpEndpoint(ssrc=3)                       # plain DH peer
    mult_resp = ZrtpEndpoint(ssrc=4, multistream_from=b1)
    run_zrtp(dh_init, mult_resp)
    assert dh_init.complete and mult_resp.complete
    assert not mult_resp._mult, "wire-negotiated mode must win"
    pa, atk, ats, _, _ = dh_init.srtp_keys()
    _, _, _, brk, brs = mult_resp.srtp_keys()
    assert (atk, ats) == (brk, brs)


def test_zrtp_mult_vs_dh_commit_contention_resolves_to_dh():
    """RFC 6189 §4.2 cross-mode contention: when a Multistream Commit
    races a DH Commit, the DH side wins (a DH peer cannot process Mult)
    and the handshake completes in DH mode."""
    a1, b1 = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    run_zrtp(a1, b1)
    mult = ZrtpEndpoint(ssrc=3, multistream_from=a1)
    dh = ZrtpEndpoint(ssrc=4)
    # both initiate after the hello exchange
    wire = [(0, p) for p in mult.hello_packets()] + \
           [(1, p) for p in dh.hello_packets()]
    committed = False
    for _ in range(30):
        nxt = []
        for who, pkt in wire:
            ep = dh if who == 0 else mult
            nxt += [(1 - who, p) for p in ep.feed(pkt)]
        wire = nxt
        if not committed and b"Hello   " in mult._peer \
                and b"Hello   " in dh._peer:
            wire += [(0, p) for p in mult.initiate()]
            wire += [(1, p) for p in dh.initiate()]
            committed = True
        if mult.complete and dh.complete:
            break
    assert mult.complete and dh.complete, "cross-mode contention wedged"
    assert dh.role == "initiator" and mult.role == "responder"
    assert not mult._mult, "resolved session must be DH mode"
    pa, atk, ats, _, _ = dh.srtp_keys()
    _, _, _, brk, brs = mult.srtp_keys()
    assert (atk, ats) == (brk, brs)


def test_zrtp_multistream_chains_from_mult_endpoint():
    """ZRTPSess is per association: a further stream can key off the
    NEWEST completed endpoint, not only the original DH one."""
    a1, b1 = ZrtpEndpoint(ssrc=1), ZrtpEndpoint(ssrc=2)
    run_zrtp(a1, b1)
    a2 = ZrtpEndpoint(ssrc=3, multistream_from=a1)
    b2 = ZrtpEndpoint(ssrc=4, multistream_from=b1)
    run_zrtp(a2, b2)
    assert a2.session_key == a1.session_key is not None
    a3 = ZrtpEndpoint(ssrc=5, multistream_from=a2)   # chained off mult
    b3 = ZrtpEndpoint(ssrc=6, multistream_from=b2)
    run_zrtp(a3, b3)
    assert a3.srtp_keys()[1] == b3.srtp_keys()[3]
    assert a3.srtp_keys()[1] != a2.srtp_keys()[1]


# ------------------------------------------------ algorithm agility (§4.1.2)

def test_negotiation_converges_with_different_orderings():
    """RFC 6189 §4.1.2 preference intersection: endpoints with DIFFERENT
    orderings converge on ONE suite — the initiator's first preference
    the responder also advertised — and both export identical keys."""
    from libjitsi_tpu.control.zrtp import (
        AUTH_HS32, AUTH_HS80, CIPHER_AES1, CIPHER_AES3, HASH_S256,
        HASH_S384, KA_DH3K, KA_EC25)
    from libjitsi_tpu.transform.srtp import SrtpProfile

    a = ZrtpEndpoint(ssrc=1, algorithms={
        "hash": (HASH_S384, HASH_S256),
        "cipher": (CIPHER_AES3, CIPHER_AES1),
        "auth": (AUTH_HS80, AUTH_HS32),
        "ka": (KA_DH3K, KA_EC25)})
    b = ZrtpEndpoint(ssrc=2, algorithms={
        "hash": (HASH_S256, HASH_S384),
        "cipher": (CIPHER_AES1, CIPHER_AES3),
        "auth": (AUTH_HS32, AUTH_HS80),
        "ka": (KA_EC25, KA_DH3K)})
    run_zrtp(a, b)
    # initiator (a) preference wins on the intersection
    assert a.suite == b.suite
    assert a.suite["hash"] == HASH_S384
    assert a.suite["cipher"] == CIPHER_AES3
    assert a.suite["auth"] == AUTH_HS80
    assert a.suite["ka"] == KA_DH3K
    assert a.sas == b.sas
    pa, aki, asi, akr, asr = a.srtp_keys()
    pb, bki, bsi, bkr, bsr = b.srtp_keys()
    assert pa == pb == SrtpProfile.AES_256_CM_HMAC_SHA1_80
    assert len(aki) == 32                   # AES3 -> 256-bit master key
    assert (aki, asi) == (bkr, bsr) and (akr, asr) == (bki, bsi)


def test_negotiated_keys_drive_srtp_roundtrip_aes256():
    """The negotiated AES-256 suite's exported keys must key working
    SRTP tables (the provider -> table contract, same as SDES/DTLS)."""
    from libjitsi_tpu.control.zrtp import CIPHER_AES1, CIPHER_AES3

    a = ZrtpEndpoint(ssrc=1,
                     algorithms={"cipher": (CIPHER_AES3, CIPHER_AES1)})
    b = ZrtpEndpoint(ssrc=2)
    run_zrtp(a, b)
    prof, tx_k, tx_s, rx_k, rx_s = a.srtp_keys()
    _, btx_k, btx_s, brx_k, brx_s = b.srtp_keys()
    tx = SrtpStreamTable(capacity=1, profile=prof)
    tx.add_stream(0, tx_k, tx_s)
    rx = SrtpStreamTable(capacity=1, profile=prof)
    rx.add_stream(0, brx_k, brx_s)
    wire = tx.protect_rtp(rtp_header.build(
        [b"negotiated-256"], [7], [0], [0xAB], [96], stream=[0]))
    dec, ok = rx.unprotect_rtp(wire)
    assert bool(ok.all())
    assert dec.to_bytes(0)[12:] == b"negotiated-256"


def test_dh3k_fallback_when_peer_lacks_ec25():
    """A peer that only offers DH3k forces the 3072-bit MODP group —
    the handshake still completes and both sides agree."""
    from libjitsi_tpu.control.zrtp import KA_DH3K, KA_EC25

    a = ZrtpEndpoint(ssrc=1)                       # default: EC25 first
    b = ZrtpEndpoint(ssrc=2, algorithms={"ka": (KA_DH3K,)})
    run_zrtp(a, b)
    assert a.suite["ka"] == KA_DH3K == b.suite["ka"]
    assert a.sas == b.sas
    assert a.srtp_keys()[1] == b.srtp_keys()[3]


def test_no_common_algorithm_refuses_commit():
    """Disjoint cipher offers: initiate() must refuse loudly (no
    silent fallback to a suite the peer never advertised)."""
    import pytest

    from libjitsi_tpu.control.zrtp import (CIPHER_AES1, CIPHER_AES3,
                                           ZrtpProtocolError)

    a = ZrtpEndpoint(ssrc=1, algorithms={"cipher": (CIPHER_AES3,)})
    b = ZrtpEndpoint(ssrc=2, algorithms={"cipher": (CIPHER_AES1,)})
    for p in a.hello_packets():
        b.feed(p)
    for p in b.hello_packets():
        a.feed(p)
    with pytest.raises(ZrtpProtocolError):
        a.initiate()


def test_commit_with_unoffered_algorithm_rejected():
    """A Commit naming an algorithm the responder never advertised is
    dropped and alerted (downgrade defense)."""
    from libjitsi_tpu.control.zrtp import CIPHER_AES1, CIPHER_AES3

    a = ZrtpEndpoint(ssrc=1)
    b = ZrtpEndpoint(ssrc=2, algorithms={"cipher": (CIPHER_AES1,)})
    for p in a.hello_packets():
        b.feed(p)
    for p in b.hello_packets():
        a.feed(p)
    commit = bytearray(a.initiate()[0])
    # forge the cipher code in the Commit: 12B packet header + 12B
    # message header + payload offset 48
    commit[12 + 12 + 48:12 + 12 + 52] = CIPHER_AES3
    replies = b.feed(_reseal(bytes(commit)))
    assert replies == []
    assert any("did not offer" in al or "MAC mismatch" in al
               for al in b.alerts)


def test_commit_contention_dh_vs_dh_different_ka_converges():
    """Both sides commit DH mode with DIFFERENT KA picks (possible with
    KA agility): §4.2's hvi tie-break must apply — exactly one side
    backs down and the handshake completes (review r5: the old
    KA-mismatch branch made both sides 'win' and deadlocked)."""
    from libjitsi_tpu.control.zrtp import KA_DH3K, KA_EC25

    a = ZrtpEndpoint(ssrc=1, algorithms={"ka": (KA_DH3K, KA_EC25)})
    b = ZrtpEndpoint(ssrc=2, algorithms={"ka": (KA_EC25, KA_DH3K)})
    for p in a.hello_packets():
        b.feed(p)
    for p in b.hello_packets():
        a.feed(p)
    # BOTH initiate: contention
    wire = [(0, p) for p in a.initiate()] + [(1, p) for p in b.initiate()]
    rounds = 0
    while (not a.complete or not b.complete) and rounds < 30:
        rounds += 1
        nxt = []
        for who, pkt in wire:
            ep = b if who == 0 else a
            nxt += [(1 - who, p) for p in ep.feed(pkt)]
        wire = nxt
    assert a.complete and b.complete, "contention deadlocked"
    assert {a.role, b.role} == {"initiator", "responder"}
    assert a.suite == b.suite and a.sas == b.sas
    # winner's KA pick is in force on both sides
    assert a.suite["ka"] in (KA_DH3K, KA_EC25)
