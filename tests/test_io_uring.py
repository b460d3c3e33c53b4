"""Generation-2 host-I/O engine: the io_uring ring mode and its
recvmmsg fallback twin.

Every behavioural test is parametrized over the engine modes THIS box
can run — the recvmmsg arm is always active, so tier-1 passes
bit-for-bit on a box with no io_uring at all; the ring arm skips (not
fails) when `uring_available()` is False.  The invariants under test
(ISSUE 12): ordered arena delivery in both modes, idempotent token
release, generation-tag invalidation across re-occupancy, and
grow-never-reuse while the kernel (or a live view) owns a buffer.
"""

import socket
import struct
import time

import numpy as np
import pytest

from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.io.udp import (UdpEngine, _ArenaToken,
                                 probe_engine_mode, uring_available)

LOCALHOST = struct.unpack("!I", socket.inet_aton("127.0.0.1"))[0]

MODES = ["recvmmsg"] + (["io_uring"] if uring_available() else [])

ring_only = pytest.mark.skipif(not uring_available(),
                               reason="io_uring engine not available "
                                      "on this box")


def _send(tx, rx, payloads):
    tx.send_batch(PacketBatch.from_payloads(payloads), LOCALHOST,
                  rx.port)


def _drain_views(rx, want, timeout_ms=50, max_windows=200):
    """Collect (payload bytes, token) via zero-copy views until `want`
    packets arrived; copies the bytes out before returning."""
    out, toks = [], []
    for _ in range(max_windows):
        batch, _sip, _sport = rx.recv_batch_view(timeout_ms=timeout_ms)
        lens = np.asarray(batch.length)
        for i in range(batch.batch_size):
            out.append(bytes(batch.data[i, :lens[i]]))
        if batch.batch_size:
            toks.append(batch.arena_token)
        if len(out) >= want:
            break
    return out, toks


# ------------------------------------------------------------- probing

def test_probe_default_is_recvmmsg_without_env_pin(monkeypatch):
    """"auto" resolves to the measured default (recvmmsg) unless the
    environment pins io_uring AND the box can run it — the ring engine
    is selectable, not the default (loopback medians lose ~30%)."""
    monkeypatch.delenv("LIBJITSI_TPU_ENGINE_MODE", raising=False)
    monkeypatch.delenv("LIBJITSI_TPU_NO_IOURING", raising=False)
    assert probe_engine_mode() == "recvmmsg"


def test_force_disable_env_wins(monkeypatch):
    """LIBJITSI_TPU_NO_IOURING=1 is the fallback-proof switch: the
    capability probe reports unavailable, "auto" resolves to recvmmsg,
    and even an explicit io_uring request degrades (with a warning)
    instead of arming a ring."""
    monkeypatch.setenv("LIBJITSI_TPU_NO_IOURING", "1")
    assert not uring_available()
    assert probe_engine_mode() == "recvmmsg"
    eng = UdpEngine(port=0, engine_mode="io_uring")
    try:
        assert eng.engine_mode == "recvmmsg"
        assert eng._u is None
    finally:
        eng.close()


def test_engine_mode_pin_recvmmsg_counts_as_disabled(monkeypatch):
    monkeypatch.setenv("LIBJITSI_TPU_ENGINE_MODE", "recvmmsg")
    monkeypatch.delenv("LIBJITSI_TPU_NO_IOURING", raising=False)
    assert not uring_available()
    assert probe_engine_mode() == "recvmmsg"


def test_invalid_engine_mode_rejected():
    with pytest.raises(ValueError):
        UdpEngine(port=0, engine_mode="dpdk")


# --------------------------------------------------- mode-twin ingest

@pytest.mark.parametrize("mode", MODES)
def test_ordered_ingest_and_parity_accept_set(mode):
    """Both engines deliver every datagram exactly once, in arrival
    order, with correct lengths — the recvmmsg run is the reference
    accept set, the ring run must be bit-identical to it."""
    tx = UdpEngine(port=0)
    rx = UdpEngine(port=0, max_batch=16, engine_mode=mode)
    try:
        assert rx.engine_mode == mode
        sent = [bytes([0x40 + i]) * (20 + i) for i in range(12)]
        _send(tx, rx, sent)
        got, toks = _drain_views(rx, len(sent))
        assert got == sent, f"{mode} scrambled or lost the accept set"
        for t in toks:
            rx.release_arena(t)
    finally:
        tx.close()
        rx.close()


@pytest.mark.parametrize("mode", MODES)
def test_double_release_is_idempotent(mode):
    """Releasing the same token twice within one occupancy must not
    steal the pin of another live view (the `released` flag, not just
    the generation check, guards this)."""
    tx = UdpEngine(port=0)
    rx = UdpEngine(port=0, max_batch=8, engine_mode=mode)
    try:
        _send(tx, rx, [b"\xAA" * 32, b"\xBB" * 32])
        got, toks = _drain_views(rx, 2)
        assert len(got) == 2 and toks
        tok = toks[0]
        assert isinstance(tok, _ArenaToken)
        a = tok.arena
        pins_before = a.pins
        rx.release_arena(tok)
        rx.release_arena(tok)               # double release: no-op
        assert a.pins == pins_before - 1
        assert tok.released
        for t in toks[1:]:
            rx.release_arena(t)
        assert all(ar.pins == 0 for ar in rx._ring)
    finally:
        tx.close()
        rx.close()


@pytest.mark.parametrize("mode", MODES)
def test_generation_tag_invalidates_stale_tokens(mode):
    """A token from a previous occupancy of an arena can never unpin
    the current occupancy: the gen bump (at arm time for the ring, per
    window for recvmmsg) invalidates it."""
    tx = UdpEngine(port=0)
    rx = UdpEngine(port=0, max_batch=4, arenas=2, engine_mode=mode)
    try:
        _send(tx, rx, [b"\x01" * 24] * 2)
        _, toks = _drain_views(rx, 2)
        tok0 = toks[0]
        a0, g0 = tok0.arena, tok0.gen
        rx.release_arena(tok0)
        # drive traffic (releasing promptly so arenas recycle) until
        # arena a0 is re-occupied and its generation moves on
        for round_ in range(64):
            _send(tx, rx, [bytes([0x10 + round_]) * 24] * 2)
            _, tk = _drain_views(rx, 2)
            for t in tk:
                rx.release_arena(t)
            if a0.gen > g0:
                break
        assert a0.gen > g0, "arena never re-occupied"
        # pin the current occupancy, then try to unpin it with the
        # STALE token's coordinates — the gen check must reject it
        _send(tx, rx, [b"\x77" * 24] * 2)
        _, live = _drain_views(rx, 2)
        pins_now = a0.pins
        rx.release_arena((a0, g0))          # stale legacy tuple
        assert a0.pins == pins_now, \
            "stale-generation token stole a live pin"
        for t in live:
            rx.release_arena(t)
    finally:
        tx.close()
        rx.close()


@pytest.mark.parametrize("mode", MODES)
def test_grow_never_reuse_while_owned(mode):
    """When every arena is pinned by a live view (and, in ring mode,
    the kernel owns the armed one), new ingest GROWS the ring instead
    of reusing a buffer — pinned bytes are never clobbered."""
    tx = UdpEngine(port=0)
    rx = UdpEngine(port=0, max_batch=4, arenas=2, engine_mode=mode)
    try:
        views = []
        for tag in (0xA1, 0xB2, 0xC3, 0xD4, 0xE5):
            _send(tx, rx, [bytes([tag]) * 48] * 2)
            got, toks = _drain_views(rx, 2)
            assert len(got) == 2
            # hold the token: the arena stays pinned across the rest
            views.append((tag, toks))
        assert rx.arena_grows >= 1, \
            f"{mode}: ring should have grown while all arenas pinned"
        for tag, toks in views:
            a = toks[0].arena
            # the payloads were copied out in _drain_views; verify the
            # ARENA rows still carry this occupancy's bytes
            rows = np.nonzero((a.buf[:, 0] == tag))[0]
            assert len(rows) >= 2, \
                f"{mode}: pinned arena bytes for {tag:#x} clobbered"
        for _tag, toks in views:
            for t in toks:
                rx.release_arena(t)
        assert all(a.pins == 0 for a in rx._ring)
    finally:
        tx.close()
        rx.close()


# ------------------------------------------------- syscall telemetry

@pytest.mark.parametrize("mode", MODES)
def test_syscall_telemetry_shape(mode):
    """`syscall_enters` is monotone in both modes; `ring_reaps` is zero
    for recvmmsg and positive for the ring once packets flowed."""
    tx = UdpEngine(port=0)
    rx = UdpEngine(port=0, max_batch=8, engine_mode=mode)
    try:
        e0 = rx.syscall_enters
        _send(tx, rx, [b"\x55" * 30] * 4)
        got, toks = _drain_views(rx, 4)
        assert len(got) == 4
        assert rx.syscall_enters >= e0
        if mode == "recvmmsg":
            assert rx.ring_reaps == 0
            assert rx.syscall_enters > e0     # every window enters
        else:
            assert rx.ring_reaps >= 4, \
                "completed ring SQEs not accounted as reaps"
        for t in toks:
            rx.release_arena(t)
    finally:
        tx.close()
        rx.close()


@ring_only
def test_uring_steady_state_recv_is_zero_syscall():
    """Once the chain is armed, reaping landed completions is entirely
    ring-side: a 0 ms poll never enters the kernel, so the enters
    counter stays FLAT across delivered windows (recvmmsg pays one
    enter per window — the contrast test_syscall_telemetry_shape
    pins)."""
    tx = UdpEngine(port=0)
    rx = UdpEngine(port=0, max_batch=16, engine_mode="io_uring")
    try:
        # warm: prove the chain is armed and delivering
        _send(tx, rx, [b"\x66" * 30] * 4)
        got, toks = _drain_views(rx, 4, timeout_ms=100)
        assert len(got) == 4
        e0 = rx.syscall_enters
        sent = [bytes([0x90 + i]) * 30 for i in range(4)]
        _send(tx, rx, sent)
        got2 = []
        for _ in range(500):
            batch, _s, _p = rx.recv_batch_view(timeout_ms=0)
            lens = np.asarray(batch.length)
            for i in range(batch.batch_size):
                got2.append(bytes(batch.data[i, :lens[i]]))
            if batch.batch_size:
                toks.append(batch.arena_token)
            if len(got2) >= 4:
                break
            time.sleep(0.002)
        assert got2 == sent
        assert rx.syscall_enters == e0, \
            "ring-side reaps entered the kernel"
        for t in toks:
            rx.release_arena(t)
    finally:
        tx.close()
        rx.close()


@ring_only
def test_uring_arena_exhaustion_rearms_across_boundary():
    """Delivering more packets than one arena holds forces the
    EXHAUSTED -> re-arm path; nothing is lost at the boundary and the
    new occupancy carries a fresh generation."""
    tx = UdpEngine(port=0)
    rx = UdpEngine(port=0, max_batch=8, arenas=2,
                   engine_mode="io_uring")
    try:
        rows = rx._rows
        n = rows + 4                     # spill into the second arena
        sent = [struct.pack("!I", i) + b"z" * 20 for i in range(n)]
        for i in range(0, n, 8):
            _send(tx, rx, sent[i:i + 8])
        got, toks = _drain_views(rx, n)
        assert got == sent, "packets lost/reordered at arena boundary"
        gens = {t.arena: t.gen for t in toks}
        assert len(gens) >= 2, "re-arm never moved to a second arena"
        for t in toks:
            rx.release_arena(t)
    finally:
        tx.close()
        rx.close()


def test_token_legacy_tuple_unpacking():
    a_like = _ArenaToken.__new__(_ArenaToken)
    a_like.arena, a_like.gen, a_like.released = "arena", 7, False
    arena, gen = a_like
    assert (arena, gen) == ("arena", 7)


def test_engine_mode_env_pin_selects_ring_when_available(monkeypatch):
    """LIBJITSI_TPU_ENGINE_MODE=io_uring flips "auto" to the ring —
    only on a box that can actually run it."""
    monkeypatch.setenv("LIBJITSI_TPU_ENGINE_MODE", "io_uring")
    monkeypatch.delenv("LIBJITSI_TPU_NO_IOURING", raising=False)
    want = "io_uring" if uring_available() else "recvmmsg"
    assert probe_engine_mode() == want
    eng = UdpEngine(port=0, engine_mode="auto")
    try:
        assert eng.engine_mode == want
    finally:
        eng.close()


@pytest.mark.parametrize("profile_name", ["ctr", "gcm"])
def test_donated_unprotect_twin_matches_plain(profile_name,
                                              monkeypatch):
    """ISSUE 12's H2D donation leg: the `donate_argnums` unprotect
    twins are selected only off-CPU, so force the selector on and
    prove the donated jit produces the byte-identical accept set (XLA
    treats donation on CPU as a no-op hint, which makes this a pure
    correctness check of the twin dispatch)."""
    from libjitsi_tpu.transform.srtp import SrtpProfile, SrtpStreamTable
    from libjitsi_tpu.transform.srtp import context as ctx_mod

    if profile_name == "ctr":
        profile, salt_len = SrtpProfile.AES_CM_128_HMAC_SHA1_80, 14
    else:
        profile, salt_len = SrtpProfile.AEAD_AES_128_GCM, 12

    def make_table():
        t = SrtpStreamTable(capacity=4, profile=profile)
        t.add_stream(0, bytes(range(16)), bytes(range(salt_len)))
        return t

    pkts = []
    for s in range(8):
        hdr = struct.pack("!BBHII", 0x80, 96, s, 3000 + s, 0x1234)
        pkts.append(hdr + bytes([s]) * 40)
    batch = PacketBatch.from_payloads(pkts, stream=[0] * 8)
    prot = make_table().protect_rtp(batch)

    dec_plain, ok_plain = make_table().unprotect_rtp(prot)
    assert np.asarray(ok_plain).all()

    monkeypatch.setattr(ctx_mod, "_donate_ingest", lambda: True)
    dec_don, ok_don = make_table().unprotect_rtp(prot)
    assert np.array_equal(np.asarray(ok_don), np.asarray(ok_plain))
    for i in range(8):
        assert dec_don.to_bytes(i) == dec_plain.to_bytes(i) == pkts[i]


def test_loop_exports_engine_metrics():
    """MediaLoop surfaces the two-engine telemetry: mode gauge, ring
    count, and the delta-accumulated ingest syscall/reap counters."""
    import libjitsi_tpu
    from libjitsi_tpu.io.loop import MediaLoop
    from libjitsi_tpu.service.media_stream import StreamRegistry

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    eng = UdpEngine(port=0)
    loop = MediaLoop(eng, StreamRegistry(
        libjitsi_tpu.configuration_service(), capacity=4),
        recv_window_ms=0)
    try:
        tx = UdpEngine(port=0)
        _send(tx, eng, [b"\x80" * 28] * 3)
        for _ in range(20):
            loop.tick()
        tx.close()
        reg = loop.metrics
        assert reg.sample_total("loop_ingest_rings") == 1.0
        assert reg.sample_total("loop_ingest_syscalls") >= 1
        assert reg.sample_total("loop_ingest_ring_reaps") >= 0
        is_ring = reg.sample_total("loop_engine_io_uring")
        assert is_ring == (1.0 if eng.engine_mode == "io_uring"
                           else 0.0)
        assert loop.engine_mode == eng.engine_mode
    finally:
        eng.close()
