"""Deep-pipeline correctness: depths 1-3 must deliver every packet
exactly once and in per-stream order, the drain barrier must collapse
the pipeline at checkpoint / lifecycle commit points, arena views must
survive pinning, and the adaptive batcher must move its knobs the way
io/batching.py documents.

The property under test (ISSUE 9): pipelining reorders WORK, never
PACKETS — a depth-3 loop's observable output is the depth-1 loop's
output shifted in time.
"""

import socket
import struct
import time

import numpy as np
import pytest

import libjitsi_tpu
from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.io.batching import AdaptiveBatcher
from libjitsi_tpu.io.loop import MediaLoop
from libjitsi_tpu.io.udp import UdpEngine
from libjitsi_tpu.service.media_stream import StreamRegistry
from libjitsi_tpu.transform.engine import TransformEngineChain
from libjitsi_tpu.transform.srtp.context import SrtpStreamTable
from libjitsi_tpu.transform.srtp.engine import SrtpTransformEngine

LOCALHOST = struct.unpack("!I", socket.inet_aton("127.0.0.1"))[0]
SSRCS = (0x1111, 0x2222)


def _table(cap=8, n_streams=2):
    t = SrtpStreamTable(capacity=cap)
    for sid in range(n_streams):
        t.add_stream(sid, bytes(range(16)), bytes(range(14)))
    return t


def _chain():
    return TransformEngineChain([SrtpTransformEngine(_table(), _table())],
                                names=["srtp"])


def _registry(cap=8):
    reg = StreamRegistry(libjitsi_tpu.configuration_service(),
                         capacity=cap)
    for i, ssrc in enumerate(SSRCS):
        reg.map_ssrc(ssrc, i)
    return reg


def _rtp(ssrc, seq, payload=b"x" * 40):
    hdr = struct.pack("!BBHII", 0x80, 96, seq & 0xFFFF, seq, ssrc)
    return hdr + payload


def _echo_loop(engine, depth, on_media=None):
    if on_media is None:
        def on_media(batch, ok):
            rows = np.nonzero(ok)[0]
            if len(rows) == 0:
                return None
            return PacketBatch(batch.data[rows].copy(),
                               np.asarray(batch.length)[rows].copy(),
                               np.asarray(batch.stream)[rows].copy())
    return MediaLoop(engine, _registry(), on_media=on_media,
                     chain=_chain(), recv_window_ms=1,
                     pipeline_depth=depth)


def _drain_replies(engine, want, timeout_s=2.0):
    """Collect reply datagrams at the peer; returns list of raw bytes."""
    out = []
    deadline = time.time() + timeout_s
    while time.time() < deadline and len(out) < want:
        rb, _, _ = engine.recv_batch(timeout_ms=20)
        lens = np.asarray(rb.length)
        for i in range(rb.batch_size):
            out.append(bytes(rb.data[i, :lens[i]]))
    return out


def _reply_seqs(raw_replies):
    """(ssrc, seq) of each reply — RTP headers ride in cleartext under
    SRTP, so the wire bytes demux without the reply-direction keys."""
    out = []
    for raw in raw_replies:
        seq = struct.unpack("!H", raw[2:4])[0]
        ssrc = struct.unpack("!I", raw[8:12])[0]
        out.append((ssrc, seq))
    return out


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_depth_delivers_every_packet_once_in_stream_order(depth):
    """No drop, no duplicate, no reorder-within-stream at any depth."""
    peer = UdpEngine(port=0)
    engine = UdpEngine(port=0)
    loop = _echo_loop(engine, depth)
    tx = _table()

    n_ticks, per_stream = 30, 2
    seq = {s: i * 1000 for i, s in enumerate(SSRCS)}
    sent = 0
    for _ in range(n_ticks):
        pkts, sids = [], []
        for sid, ssrc in enumerate(SSRCS):
            for _ in range(per_stream):
                pkts.append(_rtp(ssrc, seq[ssrc]))
                seq[ssrc] += 1
                sids.append(sid)
        b = PacketBatch.from_payloads(pkts, stream=sids)
        peer.send_batch(tx.protect_rtp(b), LOCALHOST, engine.port)
        sent += len(pkts)
        loop.tick()
    # idle ticks collapse the pipeline (n==0 -> drain)
    for _ in range(depth + 2):
        loop.tick()
    loop.drain()
    assert not loop._rx_inflight and not loop._inflight

    replies = _drain_replies(peer, sent)
    assert len(replies) == sent, f"lost/duplicated at depth {depth}"
    got = _reply_seqs(replies)
    assert len(set(got)) == sent, "duplicate (ssrc, seq) delivered"
    for ssrc in SSRCS:
        seqs = [s for (ss, s) in got if ss == ssrc]
        assert seqs == sorted(seqs), \
            f"reordered within stream {ssrc:#x} at depth {depth}"
    peer.close()
    engine.close()


def test_depth3_journey_observes_every_packet_none_parked():
    """Acceptance (ISSUE 9): under pipelined load every packet's journey
    — stamped at ingress arrival, observed at egress send, so it
    INCLUDES the depth-3 aging delay — is observed, and no dispatch
    ages past the pipeline depth: pipelining overlaps work; it must not
    park packets.  What the journey TAKES is the `journey_p99` SLO's
    to judge on the chip; a CPU container can time nothing.  Warmup
    ticks are snapshotted out of the count."""
    peer = UdpEngine(port=0)
    engine = UdpEngine(port=0)
    loop = _echo_loop(engine, depth=3)
    tx = _table()

    seq = {s: i * 1000 for i, s in enumerate(SSRCS)}

    def burst_and_tick():
        pkts, sids = [], []
        for sid, ssrc in enumerate(SSRCS):
            for _ in range(4):
                pkts.append(_rtp(ssrc, seq[ssrc]))
                seq[ssrc] += 1
                sids.append(sid)
        b = PacketBatch.from_payloads(pkts, stream=sids)
        peer.send_batch(tx.protect_rtp(b), LOCALHOST, engine.port)
        loop.tick()
        assert loop._inflight_age() <= 3, "a dispatch parked past depth 3"
        return len(pkts)

    for _ in range(12):                        # warm: compiles land here
        burst_and_tick()
    loop.drain()
    h = loop.journey_hist
    warm_counts = h.bucket_counts.copy()

    sent = sum(burst_and_tick() for _ in range(100))
    loop.drain()
    assert not loop._rx_inflight and not loop._inflight

    steady = h.bucket_counts - warm_counts
    assert int(steady.sum()) >= sent           # every packet observed
    peer.close()
    engine.close()


class _StubBridge:
    """Minimal bridge for BridgeSupervisor: the loop IS the tick."""

    def __init__(self, loop):
        self.loop = loop
        self.degraded = False

    def tick(self, now=None):
        return self.loop.tick()

    def snapshot(self):
        return {"stub": True}


def test_checkpoint_mid_pipeline_drains_then_delivers_exactly_once(
        tmp_path):
    """save_checkpoint is a drain barrier: a depth-3 checkpoint taken
    with work in flight materializes everything first, and nothing is
    lost or double-sent across it."""
    from libjitsi_tpu.service.supervisor import (BridgeSupervisor,
                                                 SupervisorConfig)

    peer = UdpEngine(port=0)
    engine = UdpEngine(port=0)
    loop = _echo_loop(engine, depth=3)
    sup = BridgeSupervisor(_StubBridge(loop),
                           SupervisorConfig(deadline_ms=1000.0))
    tx = _table()

    seq = {s: 0 for s in SSRCS}
    sent = 0
    ckpt = str(tmp_path / "mid.ckpt")
    for t in range(20):
        pkts, sids = [], []
        for sid, ssrc in enumerate(SSRCS):
            pkts.append(_rtp(ssrc, seq[ssrc]))
            seq[ssrc] += 1
            sids.append(sid)
        b = PacketBatch.from_payloads(pkts, stream=sids)
        peer.send_batch(tx.protect_rtp(b), LOCALHOST, engine.port)
        sent += len(pkts)
        sup.tick()
        if t == 9:
            # mid-run, with entries in flight: the barrier must clear
            # them BEFORE the snapshot is cut
            assert loop._rx_inflight or loop._inflight
            sup.save_checkpoint(ckpt)
            assert not loop._rx_inflight and not loop._inflight
    for _ in range(5):
        sup.tick()
    loop.drain()

    replies = _drain_replies(peer, sent)
    assert len(replies) == sent
    assert len(set(_reply_seqs(replies))) == sent
    peer.close()
    engine.close()


def test_lifecycle_commit_runs_behind_drain_barrier():
    """StreamLifecycleManager.commit() collapses the loop pipeline
    before evicting rows the in-flight work may still reference."""
    from libjitsi_tpu.service.lifecycle import StreamLifecycleManager

    calls = []

    class _Loop:
        def drain(self):
            calls.append("drain")

    class _Reg:
        free_slots = 4

    class _Bridge:
        loop = _Loop()
        registry = _Reg()
        _ssrc_of = {3: 0xAA}
        flight = None

        def remove_endpoints(self, sids):
            calls.append(("remove", list(sids)))

        def commit_endpoints(self, sids):
            calls.append(("commit", list(sids)))

    lc = StreamLifecycleManager(_Bridge())
    lc.commit()                      # nothing staged: no barrier needed
    assert calls == []
    lc._evict_q.append(3)
    lc.commit()
    assert calls == ["drain", ("remove", [3])], \
        "drain must precede the population flip"


def test_arena_views_survive_pinning_and_ring_growth():
    """A pinned recv view's bytes are never clobbered by later recv
    windows, even when every arena is pinned and the ring must grow.
    Engine-agnostic: each tag sends a full arena's worth of rows, so
    both the recvmmsg engine (fresh arena per window) and the io_uring
    engine (multiple windows share one armed arena until it exhausts —
    the registered-buffer mode this test must also hold under, see
    tests/test_io_uring.py for the mode-parametrized twins) run out of
    unpinned arenas and must grow."""
    tx_eng = UdpEngine(port=0)
    rx = UdpEngine(port=0, max_batch=8, arenas=2)
    rows = rx._rows

    def send_tagged(tag, n):
        pkts = [bytes([tag]) * 60 for _ in range(n)]
        tx_eng.send_batch(PacketBatch.from_payloads(pkts),
                          LOCALHOST, rx.port)

    views = []
    for tag in (0xA1, 0xB2, 0xC3):      # third round exceeds the ring
        send_tagged(tag, rows)
        got, batches = 0, []
        for _ in range(100):
            batch, _sip, _sport = rx.recv_batch_view(timeout_ms=20)
            if batch.batch_size:
                batches.append(batch)
            got += batch.batch_size
            if got >= rows:
                break
        assert got == rows
        views.append((tag, batches))
    assert rx.arena_grows >= 1, "ring should have grown while pinned"
    for tag, batches in views:
        for batch in batches:
            assert (batch.data[:, :60] == tag).all(), \
                f"arena bytes for {tag:#x} clobbered while pinned"
    # release: arenas recycle; double-release must not steal a pin
    for _tag, batches in views:
        for batch in batches:
            rx.release_arena(batch.arena_token)
            rx.release_arena(batch.arena_token)
    assert all(a.pins == 0 for a in rx._ring)
    tx_eng.close()
    rx.close()


def test_unknown_ssrc_warning_is_interval_suppressed(monkeypatch):
    """A flood of unmapped senders logs at most one warning per
    interval; the drop counter still counts every packet."""
    from libjitsi_tpu.io import loop as loop_mod

    warns = []
    monkeypatch.setattr(loop_mod._log, "warn",
                        lambda *a, **kw: warns.append(kw))

    peer = UdpEngine(port=0)
    engine = UdpEngine(port=0)
    loop = _echo_loop(engine, depth=1)
    loop.unknown_warn_interval = 10

    for _ in range(12):
        b = PacketBatch.from_payloads([_rtp(0xDEAD, 1), _rtp(0xBEEF, 2)])
        peer.send_batch(b, LOCALHOST, engine.port)
        for _ in range(50):
            if loop.tick():
                break
    unknown_warns = [w for w in warns if "suppressed" in w]
    assert loop.unknown_ssrc_dropped == 24
    assert 1 <= len(unknown_warns) <= 2, \
        f"expected ~1 warning per 10-tick interval, got {len(unknown_warns)}"
    if len(unknown_warns) == 2:
        assert unknown_warns[1]["suppressed"] > 0
        assert unknown_warns[1]["total"] > unknown_warns[0]["total"]
    peer.close()
    engine.close()


# ---------------------------------------------------- adaptive batching

class _FakeEngine:
    def __init__(self, max_batch=64):
        self.max_batch = max_batch


class _FakeLoop:
    def __init__(self, engine, recv_window_ms=1):
        self.engine = engine
        self.recv_window_ms = recv_window_ms
        self.rx_packets = 0


class _FakeSlo:
    def __init__(self):
        self._state = "ok"

    def state(self):
        return self._state


def test_batcher_backlog_forces_poll_mode_and_recovers():
    loop = _FakeLoop(_FakeEngine(64))
    slo = _FakeSlo()
    b = AdaptiveBatcher(loop, slo=slo)
    loop.rx_packets += 64               # window saturated
    b.on_tick()
    assert loop.recv_window_ms == 0 and loop.engine.max_batch == 64
    assert b.backlog_polls == 1
    loop.rx_packets += 3                # calm again
    b.on_tick()
    assert loop.recv_window_ms == b.base_window_ms


def test_batcher_fast_burn_shrinks_batch_then_recovers_additively():
    loop = _FakeLoop(_FakeEngine(64))
    slo = _FakeSlo()
    b = AdaptiveBatcher(loop, slo=slo, min_batch=8)
    slo._state = "fast_burn"
    for _ in range(5):
        b.on_tick()
    assert loop.engine.max_batch == 8   # halved to the floor
    assert loop.recv_window_ms == 0
    slo._state = "ok"
    b.on_tick()
    assert loop.engine.max_batch == 8 + max(1, 64 // 8)
    assert loop.recv_window_ms == b.base_window_ms
    for _ in range(20):
        b.on_tick()
    assert loop.engine.max_batch == 64  # fully recovered, never above


def test_batcher_respects_ladder_clamp():
    """While the supervisor's recv_window rung is held, the batcher
    must not write the window (the ladder owns it); the cap stays
    adaptive."""
    loop = _FakeLoop(_FakeEngine(64))
    slo = _FakeSlo()
    b = AdaptiveBatcher(loop, slo=slo)
    loop.recv_window_ms = 0             # ladder squeezed it
    b.clamp_window(True)
    slo._state = "fast_burn"
    b.on_tick()
    assert loop.recv_window_ms == 0
    assert loop.engine.max_batch == 32  # cap still adapts
    slo._state = "ok"
    b.on_tick()
    assert loop.recv_window_ms == 0, "clamped window must not re-widen"
    b.clamp_window(False)
    b.on_tick()
    assert loop.recv_window_ms == b.base_window_ms


def test_batcher_live_cap_bounds_next_recv_window():
    """engine.max_batch is honored live by the recv path: lowering it
    mid-run bounds the very next window."""
    tx_eng = UdpEngine(port=0)
    rx = UdpEngine(port=0, max_batch=32)
    pkts = [bytes([7]) * 60 for _ in range(16)]
    tx_eng.send_batch(PacketBatch.from_payloads(pkts), LOCALHOST, rx.port)
    rx.max_batch = 4
    time.sleep(0.05)
    batch, _, _ = rx.recv_batch(timeout_ms=100)
    assert 0 < batch.batch_size <= 4
    tx_eng.close()
    rx.close()
