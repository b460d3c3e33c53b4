"""The SfuBridge's one tick shape: a tick's fan-out is dispatched by
that tick and collected by the next.

- what every socket receives is, byte for byte and in order,
  `translator.translate` of the batches the ticks dispatched, and opens
  under the scalar oracle with the receiver's own key: CM, GCM and on a
  device mesh, for ticks of one launch and ticks cut into several;
- a tick that reads no media ships the fan-out in flight, and `close()`
  / `flush_egress()` collect it first;
- every entry point that mutates what a launch in flight may read
  collects it before it mutates;
- `translate` wrapped on the instance (the benchmark's fault
  `bridge-bitflip`) sees every one-launch tick and what it returns is
  what leaves.
"""

import importlib.util
import os
import socket

import numpy as np
import pytest

import libjitsi_tpu
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.sfu.translator import RtpTranslator
from libjitsi_tpu.transform.srtp import SrtpProfile, SrtpStreamTable

_ROOT = os.path.join(os.path.dirname(__file__), "..")
CM = SrtpProfile.AES_CM_128_HMAC_SHA1_80
GCM = SrtpProfile.AEAD_AES_128_GCM
SSRC_BASE = 0x45000000
CAP, N, PT = 32, 6, 111
#: packets a round: 2 x 5 receivers is one 16-row launch; 5 x 5 = 25
#: rows are cut at `launch_rows` = 16 into two
ROUNDS = (2, 5, 1, 5, 4, 3)


@pytest.fixture(scope="module")
def oracle():
    spec = importlib.util.spec_from_file_location(
        "bench_oracle", os.path.join(_ROOT, "benchmarks", "oracle.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _keys(salt: int) -> np.ndarray:
    """[N, 2] (client->bridge, bridge->client) of (key 16, salt)."""
    return np.random.default_rng([45, salt]).integers(
        0, 256, (N, 2, 16 + salt), dtype=np.uint8)


def _pair(raw) -> tuple:
    b = bytes(raw)
    return b[:16], b[16:]


class _Served:
    """An SfuBridge of N keyed endpoints in one shared mesh of routes,
    a socket each with its address latched, a tap on the hand-overs and
    on what the ticks dispatch."""

    def __init__(self, profile, egress_tap, mesh=None, launch_rows=16):
        from libjitsi_tpu.service.sfu_bridge import SfuBridge

        libjitsi_tpu.stop()
        libjitsi_tpu.init()
        self.profile = profile
        self.keys = _keys(profile.policy.salt_len)
        kwargs = {"mesh": mesh} if mesh is not None else {}
        self.bridge = SfuBridge(
            libjitsi_tpu.configuration_service(), port=0, capacity=CAP,
            profile=profile, recv_window_ms=0, **kwargs)
        self.socks, self.clients, self.sids = [], [], []
        self.seq = [700] * N
        self.sent = {}
        self.now = 3000.0
        for i in range(N):
            self.join(i)
        tr = self.bridge.translator
        tr.launch_rows = launch_rows
        self.tap = egress_tap(self.bridge)
        # what the ticks dispatch, as they dispatch it
        self.dispatched, inner = [], tr.translate_async

        def translate_async(batch, index):
            self.dispatched.append((batch, np.array(index)))
            return inner(batch, index)

        tr.translate_async = translate_async

    def join(self, i, keys=None):
        keys = self.keys[i] if keys is None else keys
        sid = self.bridge.add_endpoint(SSRC_BASE + i, _pair(keys[0]),
                                       _pair(keys[1]))
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.bind(("127.0.0.1", 0))
        s.setblocking(False)
        cl = SrtpStreamTable(capacity=1, profile=self.profile)
        cl.add_stream(0, *_pair(keys[0]))
        self.socks.append(s)
        self.clients.append(cl)
        self.sids.append(sid)
        self.bridge.loop.addr_ip[sid] = 0x7F000001        # 127.0.0.1
        self.bridge.loop.addr_port[sid] = s.getsockname()[1]
        return sid

    def send(self, senders):
        for i in senders:
            payload = b"overlap-%d-%d" % (i, self.seq[i]) * 3
            b = rtp_header.build([payload], [self.seq[i]], [0],
                                 [SSRC_BASE + i], [PT], stream=[0])
            self.sent[(SSRC_BASE + i, self.seq[i])] = payload
            self.seq[i] += 1
            self.socks[i].sendto(self.clients[i].protect_rtp(b).to_bytes(0),
                                 ("127.0.0.1", self.bridge.port))

    def tick(self):
        self.now += 0.02
        return self.bridge.tick(now=self.now)

    def drain(self):
        """(socket index, datagram) in arrival order a socket."""
        out = []
        for r, s in enumerate(self.socks):
            while True:
                try:
                    pkt = s.recv(2048)
                except BlockingIOError:
                    break
                if len(pkt) >= 12 and (pkt[1] & 0x7F) == PT:
                    out.append((r, pkt))
        return out

    def port(self, i):
        return self.socks[i].getsockname()[1]

    def close(self):
        self.bridge.close()
        for s in self.socks:
            s.close()


def _mesh4():
    import jax

    from libjitsi_tpu.mesh import make_media_mesh

    return make_media_mesh(jax.devices()[:4])


# ----------------------------------- (a) what leaves is `translate`'s rows

@pytest.fixture(scope="module", params=["cm", "gcm", "mesh"])
def rounds(request, egress_tap):
    """ROUNDS back to back, a round a tick, every tick with media: each
    tick but the first collects the fan-out of the one before it at the
    top of `_on_media`, then dispatches its own."""
    profile = GCM if request.param == "gcm" else CM
    srv = _Served(profile, egress_tap,
                  mesh=_mesh4() if request.param == "mesh" else None)
    try:
        tracer = srv.bridge.loop.tracer
        ledgers, whole, inner = [], [], srv.bridge.translator.translate

        def translate(batch, index):    # as `sut.break_fanout` wraps it
            whole.append(batch)
            return inner(batch, index)

        srv.bridge.translator.translate = translate
        for n in ROUNDS:
            srv.send([(3 * k) % N for k in range(n)] if n <= N
                     else range(n))
            srv.tick()
            ledgers.append(set(tracer.take_ledger()))
        in_flight = srv.bridge._pending_fanout is not None
        srv.bridge.flush_egress()
        yield {"srv": srv, "ledgers": ledgers, "got": srv.drain(),
               "in_flight": in_flight, "whole": whole,
               "handed": list(srv.tap.handed)}
    finally:
        srv.close()


def test_every_tick_collects_the_one_before_and_dispatches_its_own(rounds):
    assert len(rounds["srv"].dispatched) == len(ROUNDS)
    first, *later = rounds["ledgers"]
    assert "fanout_dispatch" in first and "fanout_wait" not in first
    for led in later:
        assert {"fanout_wait", "fanout_d2h", "nack_cache", "egress",
                "fanout_dispatch"} <= led
    assert rounds["in_flight"]          # the last round's, until flushed
    assert rounds["srv"].bridge._pending_fanout is None


def test_sockets_receive_translates_rows_in_order(rounds):
    """Per socket and in order: the rows `translate` of a twin
    translator (same keys, same routes, no cut) gives for the batches
    the ticks dispatched, which is what was handed over."""
    srv = rounds["srv"]
    twin = RtpTranslator(CAP, srv.profile)
    for i, sid in enumerate(srv.sids):
        twin.add_receiver(sid, *_pair(srv.keys[i, 1]))
    for sid, legs in srv.bridge.translator._routes.items():
        twin.connect(sid, legs)
    want = {srv.port(i): [] for i in range(N)}
    port_of = {sid: srv.port(i) for i, sid in enumerate(srv.sids)}
    launches = []
    for batch, index in srv.dispatched:
        wire, recv = twin.translate(batch, index)
        launches.append(len(srv.bridge.translator._plan(len(recv))))
        for j, sid in enumerate(recv.tolist()):
            want[port_of[sid]].append(wire.to_bytes(j))
    # ticks of one launch and ticks cut into two, both
    assert set(launches) == {1, 2}
    assert sum(len(v) for v in want.values()) == 5 * sum(ROUNDS)
    for i in range(N):
        assert [p for r, p in rounds["got"] if r == i] \
            == want[srv.port(i)] \
            == [p for to, p in rounds["handed"] if to == srv.port(i)]
    # (d) the wrapped `translate` saw every one-launch tick's batch, and
    # those alone
    assert [b is w for (b, _i), w in zip(
        [d for d, n in zip(srv.dispatched, launches) if n == 1],
        rounds["whole"])] == [True] * launches.count(1)
    assert len(rounds["whole"]) == launches.count(1)


def test_deliveries_open_under_the_receivers_key(rounds, oracle):
    srv = rounds["srv"]
    gcm = srv.profile is GCM
    opened = set()
    for r, pkt in rounds["got"]:
        ssrc = int.from_bytes(pkt[8:12], "big")
        seq = int.from_bytes(pkt[2:4], "big")
        mk, ms = _pair(srv.keys[r, 1])
        plain = (oracle.unprotect_gcm if gcm else oracle.unprotect_cm)(
            mk, ms, pkt, seq)
        assert plain is not None, f"bad tag for receiver {r}"
        assert plain[oracle.payload_off(plain):] == srv.sent[(ssrc, seq)]
        assert (oracle.protect_gcm if gcm else oracle.protect_cm)(
            mk, ms, plain, seq) == pkt
        assert ssrc - SSRC_BASE != r
        opened.add((r, ssrc, seq))
    assert len(opened) == len(rounds["got"]) == 5 * sum(ROUNDS)


# ------------------------------ (b) a fan-out in flight never waits for
# ------------------------------ traffic

@pytest.fixture
def served(egress_tap):
    srv = _Served(CM, egress_tap)
    yield srv
    srv.close()


def test_a_tick_without_media_ships_what_is_in_flight(served):
    served.send([0, 1])
    served.tick()
    assert served.bridge._pending_fanout is not None
    assert not served.tap.handed
    served.tick()                       # reads nothing: collects
    assert served.bridge._pending_fanout is None
    assert len(served.tap.handed) == 10
    served.bridge.flush_egress()
    assert len(served.drain()) == 10


@pytest.mark.parametrize("how", ["flush_egress", "close"])
def test_one_tick_then_flush_or_close_still_delivers(served, how):
    """A caller that ticks once and reads its sockets still does."""
    served.send([2])
    served.tick()
    assert not served.tap.handed
    getattr(served.bridge, how)()
    assert served.bridge._pending_fanout is None
    got = served.drain()
    assert sorted(r for r, _p in got) == [0, 1, 3, 4, 5]
    assert served.bridge.forwarded == 5


# --------------------------- (c) collect first, then mutate

def _state(bridge):
    """What a mutating entry point changes, and a launch in flight or
    its collection reads."""
    tr = bridge.translator
    return (tr._rk.tobytes(), tr._salt.tobytes(), tr.active.tobytes(),
            {s: r.tolist() for s, r in tr._routes.items()},
            dict(bridge._ssrc_of), set(bridge._staged),
            dict(bridge._conf_of), bridge.loop.addr_port.tobytes(),
            set(bridge._video))


def _dtls_endpoint(keys):
    class _Ep:
        def srtp_keys(self):
            return (CM, *_pair(keys[1]), *_pair(keys[0]))
    return _Ep()


def _staged_then_commit(s):
    sids = s.bridge.stage_endpoints(
        [(SSRC_BASE + 42, _pair(s.keys[2, 1]), _pair(s.keys[2, 0]), None)])
    return lambda: s.bridge.commit_endpoints(sids)


#: entry point -> what to call with a fan-out in flight (whatever it
#: needs set up first is set up when the entry is looked up)
MUTATIONS = {
    "add_endpoint": lambda s: lambda: s.bridge.add_endpoint(
        SSRC_BASE + 40, _pair(s.keys[0, 1]), _pair(s.keys[0, 0])),
    "remove_endpoint": lambda s: lambda: s.bridge.remove_endpoint(
        s.sids[1]),
    "stage_endpoints": lambda s: lambda: s.bridge.stage_endpoints(
        [(SSRC_BASE + 41, _pair(s.keys[1, 1]), _pair(s.keys[1, 0]),
          None)]),
    "commit_endpoints": _staged_then_commit,
    "stage_dtls_keys": lambda s: lambda: s.bridge.stage_dtls_keys(
        s.sids[2], _dtls_endpoint(s.keys[3])),
    "install_dtls": lambda s: lambda: s.bridge._install_dtls(
        s.sids[2], _dtls_endpoint(s.keys[3])),
    "set_broadcast_speakers": lambda s: lambda:
        s.bridge.set_broadcast_speakers(-1, [s.sids[0]]),
    "migrate_endpoints": lambda s: lambda: s.bridge.migrate_endpoints(
        {s.sids[4]: CAP - 1}),
    "add_video_track": lambda s: lambda: s.bridge.add_video_track(
        s.sids[0], [0x77000001], layer_bps=[1e5]),
    "add_svc_track": lambda s: lambda: s.bridge.add_svc_track(
        s.sids[0], 0x77000003, layer_bps=[1e5, 5e5]),
    "snapshot": lambda s: lambda: s.bridge.snapshot(),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_entry_points_collect_before_they_mutate(served, name):
    """With a fan-out in flight, the entry point collects it (its rows
    are handed over) while the bridge is still as the launch found it,
    and only then mutates."""
    bridge = served.bridge
    mutate = MUTATIONS[name](served)
    served.send([0])
    served.tick()
    assert bridge._pending_fanout is not None
    before, seen, flush = _state(bridge), [], bridge._flush_fanout

    def flushing():
        seen.append(_state(bridge) == before)
        flush()

    bridge._flush_fanout = flushing
    mutate()
    assert seen == [True], "collected once, before anything changed"
    assert bridge._pending_fanout is None
    assert len(served.tap.handed) == 5


def test_a_recycled_row_gets_nothing_under_the_old_key(served, oracle):
    """A leg removed between dispatch and collection: the removal
    collects first, so the departed leg's copy went to ITS address under
    ITS key, and whoever takes the row next receives nothing of it."""
    bridge, gone = served.bridge, 1
    sid = served.sids[gone]
    served.send([0])
    served.tick()                       # in flight, one row for `gone`
    bridge.remove_endpoint(sid)
    fresh = np.random.default_rng(46).integers(
        0, 256, (2, 30), dtype=np.uint8)
    served.join(N, keys=fresh)          # a socket and an address of its own
    assert served.sids[-1] == sid, "the row is recycled"
    served.tick()
    bridge.flush_egress()
    got = served.drain()
    assert [r for r, _p in got].count(N) == 0
    (old,) = [p for r, p in got if r == gone]
    seq = int.from_bytes(old[2:4], "big")
    assert oracle.unprotect_cm(*_pair(served.keys[gone, 1]), old, seq) \
        is not None
    assert oracle.unprotect_cm(*_pair(fresh[1]), old, seq) is None


# --------------------------- (d) the seam `break_fanout` wraps

def test_what_the_wrapped_translate_returns_is_what_leaves(served, oracle):
    """The benchmark's fault flips a bit in what `translate` returns:
    the overlapped tick sends exactly that (the tags fail), and the
    wrapper dispatched nothing of its own."""
    from libjitsi_tpu.core.packet import PacketBatch

    tr = served.bridge.translator
    inner = tr.translate

    def translate(batch, index):
        wire, recv = inner(batch, index)
        data = np.array(wire.data)
        data[:, 20] ^= 0x04
        return PacketBatch(data, wire.length, wire.stream), recv

    tr.translate = translate
    served.send([0, 3])
    served.tick()
    served.tick()
    served.bridge.flush_egress()
    got = served.drain()
    assert len(got) == 10 and len(served.dispatched) == 1
    assert tr.fanout_launches == 1
    for r, pkt in got:
        seq = int.from_bytes(pkt[2:4], "big")
        assert oracle.unprotect_cm(*_pair(served.keys[r, 1]), pkt,
                                   seq) is None


def test_collections_are_counted_on_the_metrics_page(served):
    from libjitsi_tpu.service.supervisor import (BridgeSupervisor,
                                                 SupervisorConfig)

    sup = BridgeSupervisor(served.bridge,
                           SupervisorConfig(deadline_ms=60_000.0),
                           metrics=served.bridge.loop.metrics)
    try:
        for senders in ([0], [1, 2, 3, 4, 5], [2]):   # 1 + 2 + 1 launches
            served.send(senders)
            served.tick()
        served.bridge.flush_egress()
        tr = served.bridge.translator
        assert tr.fanout_collects == tr.fanout_launches == 4
        assert 0 <= tr.fanout_collects_ready <= 4
        text = served.bridge.loop.metrics.render()
        assert "fanout_collect_total 4" in text
        assert f"fanout_collect_ready_total {tr.fanout_collects_ready}" \
            in text
    finally:
        sup.close()
