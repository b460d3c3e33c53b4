"""A split tick through `SfuBridge(mesh=4)` is the one-chip bridge's.

The cut into launches sits above `_cm_fanout_call`, the one seam the
mesh translator overrides, so a bridge on a device mesh inherits it: a
tick of three packets of a 64-member conference (189 rows, `launch_rows`
set to 64 on both instances: three launches) through a bridge whose
tables are row-partitioned over four of the host devices `conftest.py`
forces, and through a one-chip bridge with the same keys, gives every
receiver the same bytes.  Both compile as they go: a `shard_map` program
compiles slowly on XLA:CPU, so this is one tick on each.

The mesh translator cuts at `launch_rows` ALONE: it pads nothing in
`expand` (`_pads_rows` False: the owner plan pads lanes a chip), so the
one-chip translator's cut by the row classes under `launch_rows`
(`plan_launches`) is not its: the last test holds both to 1,164 rows,
with the device call stubbed out so that nothing compiles.
"""

import socket

import numpy as np
import pytest

import libjitsi_tpu
from libjitsi_tpu.core import staging
from libjitsi_tpu.core.packet import ROW_CLASSES, PacketBatch
from libjitsi_tpu.mesh import ShardedRtpTranslator, make_media_mesh
from libjitsi_tpu.sfu.translator import RtpTranslator
from libjitsi_tpu.transform.srtp import SrtpProfile, SrtpStreamTable
from libjitsi_tpu.rtp import header as rtp_header

CM = SrtpProfile.AES_CM_128_HMAC_SHA1_80
SSRC_BASE = 0x65000000
CONF, SHARDS, CAPACITY = 64, 4, 256
PACKETS = 3


def _keys(n: int) -> np.ndarray:
    return np.random.default_rng([44, 0x65]).integers(
        0, 256, (n, 2, 30), dtype=np.uint8)


def _pair(raw) -> tuple:
    b = bytes(raw)
    return b[:16], b[16:]


def _serve(mesh) -> dict:
    from libjitsi_tpu.service.sfu_bridge import SfuBridge

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    kwargs = {"mesh": mesh} if mesh is not None else {}
    bridge = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                       capacity=CAPACITY, profile=CM, recv_window_ms=0,
                       **kwargs)
    keys = _keys(CONF)
    socks = []
    try:
        sids = bridge.stage_endpoints(
            [(SSRC_BASE + i, _pair(keys[i, 0]), _pair(keys[i, 1]), None)
             for i in range(CONF)], conferences=[0] * CONF)
        bridge.commit_endpoints(sids)
        bridge.translator.launch_rows = 64
        for i in range(CONF):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.bind(("127.0.0.1", 0))
            s.setblocking(False)
            socks.append(s)
        # addresses as the bridge would have latched them (64 first
        # packets are 4,032 fan-out rows)
        for i, sid in enumerate(sids):
            bridge.loop.addr_ip[sid] = 0x7F000001     # 127.0.0.1
            bridge.loop.addr_port[sid] = socks[i].getsockname()[1]
        for k in range(PACKETS):
            i = 7 * k
            cl = SrtpStreamTable(capacity=1)
            cl.add_stream(0, *_pair(keys[i, 0]))
            b = rtp_header.build([b"meeting-%d" % k * 9], [400 + k], [0],
                                 [SSRC_BASE + i], [111], stream=[0])
            socks[i].sendto(cl.protect_rtp(b).to_bytes(0),
                            ("127.0.0.1", bridge.port))
        bridge.tick(now=3000.0)
        bridge.tick(now=3000.02)
        bridge.flush_egress()
        got = {}
        for r, s in enumerate(socks):
            while True:
                try:
                    pkt = s.recv(2048)
                except BlockingIOError:
                    break
                key = (r, int.from_bytes(pkt[8:12], "big"),
                       int.from_bytes(pkt[2:4], "big"))
                assert key not in got
                got[key] = pkt
        return {"got": got, "translator": type(bridge.translator),
                "launches": bridge.translator.fanout_launches,
                "split": bridge.translator.fanout_split_ticks,
                "forwarded": bridge.forwarded}
    finally:
        for s in socks:
            s.close()
        bridge.close()


@pytest.fixture(scope="module")
def both():
    mesh = make_media_mesh(__import__("jax").devices()[:SHARDS])
    return {"mesh": _serve(mesh), "one": _serve(None)}


def test_the_mesh_bridge_splits_the_tick_too(both):
    assert both["mesh"]["translator"] is ShardedRtpTranslator
    for rec in both.values():
        assert (rec["launches"], rec["split"]) == (3, 1)
        assert rec["forwarded"] == PACKETS * (CONF - 1)


def test_the_split_tick_is_byte_equal_to_the_one_chip_bridge(both):
    mesh, one = both["mesh"]["got"], both["one"]["got"]
    assert len(one) == PACKETS * (CONF - 1)
    assert mesh.keys() == one.keys()
    assert all(mesh[k] == one[k] for k in one)


#: legs a sender -> the rows of the one-chip translator's launches as
#: they leave `expand`, padded to the fan-out's own classes
TICKS = {"1164_rows": ((63,) * 18 + (30,), [1024, 256]),
         "301_rows": ((7,) * 43, [512]),
         "1300_rows": ((63,) * 20 + (40,), [1024, 512])}


@pytest.mark.parametrize("tick", sorted(TICKS))
@pytest.mark.parametrize("sharded", [False, True], ids=["one_chip", "mesh"])
def test_the_mesh_translator_cuts_at_launch_rows_alone(sharded, tick):
    """1,164 rows (18 senders of 63 legs and one of 30) at the real
    classes: the one-chip translator launches 1,024 + 256 rows, the
    mesh translator one call of 1,164 unpadded rows, as before.  The
    fan-out's own 512-row class is the one-chip translator's alone:
    301 rows leave it as 512 and 1,300 as 1,024 + 512, the mesh's
    `expand` as the 301 and the 1,300 they are (its lanes a chip are
    `_OwnerPlan`'s, padded to `ROW_CLASSES`), and it books no launch
    by class."""
    keys = _keys(80)
    if sharded:
        tr = ShardedRtpTranslator(
            CAPACITY, make_media_mesh(__import__("jax").devices()[:SHARDS]))
    else:
        tr = RtpTranslator(CAPACITY)
    tr.add_receivers(range(80), [bytes(k[1][:16]) for k in keys],
                     [bytes(k[1][16:]) for k in keys])
    legs_of, padded = TICKS[tick]
    for s, n in enumerate(legs_of):
        tr.connect(100 + s, [(3 * s + j) % 80 for j in range(n)])
    assert tr.launch_rows == ROW_CLASSES[-1]
    seen = []
    tr._cm_fanout_call = lambda recv, plane, *rest: (
        seen.append(plane.shape[0]) or staging.Launch(()))
    b = PacketBatch.from_payloads(
        [bytes([0x80, 111]) + bytes(10) + b"x" * 60] * len(legs_of),
        capacity=256, stream=[100 + s for s in range(len(legs_of))])
    pend = tr.translate_async(b, np.arange(len(legs_of), dtype=np.int64))
    assert seen == ([sum(legs_of)] if sharded else padded)
    assert pend.launches == len(seen)
    assert tr.fanout_class_cut_ticks == int(len(seen) > 1)
    assert tr.fanout_launch_rows == (
        {} if sharded else {c: 1 for c in padded})
