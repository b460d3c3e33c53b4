"""The served bridge on a device mesh against the scalar OpenSSL oracle
and against its one-chip twin.

Both bridges are assembled as `benchmarks/sut.py` assembles them —
`SfuBridge` under `BridgeSupervisor` and `StreamLifecycleManager`,
`enable_placement(4)`, every endpoint through `request_join`, ticked by
the supervisor — at a size the CPU holds: 64 endpoints in 8 conferences
of 8; the mesh bridge's tables are row-partitioned over four of the
eight host devices `conftest.py` forces.  Clients are plain UDP sockets
that protect and open under `benchmarks/oracle.py` alone.  One module
fixture drives the same seeded traffic through both bridges once; the
tests each hold one facet of its record.  The ladder is kept to three
row classes through `LifecycleConfig` (`pkts_per_stream` 1): a
`shard_map` program compiles slowly on XLA:CPU.
"""

import importlib.util
import os
import socket

import numpy as np
import pytest

import libjitsi_tpu
from libjitsi_tpu.core import staging
from libjitsi_tpu.core.packet import ROW_CLASSES
from libjitsi_tpu.mesh import (ShardedRtpTranslator, ShardedSrtpTable,
                               make_media_mesh)
from libjitsi_tpu.transform.srtp import SrtpProfile
from libjitsi_tpu.utils.compile_cache import compile_stats

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CM = SrtpProfile.AES_CM_128_HMAC_SHA1_80
SSRC_BASE = 0x52000000
ROWS, CONF, SHARDS = 64, 8, 4
PT = 111
#: the row classes the ladder warms for 64 endpoints at one packet a
#: stream a tick: the class of the population and one of headroom
WARMED = ROW_CLASSES[:3]


@pytest.fixture(scope="module")
def oracle():
    spec = importlib.util.spec_from_file_location(
        "bench_oracle", os.path.join(_ROOT, "benchmarks", "oracle.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _keys(seed: int, n: int) -> np.ndarray:
    """[n, 2] (client->bridge, bridge->client) of (key 16, salt 14)."""
    return np.random.default_rng([seed, 0x6D]).integers(
        0, 256, (n, 2, 30), dtype=np.uint8)


def _pair(raw) -> tuple:
    b = bytes(raw)
    return b[:16], b[16:]


def _plain(rng, ssrc: int, seq: int) -> bytes:
    hdr = (bytes([0x80, PT]) + seq.to_bytes(2, "big")
           + (seq * 960 & 0xFFFFFFFF).to_bytes(4, "big")
           + ssrc.to_bytes(4, "big"))
    return hdr + rng.integers(0, 256, int(rng.integers(40, 161)),
                              dtype=np.uint8).tobytes()


#: (senders of one tick as (endpoint, packets)) per round: fan-out rows
#: are 7 a packet and a shard holds two conferences (endpoints 0-7 and
#: 32-39 on shard 0 by least-loaded placement in admission order), so
#: these ticks put their hottest shard in each lane class the ladder
#: warmed, for the fan-out (16, 64, 256) and the unprotect (16, 64)
def _rounds(rng):
    out = []
    for _ in range(3):
        # 2 packets of one conference: 14 fan-out rows on one shard
        out.append([(int(rng.integers(0, 8)), 1), (int(rng.integers(
            8, 16)), 1)])
        # 8 packets of one conference: 56 rows on its shard -> 64 lanes
        c = int(rng.integers(0, 8))
        out.append([(8 * c + k, 1) for k in range(8)])
        # both conferences of shard 0, two packets a sender: 32 packets
        # in (64 unprotect lanes), 224 fan-out rows -> 256 lanes
        out.append([(e, 2) for e in list(range(0, 8)) + list(
            range(32, 40))])
        # a spread tick: one sender a conference, every shard alike
        out.append([(8 * c + int(rng.integers(0, 8)), 1)
                    for c in range(8)])
    return out


def _serve(oracle, mesh, ladder: bool, tap_of, synchronous: bool) -> dict:
    """Admit ROWS endpoints and drive the seeded rounds; the record.
    `synchronous`: the fan-out through the synchronous `send_batch`
    (conftest's `EgressTap`) instead of the engine's egress worker."""
    from libjitsi_tpu.service import lifecycle as lifecycle_mod
    from libjitsi_tpu.service import supervisor as supervisor_mod
    from libjitsi_tpu.service.sfu_bridge import SfuBridge

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    kwargs = {"mesh": mesh} if mesh is not None else {}
    bridge = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                       capacity=ROWS, profile=CM, recv_window_ms=0,
                       **kwargs)
    reg = bridge.loop.metrics
    sup = supervisor_mod.BridgeSupervisor(
        bridge, supervisor_mod.SupervisorConfig(deadline_ms=60_000.0),
        metrics=reg)
    lc = lifecycle_mod.StreamLifecycleManager(
        bridge, supervisor=sup,
        config=lifecycle_mod.LifecycleConfig(
            install_batch=64, max_pending=512, pkts_per_stream=1,
            # as the mesh configuration file states it; the one-chip
            # twin places over SHARDS too and states nothing
            table_shards=SHARDS if mesh is not None else 0),
        metrics=reg)
    if not ladder:
        # the twin is judged on its bytes alone: it compiles as it goes
        lc._warm_bucket = 1 << 30
    lc.enable_placement(SHARDS)
    tap = tap_of(bridge)
    tap.synchronous = synchronous
    keys = _keys(41, ROWS)
    now = [1000.0]
    rec = {"sent": {}, "got": [], "counts": [], "lanes": {
        "unprotect_wait": set(), "fanout_dispatch": set()}}

    def tick(n=1):
        for _ in range(n):
            now[0] += 0.02
            sup.tick(now=now[0])
            counts = sup.last_counts
            if "unprotect_wait" in counts:
                rec["counts"].append({k: dict(v)
                                      for k, v in counts.items()})
                for stage in rec["lanes"]:
                    lanes = counts.get(stage, {}).get("lanes")
                    if lanes is not None:
                        rec["lanes"][stage].add(int(lanes))
            elif "fanout_wait" in counts and rec["counts"]:
                # the tick that read the round dispatched its fan-out;
                # this one read nothing and collected it at its end:
                # one record a round, as one serial tick booked it
                for stage, kv in counts.items():
                    mine = rec["counts"][-1].setdefault(stage, {})
                    for k, v in kv.items():
                        mine[k] = mine.get(k, 0) + v
        # the fan-out's datagrams leave on the engine's egress worker:
        # have them out before a caller reads a client socket
        bridge.flush_egress()

    socks = []
    try:
        for i in range(ROWS):
            ok, why = lc.request_join(SSRC_BASE + i, _pair(keys[i, 0]),
                                      _pair(keys[i, 1]),
                                      conference=i // CONF)
            assert ok, why
        while lc.admits < ROWS:
            tick()
            assert sup.ticks < 64, f"{lc.admits}/{ROWS} live"
        rec["warm_rows"] = sorted(lc._warm_rows)
        rec["conf_of"] = dict(bridge._conf_of)
        for i in range(ROWS):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.bind(("127.0.0.1", 0))
            s.setblocking(False)
            socks.append(s)
        rng = np.random.default_rng(42)
        seq = np.full(ROWS, 300, dtype=np.int64)

        def fresh(i):
            s = int(seq[i])
            seq[i] += 1
            plain = _plain(rng, SSRC_BASE + i, s)
            rec["sent"][(SSRC_BASE + i, s)] = plain
            return oracle.protect_cm(*_pair(keys[i, 0]), plain, s)

        def drain(into):
            for r, s in enumerate(socks):
                while True:
                    try:
                        pkt = s.recv(2048)
                    except BlockingIOError:
                        break
                    if len(pkt) >= 12 and (pkt[1] & 0x7F) == PT:
                        into.append((r, pkt))

        # latch every address: what these first packets reach is not
        # judged (a leg without an address yet gets nothing)
        for i in range(ROWS):
            socks[i].sendto(fresh(i), ("127.0.0.1", bridge.port))
        tick(3)
        drain([])
        rec["sent"].clear()
        rec["counts"].clear()
        tap.handed.clear()
        forwarded0 = bridge.forwarded
        for lanes in rec["lanes"].values():
            lanes.clear()
        events0 = compile_stats().compile_events
        recompiles0 = lc.datapath_recompiles
        for senders in _rounds(rng):
            for i, n in senders:
                for _ in range(n):
                    socks[i].sendto(fresh(i), ("127.0.0.1", bridge.port))
            tick(2)
            drain(rec["got"])
        rec["compiles"] = compile_stats().compile_events - events0
        rec["recompiles"] = lc.datapath_recompiles - recompiles0
        rec["keys"] = keys
        rec["health"] = sup.health()
        rec["metrics"] = reg.render()
        rec["classes"] = (type(bridge.rx_table), type(bridge.translator))
        rec["placements"] = (bridge.rx_table.placements
                             if mesh is not None else None)
        rec["forwarded"] = bridge.forwarded - forwarded0
        rec["handed"] = tap.handed
        rec["ports"] = [s.getsockname()[1] for s in socks]
        rec["job_ids"] = [d.id for d in tap.reaped]
        return rec
    finally:
        for s in socks:
            s.close()
        bridge.close()


@pytest.fixture(scope="module")
def served(oracle, egress_tap):
    # the mesh bridge sends through the egress worker, its one-chip twin
    # through the synchronous call: their egress is compared below
    mesh = make_media_mesh(__import__("jax").devices()[:SHARDS])
    return {"mesh": _serve(oracle, mesh, True, egress_tap, False),
            "one": _serve(oracle, None, False, egress_tap, True)}


def _by_key(rec):
    """{(receiver, sender ssrc, seq): wire bytes}; no key twice."""
    out = {}
    for r, pkt in rec["got"]:
        k = (r, int.from_bytes(pkt[8:12], "big"),
             int.from_bytes(pkt[2:4], "big"))
        assert k not in out, f"delivered twice: {k}"
        out[k] = pkt
    return out


def test_the_mesh_bridge_is_the_sharded_one(served):
    assert served["mesh"]["classes"] == (ShardedSrtpTable,
                                         ShardedRtpTranslator)
    assert served["mesh"]["warm_rows"] == list(WARMED)


def test_every_delivery_opens_under_the_receivers_own_key(served, oracle):
    rec = served["mesh"]
    got = _by_key(rec)
    want = sum(CONF - 1 for _k in rec["sent"])
    assert len(got) == want
    for (r, ssrc, seq), pkt in got.items():
        plain = oracle.unprotect_cm(*_pair(rec["keys"][r, 1]), pkt, seq)
        assert plain is not None, f"bad tag for receiver {r}"
        sent = rec["sent"][(ssrc, seq)]
        # the bridge stamps abs-send-time: header past the X bit and the
        # whole payload are the sender's
        assert plain[1:12] == sent[1:12]
        assert plain[oracle.payload_off(plain):] == sent[12:]
        # inside its conference, never back to its sender
        assert (ssrc - SSRC_BASE) // CONF == r // CONF
        assert ssrc - SSRC_BASE != r


def test_egress_is_byte_equal_to_the_one_chip_bridge(served):
    mesh, one = _by_key(served["mesh"]), _by_key(served["one"])
    assert mesh.keys() == one.keys() and len(mesh) > 1000
    assert all(mesh[k] == one[k] for k in mesh)


def test_worker_delivers_in_order_what_the_synchronous_call_delivers(
        served):
    """The mesh bridge hands its fan-out to the egress worker, the
    one-chip twin sends it with the synchronous call: every receiver
    gets the same datagrams in the same order from both, and from each
    exactly what was handed over for its port, burst after burst."""
    mesh, one = served["mesh"], served["one"]
    assert mesh["job_ids"] and all(j > 0 for j in mesh["job_ids"])
    assert one["job_ids"] and all(j < 0 for j in one["job_ids"])
    for rec in (mesh, one):
        assert rec["forwarded"] == len(rec["handed"]) == len(rec["got"])
        for r, port in enumerate(rec["ports"]):
            assert [p for rr, p in rec["got"] if rr == r] == \
                [p for to, p in rec["handed"] if to == port]
    for r in range(ROWS):
        assert [p for rr, p in mesh["got"] if rr == r] == \
            [p for rr, p in one["got"] if rr == r]


def test_no_conference_straddles_a_shard(served):
    conf_of = served["mesh"]["conf_of"]
    assert len(conf_of) == ROWS
    shards = {}
    for sid, conf in conf_of.items():
        shards.setdefault(conf, set()).add(sid // (ROWS // SHARDS))
    assert all(len(s) == 1 for s in shards.values())
    # least-loaded in admission order: two conferences a shard
    assert sorted(len([c for c, s in shards.items() if s == {d}])
                  for d in range(SHARDS)) == [2] * SHARDS


@pytest.mark.parametrize("stage,classes", [
    ("fanout_dispatch", WARMED), ("unprotect_wait", WARMED[:2])])
def test_nothing_compiles_across_every_warmed_lane_class(served, stage,
                                                         classes):
    rec = served["mesh"]
    # the rounds put the hottest shard in each class the ladder warmed
    assert rec["lanes"][stage] == set(classes)
    assert rec["compiles"] == 0 and rec["recompiles"] == 0
    h = rec["health"]
    assert not h["shed"] and not h["quarantined"]


@pytest.mark.parametrize("stage,arrays", [
    ("unprotect_wait", ("h2d_arrays", "d2h_arrays")),
    ("fanout_dispatch", ("h2d_arrays",)), ("fanout_d2h", ("d2h_arrays",))])
def test_mesh_spans_book_the_plan_and_what_crossed(served, stage, arrays):
    for counts in served["mesh"]["counts"]:
        c = counts[stage]
        assert c["shards"] == SHARDS and c["lanes"] in ROW_CLASSES
        assert 0 < c["rows_hottest_shard"] <= c["lanes"]
        assert c["affine"] in (0, 1)
        # ONE packed lane plane each way (core/staging.py), a block a
        # chip: counted as it crosses, its bytes the lane plane's (40
        # to 160 byte payloads: the 224-byte width class both ways)
        for k in arrays:
            assert c[k] == 1
            assert c[k.replace("arrays", "bytes")] == \
                SHARDS * c["lanes"] * (224 + staging.TAIL)
        assert counts["owner_plan"] == {"rows": counts["owner_plan"][
            "rows"], "shards": 2 * SHARDS}
        assert counts["mesh_scatter"]["rows"] == \
            counts["owner_plan"]["rows"]
    # the one-chip twin books none of it
    for counts in served["one"]["counts"]:
        assert "owner_plan" not in counts
        assert "lanes" not in counts["unprotect_wait"]


@pytest.mark.parametrize("bridge", ["mesh", "one"])
@pytest.mark.parametrize("leaf,parent,keys", [
    ("unprotect_put", "unprotect_wait", ("h2d_arrays", "h2d_bytes")),
    ("unprotect_d2h", "unprotect_wait", ("d2h_arrays", "d2h_bytes")),
    ("fanout_put", "fanout_dispatch", ("h2d_arrays", "h2d_bytes"))])
def test_seam_leaves_book_what_their_calls_book(served, bridge, leaf,
                                                parent, keys):
    """On the mesh as on one chip the put is spanned once, where it
    happens (core/staging.py), and books the arrays and bytes its call
    books; the unprotect's copy back likewise (under a mesh with the
    plan's four beside them, as `fanout_d2h` has them)."""
    ticks = served[bridge]["counts"]
    assert ticks
    for counts in ticks:
        got = counts[leaf]
        assert {k: got[k] for k in keys} == \
            {k: counts[parent][k] for k in keys}
        assert got[keys[0]] == 1
        mesh_keys = {"shards", "lanes", "rows_hottest_shard", "affine"}
        if leaf.endswith("_put"):
            assert set(got) == set(keys)
        else:
            assert set(got) - set(keys) == (
                mesh_keys if bridge == "mesh" else set())
        for stage in ("unprotect_dispatch", "unprotect_block"):
            assert stage not in counts      # they carry no counts


def test_rows_per_shard_are_on_the_metrics_page(served):
    text = served["mesh"]["metrics"]
    for d in range(SHARDS):
        assert f'libjitsi_tpu_mesh_rows_per_shard{{shard="{d}"}}' in text
    assert "mesh_rows_per_shard" not in served["one"]["metrics"]


@pytest.mark.parametrize("case,want", [
    ("served", 4.0), ("an_array_an_argument", 17.0), ("no_stats", None),
    ("untraced", None)])
def test_staged_arrays_reader_sums_what_the_spans_book(served,
                                                       monkeypatch, case,
                                                       want):
    """`benchmarks/layers/mesh_staged_arrays_per_tick.paced.py` over a
    slice whose staging spans carry this bridge's own counts reads 4;
    over the counts an array an argument booked, 17; nothing where the
    spans carry no such stat or the run was not traced."""
    bench = os.path.join(_ROOT, "benchmarks")
    monkeypatch.syspath_prepend(bench)
    import xstats

    spec = importlib.util.spec_from_file_location(
        "layer_staged", os.path.join(
            bench, "layers", "mesh_staged_arrays_per_tick.paced.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    stages = ("unprotect_wait", "fanout_dispatch", "fanout_d2h")
    if case == "served":
        ticks = [{s: c[s] for s in stages}
                 for c in served["mesh"]["counts"]]
    else:
        old = {"unprotect_wait": {"h2d_arrays": 6, "d2h_arrays": 3},
               "fanout_dispatch": {"h2d_arrays": 6},
               "fanout_d2h": {"d2h_arrays": 2}}
        ticks = [{s: ({"rows": 9} if case == "no_stats" else old[s])
                  for s in stages}] * 3
    host = [("stage:" + s, 0, 1, dict(c, tick=t))
            for t, tick in enumerate(ticks) for s, c in tick.items()]
    monkeypatch.setattr(xstats, "load", lambda _p: {"host": host})
    ctx = {"trace": None if case == "untraced" else {"xplane": "x"}}
    assert len(ticks) >= 3 and reader.read(ctx) == want
