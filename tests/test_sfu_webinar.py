"""Webinar rooms: a panel that may send, visitors who only receive, and
ONE admission rule that says which a member is.

`LifecycleConfig.max_conference_participants` (Jicofo's
`jicofo.visitors.max-participants`) is a rule over the broadcast plane
the lifecycle has had since PR 11: with placement enabled
`request_join` declares a conference a broadcast conference when its
first member joins, admits a member as a speaker while the room holds
fewer speakers than the rule says and as a fanout-only listener from
then on, with no argument from the caller.  Nothing on the data path
reads it: a visitor's uplink is dropped by the loop's fanout-only mask
(after its address is latched), a participant's packet is fanned out to
every other member of its room.

The reference is `who_hears` below, a dozen lines of plain Python that
share nothing with the program; the crypto's is `benchmarks/oracle.py`
(scalar OpenSSL).  The bridge is assembled as `benchmarks/sut.py`
assembles it (`SfuBridge` -> `BridgeSupervisor` ->
`StreamLifecycleManager.request_join`, every endpoint keyed from a
seed, no role passed), two rooms of 32 with a panel of 4, under both
suites; every delivery of seeded rounds of packets, from participants
and visitors alike, is opened under its receiver's key by the oracle
and the set of deliveries compared with the reference.
"""

import importlib.util
import os
import socket

import numpy as np
import pytest

import libjitsi_tpu
from libjitsi_tpu.service import lifecycle as lifecycle_mod
from libjitsi_tpu.service import supervisor as supervisor_mod
from libjitsi_tpu.service.sfu_bridge import SfuBridge
from libjitsi_tpu.transform.srtp import SrtpProfile
from libjitsi_tpu.utils.compile_cache import compile_stats

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CM = SrtpProfile.AES_CM_128_HMAC_SHA1_80
GCM = SrtpProfile.AEAD_AES_128_GCM
SSRC_BASE = 0x77000000
ROOM, PANEL, ROOMS = 32, 4, 2
ROWS = ROOM * ROOMS
PT = 111
#: members (= ssrc - SSRC_BASE) who send in one tick, a round: the
#: presenter alone; a visitor alone; the panels of both rooms talking
#: over each other with visitors of both; a panelist and the last
#: visitor of the table
ROUNDS = ((0,), (PANEL,), (0, 1, 2, 3, 9, 32, 33, 40, 63), (35, 63),
          (1, 34, 31))


def who_hears(members, participants, sender):
    """The members a packet of `sender` reaches.  `members`: member ->
    room; `participants`: the members who take part.  A participant's
    packet reaches every other member of its room exactly once; a
    visitor's reaches nobody; nothing leaves the room."""
    if sender not in participants:
        return []
    return sorted(m for m, room in members.items()
                  if room == members[sender] and m != sender)


def test_who_hears_is_the_three_rules():
    members = {0: "a", 1: "a", 2: "a", 3: "b", 4: "b"}
    assert who_hears(members, {0, 3}, 0) == [1, 2]
    assert who_hears(members, {0, 3}, 3) == [4]
    assert who_hears(members, {0, 3}, 1) == []          # a visitor
    assert who_hears({7: "c"}, {7}, 7) == []            # alone


@pytest.fixture(scope="module")
def oracle():
    spec = importlib.util.spec_from_file_location(
        "bench_oracle", os.path.join(_ROOT, "benchmarks", "oracle.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _keys(seed: int, n: int, salt: int) -> np.ndarray:
    """[n, 2] (client->bridge, bridge->client) of (key 16, salt)."""
    return np.random.default_rng([seed, 0x77]).integers(
        0, 256, (n, 2, 16 + salt), dtype=np.uint8)


def _pair(raw) -> tuple:
    b = bytes(raw)
    return b[:16], b[16:]


def _plain(rng, ssrc: int, seq: int) -> bytes:
    hdr = (bytes([0x80, PT]) + seq.to_bytes(2, "big")
           + (seq * 960 & 0xFFFFFFFF).to_bytes(4, "big")
           + ssrc.to_bytes(4, "big"))
    return hdr + rng.integers(0, 256, int(rng.integers(40, 161)),
                              dtype=np.uint8).tobytes()


def _assemble(profile, rows, config, warm=True):
    """`(bridge, sup, lc)` as `benchmarks/sut.py` builds them; `warm`
    False: the ladder is taken for warm (what serves then compiles as
    it goes)."""
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    bridge = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                       capacity=rows, profile=profile, recv_window_ms=0)
    reg = bridge.loop.metrics
    sup = supervisor_mod.BridgeSupervisor(
        bridge, supervisor_mod.SupervisorConfig(deadline_ms=60_000.0),
        metrics=reg)
    lc = lifecycle_mod.StreamLifecycleManager(
        bridge, supervisor=sup, config=config, metrics=reg)
    if not warm:
        lc._warm_bucket = lc._warm_lbucket = 1 << 30
    lc.enable_placement(1)
    return bridge, sup, lc


def _admit(lc, sup, keys, rows, room, now, first=0):
    """Members `first..rows` through `request_join`, no role passed;
    ticked until all are live.  Returns the refusals."""
    refused = []
    for i in range(first, rows):
        ok, why = lc.request_join(SSRC_BASE + i, _pair(keys[i, 0]),
                                  _pair(keys[i, 1]), conference=i // room)
        if not ok:
            refused.append((i, why))
    target = lc.admits + rows - first - len(refused)
    while lc.admits < target:
        now[0] += 0.02
        sup.tick(now=now[0])
        assert sup.ticks < 400, f"{lc.admits}/{target} live"
    return refused


class _Clients:
    """A socket a member, and what each received."""

    def __init__(self, n, port):
        self.port = port
        self.socks = []
        for _ in range(n):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.bind(("127.0.0.1", 0))
            s.setblocking(False)
            self.socks.append(s)

    def send(self, member, wire):
        self.socks[member].sendto(wire, ("127.0.0.1", self.port))

    def drain(self):
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

    def close(self):
        for s in self.socks:
            s.close()


def _serve(profile, oracle) -> dict:
    """Two rooms admitted and served; the record.  Under CM behind the
    real ladder, and held to 0 compile events while serving; under GCM
    the ladder is taken for warm (its programs are the ones serving
    compiles, a third as many: `tests/test_gcm_served.py` has GCM's
    ladder), so that suite's case is the comparison alone."""
    salt = oracle.SUITES[profile.name][2]
    protect = oracle.SUITES[profile.name][0]
    bridge, sup, lc = _assemble(profile, ROWS, lifecycle_mod.LifecycleConfig(
        install_batch=16, max_pending=512, pkts_per_stream=1,
        max_conference_participants=PANEL), warm=profile is CM)
    keys = _keys(47, ROWS, salt)
    now = [3000.0]
    rec = {"sent": {}, "rounds": [], "keys": keys}
    clients = None

    def tick(n=2):
        counts = {}
        for _ in range(n):
            now[0] += 0.02
            sup.tick(now=now[0])
            for stage, kv in sup.last_counts.items():
                mine = counts.setdefault(stage, {})
                for k, v in kv.items():
                    mine[k] = mine.get(k, 0) + v
        bridge.flush_egress()
        return counts

    try:
        rec["refused"] = _admit(lc, sup, keys, ROWS, ROOM, now)
        sid_of = {ssrc: sid for sid, ssrc in bridge._ssrc_of.items()}
        sids = np.array([sid_of[SSRC_BASE + i] for i in range(ROWS)])
        rec["roles"] = dict(lc.admit_roles)
        rec["speakers"] = {c: sorted(s) for c, s in
                           bridge._bcast_speakers.items()}
        rec["fanout_only"] = bridge.loop.fanout_only[sids].copy()
        rec["sids"] = sids
        rec["warm"] = (sorted(lc._warm_rows), sorted(lc._warm_lrows),
                       bridge.translator.launch_rows)
        clients = _Clients(ROWS, bridge.port)
        rng = np.random.default_rng(48)
        seq = np.full(ROWS, 700, dtype=np.int64)

        def fresh(i):
            s = int(seq[i])
            seq[i] += 1
            plain = _plain(rng, SSRC_BASE + i, s)
            rec["sent"][(SSRC_BASE + i, s)] = plain
            return protect(*_pair(keys[i, 0]), plain, s)

        # every member reaches the bridge first, with one packet, as
        # the benchmark's generator has it: a room at a time
        dropped0 = bridge.loop.fanout_rtp_dropped
        first = {}
        for room in range(ROOMS):
            for i in range(room * ROOM, (room + 1) * ROOM):
                clients.send(i, fresh(i))
            first[room] = tick(3)
        rec["first_counts"] = first
        rec["first_dropped"] = bridge.loop.fanout_rtp_dropped - dropped0
        rec["latched"] = bridge.loop.addr_port[sids].copy()
        rec["ports"] = [s.getsockname()[1] for s in clients.socks]
        rec["first_got"] = clients.drain()
        events0 = compile_stats().compile_events
        recompiles0 = lc.datapath_recompiles
        for senders in ROUNDS:
            wires = [(i, fresh(i)) for i in senders]
            for i, w in wires:
                clients.send(i, w)
            counts = tick(2)
            rec["rounds"].append({
                "senders": senders, "counts": counts,
                "sent": [(SSRC_BASE + i, int(seq[i]) - 1)
                         for i in senders],
                "got": clients.drain()})
        rec["compiles"] = compile_stats().compile_events - events0
        rec["recompiles"] = lc.datapath_recompiles - recompiles0
        rec["metrics"] = bridge.loop.metrics.render()
        rec["health"] = sup.health()
        return rec
    finally:
        if clients is not None:
            clients.close()
        bridge.close()


@pytest.fixture(scope="module", params=[CM, GCM], ids=["cm", "gcm"])
def served(request, oracle):
    rec = _serve(request.param, oracle)
    rec["profile"] = request.param
    return rec


MEMBERS = {i: i // ROOM for i in range(ROWS)}
PARTICIPANTS = {i for i in range(ROWS) if i % ROOM < PANEL}


def test_the_first_k_members_take_part_and_the_rest_visit(served):
    """The (k+1)-th member is a listener and nobody is refused: 4
    speakers and 28 listeners a room, by the rule alone."""
    assert served["refused"] == []
    assert served["roles"] == {"speaker": PANEL * ROOMS,
                               "listener": (ROOM - PANEL) * ROOMS}
    sids = served["sids"]
    assert served["speakers"] == {
        room: sorted(int(sids[room * ROOM + k]) for k in range(PANEL))
        for room in range(ROOMS)}
    assert [bool(x) for x in served["fanout_only"]] == \
        [i not in PARTICIPANTS for i in range(ROWS)]


def test_a_visitors_first_packet_latches_and_is_dropped_and_counted(
        served):
    """Every member's first packet taught the bridge its address; the
    56 visitors' went no further (the loop's counter and the tick's
    `demux` span both say so), the 8 participants' were fanned out."""
    assert list(served["latched"]) == served["ports"]
    visitors = ROWS - len(PARTICIPANTS)
    assert served["first_dropped"] == visitors
    for room in range(ROOMS):
        counts = served["first_counts"][room]
        assert counts["demux"]["fanout_only_dropped"] == ROOM - PANEL
        assert counts["demux"]["rows"] == ROOM
        assert counts["parse"]["rows"] == PANEL
    sent_by = {int.from_bytes(p[8:12], "big") - SSRC_BASE
               for _r, p in served["first_got"]}
    assert sent_by <= PARTICIPANTS and len(sent_by) == len(PARTICIPANTS)
    # /metrics, rendered after the rounds: theirs count too
    total = visitors + sum(i not in PARTICIPANTS
                           for senders in ROUNDS for i in senders)
    assert f"loop_fanout_rtp_dropped {total}\n" in served["metrics"]


@pytest.mark.parametrize("k", range(len(ROUNDS)))
def test_every_delivery_is_the_references_and_opens_under_its_key(
        served, oracle, k):
    """A round's deliveries are exactly `who_hears` of each packet
    sent, once each, and each opens under ITS receiver's key, by the
    oracle, to the sender's plaintext."""
    rnd, keys = served["rounds"][k], served["keys"]
    _p, unprotect, _salt, _grow = oracle.SUITES[served["profile"].name]
    protect = oracle.SUITES[served["profile"].name][0]
    want = sorted((r, ssrc, seq) for ssrc, seq in rnd["sent"]
                  for r in who_hears(MEMBERS, PARTICIPANTS,
                                     ssrc - SSRC_BASE))
    got = sorted((r, int.from_bytes(p[8:12], "big"),
                  int.from_bytes(p[2:4], "big")) for r, p in rnd["got"])
    assert got == want
    for r, pkt in rnd["got"]:
        ssrc = int.from_bytes(pkt[8:12], "big")
        seq = int.from_bytes(pkt[2:4], "big")
        mk, ms = _pair(keys[r, 1])
        plain = unprotect(mk, ms, pkt, seq)
        assert plain is not None, f"bad tag for receiver {r}"
        sent = served["sent"][(ssrc, seq)]
        # the bridge stamps abs-send-time: header past the X bit and the
        # whole payload are the sender's
        assert plain[1:12] == sent[1:12]
        assert plain[oracle.payload_off(plain):] == sent[12:]
        assert protect(mk, ms, plain, seq) == pkt
    # the mask, not the router, silenced the round's visitors
    visitors = sum(1 for i in rnd["senders"] if i not in PARTICIPANTS)
    assert rnd["counts"]["demux"]["fanout_only_dropped"] == visitors
    if len(rnd["senders"]) > visitors:
        assert rnd["counts"]["expand"]["legs_max"] == ROOM - 1
        assert rnd["counts"]["egress"]["rows"] == len(want)
    else:
        assert "expand" not in rnd["counts"]


def test_serving_compiled_nothing_and_refused_nobody(served):
    if served["profile"] is CM:
        assert served["warm"] == ([16, 64], [16, 64, 256], 256)
        assert served["compiles"] == 0 and served["recompiles"] == 0
    h = served["health"]
    assert not h["shed"] and not h["quarantined"]
    m = served["metrics"]
    assert 'admit_roles_total{role="speaker"} 8' in m
    assert 'admit_roles_total{role="listener"} 56' in m
    assert "bcast_listeners 56" in m and "bcast_speakers 8" in m
    assert "bcast_conferences 2" in m


# ----------------------------------------------------- a room of 300

@pytest.fixture(scope="module")
def room_of_300(oracle):
    """One room of 300 (a panel of 4) through the normal path, CM; the
    presenter sends one packet.  The two ladders decide what they
    decide; of a rung's programs the fan-out's at the audio width is
    compiled and the rest is left out (RTP, SRTCP and the MTU width:
    a dozen programs of a quarter of a minute each on XLA:CPU, which
    `tests/test_lifecycle.py` and `tests/test_sfu_conf64.py` hold)."""
    n = 300
    bridge, sup, lc = _assemble(CM, n, lifecycle_mod.LifecycleConfig(
        install_batch=64, max_pending=512, pkts_per_stream=1,
        max_conference_participants=PANEL))
    for table in (bridge.rx_table, bridge.tx_table):
        table.warmup_rtp = table.warmup_rtcp = lambda *a, **k: None
    warmups = bridge.translator.fanout_warmups
    bridge.translator.fanout_warmups = \
        lambda rows, payload_len=160: warmups(rows, payload_len)[:1]
    keys = _keys(49, n, 14)
    now = [4000.0]
    clients = None
    try:
        refused = _admit(lc, sup, keys, n, n, now)
        sid_of = {ssrc: sid for sid, ssrc in bridge._ssrc_of.items()}
        sids = np.array([sid_of[SSRC_BASE + i] for i in range(n)])
        clients = _Clients(n, bridge.port)
        # one panelist says how the bridge keeps an address (and its
        # packet is the unprotect's warm-up, left out above); the
        # others' are written as it would have kept them
        clients.send(1, oracle.protect_cm(
            *_pair(keys[1, 0]), _plain(np.random.default_rng(1),
                                       SSRC_BASE + 1, 5), 5))
        for _ in range(2):
            now[0] += 0.02
            sup.tick(now=now[0])
        bridge.flush_egress()
        loop = bridge.loop
        assert loop.addr_port[sids[1]] == \
            clients.socks[1].getsockname()[1]
        loop.addr_ip[sids] = loop.addr_ip[sids[1]]
        loop.addr_port[sids] = [s.getsockname()[1] for s in clients.socks]
        by_class0 = dict(bridge.translator.fanout_launch_rows)
        recompiles0 = lc.datapath_recompiles
        events0 = compile_stats().compile_events
        plain = _plain(np.random.default_rng(2), SSRC_BASE, 9)
        clients.send(0, oracle.protect_cm(*_pair(keys[0, 0]), plain, 9))
        counts = {}
        for _ in range(2):
            now[0] += 0.02
            sup.tick(now=now[0])
            for stage, kv in sup.last_counts.items():
                counts.setdefault(stage, {}).update(kv)
        bridge.flush_egress()
        return {"refused": refused, "counts": counts, "plain": plain,
                "compiles": compile_stats().compile_events - events0,
                "recompiles": lc.datapath_recompiles - recompiles0,
                "got": clients.drain(), "keys": keys,
                "warm": (sorted(lc._warm_rows), sorted(lc._warm_lrows)),
                "launch_rows": bridge.translator.launch_rows,
                "by_class": {
                    c: k - by_class0.get(c, 0) for c, k in
                    bridge.translator.fanout_launch_rows.items()}}
    finally:
        if clients is not None:
            clients.close()
        bridge.close()


def test_a_room_of_300_is_one_512_row_launch_and_compiles_nothing(
        room_of_300, oracle):
    rec = room_of_300
    assert rec["refused"] == []
    # the panel's ladder and the visitors': the fan-out is bounded at a
    # class both warmed up to, the 512-row class among them
    assert rec["warm"] == ([16, 64], [16, 64, 256, 1024])
    assert rec["launch_rows"] == 1024
    exp = rec["counts"]["expand"]
    assert (exp["rows"], exp["legs_max"], exp["launches"]) == (299, 299, 1)
    assert (exp["rows_padded"], exp["row_class"]) == (512, 512)
    assert rec["counts"]["egress"]["rows"] == 299
    assert rec["by_class"] == {512: 1}
    assert rec["compiles"] == 0 and rec["recompiles"] == 0
    got = {r: p for r, p in rec["got"]}
    assert sorted(got) == who_hears({i: 0 for i in range(300)},
                                    set(range(PANEL)), 0)
    for r, pkt in got.items():
        plain = oracle.unprotect_cm(*_pair(rec["keys"][r, 1]), pkt, 9)
        assert plain is not None
        assert plain[oracle.payload_off(plain):] == rec["plain"][12:]


# ------------------------------------------ the rule, without a ladder

def _small(config, rows=16):
    bridge, sup, lc = _assemble(CM, rows, config, warm=False)
    return bridge, sup, lc, _keys(50, 64, 14), [5000.0]


def test_a_room_that_states_no_rule_is_admitted_as_before():
    bridge, sup, lc, keys, now = _small(lifecycle_mod.LifecycleConfig())
    try:
        assert _admit(lc, sup, keys, 12, 6, now) == []
        assert lc._bcast == {} and bridge._bcast_speakers == {}
        assert lc.admit_roles == {"speaker": 0, "listener": 0}
        assert not bridge.loop.fanout_only.any()
        assert not lc._listener_sids
        sid_of = {ssrc: sid for sid, ssrc in bridge._ssrc_of.items()}
        for i in range(12):
            room = {sid_of[SSRC_BASE + j]
                    for j in range(12) if j // 6 == i // 6}
            assert set(bridge.translator._routes[sid_of[SSRC_BASE + i]]) \
                == room - {sid_of[SSRC_BASE + i]}
        assert "fanout_only_dropped" not in sup.last_counts.get("demux", {})
    finally:
        bridge.close()


@pytest.mark.parametrize("cap", [0, 4])
def test_a_visitor_is_never_refused_conference_full(cap):
    """The room cap holds rooms of peers; where both are stated it caps
    the panel, and a visitor past it is still admitted."""
    bridge, sup, lc, keys, now = _small(lifecycle_mod.LifecycleConfig(
        max_conference_size=cap, max_conference_participants=2))
    try:
        assert _admit(lc, sup, keys, 12, 12, now) == []
        assert lc.admit_roles == {"speaker": 2, "listener": 10}
        assert lc.admit_rejected == {}
    finally:
        bridge.close()


def test_a_panel_larger_than_the_room_cap_is_refused_at_construction():
    with pytest.raises(ValueError, match="panel of 8"):
        _small(lifecycle_mod.LifecycleConfig(
            max_conference_size=4, max_conference_participants=8))


def test_a_callers_role_wins_and_a_place_on_the_panel_is_refilled():
    bridge, sup, lc, keys, now = _small(lifecycle_mod.LifecycleConfig(
        max_conference_participants=2))
    try:
        ok, _ = lc.request_join(SSRC_BASE + 40, _pair(keys[40, 0]),
                                _pair(keys[40, 1]), conference=3,
                                role="listener")
        assert ok and lc.admit_roles == {"speaker": 0, "listener": 1}
        assert _admit(lc, sup, keys, 4, 4, now) == []     # room 0
        assert lc.admit_roles == {"speaker": 2, "listener": 3}
        # the first panelist leaves: the next joiner takes the place
        assert lc.request_leave(ssrc=SSRC_BASE)
        now[0] += 0.02
        sup.tick(now=now[0])
        assert _admit(lc, sup, keys, 6, 6, now, first=4) == []
        assert lc.admit_roles == {"speaker": 3, "listener": 4}
        sid_of = {ssrc: sid for sid, ssrc in bridge._ssrc_of.items()}
        assert bridge._bcast_speakers[0] == {sid_of[SSRC_BASE + 1],
                                             sid_of[SSRC_BASE + 4]}
        # a join without a conference is a room of its own, no webinar
        ok, _ = lc.request_join(SSRC_BASE + 41, _pair(keys[41, 0]),
                                _pair(keys[41, 1]))
        assert ok and sum(lc.admit_roles.values()) == 7
    finally:
        bridge.close()


def _by_conference(bridge) -> dict:
    out: dict = {}
    for sid, conf in bridge._conf_of.items():
        out.setdefault(conf, set()).add(sid)
    return out


def test_a_panel_change_touches_its_room_and_no_other():
    """`set_broadcast_speakers` sets the masks and reconnects the
    senders of the one room: the bridge keeps its members by conference
    (`_members_of`, the inverse of `_conf_of` through joins, leaves and
    a rebalance's moves) and walks no other row."""
    bridge, sup, lc, keys, now = _small(lifecycle_mod.LifecycleConfig(
        max_conference_participants=2))
    try:
        assert _admit(lc, sup, keys, 12, 6, now) == []
        assert bridge._members_of == _by_conference(bridge)
        room0 = set(bridge._members_of[0])
        touched = []
        mask, connect = bridge.loop.set_fanout_only, \
            bridge.translator.connect
        bridge.loop.set_fanout_only = lambda sid, on: (
            touched.append(sid), mask(sid, on))
        bridge.translator.connect = lambda sid, legs: (
            touched.append(sid), connect(sid, legs))
        panel = sorted(room0)[3:5]
        bridge.set_broadcast_speakers(0, panel)
        assert set(touched) == room0 and len(touched) == 12
        assert [s for s in sorted(room0)
                if not bridge.loop.fanout_only[s]] == panel
        del bridge.loop.set_fanout_only, bridge.translator.connect
        # a leave and a move keep the two views one relation
        assert lc.request_leave(ssrc=SSRC_BASE + 7)
        now[0] += 0.02
        sup.tick(now=now[0])
        src = max(bridge._members_of[1])
        bridge.migrate_endpoints({src: 15})
        assert bridge._members_of == _by_conference(bridge)
        assert 15 in bridge._members_of[1] and src not in bridge._conf_of
    finally:
        bridge.close()


def test_a_refused_first_member_leaves_no_room_behind():
    """A full table: the join that would have opened a room is refused
    `capacity` by name, and the declaration is taken back."""
    bridge, sup, lc, keys, now = _small(lifecycle_mod.LifecycleConfig(
        max_conference_participants=2), rows=4)
    try:
        assert _admit(lc, sup, keys, 4, 4, now) == []
        ok, why = lc.request_join(SSRC_BASE + 9, _pair(keys[9, 0]),
                                  _pair(keys[9, 1]), conference=7)
        assert (ok, why) == (False, "capacity")
        assert sorted(lc._bcast) == [0] and 7 not in bridge._bcast_speakers
        assert lc.placer.shard_of(7) is None
    finally:
        bridge.close()


def test_roles_survive_a_checkpoint_and_its_recovery(tmp_path):
    """A kill with one room live, one visitor staged and one queued:
    the recovered manager holds the same panel, the same visitors and
    the same masks, completes the two joins in their roles, and goes on
    applying the rule."""
    config = lifecycle_mod.LifecycleConfig(max_conference_participants=2)
    bridge, sup, lc, keys, now = _small(config)
    cfg = libjitsi_tpu.configuration_service()
    assert _admit(lc, sup, keys, 5, 8, now) == []
    for i in (5, 6):
        assert lc.request_join(SSRC_BASE + i, _pair(keys[i, 0]),
                               _pair(keys[i, 1]), conference=0)[0]
        if i == 5:
            lc.poll()                      # staged, not committed
    assert len(lc._staged) == 1 and len(lc._join_q) == 1
    speakers = set(bridge._bcast_speakers[0])
    listeners = set(lc._listener_sids)
    ckpt = str(tmp_path / "webinar.ckpt")
    sup.save_checkpoint(ckpt)
    bridge.close()                                      # the crash

    sup2 = supervisor_mod.BridgeSupervisor.recover(
        cfg, ckpt, SfuBridge, port=0, supervisor_config=sup.cfg,
        recv_window_ms=0)
    bridge2 = sup2.bridge
    try:
        lc2 = lifecycle_mod.StreamLifecycleManager(
            bridge2, supervisor=sup2, config=config)
        lc2._warm_bucket = lc2._warm_lbucket = 1 << 30
        assert lc2._bcast[0]["speakers"] == speakers == \
            bridge2._bcast_speakers[0]
        assert lc2._listener_sids == listeners
        assert bridge2._members_of == _by_conference(bridge2)
        assert lc2.placer.size_of(0) == 2
        assert lc2.placer.listener_count(0) == 5     # 3 live, 1 + 1 due
        for _ in range(3):
            now[0] += 0.02
            sup2.tick(now=now[0])
        assert len(bridge2._ssrc_of) == 7
        live = sorted(bridge2._ssrc_of)
        assert [bool(bridge2.loop.fanout_only[s]) for s in live] == \
            [s not in speakers for s in live]
        # the rule goes on: the room's panel is full, the next member
        # visits; the first member of a new room takes part
        for i, conf, role in ((7, 0, "listener"), (8, 1, "speaker")):
            assert lc2.request_join(SSRC_BASE + i, _pair(keys[i, 0]),
                                    _pair(keys[i, 1]), conference=conf)[0]
            assert lc2._join_q[-1][5] == role
    finally:
        bridge2.close()
