"""The served bridge under AEAD_AES_128_GCM against the scalar RFC 7714
reference (OpenSSL's AESGCM, `chip_smoke.py:protect_oracle_gcm` and the
unprotect beside it), and the rule that picks the GCM form.

The bridge is assembled as `benchmarks/sut.py` assembles it —
`SfuBridge` under `BridgeSupervisor` and `StreamLifecycleManager`, every
endpoint through `request_join`, ticked by the supervisor — at a size
the CPU holds: 64 endpoints in 8 conferences of 8.  Clients are plain
UDP sockets that protect and open under the reference alone.  One
module fixture drives the traffic once, with the registry's
`_time_once` patched to raise (nothing the bridge warms or serves may
time providers); the tests each hold one facet of its record.
"""

import importlib.util
import os
import socket

import numpy as np
import pytest

import libjitsi_tpu
from libjitsi_tpu.core import staging
from libjitsi_tpu.core.packet import (ROW_CLASSES, PacketBatch,
                                      _round_fanout_rows, _round_rows)
from libjitsi_tpu.kernels import registry
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.sfu import translator as tr_mod
from libjitsi_tpu.transform.srtp import SrtpProfile
from libjitsi_tpu.transform.srtp import context as ctx
from libjitsi_tpu.transform.srtp.context import SrtpStreamTable
from libjitsi_tpu.utils.compile_cache import compile_stats
from libjitsi_tpu.utils.tracing import PipelineTracer

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GCM = SrtpProfile.AEAD_AES_128_GCM
SSRC_BASE = 0x51000000
ROWS, CONF = 64, 8
PT = 111


@pytest.fixture(scope="module")
def oracle():
    """chip_smoke.py, loaded as tests/test_chip_smoke.py loads it."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _plain(rng, ssrc: int, seq: int) -> bytes:
    hdr = (bytes([0x80, PT]) + seq.to_bytes(2, "big")
           + (seq * 960 & 0xFFFFFFFF).to_bytes(4, "big")
           + ssrc.to_bytes(4, "big"))
    return hdr + rng.integers(0, 256, int(rng.integers(40, 161)),
                              dtype=np.uint8).tobytes()


def _keys(seed: int, n: int) -> np.ndarray:
    """[n, 2] (client->bridge, bridge->client) of (key 16, salt 12)."""
    return np.random.default_rng([seed, 0x6B]).integers(
        0, 256, (n, 2, 28), dtype=np.uint8)


def _pair(raw) -> tuple:
    b = bytes(raw)
    return b[:16], b[16:]


@pytest.fixture(scope="module")
def served(oracle, egress_tap):
    from libjitsi_tpu.service import lifecycle as lifecycle_mod
    from libjitsi_tpu.service import supervisor as supervisor_mod
    from libjitsi_tpu.service.sfu_bridge import SfuBridge

    mp = pytest.MonkeyPatch()

    def no_timing(*_a, **_k):
        raise AssertionError("kernels.registry._time_once was called")

    mp.setattr(registry, "_time_once", no_timing)
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    bridge = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                       capacity=ROWS, profile=GCM, recv_window_ms=0)
    reg = bridge.loop.metrics
    sup = supervisor_mod.BridgeSupervisor(
        bridge, supervisor_mod.SupervisorConfig(deadline_ms=60_000.0),
        metrics=reg)
    lc = lifecycle_mod.StreamLifecycleManager(
        bridge, supervisor=sup,
        config=lifecycle_mod.LifecycleConfig(install_batch=64,
                                             max_pending=512),
        metrics=reg)
    lc.enable_placement(1)
    tap = egress_tap(bridge)
    keys = _keys(31, ROWS)
    now = [1000.0]

    socks = []
    rec = {"sent": {}, "got": [], "replayed": set(), "forms": [],
           "counts": {}, "arrays": []}

    def tick(n=1):
        for _ in range(n):
            now[0] += 0.02
            sup.tick(now=now[0])
            # the tick's span counts (drained a tick), summed
            for stage, counts in sup.last_counts.items():
                mine = rec["counts"].setdefault(stage, {})
                for k, v in counts.items():
                    mine[k] = mine.get(k, 0) + v
            if "unprotect_wait" in sup.last_counts:
                lc_ = sup.last_counts
                rec["arrays"].append(tuple(
                    lc_.get(stage, {}).get(key)
                    for stage, key in (
                        ("unprotect_wait", "h2d_arrays"),
                        ("unprotect_wait", "d2h_arrays"),
                        ("fanout_dispatch", "h2d_arrays"),
                        ("fanout_d2h", "d2h_arrays"))))
                rec["forms"].append(
                    (sup.last_counts["unprotect_wait"].get("grouped"),
                     sup.last_counts.get("fanout_dispatch", {}).get(
                         "grouped")))
        # the fan-out's datagrams leave on the engine's egress worker:
        # have them out before a caller reads a client socket
        bridge.flush_egress()
    try:
        for i in range(ROWS):
            ok, why = lc.request_join(SSRC_BASE + i, _pair(keys[i, 0]),
                                      _pair(keys[i, 1]),
                                      conference=i // CONF)
            assert ok, why
        while lc.admits < ROWS:
            tick()
            assert sup.ticks < 64, f"{lc.admits}/{ROWS} live"
        rec["after_ladder"] = (lc.datapath_recompiles,
                               compile_stats().compile_events)
        rec["ladder"] = (sorted(lc._warm_rows),
                         bridge.translator.launch_rows)
        for i in range(ROWS):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.bind(("127.0.0.1", 0))
            s.setblocking(False)
            socks.append(s)
        rng = np.random.default_rng(32)
        seq = np.full(ROWS, 200, dtype=np.int64)

        def send(i, wire):
            socks[i].sendto(wire, ("127.0.0.1", bridge.port))

        def fresh(i):
            s = int(seq[i])
            seq[i] += 1
            plain = _plain(rng, SSRC_BASE + i, s)
            rec["sent"][(SSRC_BASE + i, s)] = plain
            return oracle.protect_oracle_gcm(*_pair(keys[i, 0]), plain, s)

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
            send(i, fresh(i))
        tick(3)
        drain([])
        for k in list(rec["sent"]):
            del rec["sent"][k]
        tap.handed.clear()
        forwarded0 = bridge.forwarded
        events0 = compile_stats().compile_events
        wires = {}
        # 20 served rounds: a seeded set of senders, one or two packets
        # each, so that rows, width and grid change from tick to tick
        for _round in range(20):
            # odd rounds send the fan-out with the synchronous call
            # (the path before the egress worker), even ones hand it
            # to the worker: the tests below judge both alike
            tap.synchronous = bool(_round % 2)
            n_tx = int(rng.integers(3, 28))
            for i in rng.choice(ROWS, n_tx, replace=False):
                for _ in range(int(rng.integers(1, 3))):
                    w = fresh(int(i))
                    wires[(int(i), int(seq[i]) - 1)] = w
                    send(int(i), w)
            tick(2)
            drain(rec["got"])
        tap.synchronous = False
        # a round that stands in the fan-out's own 512-row class: 40
        # senders, a packet each, 280 (packet, receiver) rows in one
        # tick, judged with the rest
        for i in range(0, ROWS, 8):
            for j in range(5):
                send(i + j, fresh(i + j))
        before = dict(bridge.translator.fanout_launch_rows)
        # (dispatched by the tick that reads it; `tick` collects with
        # `flush_egress`, which books after the tick's drain: the
        # collection's counts are in the next tick's)
        counts = {}
        for _ in range(2):
            tick()
            for k, v in sup.last_counts.items():
                counts.setdefault(k, dict(v))
        drain(rec["got"])
        rec["round_of_280"] = (
            counts,
            {c: n - before.get(c, 0) for c, n in
             bridge.translator.fanout_launch_rows.items()
             if n > before.get(c, 0)})
        rec["forwarded"] = bridge.forwarded - forwarded0
        rec["handed"] = list(tap.handed)
        rec["ports"] = [s.getsockname()[1] for s in socks]
        rec["job_ids"] = [d.id for d in tap.reaped]
        # replay: packets the bridge has already forwarded, sent again
        again = []
        for (i, s), w in list(wires.items())[:12]:
            rec["replayed"].add((SSRC_BASE + i, s))
            send(i, w)
        tick(3)
        drain(again)
        rec["replay_deliveries"] = again
        rec["at_end"] = (lc.datapath_recompiles,
                         compile_stats().compile_events - events0)
        rec["keys"] = keys
        rec["health"] = sup.health()
        yield rec
    finally:
        mp.undo()
        for s in socks:
            s.close()
        bridge.close()
        libjitsi_tpu.stop()


def _sender_seq(pkt: bytes):
    return (int.from_bytes(pkt[8:12], "big"),
            int.from_bytes(pkt[2:4], "big"))


def test_served_deliveries_open_under_the_receivers_own_key(served,
                                                            oracle):
    """Every delivery opens with the scalar reference under the
    RECEIVER's own bridge->client key to the sender's plaintext: fixed
    header past the X bit (the bridge stamps abs-send-time) and the
    whole payload."""
    assert len(served["got"]) > 1000
    for r, wire in served["got"]:
        ssrc, seq = _sender_seq(wire)
        plain = oracle.unprotect_oracle_gcm(
            *_pair(served["keys"][r, 1]), wire, seq)
        assert plain is not None, (r, hex(ssrc), seq)
        sent = served["sent"][(ssrc, seq)]
        off = oracle._payload_off(plain)
        assert plain[1:12] == sent[1:12] and plain[off:] == sent[12:]
        assert plain[0] & 0xEF == sent[0]
        # and under no other endpoint's key
        other = (r + 1) % ROWS
        assert oracle.unprotect_oracle_gcm(
            *_pair(served["keys"][other, 1]), wire, seq) is None


def test_served_deliveries_stay_in_their_conference(served):
    """Every packet reaches exactly the 7 others of its conference:
    none leaves it, none returns to its sender, none arrives twice."""
    reached = {}
    for r, wire in served["got"]:
        ssrc, seq = _sender_seq(wire)
        tx = ssrc - SSRC_BASE
        assert tx // CONF == r // CONF and tx != r
        reached.setdefault((ssrc, seq), []).append(r)
    assert set(reached) == set(served["sent"])
    for (ssrc, _seq), rs in reached.items():
        tx = ssrc - SSRC_BASE
        conf = range(tx // CONF * CONF, (tx // CONF + 1) * CONF)
        assert sorted(rs) == [r for r in conf if r != tx]


def test_worker_delivers_in_order_what_the_synchronous_call_delivers(
        served):
    """Half the served rounds handed their fan-out to the egress
    worker, half sent it with the synchronous call: under both every
    receiver got exactly the datagrams handed over for its port, in
    hand-over order, and `forwarded` counts them all."""
    ids = served["job_ids"]
    assert sum(j > 0 for j in ids) >= 10 and sum(j < 0 for j in ids) >= 10
    assert served["forwarded"] == len(served["handed"]) \
        == len(served["got"])
    for r, port in enumerate(served["ports"]):
        assert [p for rr, p in served["got"] if rr == r] == \
            [p for to, p in served["handed"] if to == port]


def test_served_replay_is_not_forwarded(served):
    assert served["replayed"]
    assert served["replay_deliveries"] == []
    assert served["counts"]["unprotect_wait"]["rows"] > 0


def test_served_zero_datapath_recompiles_after_the_ladder(served):
    """Nothing compiles on the data path: the ladder warmed the one
    form the rule can select at every shape the rounds drove."""
    assert served["after_ladder"][0] == 0
    assert served["at_end"] == (0, 0)
    h = served["health"]
    assert not h["shed"] and not h["quarantined"] and not h["level"]


def test_a_served_tick_of_280_rows_runs_the_512_row_fanout(served):
    """The ladder of 64 endpoints covers 1,024 rows and warmed the
    fan-out's own 512-row class with that rung: a tick of 40 packets x
    7 receivers is ONE launch padded to 512 rows, not 1,024 (and its
    deliveries opened under the oracle, stayed in their conferences
    and compiled nothing, with the rounds' above)."""
    assert served["ladder"] == ([16, 64, 256, 1024], 1024)
    counts, by_class = served["round_of_280"]
    exp = counts["expand"]
    assert (exp["rows"], exp["rows_padded"], exp["row_class"]) == \
        (280, 512, 512)
    assert (exp["launches"], exp["class_cut"]) == (1, 0)
    assert by_class == {512: 1}
    plane = 512 * (224 + staging.TAIL)
    assert counts["fanout_dispatch"]["h2d_bytes"] == plane \
        == counts["fanout_d2h"]["d2h_bytes"]
    assert counts["fanout_dispatch"]["gm_gather_bytes"] \
        == 512 * tr_mod.GM_BYTES


def test_served_path_never_times_providers(served):
    """The ladder and 20 served ticks ran with `_time_once` raising; the
    single-chip GCM ops are not in the timed registry at all; and every
    served launch took the per-row form (all inside the row classes)."""
    assert not [op for op in registry.report() if op.startswith("gcm_")]
    assert len(served["forms"]) >= 20
    assert all(f in ((0, 0), (0, None)) for f in served["forms"])
    c = served["counts"]
    assert c["unprotect_wait"]["gm_gather_bytes"] \
        == c["unprotect_wait"]["rows_padded"] * ctx.GM_BYTES
    assert c["fanout_dispatch"]["gm_gather_bytes"] \
        == c["expand"]["rows_padded"] * tr_mod.GM_BYTES
    assert c["expand"]["rows"] <= c["expand"]["rows_padded"]
    # every tick: one packed plane in and one back for each launch
    # (one size class, so one unprotect launch a tick; no tick of
    # these, all under 1,024 rows, is cut by the row classes).  The
    # fan-out's plane comes back where it is collected: after the
    # tick's drain here (`tick` ends on `flush_egress`), so in the
    # counts of the tick after the one that dispatched it
    assert all(a in ((1, 1, 1, None), (1, 1, None, None),
                     (1, 1, None, 1))
               for a in served["arrays"]), served["arrays"]
    assert c["fanout_d2h"]["d2h_arrays"] \
        == c["fanout_dispatch"]["h2d_arrays"] \
        == c["fanout_wait"]["collected"]
    assert c["expand"]["class_cut"] == 0
    u, f = c["unprotect_wait"], c["fanout_dispatch"]
    plane = 224 + staging.TAIL
    assert u["h2d_bytes"] == u["d2h_bytes"] == u["rows_padded"] * plane
    assert f["h2d_bytes"] == c["fanout_d2h"]["d2h_bytes"] \
        == c["expand"]["rows_padded"] * plane


@pytest.mark.parametrize("leaf,parent,keys", [
    ("unprotect_put", "unprotect_wait", ("h2d_arrays", "h2d_bytes")),
    ("unprotect_d2h", "unprotect_wait", ("d2h_arrays", "d2h_bytes")),
    ("fanout_put", "fanout_dispatch", ("h2d_arrays", "h2d_bytes"))])
def test_served_seam_leaves_book_what_their_calls_book(served, leaf,
                                                       parent, keys):
    """Under GCM as under CM the put is spanned once, where it happens
    (core/staging.py), and books the arrays and bytes its call books;
    the unprotect's copy back likewise."""
    c = served["counts"]
    assert c[leaf] == {k: c[parent][k] for k in keys}
    assert c[leaf][keys[0]] > 0


# ------------------------------------------------------------- the rule

def _streams(rows: int, per: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    s = np.repeat(rng.choice(1 << 20, rows // per, replace=False), per)
    rng.shuffle(s)
    return s.astype(np.int64)


@pytest.mark.parametrize("rows", ROW_CLASSES + (8192, 16384))
@pytest.mark.parametrize("per", (2, 4, 16))
def test_equal_shapes_give_equal_forms(rows, per):
    """The form is a function of the shape alone: two batches of one
    shape and different streams take the same form; inside the row
    classes that is per-row, above them grouped."""
    a, b = _streams(rows, per, 1), _streams(rows, per, 2)
    ga, gb = ctx._gcm_form_grid(a), ctx._gcm_form_grid(b)
    assert (ga is None) == (gb is None) == (rows <= ROW_CLASSES[-1])
    if ga is not None:
        assert ga[0].shape == gb[0].shape == ctx._gcm_grid(a)[0].shape
    # no grid, no grouped form, whatever the rows
    assert ctx._gcm_form_grid(np.arange(rows, dtype=np.int64)) is None


@pytest.mark.parametrize("legs,packets,want", [
    (7, 2, False), (7, 16, False), (15, 64, False), (16, 1, False),
    (16, 2, False), (16, 16, True), (64, 16, True), (256, 16, True),
    (4096, 16, True), (17, 2, False),
    # legs and packets round by the fan-out's own classes: 300 legs
    # are a grid of 512 x 16 (not 1,024 x 16) against 8,192 rows
    (300, 16, True), (300, 2, False), (512, 64, True), (513, 2, False)])
def test_leg_major_rule_is_a_function_of_the_shape(legs, packets, want):
    assert tr_mod._gcm_leg_major(legs, packets) is want
    if want:
        assert (_round_fanout_rows(legs) * _round_fanout_rows(packets)
                <= 2 * _round_fanout_rows(legs * packets))


# ------------------------------- both forms against the reference (c)

def _table(n: int, seed: int):
    rng = np.random.default_rng(seed)
    mk = rng.integers(0, 256, (n, 16), dtype=np.uint8)
    ms = rng.integers(0, 256, (n, 12), dtype=np.uint8)
    t = SrtpStreamTable(n, GCM)
    t.add_streams(np.arange(n), mk, ms)
    return t, mk, ms


def _batch(rows: int, per: int, seed: int, mixed: bool):
    """`rows` packets, `per` a stream, shuffled; payloads 8-60 bytes;
    `mixed`: a CSRC on every third packet, so payload offsets differ."""
    rng = np.random.default_rng(seed)
    n = rows // per
    streams = np.repeat(np.arange(n), per)
    rng.shuffle(streams)
    seqs = np.zeros(rows, np.int64)
    for s in range(n):
        at = np.nonzero(streams == s)[0]
        seqs[at] = 100 + np.arange(len(at))
    pls = [rng.integers(0, 256, int(rng.integers(8, 61)),
                        dtype=np.uint8).tobytes() for _ in range(rows)]
    csrcs = [[7] if mixed and i % 3 == 0 else [] for i in range(rows)]
    b = rtp_header.build(pls, seqs.tolist(), [0] * rows,
                         (0x1000 + streams).tolist(), [96] * rows,
                         csrcs=csrcs, stream=streams.tolist())
    return b, streams, seqs


@pytest.mark.parametrize("rows,per,mixed", [
    (16, 4, False), (64, 4, False), (256, 4, True), (320, 40, False),
    (1024, 4, False), (4096, 4, False), (8192, 4, False)],
    ids=lambda v: str(v))
def test_rule_selected_form_matches_the_reference(rows, per, mixed,
                                                  oracle):
    """At every shape the rule can select — per-row in each row class,
    grouped above them — the table's protect is byte-equal to the scalar
    reference under each stream's key and its unprotect gives the
    plaintext back; and where a grid exists the OTHER form's program is
    byte-equal too, so the rule moves time and never bytes."""
    import jax.numpy as jnp

    n = rows // per
    b, streams, seqs = _batch(rows, per, rows + per, mixed)
    tx, mk, ms = _table(n, 3)
    wire = tx.protect_rtp(b)
    step = max(1, rows // 256)           # the reference is scalar
    for i in range(0, rows, step):
        s = int(streams[i])
        assert wire.to_bytes(i) == oracle.protect_oracle_gcm(
            bytes(mk[s]), bytes(ms[s]), b.to_bytes(i), int(seqs[i])), i
    rx, _, _ = _table(n, 3)
    rx.tracer = tracer = PipelineTracer(annotate=False)
    dec, ok = rx.unprotect_rtp(wire)
    assert ok.all()
    for i in range(0, rows, step):
        assert dec.to_bytes(i) == b.to_bytes(i), i
    # per-row packs one plane each way; the grouped form above the row
    # classes still stages an array an argument (five and the grid's
    # three in, three back)
    tracer.take_ledger()
    c = tracer.last_counts["unprotect_wait"]
    grouped = rows > ROW_CLASSES[-1]
    assert c["grouped"] == int(grouped)
    assert (c["h2d_arrays"], c["d2h_arrays"]) == ((8, 3) if grouped
                                                  else (1, 1))
    # ONE `unprotect_put` a call, with the count of its arrays
    # (`staging.put_each` under the grouped form), and one copy back
    put, back = (tracer.last_counts[k] for k in ("unprotect_put",
                                                 "unprotect_d2h"))
    assert put == {k: c[k] for k in ("h2d_arrays", "h2d_bytes")}
    assert back == {k: c[k] for k in ("d2h_arrays", "d2h_bytes")}
    assert grouped or c["gm_gather_bytes"] \
        == c["rows_padded"] * ctx.GM_BYTES
    # the two programs at this batch's own shape
    pad = _round_rows(rows)
    grid = ctx._gcm_grid(np.resize(streams, pad).astype(np.int64))
    if grid is None or pad != rows or mixed:
        return
    hdr = rtp_header.parse(b)
    fresh, _, _ = _table(n, 3)
    tab_rk, tab_gm, _, _ = fresh._device()
    iv12 = fresh._gcm_rtp_iv(fresh._salt_rtp[streams], hdr.ssrc, seqs)
    data = np.zeros((rows, 224), np.uint8)
    data[:, :b.capacity] = b.data[:, :224]
    args = (tab_rk, tab_gm, jnp.asarray(streams, dtype=jnp.int32),
            jnp.asarray(data), jnp.asarray(b.length),
            jnp.asarray(hdr.payload_off, dtype=jnp.int32),
            jnp.asarray(iv12))
    gr, us, inv = grid
    out_r, len_r = ctx._protect_gcm_dev(*args, aad_const=12)
    out_g, len_g = ctx._protect_gcm_grouped_dev(
        *args, jnp.asarray(gr), jnp.asarray(us, dtype=jnp.int32),
        jnp.asarray(inv), aad_const=12)
    assert np.array_equal(np.asarray(len_r), np.asarray(len_g))
    assert np.array_equal(np.asarray(out_r), np.asarray(out_g))
    for i in range(0, rows, step):
        n_i = int(np.asarray(len_r)[i])
        assert np.asarray(out_r)[i, :n_i].tobytes() == wire.to_bytes(i)


@pytest.mark.parametrize("legs,packets", [(7, 3), (16, 16), (20, 2)])
def test_fanout_forms_match_the_reference(legs, packets, oracle):
    """Senders that share one receiver list: whichever fan-out form
    `_gcm_leg_major` picks for (legs, packets), every output row opens
    under its receiver's key to the sender's packet."""
    rng = np.random.default_rng(legs * 100 + packets)
    t = tr_mod.RtpTranslator(64, GCM)
    keys = [(rng.integers(0, 256, 16, dtype=np.uint8).tobytes(),
             rng.integers(0, 256, 12, dtype=np.uint8).tobytes())
            for _ in range(legs)]
    for r, (k, s) in enumerate(keys):
        t.add_receiver(r, k, s)
    senders = list(range(40, 40 + packets))
    for sid in senders:
        t.connect(sid, range(legs))
    pls = [rng.integers(0, 256, int(rng.integers(40, 161)),
                        dtype=np.uint8).tobytes() for _ in senders]
    seqs = [500 + i for i in range(packets)]
    b = rtp_header.build(pls, seqs, [0] * packets,
                         [0x2000 + s for s in senders], [96] * packets,
                         stream=senders)
    pend = t.translate_async(b, np.asarray(seqs, dtype=np.int64))
    assert (pend._parts[0][1] is not None) \
        is tr_mod._gcm_leg_major(legs, packets)
    wire, recv = pend.result()
    assert wire.batch_size == legs * packets
    for j in range(wire.batch_size):
        p, r = divmod(j, legs)
        assert int(recv[j]) == r
        assert wire.to_bytes(j) == oracle.protect_oracle_gcm(
            *keys[r], b.to_bytes(p), seqs[p]), j


# --------------------- the packed per-row programs (core/staging.py)

def _plain_rows(rows: int, width: int, offsets: str, seed: int):
    """`rows` RTP packets in a `[rows, width]` buffer with room for the
    tag: payload offsets all 12 (`uniform`), 12 or 16 by a CSRC
    (`per-row`), or as `per-row` with the offsets of rows 3 and 7
    forged past the packet (`forged`: what a hostile ext_words field
    makes the header parse say)."""
    rng = np.random.default_rng([rows, width, seed])
    per = 4
    streams = np.repeat(np.arange(rows // per), per)
    rng.shuffle(streams)
    seqs = 100 + np.arange(rows)
    room = width - 16 - 16
    pls = [rng.integers(0, 256, int(rng.integers(8, room - 11)),
                        dtype=np.uint8).tobytes() for _ in range(rows)]
    csrcs = [[7] if offsets != "uniform" and i % 3 == 0 else []
             for i in range(rows)]
    b = rtp_header.build(pls, seqs.tolist(), [0] * rows,
                         (0x1000 + streams).tolist(), [96] * rows,
                         csrcs=csrcs, stream=streams.tolist())
    data = np.zeros((rows, width), np.uint8)
    take = min(width, b.capacity)
    data[:, :take] = b.data[:, :take]
    off = np.asarray(rtp_header.parse(b).payload_off, dtype=np.int32)
    if offsets == "forged":
        off = off.copy()
        off[3], off[7] = 4000, width + 5
    return b, data, np.asarray(b.length, np.int32), off, streams, seqs


@pytest.mark.parametrize("rows,width,offsets", [
    (64, 192, "uniform"), (64, 224, "per-row"), (256, 224, "forged"),
    (256, 192, "per-row"), (1024, 224, "uniform"),
    (1024, 192, "forged"), (4096, 224, "per-row")],
    ids=lambda v: str(v))
def test_packed_per_row_programs_match_the_unpacked_reference(
        rows, width, offsets, oracle):
    """The packed per-row fan-out and unprotect programs against the
    scalar reference and against the same arithmetic with an array an
    argument (`_protect_gcm_dev`, `_open_gcm_dev`): every output byte,
    length and verdict, with a tag that does not verify among them."""
    import jax
    import jax.numpy as jnp

    n = rows // 4
    b, data, length, off, streams, seqs = _plain_rows(rows, width,
                                                      offsets, 5)
    t, mk, ms = _table(n, 9)
    tab_rk, tab_gm, _, _ = t._device()
    hdr = rtp_header.parse(b)
    iv12 = t._gcm_rtp_iv(t._salt_rtp[streams], hdr.ssrc, seqs)
    aad = ctx._uniform_off(off, width)
    assert (aad is None) == (offsets != "uniform")
    unpacked = (jnp.asarray(streams, dtype=jnp.int32), jnp.asarray(data),
                jnp.asarray(length), jnp.asarray(off), jnp.asarray(iv12))
    ref, ref_len = map(np.asarray, ctx._protect_gcm_dev(
        tab_rk, tab_gm, *unpacked, aad_const=aad))
    forged = {3, 7} if offsets == "forged" else set()
    for i in range(0, rows, max(1, rows // 64)):
        if i not in forged:
            s = int(streams[i])
            assert ref[i, :ref_len[i]].tobytes() \
                == oracle.protect_oracle_gcm(
                    bytes(mk[s]), bytes(ms[s]), b.to_bytes(i),
                    int(seqs[i])), i
    # the packed fan-out: one plane in, one of its shape back
    plane = staging.alloc(rows, width)
    plane[:, :width] = data
    staging.pack(plane, (streams, length, off), iv12)
    back = np.asarray(tr_mod._fanout_protect_gcm(
        tab_rk, tab_gm, jax.device_put(plane)))
    assert back.shape == plane.shape and back.dtype == np.uint8
    out, out_len = tr_mod._split_fanout(back)
    np.testing.assert_array_equal(out_len, ref_len)
    np.testing.assert_array_equal(out, ref)
    # the packed unprotect of that wire, one tag bit flipped
    wire = ref.copy()
    wire[5, ref_len[5] - 1] ^= 0x01
    want = [np.asarray(a) for a in ctx._open_gcm_dev(
        tab_rk, tab_gm, unpacked[0], jnp.asarray(wire),
        jnp.asarray(ref_len), unpacked[3], unpacked[4], aad_const=aad)]
    plane = staging.alloc(rows, width)
    plane[:, :width] = wire
    staging.pack(plane, (streams, ref_len, off), iv12)
    dec, mlen, auth_ok = ctx._split_unprotect(np.asarray(
        ctx._unprotect_gcm_dev(tab_rk, tab_gm, jax.device_put(plane),
                               aad_const=aad)))
    np.testing.assert_array_equal(dec, want[0])
    np.testing.assert_array_equal(mlen, want[1])
    np.testing.assert_array_equal(auth_ok, want[2])
    # forged offsets read 12 bytes too few or too many as AAD: dead
    assert not auth_ok[5] and auth_ok.sum() == rows - 1 - len(forged)
    good = np.nonzero(auth_ok)[0]
    np.testing.assert_array_equal(mlen[good], length[good])
    for i in good[:: max(1, rows // 64)]:
        assert dec[i, :mlen[i]].tobytes() == b.to_bytes(int(i)), i


def test_packed_gcm_unprotect_one_array_each_way(warmed_launch_guard):
    """With the tracer on, a warmed per-row GCM unprotect sends ONE
    array to the device and copies one back, compiles nothing, starts
    no `convert_element_type` program, and still books what the GHASH
    gathers read; a row whose tag does not verify keeps its bytes."""
    n = 4
    tx, _, _ = _table(n, 11)
    rx, _, _ = _table(n, 11)
    rx.tracer = tracer = PipelineTracer(annotate=False)

    def wire(seq0, mixed):
        pls = [bytes([i]) * (20 + 10 * (i % 3)) for i in range(12)]
        return tx.protect_rtp(rtp_header.build(
            pls, [seq0 + i // n for i in range(12)], [0] * 12,
            [0x1000 + i % n for i in range(12)], [96] * 12,
            csrcs=[[7] if mixed and i % 3 == 0 else []
                   for i in range(12)],
            stream=[i % n for i in range(12)]))

    for mixed in (False, True):
        first, second = wire(10 + 20 * mixed, mixed), \
            wire(20 + 20 * mixed, mixed)
        second.data[5, second.length[5] - 3] ^= 0x10
        before = second.to_bytes(5)
        _, ok = rx.unprotect_rtp(first)           # warms the program
        assert ok.all()
        tracer.take_ledger()
        with warmed_launch_guard():
            out, ok = rx.unprotect_rtp(second)
        assert not ok[5] and ok.sum() == 11
        assert out.to_bytes(5) == before
        tracer.take_ledger()
        c = tracer.last_counts["unprotect_wait"]
        assert c["h2d_arrays"] == 1 and c["d2h_arrays"] == 1
        assert c["rows"] == 12 and c["rows_padded"] == 16
        assert c["h2d_bytes"] == c["d2h_bytes"] \
            == 16 * (192 + 32 + staging.TAIL)
        assert c["gm_gather_bytes"] == 16 * ctx.GM_BYTES
        assert c["grouped"] == 0
    assert rx.auth_fail.sum() == 2


def test_packed_gcm_unprotect_async_matches_sync():
    """`unprotect_rtp_async` goes through the same packed seam."""
    n = 4
    tx, _, _ = _table(n, 12)
    t_sync, _, _ = _table(n, 12)
    t_async, _, _ = _table(n, 12)
    pls = [bytes([i]) * (20 + 30 * (i % 3)) for i in range(12)]
    wire = tx.protect_rtp(rtp_header.build(
        pls, [300 + i // n for i in range(12)], [0] * 12,
        [0x1000 + i % n for i in range(12)], [96] * 12,
        stream=[i % n for i in range(12)]))
    bad = wire.copy()
    bad.data[5, 14] ^= 0x40
    want, want_ok = t_sync.unprotect_rtp(bad)
    got, ok = t_async.unprotect_rtp_async(bad).block_until_ready() \
        .result()
    np.testing.assert_array_equal(ok, want_ok)
    assert not ok[5] and ok.sum() == 11
    for i in range(12):
        assert got.to_bytes(i) == want.to_bytes(i), i
    np.testing.assert_array_equal(t_async.rx_max, t_sync.rx_max)


def test_packed_gcm_fanout_one_array_each_way(warmed_launch_guard,
                                              oracle):
    """With the tracer on, a warmed per-row GCM fan-out sends ONE array
    to the device and copies one back, compiles nothing and starts no
    `convert_element_type` program; every row opens under its leg."""
    rng = np.random.default_rng(77)
    t = tr_mod.RtpTranslator(8, GCM)
    keys = {r: (rng.integers(0, 256, 16, dtype=np.uint8).tobytes(),
                rng.integers(0, 256, 12, dtype=np.uint8).tobytes())
            for r in (1, 2, 3)}
    for r, (k, s) in keys.items():
        t.add_receiver(r, k, s)
    t.connect(0, [1, 2, 3])
    t.tracer = tracer = PipelineTracer(annotate=False)

    def batch(seq0):
        pls = [bytes([seq0 + i & 0xFF]) * (40 + 9 * i) for i in range(4)]
        return rtp_header.build(pls, [seq0 + i for i in range(4)],
                                [0] * 4, [0x2000] * 4, [96] * 4,
                                stream=[0] * 4)

    t.translate(batch(1000), np.arange(1000, 1004))      # warms
    tracer.take_ledger()
    b = batch(1004)
    with warmed_launch_guard():
        out, recv = t.translate(b, np.arange(1004, 1008))
    assert out.batch_size == 12
    for j in range(12):
        assert out.to_bytes(j) == oracle.protect_oracle_gcm(
            *keys[int(recv[j])], b.to_bytes(j // 3), 1004 + j // 3), j
    tracer.take_ledger()
    counts = tracer.last_counts
    plane = 16 * (192 + 32 + staging.TAIL)    # 12 rows padded to 16
    assert counts["fanout_dispatch"] == {
        "h2d_arrays": 1, "h2d_bytes": plane,
        "gm_gather_bytes": 16 * tr_mod.GM_BYTES, "grouped": 0}
    assert counts["fanout_d2h"] == {"d2h_arrays": 1, "d2h_bytes": plane}
    assert counts["expand"] == {"rows": 12, "rows_padded": 16,
                                "width": 224, "launches": 1,
                                "legs_max": 3, "class_cut": 0,
                                "row_class": 16}


# --------------------- the payload offset is an operand (PR 43) ---

def _rtp_with_header(hlen, seq: int, ssrc: int, payload: bytes) -> bytes:
    """An RTP packet whose header is `hlen` bytes: 12 bare, 16 one CSRC,
    20 one extension word, 24 one CSRC and one extension word (what
    WebRTC clients send); "forged": an X bit whose `ext_words` claims
    1,000 words, past any packet."""
    cc = 1 if hlen in (16, 24) else 0
    ext = {20: b"\xbe\xde\x00\x01\x32\xaa\xbb\xcc",
           24: b"\xbe\xde\x00\x01\x32\xaa\xbb\xcc",
           "forged": b"\xbe\xde\x03\xe8"}.get(hlen, b"")
    first = 0x80 | (0x10 if ext else 0) | cc
    return (bytes([first, PT]) + seq.to_bytes(2, "big")
            + (seq * 960 & 0xFFFFFFFF).to_bytes(4, "big")
            + ssrc.to_bytes(4, "big") + b"\x00\x00\x00\x07" * cc + ext
            + payload)


@pytest.mark.parametrize("header", [12, 16, 20, 24, "mixed", "forged"],
                         ids=str)
@pytest.mark.parametrize("suite", ["cm", "gcm"])
def test_fanout_takes_any_header_length_after_the_ladder(suite, header,
                                                         oracle):
    """The per-row fan-out has ONE program a (row class, width): its
    payload offset is a word of the plane.  `fanout_warmups` gives one
    thunk a width; after them a tick of 12-, 16-, 20- or 24-byte
    headers, of all four at once, or with a forged `ext_words` among
    them compiles NOTHING (a uniform 16 or 24 compiled a program on the
    tick thread until PR 43), and every row is the scalar oracle's."""
    prof = GCM if suite == "gcm" else SrtpProfile.AES_CM_128_HMAC_SHA1_80
    rng = np.random.default_rng(4300)
    t = tr_mod.RtpTranslator(24, prof)     # no other test's table shape
    keys = {r: (rng.integers(0, 256, 16, dtype=np.uint8).tobytes(),
                rng.integers(0, 256, prof.policy.salt_len,
                             dtype=np.uint8).tobytes())
            for r in range(1, 6)}
    for r, (k, s) in keys.items():
        t.add_receiver(r, k, s)
    # two receiver lists: the per-row form under GCM too
    t.connect(0, [1, 2, 3])
    t.connect(1, [2, 3, 4, 5])
    thunks = t.fanout_warmups(16)
    assert len(thunks) == 2                # the audio width, the MTU
    for thunk in thunks:
        thunk()
    before = compile_stats().compile_events

    kinds = {"mixed": [12, 16, 20, 24],
             "forged": [12, "forged", "forged", 12]}.get(header,
                                                         [header] * 4)
    index = [(2 << 16) + 500 + i for i in range(4)]    # ROC 2
    plain = [_rtp_with_header(
        k, index[i] & 0xFFFF, 0x3000 + i % 2,
        rng.integers(0, 256, 60 + 17 * i, dtype=np.uint8).tobytes())
        for i, k in enumerate(kinds)]
    src = np.repeat(np.arange(4), [3, 4, 3, 4])
    seal = oracle.protect_oracle_gcm if suite == "gcm" \
        else oracle.protect_oracle
    # one tick at the audio width, one at the MTU's: the last packet
    # fills its row up to the room the tag needs (the longest payload
    # window a row of that width can have)
    tag = prof.policy.auth_tag_len
    for full in (192 + 32 - tag, 1504 - tag):
        pkts = plain[:-1] + [plain[-1] + bytes(full - len(plain[-1]))]
        b = PacketBatch.from_payloads(pkts, stream=[0, 1, 0, 1])
        out, recv = t.translate(b, np.asarray(index))
        assert out.batch_size == len(src) == 14      # the 16-row class
        for j, i in enumerate(src.tolist()):
            if suite == "gcm" and kinds[i] == "forged":
                continue      # no AEAD of a header longer than the packet
            assert out.to_bytes(j) == seal(*keys[int(recv[j])], pkts[i],
                                           index[i]), (j, kinds[i])
    assert compile_stats().compile_events == before
